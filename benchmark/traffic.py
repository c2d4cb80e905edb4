"""The one traffic generator. A mix is a data file, `traffic/<mix>.json`:

    {"unit": "pod", "batches": [32, 128, 512], "delete": true}

Each cycle takes the next count of `batches` (round robin), deletes that
many units drawn from the live set by the seed and creates as many fresh
ones; then the client runs one pass. What a unit is the configuration's
generator says; `delete: false` only adds. Every seed gets the same
counts in the same order and the same pods; the seed draws where the
residents sit and which pods each cycle deletes."""

from __future__ import annotations

import random

from harness import load_module


class Traffic:
    def __init__(self, spec: dict, seed: int):
        self.config = spec["config"]
        self.params = spec["traffic"]
        self.batches = [int(b) for b in self.params["batches"]]
        self.gen = load_module("generators", self.config["generator"])
        self.seed = int(seed)
        self.rng = random.Random(f"{self.seed}/traffic")

    def cluster(self) -> tuple[list, list]:
        """(nodes, bound resident pods)."""
        return self.gen.make_cluster(self.config, random.Random(f"{self.seed}/cluster"))

    def cycle(self, live: dict, serial: int) -> tuple[int, list, list]:
        """(count, pods to delete, pods to create) of cycle `serial`."""
        k = self.batches[serial % len(self.batches)]
        deletes, creates = self.gen.churn(live, k, self.rng, serial, self.config, self.params)
        return k, deletes, creates
