"""What the benchmark's parts share: finding a cell's files by name, loading
the module a file names, and JSON over HTTP to the served simulator.

Nothing here imports JAX or the program under test: the load client runs
in a child process that must never touch the chip, and the yardstick
(generators, traffic, reference, metric readers) must not move when the
program does.

Layout, found by name from `BENCHMARK.json`:

    benchmark/workloads/<cell>.json    config, traffic, chips, why
    benchmark/configs/<config>.json    cluster shape, generator, reference,
                                       server environment, guarantees
    benchmark/traffic/<mix>.json       parameters of the one traffic
                                       generator (traffic.py)
    benchmark/generators/<name>.py     cluster generator of a configuration
    benchmark/references/<name>.py     plain reference of the semantics
    benchmark/metrics/<metric>.py      one reader per metric
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import os
import socket
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(cell: str, root: str = ROOT) -> dict:
    """Everything one run of `cell` needs, as one JSON-able dict: the
    cell's entry and file, its configuration, its traffic mix, and the
    metrics of `BENCHMARK.json` that the cell reports."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    bdir = os.path.join(root, "benchmark")
    path = os.path.join(bdir, "workloads", f"{cell}.json")
    if cell not in entries and not os.path.exists(path):
        raise SystemExit(f"unknown workload {cell!r}; BENCHMARK.json has {sorted(entries)}")
    workload = read_json(path)
    # a cell whose files are in place but that BENCHMARK.json does not list
    # yet (measured by a later benchmark PR) runs from its workload file
    entry = entries.get(cell, workload)
    config = read_json(os.path.join(bdir, "configs", f"{entry['config']}.json"))
    traffic = read_json(os.path.join(bdir, "traffic", f"{entry['traffic']}.json"))

    def reported(metrics: list) -> list:
        return [m for m in metrics if cell in m.get("workloads", [cell])]

    return {
        "cell": cell,
        "chips": int(entry["chips"]),
        "workload": workload,
        "config": config,
        "traffic": traffic,
        "end_to_end": reported(bench["end_to_end"]),
        "per_layer": reported(bench["per_layer"]),
    }


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module `benchmark/<kind>/<name>.py`, imported from its path
    (a name may hold dots, so it is never a package path)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if mod_spec is None or mod_spec.loader is None:
        raise SystemExit(f"no {kind} module {name!r} at {path}")
    if mod_spec.name in sys.modules:
        return sys.modules[mod_spec.name]
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_spec.name] = mod  # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


class HttpError(RuntimeError):
    def __init__(self, status: int, body: bytes):
        super().__init__(f"HTTP {status}: {body[:300]!r}")
        self.status = status


class _Connection(http.client.HTTPConnection):
    """http.client sends a request's headers and body in two writes; with
    Nagle's algorithm on, the body then waits for the server's delayed
    ACK (about 40 ms). Clients such as Go's net/http turn it off."""

    def connect(self) -> None:
        super().connect()
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


class Api:
    """JSON over one keep-alive HTTP/1.1 connection to the server."""

    def __init__(self, port: int, host: str = "127.0.0.1"):
        self.conn = _Connection(host, port, timeout=1800)

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        headers = {"Content-Type": "application/json"} if data is not None else {}
        self.conn.request(method, "/api/v1" + path, body=data, headers=headers)
        resp = self.conn.getresponse()
        raw = resp.read()
        if resp.status >= 400:
            raise HttpError(resp.status, raw)
        return json.loads(raw) if raw else None

    def close(self) -> None:
        self.conn.close()


def now() -> float:
    """The one clock of the benchmark: `time.perf_counter`, which is
    CLOCK_MONOTONIC on Linux, shared by the parent, the client process
    and the program's spans (utils/telemetry.py stamps perf_counter)."""
    return time.perf_counter()
