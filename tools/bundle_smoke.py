"""`make bundle-smoke`: the AOT-bundle cross-process reuse gate.

The persistent bundle store's whole point (utils/bundles.py,
docs/performance.md) is that a COLD PROCESS never re-compiles an engine
program another process already compiled. This smoke proves exactly
that, end to end, on CPU:

1. Run the cold-start probe workload (the serving path's
   `schedule_gang` over a small synthetic cluster) in a FRESH
   subprocess with `KSS_AOT_BUNDLES=1` against an empty bundle dir and
   an empty XLA compile-cache dir: the run compiles, SAVES bundles
   (`bundleSaves >= 1`), and reports its placements digest.

2. Run the identical workload in a SECOND fresh subprocess sharing the
   now-warm bundle dir: every engine program must resolve from the
   store — `bundleMisses == 0` (zero program compiles: a miss is
   precisely "an engine program had to be compiled") and
   `bundleLoads >= 1` — with a byte-identical placements digest.

Exit 0 on pass, 1 with the problem list otherwise; one JSON line either
way. Small enough for tier-1-adjacent use (seconds, CPU-only); the
measured ≥5x time-to-first-scheduled-pod gate lives in
`python bench.py` (`coldStartBundled`).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child workload: the cold-start probe's serving-path pass, plus
# the bundle-store accounting the parent asserts on. Kept inline so the
# smoke has exactly one moving part.
_CHILD = """
import json

from kube_scheduler_simulator_tpu.models.store import ResourceStore
from kube_scheduler_simulator_tpu.server.service import SchedulerService
from kube_scheduler_simulator_tpu.utils import bundles

store = ResourceStore()
for i in range(8):
    store.apply(
        "nodes",
        {
            "metadata": {"name": f"bn{i}"},
            "status": {
                "allocatable": {"cpu": "64", "memory": "128Gi", "pods": "110"}
            },
        },
    )
for i in range(32):
    store.apply(
        "pods",
        {
            "metadata": {"name": f"bp{i}"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "resources": {
                            "requests": {"cpu": "250m", "memory": "256Mi"}
                        },
                    }
                ]
            },
        },
    )
svc = SchedulerService(store)
placements, _, _ = svc.schedule_gang(record=False)
bundles.STORE.flush(60.0)
print(
    json.dumps(
        {
            "placements": sorted(
                [ns, name, node] for (ns, name), node in placements.items()
            ),
            "bundles": bundles.STORE.stats(),
            "compile": {
                "compileMisses": svc.broker.compile_misses,
                "compileHits": svc.broker.compile_hits,
            },
        }
    )
)
"""


def _run_child(env: dict) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=REPO_ROOT,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"child exited {out.returncode}:\n{out.stdout}\n{out.stderr}"
        )
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(doc, dict) and "bundles" in doc:
            return doc
    raise RuntimeError(f"child emitted no result line:\n{out.stdout}")


def main() -> int:
    problems: list[str] = []
    bundle_dir = tempfile.mkdtemp(prefix="kss-bundle-smoke-")
    cache_dir = tempfile.mkdtemp(prefix="kss-bundle-smoke-cache-")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        KSS_AOT_BUNDLES="1",
        KSS_BUNDLE_DIR=bundle_dir,
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        # a deterministic program set: no background speculative builds
        # racing the exit flush
        KSS_NO_SPECULATIVE_COMPILE="1",
    )

    first = _run_child(env)
    second = _run_child(env)

    f_stats, s_stats = first["bundles"], second["bundles"]
    if f_stats["bundleSaves"] < 1:
        problems.append(
            f"first process saved no bundles: {f_stats}"
        )
    # "compileMisses == 0 for engine programs": a bundle-store MISS is
    # exactly "an engine program had to be compiled" — the second
    # process must have none (the broker's engine-level compileMisses
    # stays 1 per process: the warm-engine MAP is per-process; what the
    # bundles eliminate is the program compile inside that build)
    if s_stats["bundleMisses"] != 0:
        problems.append(
            f"second process compiled engine programs: {s_stats}"
        )
    if s_stats["bundleLoads"] < 1:
        problems.append(
            f"second process loaded no bundles: {s_stats}"
        )
    if s_stats["bundleBypasses"] != 0:
        problems.append(
            f"second process bypassed bundles: {s_stats}"
        )
    if first["placements"] != second["placements"]:
        problems.append("bundled placements diverged from the compiled run")
    if not first["placements"]:
        problems.append("workload scheduled nothing — the gate proved nothing")

    line = {
        "ok": not problems,
        "firstProcess": {
            "bundles": f_stats,
            "compile": first["compile"],
        },
        "secondProcess": {
            "bundles": s_stats,
            "compile": second["compile"],
        },
        "placementsIdentical": first["placements"] == second["placements"],
        "problems": problems,
    }
    print(json.dumps(line), flush=True)
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
