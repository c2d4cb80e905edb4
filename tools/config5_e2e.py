"""BASELINE config #5 end-to-end: 50k pods x 5k nodes, mixed priorities.

The one BASELINE row that never had a scheduling number (VERDICT r4
missing #4: import + encode were timed in round 4, the scheduling pass
never ran at this shape on any backend). This script runs the WHOLE
path the way a user would: snapshot import into the store -> list back
out -> encode -> gang fixpoint (full default plugin set incl.
DefaultPreemption) -> placement count, printing one JSON line per phase
and a final summary line.

Runs on whatever backend JAX starts (the chip, or the CPU with
JAX_PLATFORMS=cpu):

    python tools/config5_e2e.py [--nodes 5000 --pods 50000]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--pods", type=int, default=50000)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument(
        "--window", type=int, default=0,
        help="eval_window (0 = off): queue-prefix eval bounding — the"
        " round-5 eval-dominance lever; cuts per-round evaluation from"
        " all-pending to a queue prefix (see GangScheduler)",
    )
    args = ap.parse_args()

    # persistent compile cache: a killed/retried run at this shape must
    # not repay the (many-minute, host-CPU-bound) compile
    from kube_scheduler_simulator_tpu.utils.compilecache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    def phase(name, t0):
        dt = time.perf_counter() - t0
        print(json.dumps({"phase": name, "seconds": round(dt, 2)}), flush=True)
        return dt

    from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import supported_config
    from kube_scheduler_simulator_tpu.engine.gang import GangScheduler
    from kube_scheduler_simulator_tpu.models.snapshot import (
        export_snapshot,
        import_snapshot,
    )
    from kube_scheduler_simulator_tpu.models.store import ResourceStore
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster

    import numpy as np

    t0 = time.perf_counter()
    nodes, pods = synthetic_cluster(
        args.nodes, args.pods, seed=args.seed, priorities=True
    )
    t_synth = phase("synth", t0)

    # import the manifests through the snapshot path (the reference's
    # one-shot cluster import, simulator/docs export/import API)
    t0 = time.perf_counter()
    src = ResourceStore()
    for n in nodes:
        src.apply("nodes", n)
    for p in pods:
        src.apply("pods", p)
    snap = export_snapshot(src, None)
    store = ResourceStore()
    import_snapshot(store, snap)
    t_import = phase("import", t0)

    t0 = time.perf_counter()
    enc = encode_cluster(
        store.list("nodes"),
        store.list("pods"),
        supported_config(),
        policy=TPU32,
    )
    t_encode = phase("encode", t0)

    t0 = time.perf_counter()
    gang = GangScheduler(
        enc, chunk=args.chunk, eval_window=args.window or None
    )
    state, rounds = gang.run()
    placed = int((np.asarray(state.assignment) >= 0).sum())
    t_sched = phase("gang_schedule", t0)

    import jax

    print(
        json.dumps(
            {
                "config5_dps": round(args.pods / t_sched, 1),
                "shape": f"{args.pods}x{args.nodes}",
                **({"window": args.window} if args.window else {}),
                "rounds": int(np.asarray(rounds)),
                "placed": placed,
                "pods": args.pods,
                "platform": jax.devices()[0].platform,
                "phases_s": {
                    "synth": round(t_synth, 2),
                    "import": round(t_import, 2),
                    "encode": round(t_encode, 2),
                    "schedule": round(t_sched, 2),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    sys.exit(main())
