"""Compile rehearsal, without the chip: each cell's served `gang.fixpoint`
program at the cell's real capacity buckets, compiled for a described
TPU v5e (one chip of a v5e:2x2 topology) on this host's CPU.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py [cell ...]

For every count of the cell's traffic mix, it builds the store the pass
would see (the generated cluster after one cycle of that count), encodes
it through the service's own encode path, and compiles the program the
service would dispatch. Programs whose compile signature is the same are
compiled once: run.py's set-up warms one pass, so a cell whose counts
need more than one program shows here. It prints the compiler's memory analysis of each; what
the compiler refuses, it raises. Nothing runs, and no time it prints is
a chip time.
"""

from __future__ import annotations

import json
import os
import sys

from harness import ROOT, load_spec, read_json
from traffic import Traffic

sys.path.insert(1, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _shapes(tree, sharding):
    import jax
    import jax.numpy as jnp
    import numpy as np

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype, sharding=sharding), tree
    )


def rehearse(cell: str, one_chip) -> list:
    import jax

    from kube_scheduler_simulator_tpu.engine.gang import GangScheduler
    from kube_scheduler_simulator_tpu.server.service import SimulatorService, gang_chunk

    spec = load_spec(cell)
    os.environ.update(spec["config"]["server_env"])
    traffic = Traffic(spec, seed=0)
    nodes, pods = traffic.cluster()
    out, seen = [], {}
    for serial, _ in enumerate(traffic.batches):
        service = SimulatorService()
        service.scheduler.restart(spec["config"]["schedulerConfiguration"])
        service.import_({"nodes": nodes, "pods": pods})
        live = {p["metadata"]["name"]: p for p in pods}
        k, deletes, creates = traffic.cycle(live, serial)
        for p in deletes:
            service.store.delete("pods", p["metadata"]["name"], p["metadata"]["namespace"])
        for p in creates:
            service.store.apply("pods", p)
        sched = service.scheduler
        enc = sched._encode_current(sched.config)
        sig = GangScheduler.compile_signature(enc)
        row = {"cell": cell, "count": k, "queued": len(creates), "N": enc.N, "P": enc.P}
        if sig in seen:
            row["same_program_as_count"] = seen[sig]
            out.append(row)
            continue
        seen[sig] = k
        g = GangScheduler(enc, strict=True, chunk=gang_chunk())
        order, _ = g.order_arrays()
        args = (enc.arrays, enc.state0, order, g.weights)
        compiled = jax.jit(g.fixpoint_fn).lower(*_shapes(args, one_chip)).compile()
        m = compiled.memory_analysis()
        row.update(
            argument_bytes=m.argument_size_in_bytes,
            output_bytes=m.output_size_in_bytes,
            temp_bytes=m.temp_size_in_bytes,
            generated_code_bytes=m.generated_code_size_in_bytes,
        )
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    cells = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in read_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]
    ]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for cell in cells:
        for row in rehearse(cell, one_chip):
            if "same_program_as_count" in row:
                print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
