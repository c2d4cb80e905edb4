"""Bring-up smoke on the TPU: the served scheduling pass on one chip, or
the Monte-Carlo policy sweep on a 2 x 2 mesh of four chips.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the 2 x 2 mesh sweep, and only that

Everything runs in this one process, which holds the chip: the HTTP
server runs in a thread, never as a child. Each phase raises on the
first thing that is wrong, and none catches its own failure. The last
line of standard output is the one JSON object

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and it is printed only when every phase passed on a TPU. Wall times
printed on the way are set-up and run times of this smoke, not
benchmark metrics.

One chip (BASELINE config #2, full default plugin set, TPU32 policy):

  * served: `synthetic_cluster(1024 nodes, 10 000 pods)` is imported over
    HTTP and scheduled through `POST /api/v1/schedule?mode=gang&record=0`
    (the bulk served pass: the sequential record trace at this shape
    would be about 200 GB). Then 100 pods are added with
    `PUT /api/v1/resources/pods` in two batches, each scheduled on the
    warm engine: the first re-encodes in full (the bulk pass's own
    write-backs), the second is the delta-encode path. Every pod must
    be placed or reported unschedulable, and no node may hold more cpu,
    memory or pods than it can allocate.
  * parity: at 512 pods x 64 nodes the same server's sequential pass
    (`POST /api/v1/schedule`) must place every pod where
    `sched/oracle.py` places it.
  * the device is not hidden: the CPU-failover, eager and degraded
    counters of `GET /api/v1/metrics` must stay 0.

Four chips (BASELINE config #4): a sequential sweep of 32 variants and a
gang sweep of 8 variants, each over 2 048 pods x 1 024 nodes, sharded
(replicas x nodes) = (2 x 2). Variant 0 must equal the unsharded
single-chip program, every sharded input must span all four devices,
and every device must hold bytes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

SERVED_NODES = 1024
SERVED_PODS = 10_000
SERVED_DELTA_PODS = 100
PARITY_NODES = 64
PARITY_PODS = 512
MESH_NODES = 1024
MESH_PODS = 2048
MESH_SEQ_VARIANTS = 32
MESH_GANG_VARIANTS = 8
MESH_SHAPE = (2, 2)  # (replicas, nodes)

# the counters that say a pass left the compiled device path
HIDDEN_DEVICE_COUNTERS = ("deviceFailovers", "eagerFallbacks", "degradedPasses")


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require_tpu(n_chips: int) -> list:
    """The visible devices, or SystemExit naming what was found: the
    smoke never runs anywhere but on a TPU."""
    import jax

    devices = jax.devices()
    if not devices:
        raise SystemExit("chip_smoke: jax.devices() is empty")
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX platform is {platform!r} "
            f"({len(devices)} device(s)), not 'tpu'"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: {len(devices)} TPU device(s), {n_chips} needed"
        )
    return devices


def result_line(devices) -> str:
    """The driver's contract: `ok` and `device`, nothing else."""
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            },
        }
    )


# -- the served path -------------------------------------------------------


class _Client:
    """JSON over HTTP to the in-process server."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}/api/v1"

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path,
            data=data,
            method=method,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=1800) as resp:
            return json.loads(resp.read() or b"null")


def start_server():
    """Service + server exactly as `python -m ...server` builds them
    (server/__main__.py), on a free port, serving from a thread of this
    process."""
    from kube_scheduler_simulator_tpu.server import config as envconfig
    from kube_scheduler_simulator_tpu.server.httpserver import SimulatorServer
    from kube_scheduler_simulator_tpu.server.service import SimulatorService

    cfg = envconfig.from_env()
    service = SimulatorService(
        initial_config=cfg.initial_scheduler_config,
        external_scheduler_enabled=cfg.external_scheduler_enabled,
    )
    server = SimulatorServer(
        service, host="127.0.0.1", port=0,
        cors_allowed_origins=cfg.cors_allowed_origins,
    )
    server.start()
    return server, _Client(server.port)


def check_capacity(nodes: list, pods: list) -> None:
    """No node holds more cpu, memory or pods than it can allocate,
    counted with the oracle's own node accounting."""
    from kube_scheduler_simulator_tpu.sched.oracle import ClusterSnapshot

    snap = ClusterSnapshot.build(nodes)
    for p in pods:
        name = (p.get("spec") or {}).get("nodeName")
        if name:
            snap.nodes[name].add_pod(p)
    for name, ni in snap.nodes.items():
        for r in ("cpu", "memory"):
            used, cap = ni.requested.get(r, 0), ni.allocatable.get(r, 0)
            if used > cap:
                raise AssertionError(f"node {name}: {r} {used} > allocatable {cap}")
        if len(ni.pods) > ni.allocatable.get("pods", 0):
            raise AssertionError(
                f"node {name}: {len(ni.pods)} pods > allocatable "
                f"{ni.allocatable.get('pods', 0)}"
            )


def check_device_counters(metrics: dict) -> None:
    phases = metrics["phases"]
    hidden = {k: phases[k] for k in HIDDEN_DEVICE_COUNTERS if phases[k]}
    if hidden:
        raise AssertionError(f"a pass left the compiled device path: {hidden}")


def _gang_pass(client: _Client, expect: int, label: str) -> dict:
    t0 = time.perf_counter()
    body = client.call("POST", "/schedule?mode=gang&record=0")
    wall = time.perf_counter() - t0
    decided = body["scheduled"] + body["unschedulable"]
    if decided != expect:
        raise AssertionError(f"{label}: {decided} pods decided, {expect} pending")
    log(
        f"{label}: {body['scheduled']} scheduled, {body['unschedulable']} "
        f"unschedulable, {body['rounds']} rounds, wall {wall:.3f}s"
    )
    return body


def served_phase(
    client: _Client,
    n_nodes: int = SERVED_NODES,
    n_pods: int = SERVED_PODS,
    n_delta: int = SERVED_DELTA_PODS,
    seed: int = 0,
) -> dict:
    """Import, bulk pass, two passes over added pods (the second one
    delta-encoded), metrics, capacity check."""
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster

    nodes, pods = synthetic_cluster(n_nodes, n_pods + n_delta, seed=seed)
    first, delta = pods[:n_pods], pods[n_pods:]
    for p in delta:
        p["metadata"]["name"] = "delta-" + p["metadata"]["name"]
    log(f"served: {n_pods} pods x {n_nodes} nodes, then +{n_delta} pods")

    t0 = time.perf_counter()
    errors = client.call("POST", "/import", {"nodes": nodes, "pods": first})
    if errors["errors"]:
        raise AssertionError(f"import errors: {errors['errors'][:5]}")
    log(f"set-up: import over HTTP {time.perf_counter() - t0:.3f}s")
    _gang_pass(client, n_pods, "first pass (compile + run)")
    before = client.call("GET", "/metrics")["phases"]
    log(
        "first pass host-clock phases (set-up and run, not benchmark "
        "metrics): "
        + ", ".join(
            f"{k} {before[k]}s"
            for k in ("encodeSeconds", "compileSeconds", "executeSeconds",
                      "decodeSeconds")
        )
    )

    # The pass after the bulk pass re-encodes in full: the bulk pass's
    # own write-backs bind every pod, far past the delta encoder's dirty
    # fraction. The pass after that one is the delta-encode path.
    half = len(delta) // 2
    for label, batch, mode in (
        ("add pass 1", delta[:half], "fullEncodes"),
        ("add pass 2", delta[half:], "deltaEncodes"),
    ):
        for p in batch:
            client.call("PUT", "/resources/pods", p)
        _gang_pass(client, len(batch), label)
        after = client.call("GET", "/metrics")["phases"]
        if after[mode] != before[mode] + 1:
            raise AssertionError(f"{label}: expected one more {mode}: {after}")
        if after["engineBuilds"] != before["engineBuilds"]:
            raise AssertionError(f"{label}: the engine was rebuilt")
        log(f"{label}: {mode} +1, warm engine (engineBuilds "
            f"{after['engineBuilds']})")
        before = after
    metrics = client.call("GET", "/metrics")

    stored = client.call("GET", "/resources/pods")["items"]
    if len(stored) != n_pods + n_delta:
        raise AssertionError(f"{len(stored)} pods in the store")
    check_capacity(nodes, stored)
    placed = sum(1 for p in stored if p["spec"].get("nodeName"))
    log(f"served: {placed}/{len(stored)} placed, no node over capacity")
    check_device_counters(metrics)
    return metrics


def parity_phase(
    client: _Client,
    n_nodes: int = PARITY_NODES,
    n_pods: int = PARITY_PODS,
    seed: int = 0,
) -> None:
    """The server's sequential pass places every pod where the oracle
    does."""
    from kube_scheduler_simulator_tpu.sched.oracle import Oracle
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster

    nodes, pods = synthetic_cluster(n_nodes, n_pods, seed=seed + 1)
    client.call("PUT", "/reset")
    client.call("POST", "/import", {"nodes": nodes, "pods": pods})
    t0 = time.perf_counter()
    client.call("POST", "/schedule")
    log(f"parity: sequential pass {n_pods} x {n_nodes} (compile + run) "
        f"{time.perf_counter() - t0:.3f}s")
    served = {
        p["metadata"]["name"]: p["spec"].get("nodeName") or ""
        for p in client.call("GET", "/resources/pods")["items"]
    }
    want = {p["metadata"]["name"]: "" for p in pods}
    oracle = Oracle(
        [dict(n) for n in nodes], [dict(p) for p in pods],
        _server_config(client),
    )
    for r in oracle.schedule_all():
        want[r.pod_name] = r.selected_node if r.status == "Scheduled" else ""
    diff = [k for k in want if want[k] != served.get(k)]
    if diff:
        k = diff[0]
        raise AssertionError(
            f"parity: {len(diff)} pods differ from the oracle, e.g. {k}: "
            f"served {served.get(k)!r}, oracle {want[k]!r}"
        )
    log(f"parity: {len(want)} placements equal the oracle's, pod for pod")


def _server_config(client: _Client):
    from kube_scheduler_simulator_tpu.sched.config import SchedulerConfiguration

    return SchedulerConfiguration.from_dict(
        client.call("GET", "/schedulerconfiguration")
    )


def one_chip(seed: int) -> None:
    server, client = start_server()
    try:
        served_phase(client, seed=seed)
        parity_phase(client, seed=seed)
        check_device_counters(client.call("GET", "/metrics"))
        log("failover, eager and degraded counters are 0")
    finally:
        server.shutdown()


# -- the four-chip mesh ----------------------------------------------------


def mesh_phase(
    devices,
    n_nodes: int = MESH_NODES,
    n_pods: int = MESH_PODS,
    seq_variants: int = MESH_SEQ_VARIANTS,
    gang_variants: int = MESH_GANG_VARIANTS,
    seed: int = 0,
) -> None:
    """Sequential and gang sweeps on a (2 x 2) mesh of `devices`, each
    against the unsharded single-device program for variant 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import (
        BatchedScheduler,
        supported_config,
    )
    from kube_scheduler_simulator_tpu.engine.gang import GangScheduler
    from kube_scheduler_simulator_tpu.parallel import GangSweep, WeightSweep, build_mesh
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster

    reps, shards = MESH_SHAPE
    devices = list(devices)[: reps * shards]
    mesh = build_mesh(replicas=reps, node_shards=shards, devices=devices)
    nodes, pods = synthetic_cluster(n_nodes, n_pods, seed=seed)
    enc = encode_cluster(nodes, pods, supported_config(), policy=TPU32)
    log(f"mesh {reps}x{shards}: {n_pods} pods x {n_nodes} nodes")

    def spans_mesh(label, tree):
        for leaf in jax.tree_util.tree_leaves(tree):
            if len(leaf.sharding.device_set) != len(devices):
                raise AssertionError(
                    f"{label}: an input spans {len(leaf.sharding.device_set)} "
                    f"of {len(devices)} devices"
                )

    sweep = WeightSweep(enc, mesh=mesh)
    spans_mesh("seq sweep", sweep._args)
    wbase = np.asarray(sweep.sched.weights)
    weights = np.stack([wbase + i for i in range(seq_variants)]).astype(wbase.dtype)
    t0 = time.perf_counter()
    _, sel = sweep.run(weights)
    sel = np.asarray(sel)
    log(f"seq sweep {seq_variants} variants (compile + run) "
        f"{time.perf_counter() - t0:.3f}s")
    if not (sel >= 0).all():
        raise AssertionError("seq sweep left pods unscheduled")
    solo = BatchedScheduler(enc, record=False)
    _, solo_sel = jax.jit(solo.run_fn)(
        enc.arrays, enc.state0, jnp.asarray(enc.queue), jnp.asarray(weights[0])
    )
    if not (sel[0] == np.asarray(solo_sel)).all():
        raise AssertionError("seq sweep variant 0 differs from the solo program")
    log("seq sweep: variant 0 equals the unsharded single-chip program")

    gsweep = GangSweep(enc, mesh=mesh, chunk=256)
    spans_mesh("gang sweep", gsweep._args)
    t0 = time.perf_counter()
    assignments, rounds = gsweep.run(weights[:gang_variants])
    gplaced = gsweep.placements(assignments)
    log(f"gang sweep {gang_variants} variants (compile + run) "
        f"{time.perf_counter() - t0:.3f}s, max {int(np.asarray(rounds).max())} rounds")
    for d in gplaced:
        if not all(d.values()):
            raise AssertionError("gang sweep left pods unscheduled")
    solo_gang = GangScheduler(enc, chunk=256)
    solo_gang.run(weights=np.asarray(weights[0]))
    if gplaced[0] != solo_gang.placements():
        raise AssertionError("gang sweep variant 0 differs from the solo program")
    log("gang sweep: variant 0 equals the unsharded single-chip program")

    for d in devices:
        stats = d.memory_stats()
        in_use = (stats or {}).get("bytes_in_use", 0)
        if d.platform == "tpu" and in_use <= 0:
            raise AssertionError(f"{d} holds no bytes")
        log(f"{d}: bytes_in_use {in_use if stats else 'not reported'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # a fixed program set: no background compiles of buckets this smoke
    # never reaches
    os.environ.setdefault("KSS_NO_SPECULATIVE_COMPILE", "1")
    devices = require_tpu(args.chips)
    log(f"{len(devices)} x {devices[0].device_kind} ({devices[0].platform})")
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(devices, seed=args.seed)
    else:
        one_chip(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
