"""Benchmark: the batched TPU engine on the FULL default plugin set.

Three measurements on the real chip:

  1. `single`  — one scheduling pass, 2048 pods x 256 nodes: the
     sequential-parity mode (bit-identical placements to the reference's
     one-pod-at-a-time loop).
  2. `sweep`   — the Monte-Carlo axis (BASELINE config #4): 32 policy
     variants vmapped over the same cluster in ONE XLA program. This is
     the workload the north star counts (pods x variants decisions) and
     what fills the chip: the per-step kernels are latency-bound alone,
     so variants supply the parallel work. The sweep config disables the
     DefaultPreemption postFilter: under vmap the preemption lax.cond
     lowers to a both-branches select, so the full victim dry-run would
     run for EVERY pod in EVERY variant — score-weight sweeps don't
     change preemption semantics anyway (the preemption sweep is its own
     measurement, `_sweep_preempt_probe`).
  3. `atscale` — BASELINE config #2 shape (10k pods x 1k nodes), single
     pass, full default set incl. preemption, record=False.
  4. `affinity` — BASELINE config #3 shape (5k pods x 500 nodes of
     required anti-affinity chains + cross-service zone affinity),
     single pass, record=False — the InterPodAffinity stress shape.

Primary metric (the one JSON line): sweep decisions/sec/chip, where one
decision = one pod through Filter→Score→Normalize→select→bind over every
node under one policy variant.

`vs_baseline` is measured against this repo's pure-Python oracle on a
sample of the same workload — the reference itself publishes no numbers
and cannot run in this image (no Go toolchain, no etcd; see BASELINE.md).
The oracle is a faithful per-pod reimplementation of the reference's
sequential scheduling loop, so the ratio compares like semantics, but it
is NOT a measurement of the Go binary.

Timing: every timed call ends in a host transfer of its output
(`np.asarray`), which waits for the device.

One process per chip: every probe that touches the device runs in this
process. The only children are CPU-pinned (`--lifecycle-probe`,
`--fleet-probe`) or run before this process touches JAX (the
`--cold-start` probes, whose process start is the measurement).
"""

from __future__ import annotations

import json
import os as _os
import time

N_NODES = 256
N_PODS = 2048
N_VARIANTS = 32
SCALE_NODES = 1024
SCALE_PODS = 10_000
UNROLL = 4  # scan unroll: ~13% step-overhead win at moderate compile cost
BASELINE_PODS = 48  # oracle sample (sequential python, full plugin set)
AFF_NODES = 500
AFF_PODS = 5000


def _enable_compile_cache() -> None:
    """Point JAX at the persistent compilation cache (the single
    definition in utils/compilecache.py — JAX_COMPILATION_CACHE_DIR when
    set, else the repo-local default). Every bench entry point calls this so repeat
    compiles of an identical program (including the AOT
    lower().compile() the cost telemetry takes) are disk hits, not
    fresh XLA compiles."""
    from kube_scheduler_simulator_tpu.utils.compilecache import (
        enable_compile_cache,
    )

    enable_compile_cache()


def _best_of(fn, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _arm_ledger() -> None:
    """Arm the program ledger BEFORE any engine is built (ledger hooking
    happens at jit-wrap time): the gang and encoding probes count device
    dispatches per pass from it, and its per-call record (a locked
    counter bump) is noise against a multi-ms pass."""
    _os.environ["KSS_PROGRAM_LEDGER"] = "1"


def _gang_probe(
    mode: str = "dynamic", shape: str = "bench",
    inner_iters: int = 64, window: "int | None" = None,
) -> dict:
    """The gang scheduler at the bench shape or (shape=atscale) the
    BASELINE #2 shape (10k pods x 1k nodes) — the step-count-reduction
    claim: ~a-dozen dense rounds instead of 10k dependent scan steps.
    `mode` "dynamic" is the fused `gang.fixpoint` (one dispatch per
    pass); "static" the counted-loop variant; "hybrid" static rounds
    with while-loop matching. Returns the probe's JSON dict
    (`bench.py --gang-probe=<mode>` prints it)."""
    _arm_ledger()
    import numpy as np

    from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import supported_config
    from kube_scheduler_simulator_tpu.engine.gang import GangScheduler
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster
    from kube_scheduler_simulator_tpu.utils import ledger as ledger_mod
    from kube_scheduler_simulator_tpu.utils.metrics import cost_fields

    if shape == "atscale":
        n_nodes, n_pods = SCALE_NODES, SCALE_PODS
        seed, chunk, reps = 7, 256, 1
    else:
        n_nodes, n_pods = N_NODES, N_PODS
        seed, chunk, reps = 42, 128, 3
    nodes, pods = synthetic_cluster(n_nodes, n_pods, seed=seed)
    enc = encode_cluster(nodes, pods, supported_config(), policy=TPU32)
    # --gang-inner=K trades matching depth for rounds; --gang-window=W:
    # queue-prefix windowed rounds (a live round is mostly evaluation,
    # and only ~N of the pending pods can commit per round)
    kw: dict = {"chunk": chunk, "inner_iters": inner_iters}
    if window is not None:
        kw["eval_window"] = window
    if mode == "static":
        gang = GangScheduler(enc, loop="static", **kw)
    elif mode == "hybrid":
        gang = GangScheduler(enc, loop="static", inner_loop="dynamic", **kw)
    else:
        gang = GangScheduler(enc, **kw)

    # measure through run(): it owns the static auto-resume passes and
    # the preemption phases — the number prices the whole schedule
    def once():
        state, rounds = gang.run()
        np.asarray(state.assignment)
        return state, rounds

    state, rounds = once()  # compile + warm; deterministic → reuse below
    best = _best_of(once, reps=reps)
    result = {
        "gang_dps": round(n_pods / best, 1),
        "mode": mode,
        **({"window": window} if window else {}),
        **({"inner_iters": inner_iters} if inner_iters != 64 else {}),
        "shape": f"{n_pods}x{n_nodes}",
        "rounds": int(np.asarray(rounds)),
        "scheduled": int((np.asarray(state.assignment) >= 0).sum()),
        "pods": n_pods,
    }

    # device dispatches per schedule, counted by the ledger over ONE
    # warm drive: dynamic mode's fused `gang.fixpoint` must report
    # exactly 1; static/hybrid keep the host auto-resume driver, so
    # their count is the per-resume dispatch tally. Counted as a calls
    # DELTA (reset() would orphan the live wrappers' record handles).
    def _gang_calls():
        return {
            rec["label"]: rec["calls"]
            for rec in ledger_mod.LEDGER.snapshot()["programs"]
            if rec["label"].startswith("gang.")
        }

    before = _gang_calls()
    once()
    result["gang_dispatches_per_pass"] = sum(
        calls - before.get(label, 0)
        for label, calls in _gang_calls().items()
    )
    # XLA cost model of ONE compiled gang pass (run() may chain several
    # under auto-resume/preempt phases — per-pass work, not per-schedule)
    order, _ = gang.order_arrays()
    result.update(
        cost_fields(
            gang._run,
            (enc.arrays, enc.state0, order, gang.weights),
            per="pass",
            label="bench.gang",
        )
    )
    return result


def _gang_sweep_probe(window: "int | None" = None) -> dict:
    """V policy-weight variants x the gang fixpoint, vmapped into ONE
    program (`GangSweep(loop="static")`) at the bench shape — the
    north-star program shape (variants x dense rounds x nodes): the
    variant axis amortizes each round's dependent small ops exactly like
    the sequential sweep amortizes step latency. Returns the probe's
    JSON dict (`bench.py --gang-sweep-probe` prints it)."""
    import jax.numpy as jnp
    import numpy as np

    from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import supported_config
    from kube_scheduler_simulator_tpu.parallel import GangSweep
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster
    from kube_scheduler_simulator_tpu.utils.metrics import cost_fields

    n_nodes, n_pods, n_var = N_NODES, N_PODS, 8
    nodes, pods = synthetic_cluster(n_nodes, n_pods, seed=42)
    enc = encode_cluster(nodes, pods, supported_config(), policy=TPU32)
    # --gang-window: the eval_window is a STATIC per-round shrink (row-
    # subset rounds), so unlike compaction it survives the variant vmap
    sweep = GangSweep(enc, chunk=128, loop="static", eval_window=window)
    wbase = np.asarray(sweep.gang.weights)
    variants = np.stack([wbase + i for i in range(n_var)]).astype(np.int32)

    def once():
        assignments, rounds = sweep.run(variants)
        return np.asarray(assignments), np.asarray(rounds)

    assigns, rounds = once()  # compile + warm
    best = _best_of(once, reps=2)
    result = {
        "gang_sweep_dps": round(n_var * n_pods / best, 1),
        "variants": n_var,
        **({"window": window} if window else {}),
        "shape": f"{n_pods}x{n_nodes}",
        "rounds_max": int(rounds.max()),
        "scheduled": int((assigns >= 0).sum()),
        "pods": n_var * n_pods,
    }
    result.update(
        cost_fields(
            sweep._vrun,
            (*sweep._args, jnp.asarray(variants, sweep.enc.policy.score)),
            per="pass",
            label="bench.gang_sweep",
            variants=n_var,
        )
    )
    return result


def _encoding_probe() -> dict:
    """The packed low-precision encoding plane (KSS_DTYPE_POLICY=packed,
    engine/packing.py) measured against the TPU32 baseline; returns the
    probe's JSON dict (`bench.py --encoding-probe` prints it). The vehicle is the label-rich affinity cluster — the shape the
    bitpacked mask planes target (the plain synthetic cluster carries no
    label vocabulary, so its presence planes are tiny and the byte win
    understates). Per policy: encoded-cluster device bytes
    (arrays/state0/total, the same accounting the perf-smoke packing
    gate reads), host→device delta-transfer bytes for one warm bind
    burst (DeltaEncoder.last_transfer_bytes), warm decisions/s, and
    ledger-counted device dispatches per warm pass — the in-kernel
    unpack contract is ZERO extra programs, so the counts must be equal.
    Placements are cross-checked identical BEFORE anything is returned:
    a byte win that moves a pod is a bug, not a result."""
    _arm_ledger()
    import numpy as np

    from kube_scheduler_simulator_tpu.engine import PACKED, TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.delta import DeltaEncoder
    from kube_scheduler_simulator_tpu.engine.engine import (
        BatchedScheduler,
        supported_config,
    )
    from kube_scheduler_simulator_tpu.engine.packing import encoded_device_bytes
    from kube_scheduler_simulator_tpu.models.store import ResourceStore
    from kube_scheduler_simulator_tpu.synth import synthetic_affinity_cluster
    from kube_scheduler_simulator_tpu.utils import ledger as ledger_mod

    n_nodes, n_pods = AFF_NODES, AFF_PODS
    nodes, pods = synthetic_affinity_cluster(n_nodes, n_pods, seed=11)
    cfg = supported_config()
    node_names = [m["metadata"]["name"] for m in nodes]

    def _seq_calls():
        # keyed (label, fingerprint): BOTH policies' programs share the
        # "seq.run" label (a policy flip is a distinct compile, not a
        # distinct site), so a label-only dict would hide one of them
        return {
            (rec["label"], rec["fingerprint"]): rec["calls"]
            for rec in ledger_mod.LEDGER.snapshot()["programs"]
            if rec["label"].startswith("seq.")
        }

    def measure(policy):
        enc = encode_cluster(nodes, pods, cfg, policy=policy)
        sc = BatchedScheduler(enc, record=False, unroll=UNROLL)

        def once():
            state, _ = sc.run()
            return np.asarray(state.assignment)

        placements = once()  # compile + warm
        best = _best_of(once, reps=3)
        # dispatches per WARM pass, as a ledger calls delta (reset()
        # would orphan live record handles)
        before = _seq_calls()
        once()
        dispatches = sum(
            calls - before.get(label, 0)
            for label, calls in _seq_calls().items()
        )
        # delta-transfer bytes: replay the same cluster through the
        # watch-store path, then bind a burst — the bytes a warm tenant
        # ships per reconcile under this policy (packed mask rows and
        # narrowed int rows travel at their stored width)
        store = ResourceStore()
        for m in nodes:
            store.apply("nodes", m)
        for m in pods:
            store.apply("pods", m)
        delta = DeltaEncoder(policy=policy)
        _, info = delta.encode(store, cfg)
        assert info["mode"] == "full", info
        for i in range(16):
            store.apply(
                "pods",
                {
                    **pods[i],
                    "spec": {
                        **pods[i]["spec"],
                        "nodeName": node_names[i % len(node_names)],
                    },
                },
            )
        _, info = delta.encode(store, cfg)
        return {
            "device_bytes": encoded_device_bytes(enc),
            "delta_transfer_bytes": int(delta.last_transfer_bytes),
            "delta_mode": info["mode"],
            "warm_dps": round(n_pods / best, 1),
            "dispatches_per_pass": dispatches,
        }, placements

    base, base_asg = measure(TPU32)
    packed, packed_asg = measure(PACKED)
    if not np.array_equal(base_asg, packed_asg):
        raise SystemExit(
            "encoding-probe: PACKED placements diverge from TPU32"
        )
    result = {
        "shape": f"{n_pods}x{n_nodes}",
        "policies": {"tpu32": base, "packed": packed},
        # the headline ratios: encoded-cluster device bytes and warm
        # delta-transfer bytes, TPU32 over PACKED (>= 2x is the gate)
        "bytes_ratio": round(
            base["device_bytes"]["total"] / packed["device_bytes"]["total"],
            2,
        ),
        "delta_bytes_ratio": round(
            base["delta_transfer_bytes"]
            / max(packed["delta_transfer_bytes"], 1),
            2,
        ),
        "warm_dps_ratio": round(
            packed["warm_dps"] / base["warm_dps"], 3
        ),
        "extra_dispatches": packed["dispatches_per_pass"]
        - base["dispatches_per_pass"],
        "placements_match": True,
    }
    return result


def _lifecycle_probe(events: int = 300, n_nodes: int = 64, seed_pods: int = 500):
    """CPU subprocess mode (`bench.py --lifecycle-probe`): the churn-heavy
    lifecycle measurement — a seeded Poisson arrival storm (plus cordon
    flaps) against a pre-loaded cluster, driven through the full service
    stack (store events → delta encoder → compiled engine → write-backs).
    The number that matters is events/sec of simulated cluster churn and
    the encode-time fraction: before the incremental encoder, encode
    dominated this wall-clock; now steady-state passes are O(Δ). One
    JSON line, same contract as the other probes. Sized to stay inside
    one capacity bucket AND below its 80% speculation watermark
    (seed 500 + 300 arrivals = 800 < 819) so the warm run measures the
    steady state — no bucket crossing, and no background speculative
    compile competing for the box during the measurement.

    Pinned to the CPU backend: the measurement is host-path throughput,
    and the parent process holds the chip."""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    # the fleet & memory observatory rides the probe (docs/
    # observability.md): peak HBM bytes + the end-of-run fragmentation
    # index join the headline so BENCH_r* files carry a memory
    # trajectory. Sampled every 8th pass — placements are
    # sampling-invariant (test-pinned), and the cadence keeps the
    # per-pass host fetch out of the throughput number's noise floor.
    _os.environ.setdefault("KSS_FLEET_STATS", "1")
    _os.environ.setdefault("KSS_FLEET_SAMPLE", "8")
    # the SLO plane rides the probe too (utils/slo.py): per-objective
    # compliance + alerts fired join the headline — placements are
    # pinned identical with the plane armed or off, so the throughput
    # number is untouched (tests/test_slo.py)
    _os.environ.setdefault("KSS_SLO", "1")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from kube_scheduler_simulator_tpu.lifecycle.engine import LifecycleEngine
    from kube_scheduler_simulator_tpu.scenario.chaos import ChaosSpec

    nodes = [
        {
            "metadata": {"name": f"bn{i}"},
            "status": {
                "allocatable": {"cpu": "64", "memory": "128Gi", "pods": "110"}
            },
        }
        for i in range(n_nodes)
    ]
    pods = [
        {
            "metadata": {"name": f"seed-{i}"},
            "spec": {
                "nodeName": f"bn{i % n_nodes}",
                "containers": [
                    {
                        "name": "c",
                        "resources": {
                            "requests": {"cpu": "250m", "memory": "256Mi"}
                        },
                    }
                ],
            },
        }
        for i in range(seed_pods)
    ]
    spec = ChaosSpec.from_dict(
        {
            "name": "bench-lifecycle",
            "seed": 42,
            "horizon": 10_000.0,
            "schedulerMode": "gang",
            # the async pipelined dispatch (byte-identical trace,
            # parity-pinned): device execution overlaps host-side event
            # application, decode is one batched device transfer
            "pipeline": "async",
            "snapshot": {"nodes": nodes, "pods": pods},
            "arrivals": [
                {
                    "kind": "poisson",
                    "rate": 1.0,
                    "count": events,
                    "template": {
                        "metadata": {"name": "churn"},
                        "spec": {
                            "containers": [
                                {
                                    "name": "c",
                                    "resources": {
                                        "requests": {
                                            "cpu": "250m",
                                            "memory": "256Mi",
                                        }
                                    },
                                }
                            ]
                        },
                    },
                },
            ],
            "faults": [
                {"at": 50.0, "action": "cordon", "node": "bn0"},
                {"at": 120.0, "action": "uncordon", "node": "bn0"},
            ],
        }
    )
    eng = LifecycleEngine(spec)
    result = eng.run()
    phases = result["metrics"]["phases"]
    wall = result["wallSeconds"]
    # warm-steady-state view: drop the slowest pass (the compile) so the
    # throughput number reflects the O(Δ) regime the PR targets
    warm = sorted(x["wallSeconds"] for x in eng.timings)
    warm_wall = sum(warm[:-1]) if len(warm) > 1 else wall
    warm_events = max(1, result["events"] - 1)
    line = {
        "lifecycle_events_per_s": round(result["events"] / wall, 1)
        if wall > 0
        else 0.0,
        "warm_events_per_s": round(warm_events / warm_wall, 1)
        if warm_wall > 0
        else 0.0,
        "phase": result["phase"],
        "events": result["events"],
        "passes": result["passes"],
        "arrived": result["pods"]["arrived"],
        "shape": f"{seed_pods}+{events}x{n_nodes}",
        "encode_frac": round(phases["encodeSeconds"] / wall, 4)
        if wall > 0
        else 0.0,
        "delta_encodes": phases["deltaEncodes"],
        "full_encodes": phases["fullEncodes"],
        "engine_builds": phases["engineBuilds"],
        "pipeline": "async",
        # compile-broker counters (utils/broker.py): serving-thread
        # compile stalls vs broker-warm passes vs background compiles
        "compile_hits": phases["compileHits"],
        "compile_misses": phases["compileMisses"],
        "speculative_compiles": phases["speculativeCompiles"],
        "stall_seconds": phases["stallSeconds"],
        # run-supervision counters (docs/resilience.md): a healthy bench
        # reports zeros — any non-zero means the degradation ladder
        # carried passes the compiled path could not serve
        "compile_retries": phases["compileRetries"],
        "eager_fallbacks": phases["eagerFallbacks"],
        "degraded_passes": phases["degradedPasses"],
        "broker_worker_crashes": phases["brokerWorkerCrashes"],
    }
    # the memory trajectory (utils/fleetstats.py): peak device bytes
    # across the run's samples (allocator stats when the backend
    # reports them, the live-buffer census on CPU) and the end-of-run
    # fragmentation index + pending depth
    from kube_scheduler_simulator_tpu.utils import fleetstats

    frec = fleetstats.active()
    samples = frec.snapshot() if frec is not None else []
    if samples:
        peaks = [
            s["hbm"].get("peakBytesInUse")
            or s["hbm"].get("bytesInUse")
            or s.get("buffers", {}).get("liveBytes", 0)
            for s in samples
        ]
        last = samples[-1]
        line["fleet_samples"] = frec.emitted
        line["peak_hbm_bytes"] = max(peaks)
        line["fragmentation_index"] = last["fleet"]["fragmentationIndex"]
        line["pending_pods_end"] = last["fleet"]["pendingPods"]
    # the SLO block (utils/slo.py): per-objective compliance over the
    # run + alerts fired — the judged view of the same signals the
    # counters above report raw
    slo_plane = eng.scheduler.metrics.slo_plane()
    if slo_plane is not None:
        line["slo"] = slo_plane.headline()
    # flight-recorder accounting when the probe ran under KSS_TRACE=1
    # (off by default: the headline number must measure the untraced
    # serving path — docs/observability.md)
    from kube_scheduler_simulator_tpu.utils import telemetry

    rec = telemetry.active()
    if rec is not None:
        line["trace_events"] = rec.emitted
        line["trace_dropped"] = rec.dropped
    print(json.dumps(line), flush=True)


def _cold_start_probe(n_nodes: int = 32, n_pods: int = 128):
    """Subprocess mode (`bench.py --cold-start`): **time-to-first-
    scheduled-pod from a cold process** — the ROADMAP #1 headline the
    AOT-bundle work will be gated on. This probe process IS the cold
    process: the clock (utils/ledger.COLD_START) starts at the first
    package import, the boot probe / first encode / first compile /
    first pass marks land as the serving path reaches them, and the
    one JSON line reports the phase breakdown plus the headline
    `cold_start_s` (== timeToFirstPassSeconds). `python bench.py` runs
    it as a child before it touches JAX itself, or run it standalone.

    Import order is the measurement: the ledger module goes FIRST —
    its import stamps the clock origin — so jax's own module-import
    wall (a real part of any cold rolling restart, and included on the
    server path, which imports the package before touching jax) counts
    toward `cold_start_s` instead of silently escaping it."""
    from kube_scheduler_simulator_tpu.utils import ledger as ledger_mod

    _enable_compile_cache()
    import jax

    from kube_scheduler_simulator_tpu.models.store import ResourceStore
    from kube_scheduler_simulator_tpu.server.service import SchedulerService

    platform = jax.devices()[0].platform  # the boot probe
    ledger_mod.COLD_START.mark("bootProbe")
    store = ResourceStore()
    for i in range(n_nodes):
        store.apply(
            "nodes",
            {
                "metadata": {"name": f"cn{i}"},
                "status": {
                    "allocatable": {
                        "cpu": "64", "memory": "128Gi", "pods": "110"
                    }
                },
            },
        )
    for i in range(n_pods):
        store.apply(
            "pods",
            {
                "metadata": {"name": f"cold-{i}"},
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "resources": {
                                "requests": {
                                    "cpu": "250m", "memory": "256Mi"
                                }
                            },
                        }
                    ]
                },
            },
        )
    svc = SchedulerService(store)
    placements, _, _ = svc.schedule_gang(record=False)
    snap = ledger_mod.COLD_START.snapshot()
    line = {
        "cold_start_s": snap["timeToFirstPassSeconds"],
        "cold_start_phases": snap["phases"],
        "scheduled": sum(1 for v in placements.values() if v),
        "pods": n_pods,
        "shape": f"{n_pods}x{n_nodes}",
        "platform": platform,
        # the byte-deterministic placement digest: the AOT-bundle gate
        # compares it across the empty-dir and warm-dir runs
        "placements_sha256": _placements_digest(placements),
    }
    # AOT-bundle accounting (utils/bundles.py): with KSS_AOT_BUNDLES=1
    # the line proves WHICH path served the boot — loads on a warm
    # bundle dir, saves on an empty one — and the flush guarantees the
    # warm dir is complete before the parent launches the second run
    from kube_scheduler_simulator_tpu.utils import bundles

    if bundles.bundles_enabled():
        bundles.STORE.flush(60.0)
        line["bundles"] = bundles.STORE.stats()
    print(json.dumps(line), flush=True)


def _placements_digest(placements: dict) -> str:
    import hashlib

    doc = json.dumps(
        sorted((ns, name, node) for (ns, name), node in placements.items())
    )
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


def _concurrency_probe(
    n_nodes: int = 16, n_pods: int = 48, rounds: int = 3
) -> dict:
    """**Aggregate
    decisions/s/process vs concurrent-session count** with cross-tenant
    continuous batching armed (server/batchplane.py, docs/sessions.md)
    — ROADMAP #2's headline, the curve that says "millions of users".

    A serialized solo baseline (one tenant, batching off) anchors the
    comparison; then 1/2/4/8 bucket-compatible sessions schedule
    concurrently through one SessionManager + BatchPlane, each level
    re-pending its pods between timed rounds so every round schedules
    the full queue. Decisions = pods evaluated; the wall is the
    concurrent phase's wall-clock (barrier-aligned), so the reported
    number is per-PROCESS aggregate throughput, exactly what one more
    concurrent tenant should no longer flatten. Returns the probe's JSON
    dict (`bench.py --concurrency-probe` prints it)."""
    import threading

    from kube_scheduler_simulator_tpu.server.batchplane import BatchPlane
    from kube_scheduler_simulator_tpu.server.service import SimulatorService
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager

    def node_doc(j):
        return {
            "metadata": {"name": f"cn{j}"},
            "status": {
                "allocatable": {
                    "cpu": "64", "memory": "128Gi", "pods": "110"
                }
            },
        }

    def pod_doc(i, j):
        return {
            "metadata": {"name": f"cp{j}", "namespace": "default"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "resources": {
                            "requests": {
                                "cpu": f"{100 + 10 * i + (j % 7) * 20}m",
                                "memory": "256Mi",
                            }
                        },
                    }
                ]
            },
        }

    def snapshot(i):
        # identical shapes across tenants (one batch key), distinct
        # request values (distinct placements — no degenerate sharing)
        return {
            "nodes": [node_doc(j) for j in range(n_nodes)],
            "pods": [pod_doc(i, j) for j in range(n_pods)],
        }

    def repend(svc, i):
        for j in range(n_pods):
            svc.store.delete("pods", f"cp{j}", "default")
        svc.import_({"pods": snapshot(i)["pods"]})

    # -- serialized solo baseline (batching off) -------------------------
    mgr = SessionManager(
        SimulatorService(), max_sessions=12, max_concurrent_passes=8
    )
    sess, _ = mgr.create(name="solo", snapshot=snapshot(0))
    sess.service.scheduler.schedule()  # warm: compile + caches
    solo_wall = 0.0
    for _r in range(rounds):
        repend(sess.service, 0)
        t0 = time.perf_counter()
        sess.service.scheduler.schedule()
        solo_wall += time.perf_counter() - t0
    baseline_dps = rounds * n_pods / solo_wall if solo_wall > 0 else 0.0
    mgr.shutdown()

    # -- batched concurrency ladder --------------------------------------
    levels = (1, 2, 4, 8)
    curve: dict = {}
    for conc in levels:
        mgr = SessionManager(
            SimulatorService(),
            max_sessions=conc + 2,
            max_concurrent_passes=max(8, conc),
        )
        # a generous window so barrier-aligned arrivals reliably form
        # FULL windows (a full window flushes immediately, so the
        # window length is an upper bound, not a per-pass tax; partial
        # windows would also scatter fills across batch buckets and
        # re-pay the vmapped compile mid-measurement)
        plane = BatchPlane(
            window_ms=150.0,
            max_sessions=conc,
            metrics=mgr.get("default").service.scheduler.metrics,
        )
        mgr.batch_plane = plane
        mgr.get("default").service.scheduler.batch_plane = plane
        sessions = [
            mgr.create(name=f"t{i}", snapshot=snapshot(i))[0]
            for i in range(conc)
        ]

        def one_round(timed: bool) -> float:
            start = threading.Barrier(conc + 1)
            errors: list = []

            def run(i):
                try:
                    start.wait(timeout=120)
                    with mgr.pass_slot():
                        sessions[i].service.scheduler.schedule()
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(repr(e))

            threads = [
                threading.Thread(target=run, args=(i,)) for i in range(conc)
            ]
            for t in threads:
                t.start()
            start.wait(timeout=120)
            t0 = time.perf_counter()
            for t in threads:
                t.join(timeout=900)
            wall = time.perf_counter() - t0
            if errors:
                raise RuntimeError(f"concurrency {conc}: {errors}")
            return wall if timed else 0.0

        for i in range(conc):
            repend(sessions[i].service, i)
        one_round(timed=False)  # warm: the batched program's compile
        total_wall = 0.0
        for _r in range(rounds):
            for i in range(conc):
                repend(sessions[i].service, i)
            total_wall += one_round(timed=True)
        agg_dps = (
            rounds * conc * n_pods / total_wall if total_wall > 0 else 0.0
        )
        default_snap = (
            mgr.get("default").service.scheduler.metrics.snapshot()
        )
        curve[str(conc)] = {
            "aggregate_dps": round(agg_dps, 1),
            "speedup_vs_solo": round(agg_dps / baseline_dps, 2)
            if baseline_dps
            else None,
            "batch_windows": default_snap["phases"]["batchWindows"],
            "batch_occupancy": default_snap["batching"]["batchOccupancy"],
        }
        mgr.shutdown()
    return {
        "baseline_solo_dps": round(baseline_dps, 1),
        "pods_per_session": n_pods,
        "nodes": n_nodes,
        "rounds": rounds,
        "concurrency": curve,
    }


def _fleet_probe(n_nodes: int = 8, n_pods: int = 24, rounds: int = 2):
    """CPU subprocess mode (`bench.py --fleet-probe`): **aggregate
    decisions/s/HOST vs fleet width** (fleet/router.py, docs/fleet.md)
    — what horizontal workers buy on one machine when each session's
    passes stay affine to one process and all workers share the AOT
    bundle store.

    A serialized in-process baseline (the single-process server's
    scheduling path, no HTTP) anchors the comparison; then fleets of
    1/2/4 REAL spawned workers each serve one session per worker, all
    sessions scheduling concurrently through the router. Decisions =
    pods evaluated; the wall is the concurrent phase's (barrier-aligned)
    wall-clock, so the number is per-host aggregate throughput.
    Re-pending happens OUTSIDE the timed window — the probe measures
    scheduling, not pod CRUD.

    The later fleets boot against the bundle dir the first fleet
    warmed, and the probe's last act measures **time-to-first-scheduled
    -pod on a bundle-warmed worker**: a fresh 1-worker fleet from
    process spawn to the first pod bound, everything served from the
    shared store. Pinned to CPU (host-throughput measurement); one JSON
    line."""
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from kube_scheduler_simulator_tpu.fleet import FleetRouter
    from kube_scheduler_simulator_tpu.server.service import SimulatorService

    env = dict(
        _os.environ,
        JAX_PLATFORMS="cpu",
        KSS_AOT_BUNDLES="1",
        KSS_NO_SPECULATIVE_COMPILE="1",
        JAX_COMPILATION_CACHE_DIR=tempfile.mkdtemp(
            prefix="kss-fleet-bench-cache-"
        ),
    )
    env.pop("KSS_WORKER_ID", None)
    bundle_dir = tempfile.mkdtemp(prefix="kss-fleet-bench-bundles-")

    def node_doc(j):
        return {
            "metadata": {"name": f"fn{j}"},
            "status": {
                "allocatable": {"cpu": "64", "memory": "128Gi", "pods": "110"}
            },
        }

    def pod_doc(i, j):
        return {
            "metadata": {"name": f"fp{j}", "namespace": "default"},
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "resources": {
                            "requests": {
                                "cpu": f"{100 + 10 * i + (j % 7) * 20}m",
                                "memory": "256Mi",
                            }
                        },
                    }
                ]
            },
        }

    def _req(port, method, path, body=None, timeout=600):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            method=method,
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                raw = resp.read()
                return resp.status, json.loads(raw) if raw else None
        except urllib.error.HTTPError as e:
            raw = e.read()
            return e.code, json.loads(raw) if raw else None

    # -- serialized single-process baseline (no HTTP, no fleet) ----------
    svc = SimulatorService()
    for j in range(n_nodes):
        svc.store.apply("nodes", node_doc(j))
    svc.import_({"pods": [pod_doc(0, j) for j in range(n_pods)]})
    svc.scheduler.schedule()  # warm: compile + caches

    def repend_local(i):
        for j in range(n_pods):
            svc.store.delete("pods", f"fp{j}", "default")
        svc.import_({"pods": [pod_doc(i, j) for j in range(n_pods)]})

    solo_wall = 0.0
    for r in range(rounds):
        repend_local(r)
        t0 = time.perf_counter()
        svc.scheduler.schedule()
        solo_wall += time.perf_counter() - t0
    baseline_dps = rounds * n_pods / solo_wall if solo_wall > 0 else 0.0

    # -- the fleet ladder ------------------------------------------------
    def session_on(router, wid, prefix):
        for i in range(64):
            sid = f"{prefix}-{i}"
            w, _ = router.place_session({"id": sid})
            if w is not None and w.id == wid:
                code, _doc = _req(
                    router.port, "POST", "/api/v1/sessions", {"id": sid}
                )
                if code != 201:
                    raise RuntimeError(f"create {sid}: {code}")
                return sid
        raise RuntimeError(f"no id hashed to {wid} in 64 tries")

    def repend_http(router, sid, i):
        base = f"/api/v1/sessions/{sid}"
        for j in range(n_pods):
            _req(
                router.port, "DELETE", f"{base}/resources/pods/default/fp{j}"
            )
            _req(router.port, "PUT", f"{base}/resources/pods", pod_doc(i, j))

    curve: dict = {}
    for width in (1, 2, 4):
        router = FleetRouter(
            n_workers=width,
            fleet_dir=tempfile.mkdtemp(prefix=f"kss-fleet-bench-{width}-"),
            bundle_dir=bundle_dir,
            probe_interval_s=5.0,
            env=env,
        ).start()
        try:
            sids = [
                session_on(router, wid, f"b{width}")
                for wid in router.worker_ids()
            ]
            for sid in sids:
                base = f"/api/v1/sessions/{sid}"
                for j in range(n_nodes):
                    _req(
                        router.port,
                        "PUT",
                        f"{base}/resources/nodes",
                        node_doc(j),
                    )
                repend_http(router, sid, 0)
                code, _doc = _req(router.port, "POST", f"{base}/schedule")
                if code != 200:
                    raise RuntimeError(f"warm schedule on {sid}: {code}")

            def one_round() -> float:
                start = threading.Barrier(width + 1)
                errors: list = []

                def run(sid):
                    try:
                        start.wait(timeout=120)
                        code, _d = _req(
                            router.port,
                            "POST",
                            f"/api/v1/sessions/{sid}/schedule",
                        )
                        if code != 200:
                            errors.append(f"{sid}: {code}")
                    except Exception as e:  # noqa: BLE001 — surfaced below
                        errors.append(repr(e))

                threads = [
                    threading.Thread(target=run, args=(sid,)) for sid in sids
                ]
                for t in threads:
                    t.start()
                start.wait(timeout=120)
                t0 = time.perf_counter()
                for t in threads:
                    t.join(timeout=900)
                wall = time.perf_counter() - t0
                if errors:
                    raise RuntimeError(f"fleet width {width}: {errors}")
                return wall

            total_wall = 0.0
            for r in range(rounds):
                for sid in sids:
                    repend_http(router, sid, r + 1)
                total_wall += one_round()
            agg_dps = (
                rounds * width * n_pods / total_wall
                if total_wall > 0
                else 0.0
            )
            # router-added latency from the request ring
            # (/api/v1/fleet/requests, docs/observability.md):
            # routerSeconds is total wall minus time spent on worker
            # calls — the proxy's own overhead, p50/p99 so regressions
            # in the routing path show up in the campaign headline
            _code, ring = _req(router.port, "GET", "/api/v1/fleet/requests")
            added = sorted(
                float(e.get("routerSeconds") or 0.0)
                for e in (ring or {}).get("requests") or []
                if e.get("worker") is not None
            )

            def pct(q):
                if not added:
                    return None
                return round(
                    added[min(len(added) - 1, int(q * len(added)))] * 1e3, 3
                )

            curve[str(width)] = {
                "aggregate_dps": round(agg_dps, 1),
                "speedup_vs_single_process": round(agg_dps / baseline_dps, 2)
                if baseline_dps
                else None,
                "router_latency": {
                    "p50_ms": pct(0.50),
                    "p99_ms": pct(0.99),
                    "requests": len(added),
                },
            }
        finally:
            router.shutdown(drain=False)

    # -- time-to-first-scheduled-pod on a bundle-warmed worker -----------
    t0 = time.perf_counter()
    router = FleetRouter(
        n_workers=1,
        fleet_dir=tempfile.mkdtemp(prefix="kss-fleet-bench-warm-"),
        bundle_dir=bundle_dir,
        probe_interval_s=5.0,
        env=env,
    ).start()
    try:
        # the ladder's exact workload shape, so the warm worker's
        # engine program resolves from the store instead of compiling
        # (bundles are keyed by compile signature — a different shape
        # bucket would be an honest miss)
        base = "/api/v1/sessions/warm-1"
        _req(router.port, "POST", "/api/v1/sessions", {"id": "warm-1"})
        for j in range(n_nodes):
            _req(router.port, "PUT", f"{base}/resources/nodes", node_doc(j))
        for j in range(n_pods):
            _req(router.port, "PUT", f"{base}/resources/pods", pod_doc(0, j))
        code, out = _req(router.port, "POST", f"{base}/schedule")
        warm_ttfp = time.perf_counter() - t0
        if code != 200 or not out.get("scheduled"):
            raise RuntimeError(f"warm worker scheduled nothing: {code} {out}")
        _, mdoc = _req(router.port, "GET", "/api/v1/metrics")
        warm_bundles = (mdoc["workers"].get("w0") or {}).get("bundles") or {}
    finally:
        router.shutdown(drain=False)

    print(
        json.dumps(
            {
                "fleet_baseline_dps": round(baseline_dps, 1),
                "pods_per_session": n_pods,
                "nodes": n_nodes,
                "rounds": rounds,
                "fleet": curve,
                "warm_worker_first_pod_s": round(warm_ttfp, 3),
                "warm_worker_bundles": warm_bundles,
            }
        )
    )


def _sweep_preempt_probe() -> dict:
    """The Monte-Carlo sweep WITH the full default set incl.
    DefaultPreemption; "mode" carries the preemption strategy.
    `WeightSweep` defaults to the two-phase EVENT LOOP
    (`preempt="phase"`, parallel/sweep.py): the scan never carries the
    [N, P] victim dry-run — it stops at each variant's first failure, a
    single-pod preempt program handles it, the scan resumes. Same
    placements as masked mode (test-pinned). Returns the probe's JSON
    dict (`bench.py --sweep-preempt-probe` prints it)."""
    import numpy as np

    from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import supported_config
    from kube_scheduler_simulator_tpu.parallel import WeightSweep
    from kube_scheduler_simulator_tpu.synth import synthetic_cluster

    n_nodes, n_pods, n_var = N_NODES, N_PODS, N_VARIANTS
    nodes, pods = synthetic_cluster(n_nodes, n_pods, seed=42)
    enc = encode_cluster(nodes, pods, supported_config(), policy=TPU32)
    sweep = WeightSweep(enc)
    wbase = np.asarray(sweep.sched.weights)
    variants = np.stack([wbase + i for i in range(n_var)]).astype(np.int32)
    np.asarray(sweep.run(variants)[1])  # compile
    best = _best_of(lambda: np.asarray(sweep.run(variants)[1]), reps=2)
    return {
        "sweep_pre_dps": round(n_var * n_pods / best, 1),
        "variants": n_var,
        "shape": f"{n_pods}x{n_nodes}",
        "mode": sweep.preempt,
    }


def _child_probe(
    argv,
    timeout_s: float,
    key: str,
    *,
    device: bool = False,
    extra_env: "dict[str, str] | None" = None,
) -> "dict | None":
    """Run `bench.py <argv...>` as a child and return the last stdout
    JSON line carrying `key` (None when it fails or overruns its window
    — a timed-out child is killed).

    One process per chip: a `device` child may only start while this
    process has not imported JAX (a parent that has touched JAX holds
    the chip, and the child would fail or hang); every other child runs
    pinned to the CPU backend."""
    import subprocess
    import sys

    env = _os.environ.copy()
    if device:
        if "jax" in sys.modules:
            raise RuntimeError(
                f"bench: device child {argv} after this process imported "
                "JAX — it would not get the chip"
            )
    else:
        env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    try:
        proc = subprocess.run(
            [sys.executable, _os.path.abspath(__file__), *argv],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return None
    if proc.returncode != 0:
        return None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            out = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(out, dict) and key in out:
            return out
    return None


def _cold_start_probes() -> "tuple[dict | None, dict | None]":
    """Time-to-first-scheduled-pod from cold processes, run before this
    process touches JAX (each child needs the chip). Returns (cold,
    bundled): `cold` is one `--cold-start` child on the shared compile
    cache; `bundled` is the AOT-bundle gate (docs/performance.md) — the
    same probe twice over one empty bundle dir and one empty compile-
    cache dir: run 1 is the empty-everything cold start (it compiles and
    saves bundles), run 2 must deserialize instead of compiling, with
    byte-identical placements."""
    import shutil
    import tempfile

    cold = _child_probe(["--cold-start"], 900.0, "cold_start_s", device=True)
    bundle_dir = tempfile.mkdtemp(prefix="kss-bench-bundles-")
    cache_dir = tempfile.mkdtemp(prefix="kss-bench-cache-")
    bundle_env = {
        "KSS_AOT_BUNDLES": "1",
        "KSS_BUNDLE_DIR": bundle_dir,
        # a deliberately cold compile cache for the gate alone
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
        # deterministic program set: both runs compile/load exactly
        # the serving pass's programs, nothing speculative
        "KSS_NO_SPECULATIVE_COMPILE": "1",
    }
    try:
        empty, warm = (
            _child_probe(
                ["--cold-start"], 900.0, "cold_start_s",
                device=True, extra_env=bundle_env,
            )
            for _ in range(2)
        )
    finally:
        # serialized executables (tens of MB per campaign): never leak
        shutil.rmtree(bundle_dir, ignore_errors=True)
        shutil.rmtree(cache_dir, ignore_errors=True)
    if not (empty and warm):
        return cold, None
    return cold, {
        "emptyDirColdStartS": empty["cold_start_s"],
        "bundledColdStartS": warm["cold_start_s"],
        "speedup": round(empty["cold_start_s"] / warm["cold_start_s"], 2)
        if warm["cold_start_s"]
        else None,
        "bundleLoads": (warm.get("bundles") or {}).get("bundleLoads"),
        "bundleBypasses": (warm.get("bundles") or {}).get("bundleBypasses"),
        "placementsIdentical": (
            empty.get("placements_sha256") == warm.get("placements_sha256")
        ),
    }


def main(profile_dir: "str | None" = None):
    """`profile_dir` (from --profile=DIR): capture a JAX profiler trace
    (TensorBoard/XProf format) of one warm pass per in-process measured
    program — single, the headline sweep, atscale, affinity — into DIR,
    and print per-phase host timings to stderr as JSON: the SURVEY §5
    tracing artifact. The probes (gang, sweeps, batching, encoding) are
    not traced; their JSON blocks carry the throughput numbers instead.
    Off by default: the driver contract is ONE stdout JSON line either
    way."""
    import sys

    # the cold-process probes need the chip: they run before this
    # process imports JAX, and every device measurement after them runs
    # in this process
    cold, cold_bundled = _cold_start_probes()
    _arm_ledger()
    _enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import (
        BatchedScheduler,
        supported_config,
    )
    from kube_scheduler_simulator_tpu.sched.oracle import Oracle
    from kube_scheduler_simulator_tpu.synth import (
        synthetic_affinity_cluster,
        synthetic_cluster,
    )

    from kube_scheduler_simulator_tpu.sched.config import SchedulerConfiguration

    device = jax.devices()[0]
    platform = (
        f"{device.platform} ({device.device_kind}, "
        f"{len(jax.devices())} device(s))"
    )
    phases: dict[str, dict] = {}

    from kube_scheduler_simulator_tpu.utils.metrics import cost_fields

    def timed_pass(nodes_, pods_, config, reps=3, label=None):
        """Encode → jit → compile → best-of timing of one sequential pass
        (the shared idiom for every single-pass measurement; sync via
        host transfer — see module docstring). Per-phase host timings +
        XLA cost-model FLOPs/bytes + derived MFU land in
        `phases[label]` (cost is read AFTER the measurement through the
        cached AOT handle, so the proven jit execution path is what gets
        timed); under --profile the warm pass also runs inside a
        jax.profiler trace."""
        t0 = time.perf_counter()
        e = encode_cluster(nodes_, pods_, config, policy=TPU32)
        sc = BatchedScheduler(e, record=False, unroll=UNROLL)
        t_encode = time.perf_counter() - t0
        a = (e.arrays, e.state0, jnp.asarray(e.queue), sc.weights)
        r = jax.jit(sc.run_fn)
        t0 = time.perf_counter()
        np.asarray(r(*a)[1])  # compile
        t_compile = time.perf_counter() - t0
        best = _best_of(lambda: np.asarray(r(*a)[1]), reps=reps)
        if label:
            phases[label] = {
                "encode_s": round(t_encode, 4),
                "compile_s": round(t_compile, 4),
                "best_run_s": round(best, 4),
            }
            phases[label].update(
                cost_fields(r, a, best, device, label=f"bench.{label}")
            )
        if profile_dir:
            from kube_scheduler_simulator_tpu.utils.metrics import profile_trace

            with profile_trace(profile_dir):
                np.asarray(r(*a)[1])
        return best

    cfg = supported_config()  # == the full default KubeSchedulerConfiguration
    nodes, pods = synthetic_cluster(N_NODES, N_PODS, seed=42)

    # 1) single pass
    single_dps = N_PODS / timed_pass(nodes, pods, cfg, label="single")

    # 2) Monte-Carlo sweep: V variants in one program (preemption off —
    # see module docstring)
    d = cfg.to_dict()
    d["profiles"][0]["plugins"]["postFilter"] = {
        "disabled": [{"name": "*"}],
        "enabled": [],
    }
    sweep_cfg = SchedulerConfiguration.from_dict(d)
    sweep_enc = encode_cluster(nodes, pods, sweep_cfg, policy=TPU32)
    sweep_sched = BatchedScheduler(sweep_enc, record=False)
    vrun = jax.jit(jax.vmap(sweep_sched.run_fn, in_axes=(None, None, None, 0)))
    wbase = np.asarray(sweep_sched.weights)
    variants = jnp.asarray(
        np.stack([wbase + i for i in range(N_VARIANTS)]), wbase.dtype
    )
    vargs = (
        sweep_enc.arrays,
        sweep_enc.state0,
        jnp.asarray(sweep_enc.queue),
        variants,
    )
    np.asarray(vrun(*vargs)[1])  # compile
    t_sweep = _best_of(lambda: np.asarray(vrun(*vargs)[1]))
    sweep_dps = N_VARIANTS * N_PODS / t_sweep
    phases["sweep"] = {"best_run_s": round(t_sweep, 4)}
    phases["sweep"].update(
        cost_fields(
            vrun, vargs, t_sweep, device,
            label="bench.sweep", variants=N_VARIANTS,
        )
    )
    # Sweep FLOPs normalization (docs/benchmarking.md): BENCH_r05_chip
    # reported the vmapped program's cost-model total BELOW the
    # single-variant program's (2.0e7 vs 1.7e8) — the vmapped total is
    # not per-variant-consistent, so an MFU derived from it is
    # incomparable with the single-pass MFU. Re-derive the sweep's work
    # as variants x the UNVMAPPED single-variant program's cost model
    # (one extra compile, cached on disk) and make THAT the sweep's
    # headline `mfu`; the raw vmapped number stays as `mfu_vmapped_raw`.
    from kube_scheduler_simulator_tpu.utils.metrics import mfu as _mfu
    base_fields = cost_fields(
        jax.jit(sweep_sched.run_fn),
        (
            sweep_enc.arrays,
            sweep_enc.state0,
            jnp.asarray(sweep_enc.queue),
            jnp.asarray(wbase),
        ),
        label="bench.sweep_base",
    )
    if base_fields.get("flops"):
        norm_flops = base_fields["flops"] * N_VARIANTS
        phases["sweep"]["flops_base_program"] = base_fields["flops"]
        phases["sweep"]["flops_normalized"] = norm_flops
        phases["sweep"]["flops_denominator"] = (
            "variants x single-variant program cost model"
        )
        m_norm = _mfu(norm_flops, t_sweep, device)
        if m_norm is not None:
            if "mfu" in phases["sweep"]:
                phases["sweep"]["mfu_vmapped_raw"] = phases["sweep"]["mfu"]
            phases["sweep"]["mfu"] = m_norm
    if profile_dir:
        from kube_scheduler_simulator_tpu.utils.metrics import profile_trace

        # the headline program's trace — one warm pass
        with profile_trace(profile_dir):
            np.asarray(vrun(*vargs)[1])

    # 3) at-scale single pass (BASELINE config #2 shape)
    s_nodes, s_pods = synthetic_cluster(SCALE_NODES, SCALE_PODS, seed=7)
    scale_dps = SCALE_PODS / timed_pass(
        s_nodes, s_pods, cfg, reps=2, label="atscale"
    )

    # 4) affinity-heavy pass (BASELINE config #3 shape)
    a_nodes, a_pods = synthetic_affinity_cluster(AFF_NODES, AFF_PODS, seed=11)
    aff_dps = AFF_PODS / timed_pass(
        a_nodes, a_pods, cfg, reps=2, label="affinity"
    )

    # 4b) encoded-cluster device bytes under the ACTIVE dtype policy
    # (KSS_DTYPE_POLICY, engine/packing.py) on the affinity shape — the
    # label-rich vehicle the bitpacked mask planes target. Always in the
    # headline so a byte regression shows up in every campaign, not only
    # when the full --encoding-probe subprocess runs.
    from kube_scheduler_simulator_tpu.engine import policy_from_env
    from kube_scheduler_simulator_tpu.engine.packing import encoded_device_bytes

    enc_policy = policy_from_env()
    enc_bytes = encoded_device_bytes(
        encode_cluster(a_nodes, a_pods, cfg, policy=enc_policy)
    )

    # oracle baseline: sequential python on a sample of the same workload
    oracle = Oracle(nodes, pods[:BASELINE_PODS], cfg)
    t0 = time.perf_counter()
    oracle.schedule_all()
    base_dps = BASELINE_PODS / (time.perf_counter() - t0)

    def gang_desc(g):
        """One fragment: the measured shape always printed, incomplete
        passes labeled."""
        d = f"({g['mode']},{g['shape']})={g['gang_dps']}/s in {g['rounds']} rounds"
        if g.get("scheduled") != g.get("pods"):
            d += f" INCOMPLETE ({g['scheduled']}/{g['pods']} placed)"
        return d

    # gang mode: the fused fixpoint at the bench shape and at the
    # BASELINE #2 shape (the dense-rounds-vs-10k-steps claim)
    gang = _gang_probe("dynamic", "bench")
    gang_sc = _gang_probe("dynamic", "atscale")
    gang_note = f", gang fixpoint{gang_desc(gang)}, gang atscale{gang_desc(gang_sc)}"
    # only a COMPLETE pass at the full bench shape may take the headline
    gang_headline = (
        gang["gang_dps"] if gang["scheduled"] == gang["pods"] == N_PODS else 0.0
    )
    # vmapped gang sweep (variants x dense rounds in one program — the
    # north-star shape), headline-eligible when every variant places
    # every pod
    gang_sweep = _gang_sweep_probe()
    gang_note += (
        f", gang sweep {gang_sweep['variants']}x{gang_sweep['shape']}="
        f"{gang_sweep['gang_sweep_dps']}/s in <={gang_sweep['rounds_max']} rounds"
    )
    if gang_sweep["scheduled"] == gang_sweep["pods"]:
        gang_headline = max(gang_headline, gang_sweep["gang_sweep_dps"])
    else:
        gang_note += (
            f" INCOMPLETE ({gang_sweep['scheduled']}/{gang_sweep['pods']})"
        )
    # sweep WITH preemption (parallel.WeightSweep, two-phase event loop
    # by default — see _sweep_preempt_probe); "mode" says which ran
    pre = _sweep_preempt_probe()
    pre_note = (
        f"sweep+preemption {pre['variants']}x{pre['shape']}="
        f"{pre['sweep_pre_dps']}/s (full default set, "
        f"{pre['mode']} preemption)"
    )
    headline = max(sweep_dps, gang_headline)

    # churn-heavy lifecycle measurement (incremental-encoding path):
    # host-dominated by design, a CPU-pinned child
    life = _child_probe(
        ["--lifecycle-probe"], 600.0, "lifecycle_events_per_s"
    )

    # aggregate decisions/s/process vs concurrent-session count with
    # cross-tenant continuous batching armed (server/batchplane.py) —
    # ROADMAP #2's "millions of users" curve
    batching = _concurrency_probe()

    # aggregate decisions/s/HOST vs horizontal fleet width (1/2/4 real
    # spawned workers behind the session-affine router, one shared
    # bundle store; fleet/router.py, docs/fleet.md), plus
    # time-to-first-scheduled-pod on a bundle-warmed worker. A
    # CPU-pinned child (host-throughput measurement).
    fleet = _child_probe(["--fleet-probe"], 900.0, "fleet_baseline_dps")

    # packed-encoding plane, PACKED vs TPU32 head-to-head (device bytes,
    # delta-transfer bytes, warm dps parity, dispatch-count parity)
    encoding_probe = _encoding_probe()

    print(
        json.dumps(
            {
                "metric": "scheduling decisions/sec/chip",
                "value": round(headline, 1),
                # events/sec of simulated cluster churn through the full
                # service stack + the encode-time fraction and the
                # delta/full encode counters (docs/performance.md)
                "lifecycle": life
                or {"error": "probe did not complete in its window"},
                # aggregate decisions/s/process vs concurrent sessions
                # with continuous batching armed (docs/sessions.md):
                # per-level aggregate dps, speedup vs the serialized
                # solo baseline, and the windows/occupancy that prove
                # one dispatch served N tenants
                "batching": batching,
                # the gang pass as a first-class headline block
                # (docs/performance.md "gang fixpoint on device"):
                # decisions/s, rounds-to-fixpoint, and the ledger-counted
                # device dispatches per pass — the fused-fixpoint
                # contract is exactly 1 on the dynamic path (static/
                # hybrid report their honest per-resume tally)
                "gang": {
                    "dps": gang["gang_dps"],
                    "rounds": gang["rounds"],
                    "dispatchesPerPass": gang["gang_dispatches_per_pass"],
                    "mode": gang["mode"],
                    "shape": gang["shape"],
                    "headline_dps": round(gang_headline, 1),
                },
                # aggregate decisions/s/host at fleet widths 1/2/4 vs
                # the single-process baseline, and the bundle-warmed
                # worker's time-to-first-scheduled-pod (docs/fleet.md)
                "fleet": fleet
                or {"error": "probe did not complete in its window"},
                # the packed-encoding plane (docs/performance.md
                # "Encoding widths"): encoded-cluster device bytes under
                # the ACTIVE policy are always present; `probe` carries
                # the PACKED-vs-TPU32 head-to-head (bytes_ratio,
                # delta_bytes_ratio, warm_dps_ratio, extra_dispatches,
                # placements_match)
                "encoding": {
                    "policy": enc_policy.name,
                    "shape": f"{AFF_PODS}podsx{AFF_NODES}nodes",
                    "deviceBytes": enc_bytes,
                    "probe": encoding_probe,
                },
                # the memory trajectory hoisted to the headline (the
                # fleet & memory observatory, docs/observability.md):
                # peak device bytes over the churn run and how
                # shattered free capacity ended up
                "memory": {
                    "peakHbmBytes": life.get("peak_hbm_bytes"),
                    "fragmentationIndex": life.get("fragmentation_index"),
                    "fleetSamples": life.get("fleet_samples"),
                }
                if life
                else None,
                # the judged view (utils/slo.py): per-objective
                # compliance over the churn run + alerts fired — the
                # SLO plane riding the same probe
                "slo": life.get("slo") if life else None,
                # cold-process boot → first scheduled pod, with the
                # bootProbe/firstEncode/firstCompile/firstPass phase
                # walls (utils/ledger.py cold-start accounting)
                "coldStart": cold
                or {"error": "probe did not complete in its window"},
                # the AOT-bundle gate (docs/performance.md): empty-dir
                # vs warm-bundle-dir cold start over isolated caches —
                # the >= 5x time-to-first-scheduled-pod headline
                "coldStartBundled": cold_bundled
                or {"error": "bundle probes did not complete"},
                "unit": (
                    f"decisions/s on {platform}; sweep {N_VARIANTS}x{N_PODS}pods"
                    f"x{N_NODES}nodes={round(sweep_dps, 1)}/s (default set "
                    f"minus postFilter), {pre_note}, single full default set="
                    f"{round(single_dps, 1)}/s, {SCALE_PODS}pods"
                    f"x{SCALE_NODES}nodes={round(scale_dps, 1)}/s, "
                    f"affinity {AFF_PODS}podsx{AFF_NODES}nodes="
                    f"{round(aff_dps, 1)}/s{gang_note}; "
                    f"vs_baseline = single vs the repo's python oracle on "
                    f"the same config (Go reference unrunnable here)"
                ),
                # like-for-like: single pass and oracle share the config
                "vs_baseline": round(single_dps / base_dps, 2),
                # per-program phase walls + XLA cost-model work + MFU
                # (VERDICT r4 #4): mfu is vs the device kind's published
                # bf16 peak (utils/metrics.PEAKS) and only reported on
                # the accelerator; a missing label means the backend
                # exposed no cost model for that program.
                "phase_s": {
                    lbl: {
                        k: v
                        for k, v in p.items()
                        if k in ("encode_s", "compile_s", "best_run_s")
                    }
                    for lbl, p in phases.items()
                },
                "flops": {
                    lbl: p["flops"] for lbl, p in phases.items() if "flops" in p
                },
                "flops_per_s": {
                    lbl: p["flops_per_s"]
                    for lbl, p in phases.items()
                    if "flops_per_s" in p
                },
                "mfu": {
                    lbl: round(p["mfu"], 8)
                    for lbl, p in phases.items()
                    if "mfu" in p
                },
            }
        )
    )
    if profile_dir:
        # per-phase host timings + the trace artifact location, on
        # stderr so the stdout driver contract stays one JSON line
        sys.stderr.write(
            "bench phases: "
            + json.dumps({"profile_dir": profile_dir, "passes": phases})
            + "\n"
        )


if __name__ == "__main__":
    import sys

    def _flag(name: str) -> "str | None":
        hits = [a for a in sys.argv if a.startswith(name)]
        return hits[0].partition("=")[2] if hits else None

    if "--cold-start" in sys.argv:
        # BEFORE _enable_compile_cache: the probe owns its import order
        # (the ledger module's import stamps the cold-start origin, and
        # arming the cache here would drag jax in first)
        _cold_start_probe()
        sys.exit(0)
    window = _flag("--gang-window")
    window = int(window) if window else None
    probes = {
        "--lifecycle-probe": _lifecycle_probe,
        "--fleet-probe": _fleet_probe,
        "--concurrency-probe": lambda: print(json.dumps(_concurrency_probe())),
        "--sweep-preempt-probe": lambda: print(
            json.dumps(_sweep_preempt_probe())
        ),
        "--encoding-probe": lambda: print(json.dumps(_encoding_probe())),
        "--gang-sweep-probe": lambda: print(
            json.dumps(_gang_sweep_probe(window=window))
        ),
    }
    for flag, probe in probes.items():
        if flag in sys.argv:
            _enable_compile_cache()
            probe()
            sys.exit(0)
    mode = _flag("--gang-probe")
    if mode is not None:
        _enable_compile_cache()
        mode = mode or "dynamic"
        if mode not in ("dynamic", "static", "hybrid"):
            raise SystemExit(
                f"--gang-probe mode must be dynamic|static|hybrid, got {mode!r}"
            )
        shape = _flag("--gang-shape") or "bench"
        if shape not in ("bench", "atscale"):
            raise SystemExit(
                f"--gang-shape must be bench|atscale, got {shape!r}"
            )
        inner = _flag("--gang-inner")
        print(
            json.dumps(
                _gang_probe(
                    mode, shape,
                    inner_iters=int(inner) if inner else 64,
                    window=window,
                )
            )
        )
        sys.exit(0)
    profile_dir = _flag("--profile")
    if profile_dir is not None:
        profile_dir = profile_dir or "bench_profile"
    main(profile_dir)
