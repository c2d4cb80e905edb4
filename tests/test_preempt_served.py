"""The served gang pass on small PreemptionBasic-shaped clusters, against
the benchmark's plain reference (benchmark/references/default_preemption.py):
every preemptor lands on the reference's node with the reference's victim
count, the preempt phase steps once per pod that enters it, and the
preemption program holds no `[N, P]` value and sorts no `[N, P]` keys."""

from __future__ import annotations

import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
)

from harness import load_module, read_json  # noqa: E402

from kube_scheduler_simulator_tpu.server.service import SimulatorService  # noqa: E402

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
ref = load_module("references", "default_preemption")
gen = load_module("generators", "scheduler_perf_preemption")


def _config(nodes: int, low: int) -> dict:
    cfg = read_json(os.path.join(BENCH, "configs", "preemption-500.json"))
    cfg.update(initNodes=nodes, initPods=low)
    return cfg


def _drive(cfg: dict, waves: list, seed: int):
    """Import the cluster, then run the mix's cycles (preempt / refill,
    `waves` high pods each) as served gang passes. Yields, per pass, the
    pass's (before, after) and the service's preempt counters."""
    svc = SimulatorService()
    svc.scheduler.restart(cfg["schedulerConfiguration"])
    nodes, pods = gen.make_cluster(cfg, random.Random(f"{seed}/cluster"))
    assert not svc.import_({"nodes": nodes, "pods": pods})
    params = {"unit": "pod", "batches": [k for k in waves for _ in (0, 1)],
              "phases": ["preempt", "refill"] * len(waves)}
    cluster = ref.Cluster(nodes, cfg)
    live = {p["metadata"]["name"]: p for p in pods}
    specs = {n: ref.pod_spec(p) for n, p in live.items()}
    node_of = {n: p["spec"]["nodeName"] for n, p in live.items()}
    rng = random.Random(f"{seed}/traffic")
    for serial, k in enumerate(params["batches"]):
        deletes, creates = gen.churn(live, k, rng, serial, cfg, params)
        for p in deletes:
            svc.store.delete("pods", p["metadata"]["name"], p["metadata"]["namespace"])
            del live[p["metadata"]["name"]], node_of[p["metadata"]["name"]]
        for p in creates:
            svc.store.apply("pods", p)
            live[p["metadata"]["name"]] = p
            specs[p["metadata"]["name"]] = ref.pod_spec(p)
            node_of[p["metadata"]["name"]] = None
        before = dict(node_of)
        phases0 = dict(svc.scheduler.metrics.snapshot()["phases"])
        svc.scheduler.schedule_gang(record=False)
        phases1 = svc.scheduler.metrics.snapshot()["phases"]
        after = {
            p["metadata"]["name"]: p["spec"].get("nodeName") or None
            for p in svc.store.list("pods")
        }
        node_of = {n: after.get(n) for n in live}
        counts = {
            key: phases1[key] - phases0[key]
            for key in ("gangPreemptSteps", "gangPreemptBound", "gangPreemptVictims",
                        "engineBuilds")
        }
        yield params["phases"][serial], k, before, after, cluster, specs, counts


SHAPES = [
    # nodes, low pods (four to a node), waves of high pods, seed
    (16, 64, [4, 16], 7),
    (16, 64, [16, 4], 2**31 + 11),
    (24, 96, [5, 24], 3),
]


@pytest.mark.parametrize("nodes,low,waves,seed", SHAPES)
def test_served_gang_pass_agrees_with_the_reference(nodes, low, waves, seed):
    cfg = _config(nodes, low)
    per = gen.victims_per_preemptor(cfg)
    for serial, (phase, k, before, after, cluster, specs, counts) in enumerate(
        _drive(cfg, waves, seed)
    ):
        # the first pass compiles the one program every later pass runs,
        # though the pod count swings across a bucket edge and the high
        # pods' namespace leaves the store at each refill
        assert counts.pop("engineBuilds") == (1 if serial == 0 else 0)
        check = ref.check_pass(cluster, specs, before, after)
        assert (check.broken, check.gap) == (0, 0), check.reasons
        placed = [n for n, v in before.items() if v is None and after.get(n)]
        if phase == "preempt":
            # every high pod preempted: k phase steps, k binds, 3k victims
            assert len(placed) == k
            assert counts == {
                "gangPreemptSteps": k, "gangPreemptBound": k, "gangPreemptVictims": per * k,
            }
            evicted = [n for n, v in before.items() if v and n not in after]
            assert len(evicted) == per * k
            assert {before[v] for v in evicted} == {after[n] for n in placed}
        else:
            # the refill fills the freed nodes back; no pod enters a phase
            assert len(placed) == per * k
            assert counts == {"gangPreemptSteps": 0, "gangPreemptBound": 0, "gangPreemptVictims": 0}
            load = {}
            for n, v in after.items():
                if v is not None:
                    load[v] = load.get(v, 0) + 1
            assert set(load.values()) == {gen.residents_per_node(cfg)}


def test_reference_rejects_a_pass_that_skips_preemption():
    """The same pass with the server's answer replaced by the control's
    (preemptors left pending) reads broken; the served answer does not."""
    cfg = _config(16, 64)
    _, _, before, after, _, specs, _ = next(_drive(cfg, [4], 5))
    control = ref.schedule(ref.Cluster(gen.nodes(cfg), cfg), specs, before, "no_preemption")
    after_c = {n: (control.get(n) if v is None else v) for n, v in before.items()}
    assert ref.check_pass(ref.Cluster(gen.nodes(cfg), cfg), specs, before, after_c).broken > 0
    assert ref.check_pass(ref.Cluster(gen.nodes(cfg), cfg), specs, before, after).broken == 0


def _preempt_program(nodes: int, pods: int):
    """(jaxpr of the preemption dry run, N, P) at capacities N != P, the
    nodes holding at most 8 pods so the victim bound V is neither."""
    from kube_scheduler_simulator_tpu.engine import kernels as K
    from kube_scheduler_simulator_tpu.engine.encode import TPU32, encode_cluster
    from kube_scheduler_simulator_tpu.engine.engine import supported_config
    from kube_scheduler_simulator_tpu.utils.compilecache import shape_bucket

    cfg = supported_config()
    node_objs = [
        {"metadata": {"name": f"n{i}"},
         "status": {"allocatable": {"cpu": "4", "memory": "32Gi", "pods": "8"}}}
        for i in range(nodes // 2)
    ]

    def pod(name, prio, node=None):
        spec = {"priority": prio, "containers": [
            {"name": "c", "resources": {"requests": {"cpu": "900m", "memory": "500Mi"}}}]}
        if node:
            spec["nodeName"] = node
        return {"metadata": {"name": name, "namespace": "default"}, "spec": spec}

    pod_objs = [pod(f"low-{i}", 0, f"n{i % len(node_objs)}") for i in range(2 * len(node_objs))]
    pod_objs.append(pod("high", 10))
    enc = encode_cluster(node_objs, pod_objs, cfg, policy=TPU32,
                         node_capacity=nodes, pod_capacity=pods)
    filters = [n for n in cfg.enabled("filter") if n in K.FILTER_KERNELS]
    preempt = K.POSTFILTER_KERNELS["DefaultPreemption"](enc, filters)
    assert preempt.victim_slots == shape_bucket(8, lo=1)
    assert preempt.victim_slots not in (nodes, pods)
    jaxpr = jax.make_jaxpr(preempt)(enc.arrays, enc.state0, jnp.int32(len(pod_objs) - 1))
    return jaxpr, enc.N, enc.P


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("nodes,pods", [(32, 256), (64, 128)])
def test_preempt_program_holds_no_node_by_pod_value(nodes, pods):
    jaxpr, N, P = _preempt_program(nodes, pods)
    assert (N, P) == (nodes, pods)
    sorts = []
    for eqn in _eqns(jaxpr.jaxpr):
        for v in list(eqn.outvars) + [x for x in eqn.invars if hasattr(x, "aval")]:
            shape = getattr(v.aval, "shape", ())
            assert not (N in shape and P in shape), (eqn.primitive, shape)
        if eqn.primitive.name == "sort":
            sorts.append([tuple(x.aval.shape) for x in eqn.invars])
    # the one slot-table sort: keys and the index of P pods, not per node
    assert sorts and all(s == (P,) for op in sorts for s in op), sorts


def test_phase_steps_follow_the_pending_pods_not_the_pod_bucket():
    """A pass whose queue holds 4 preemptors, in a pod bucket many times
    larger, steps 4 times: the phase loop's trip count is the pods that
    entered it."""
    cfg = _config(16, 64)
    phase, k, before, after, _, _, counts = next(_drive(cfg, [4], 9))
    assert (phase, k) == ("preempt", 4)
    assert counts["gangPreemptSteps"] == 4
    assert np.sum([v is None and after.get(n) is not None for n, v in before.items()]) == 4
