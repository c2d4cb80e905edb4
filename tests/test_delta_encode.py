"""Delta-vs-full encoding equivalence (the tentpole's correctness
contract): for ANY store event sequence, the `DeltaEncoder`'s retained
encoding must be ARRAY-IDENTICAL to a from-scratch `encode_cluster` of
the same store state at the same capacity buckets — whether the pass
took the incremental path or any fallback.

The property tests drive randomized `ChaosSpec` timelines (plus
synthetic scheduling write-backs and evictions between events, so the
binding delta path is exercised) and assert equality after EVERY event
batch. Separate cases pin the fallback triggers: stale resourceVersion,
capacity-bucket crossing, config identity change, vocabulary growth,
inter-pod affinity pods, PVC pods, taint flaps, deletions, and the
dirty-fraction threshold.
"""

from __future__ import annotations

import random

import jax
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.engine.delta import DeltaEncoder
from kube_scheduler_simulator_tpu.engine.encode import PACKED, TPU32, encode_cluster
from kube_scheduler_simulator_tpu.lifecycle.engine import LifecycleEngine
from kube_scheduler_simulator_tpu.models.store import ResourceStore
from kube_scheduler_simulator_tpu.scenario.chaos import ChaosSpec
from kube_scheduler_simulator_tpu.sched.config import SchedulerConfiguration
from kube_scheduler_simulator_tpu.utils.broker import BUCKET_WATERMARK
from kube_scheduler_simulator_tpu.utils.compilecache import capacity_buckets

from helpers import node, pod


def full_encode(
    store, config, *, node_lo=8, pod_lo=8, policy=TPU32, pod_capacity=None
):
    """The from-scratch reference: exactly what the service's full path
    builds for this store state (at `pod_capacity` when given: a pod
    bucket the encoder kept on a shrink)."""
    nodes = store.list("nodes")
    pods = store.list("pods")
    ncap, pcap = capacity_buckets(
        len(nodes), len(pods), node_lo=node_lo, pod_lo=pod_lo
    )
    pcap = pod_capacity or pcap
    return encode_cluster(
        nodes,
        pods,
        config,
        policy=policy,
        priorityclasses=store.list("priorityclasses"),
        namespaces=store.list("namespaces"),
        pvcs=store.list("pvcs"),
        pvs=store.list("pvs"),
        storageclasses=store.list("storageclasses"),
        node_capacity=ncap,
        pod_capacity=pcap,
    )


def assert_enc_equal(got, want, ctx=""):
    """Every array leaf (ClusterArrays + SchedState), the queue, and the
    host decode metadata must match exactly."""
    assert got.node_names == want.node_names, ctx
    assert got.pod_keys == want.pod_keys, ctx
    assert got.resource_names == want.resource_names, ctx
    assert (got.n_nodes, got.n_pods) == (want.n_nodes, want.n_pods), ctx
    np.testing.assert_array_equal(
        np.asarray(got.queue), np.asarray(want.queue), err_msg=f"queue {ctx}"
    )
    g_leaves = jax.tree_util.tree_flatten_with_path((got.arrays, got.state0))[0]
    w_leaves = jax.tree_util.tree_flatten_with_path((want.arrays, want.state0))[0]
    assert len(g_leaves) == len(w_leaves)
    for (gp, gx), (_, wx) in zip(g_leaves, w_leaves):
        path = jax.tree_util.keystr(gp)
        assert gx.shape == wx.shape, f"{path} shape {gx.shape}!={wx.shape} {ctx}"
        np.testing.assert_array_equal(
            np.asarray(gx), np.asarray(wx), err_msg=f"{path} {ctx}"
        )


def check(delta, store, config, ctx=""):
    """One delta pass + one from-scratch pass, compared. Returns the
    pass's info dict (mode/reason) for coverage accounting."""
    enc, info = delta.encode(store, config)
    retained = delta._st.enc if delta._st is not None else None
    if enc is not None:
        assert retained is enc
    if retained is not None:
        # the pod bucket is the fresh one, or a larger one kept because
        # the count still exceeds the watermark of the bucket below it
        n_pods = len(store.list("pods"))
        fresh = capacity_buckets(len(store.list("nodes")), n_pods)[1]
        assert retained.P == fresh or (
            retained.P > fresh and n_pods > BUCKET_WATERMARK * (retained.P // 2)
        ), ctx
        # the reference is a from-scratch encode under the encoder's OWN
        # policy, so under PACKED the comparison covers the packed words
        # and narrowed dtypes bit-for-bit
        assert_enc_equal(
            retained,
            full_encode(
                store, config, policy=delta.policy, pod_capacity=retained.P
            ),
            ctx,
        )
    else:
        # nothing retained: legitimately nothing schedulable right now
        pods = store.list("pods")
        pending = [
            p for p in pods if not (p.get("spec", {}) or {}).get("nodeName")
        ]
        assert not store.list("nodes") or not pods or not pending, ctx
    return info


# -- randomized chaos timelines ---------------------------------------------

_TEMPLATES = [
    {"metadata": {"name": "plain"}, "spec": {"containers": [
        {"name": "c", "resources": {"requests": {"cpu": "100m", "memory": "64Mi"}}}]}},
    {"metadata": {"name": "tol"}, "spec": {
        "tolerations": [{"key": "flaky", "operator": "Exists", "effect": "NoSchedule"}],
        "containers": [{"name": "c", "resources": {"requests": {"cpu": "50m"}}}]}},
    {"metadata": {"name": "lab", "labels": {"app": "web", "tier": "fe"}}, "spec": {
        "containers": [{"name": "c", "resources": {"requests": {"memory": "32Mi"}}}]}},
    {"metadata": {"name": "sel"}, "spec": {
        "nodeSelector": {"zone": "a"},
        "containers": [{"name": "c", "resources": {"requests": {"cpu": "25m"}}}]}},
    {"metadata": {"name": "spread", "labels": {"app": "web"}}, "spec": {
        "topologySpreadConstraints": [{
            "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "web"}}}],
        "containers": [{"name": "c", "resources": {"requests": {"cpu": "10m"}}}]}},
]


def _snapshot(n_nodes=5):
    nodes = [
        node(
            f"n{i}",
            cpu="8",
            mem="16Gi",
            labels={"zone": "a" if i % 2 else "b", "kubernetes.io/hostname": f"n{i}"},
        )
        for i in range(n_nodes)
    ]
    # one primer pod per template flavor so the first full encode interns
    # the recurring vocabulary (later arrivals of the same flavors can
    # then take the delta path)
    pods = []
    for t in _TEMPLATES:
        p = {"metadata": dict(t["metadata"]), "spec": dict(t["spec"])}
        p["metadata"] = {**p["metadata"], "name": p["metadata"]["name"] + "-seed"}
        pods.append(p)
    return {"nodes": nodes, "pods": pods}


def _chaos_spec(seed: int) -> ChaosSpec:
    return ChaosSpec.from_dict(
        {
            "seed": seed,
            "horizon": 30.0,
            "name": f"delta-prop-{seed}",
            "snapshot": _snapshot(),
            "arrivals": [
                {"kind": "poisson", "rate": 1.0, "count": 12, "template": t}
                for t in _TEMPLATES
            ],
            "faults": [
                {"at": 6.0, "action": "cordon", "node": "n1"},
                {"at": 9.0, "action": "taint", "node": "n2",
                 "taint": {"key": "flaky", "effect": "NoSchedule"}},
                {"at": 12.0, "action": "uncordon", "node": "n1"},
                {"at": 15.0, "action": "fail", "node": "n3"},
                {"at": 18.0, "action": "untaint", "node": "n2",
                 "taint": {"key": "flaky", "effect": "NoSchedule"}},
                {"at": 21.0, "action": "recover", "node": "n3"},
                {"at": 24.0, "action": "drain", "node": "n0"},
            ],
        }
    )


class _AssertingEngine(LifecycleEngine):
    """LifecycleEngine whose convergence step is replaced by the
    delta-vs-full assertion plus synthetic scheduling write-backs (binds
    and occasional evictions/deletions) so the MODIFIED-pod delta path
    gets real coverage without running the scheduling engine."""

    def __init__(self, spec, config, rng, policy=TPU32):
        super().__init__(spec)
        self.cfg = config
        self.rng = rng
        self.delta = DeltaEncoder(policy=policy)
        self.infos = []

    def _converge(self, t):
        self.infos.append(check(self.delta, self.store, self.cfg, f"t={t} pre"))
        # synthetic write-backs: bind ~half the pending pods, evict an
        # occasional bound one (replace strips nodeName = MODIFIED), and
        # rarely hard-delete one (forces the deletion fallback)
        names = [n["metadata"]["name"] for n in self.store.list("nodes")]
        for p in self.store.list("pods"):
            meta = p["metadata"]
            bound = (p.get("spec") or {}).get("nodeName")
            if not bound and names and self.rng.random() < 0.6:
                self.store.apply(
                    "pods",
                    {
                        "metadata": {
                            "name": meta["name"],
                            "namespace": meta.get("namespace", "default"),
                            "annotations": {"kss/result": "Scheduled"},
                        },
                        "spec": {"nodeName": self.rng.choice(names)},
                    },
                )
            elif bound and self.rng.random() < 0.08:
                q = {k: v for k, v in p.items() if k != "status"}
                q["spec"] = {
                    k: v for k, v in (p.get("spec") or {}).items() if k != "nodeName"
                }
                self.store.replace("pods", q)
            elif self.rng.random() < 0.03:
                self.store.delete(
                    "pods", meta["name"], meta.get("namespace", "default")
                )
        self.infos.append(check(self.delta, self.store, self.cfg, f"t={t} post"))


@pytest.mark.parametrize(
    "seed, policy",
    [(0, TPU32), (1, TPU32), (2, TPU32), (0, PACKED), (1, PACKED)],
    ids=["0-i32", "1-i32", "2-i32", "0-packed", "1-packed"],
)
def test_random_chaos_delta_equals_full(seed, policy):
    spec = _chaos_spec(seed)
    eng = _AssertingEngine(
        spec, SchedulerConfiguration.default(), random.Random(seed), policy
    )
    res = eng.run()
    assert res["phase"] == "Succeeded"
    modes = [i["mode"] for i in eng.infos]
    # the property is vacuous if the delta path never engaged
    assert "delta" in modes, modes
    assert "full" in modes  # and the fallback paths were exercised too


def test_pure_arrival_churn_stays_incremental():
    """The O(Δ) claim: homogeneous arrivals + binds against a warm
    encoding never fall back to a full re-encode."""
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    for i in range(4):
        store.apply("nodes", node(f"n{i}", cpu="16"))
    # seed the store mid-bucket (18 pods → capacity 32) so the churn
    # below never crosses the capacity bucket
    for i in range(17):
        store.apply("pods", pod(f"seed-{i}", cpu="100m", node_name=f"n{i % 4}"))
    store.apply("pods", pod("seed-pending", cpu="100m"))
    delta = DeltaEncoder()
    assert check(delta, store, cfg, "warmup")["mode"] == "full"
    modes = []
    for i in range(12):
        store.apply("pods", pod(f"churn-{i}", cpu="100m"))
        modes.append(check(delta, store, cfg, f"arrival {i}")["mode"])
        # write-back: bind the pod (what a scheduling pass does)
        store.apply(
            "pods",
            {"metadata": {"name": f"churn-{i}"}, "spec": {"nodeName": f"n{i % 4}"}},
        )
        modes.append(check(delta, store, cfg, f"bind {i}")["mode"])
    assert set(modes) == {"delta"}, modes


def test_unbind_via_replace_is_incremental():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0"))
    store.apply("pods", pod("a", node_name="n0"))
    store.apply("pods", pod("b"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    a = store.get("pods", "a")
    a["spec"].pop("nodeName")
    a.pop("status", None)
    store.replace("pods", a)
    info = check(delta, store, cfg, "unbind")
    assert info["mode"] == "delta"


def test_transient_readd_appends_in_store_order():
    """add a, add b, delete a, re-add a inside ONE window: a nets to
    ADDED but moved to the END of store iteration order — the delta
    append order must match (regression for the dirty_since ordering
    bug: a kept its first-event slot and encoded before b)."""
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0", cpu="16"))
    store.apply("pods", pod("seed"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    store.apply("pods", pod("a"))
    store.apply("pods", pod("b"))
    store.delete("pods", "a")
    store.apply("pods", pod("a"))
    info = check(delta, store, cfg, "transient re-add")
    assert info["mode"] == "delta", info
    assert delta._st.enc.pod_keys[-2:] == [("default", "b"), ("default", "a")]


def test_stale_rv_falls_back_to_full():
    store = ResourceStore(event_log_capacity=8)
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0"))
    store.apply("pods", pod("p0"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    for i in range(32):  # blow past the event log window
        store.apply("pods", pod(f"flood-{i}"))
    info = check(delta, store, cfg, "stale")
    assert info["mode"] == "full" and info["reason"] == "stale-rv"


def test_bucket_crossing_falls_back_and_grows_shapes():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0", cpu="64", pods="200"))
    for i in range(7):
        store.apply("pods", pod(f"p{i}"))
    delta = DeltaEncoder()
    enc, _ = delta.encode(store, cfg)
    assert enc.P == 8
    store.apply("pods", pod("p7"))
    info = check(delta, store, cfg, "fills bucket")
    assert info["mode"] == "delta"
    store.apply("pods", pod("p8"))  # 9 pods: crosses the 8-bucket
    info = check(delta, store, cfg, "crossing")
    assert info["mode"] == "full" and "bucket" in info["reason"]
    assert delta._st.enc.P == 16


@pytest.mark.parametrize(
    "start,steps",
    [
        # a count above 0.8 of the bucket below keeps the bucket
        (20, [(20, 32), (16, 32), (13, 32), (12, 16), (6, 8)]),
        # a count far below drops straight to its own bucket
        (40, [(40, 64), (26, 64), (10, 16)]),
    ],
)
def test_pod_bucket_hysteresis_on_shrink(start, steps):
    """Deletions re-encode in full; the pod capacity shrinks only once
    the count fits the bucket below within the bucket watermark, so a
    count that swings across a power of two keeps one program. Every
    encoding is still exactly the from-scratch one at its capacity."""
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0", cpu="64", pods="200"))
    for i in range(start):
        store.apply("pods", pod(f"p{i}"))
    delta = DeltaEncoder()
    live = start
    for count, want in steps:
        while live > count:
            live -= 1
            store.delete("pods", f"p{live}")
        enc, _ = delta.encode(store, cfg)
        assert (enc.n_pods, enc.P) == (count, want)
        nodes, pods = store.list("nodes"), store.list("pods")
        ref = encode_cluster(
            nodes, pods, cfg, policy=TPU32,
            node_capacity=capacity_buckets(len(nodes), len(pods))[0], pod_capacity=enc.P,
        )
        assert_enc_equal(enc, ref, f"{count} pods")


def test_config_identity_change_falls_back():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0"))
    store.apply("pods", pod("p0"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    store.apply("pods", pod("p1"))
    cfg2 = SchedulerConfiguration.default()  # equal value, new identity
    info = check(delta, store, cfg2, "config swap")
    assert info["mode"] == "full" and info["reason"] == "config-change"


@pytest.mark.parametrize(
    "manifest, why",
    [
        (pod("novel-label", labels={"brand-new-key": "x"}), "label vocab"),
        (pod("novel-res") | {"spec": {"containers": [{"name": "c", "resources": {
            "requests": {"example.com/fpga": "1"}}}]}}, "resource vocab"),
        (pod("claims", volumes=[{"name": "v", "persistentVolumeClaim": {
            "claimName": "c0"}}]), "pvc pod"),
        (pod("affine", affinity={"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {"app": "web"}}}]}}),
         "inter-pod affinity"),
    ],
)
def test_ineligible_pods_fall_back_but_stay_exact(manifest, why):
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0", labels={"kubernetes.io/hostname": "n0"}))
    store.apply("pods", pod("p0"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    store.apply("pods", manifest)
    info = check(delta, store, cfg, why)
    assert info["mode"] == "full", (why, info)


def test_taint_flap_and_node_delete_fall_back():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    for i in range(2):
        store.apply("nodes", node(f"n{i}"))
    store.apply("pods", pod("p0"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    store.apply(
        "nodes",
        {"metadata": {"name": "n1"},
         "spec": {"taints": [{"key": "k", "effect": "NoSchedule"}]}},
    )
    assert check(delta, store, cfg, "taint")["mode"] == "full"
    store.apply("pods", pod("p1"))
    assert check(delta, store, cfg, "arrival")["mode"] == "delta"
    store.delete("nodes", "n1")
    assert check(delta, store, cfg, "node delete")["mode"] == "full"


def test_cordon_uncordon_is_incremental():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    for i in range(2):
        store.apply("nodes", node(f"n{i}"))
    store.apply("pods", pod("p0"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    store.apply(
        "nodes", {"metadata": {"name": "n1"}, "spec": {"unschedulable": True}}
    )
    assert check(delta, store, cfg, "cordon")["mode"] == "delta"
    store.apply(
        "nodes", {"metadata": {"name": "n1"}, "spec": {"unschedulable": False}}
    )
    assert check(delta, store, cfg, "uncordon")["mode"] == "delta"


def test_dirty_fraction_threshold_falls_back():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    for i in range(2):
        store.apply("nodes", node(f"n{i}", cpu="64", pods="200"))
    for i in range(20):
        store.apply("pods", pod(f"p{i}"))
    delta = DeltaEncoder(max_dirty_frac=0.25)
    check(delta, store, cfg, "warm")
    # touch well past 25% of the cluster in one window
    for i in range(12):
        store.apply(
            "pods", {"metadata": {"name": f"p{i}"}, "spec": {"nodeName": "n0"}}
        )
    info = check(delta, store, cfg, "bulk rebind")
    assert info["mode"] == "full" and "dirty fraction" in info["reason"]


def test_priorityclass_event_falls_back():
    store = ResourceStore()
    cfg = SchedulerConfiguration.default()
    store.apply("nodes", node("n0"))
    store.apply("pods", pod("p0"))
    delta = DeltaEncoder()
    check(delta, store, cfg, "warm")
    store.apply(
        "priorityclasses",
        {"metadata": {"name": "high"}, "value": 1000},
    )
    info = check(delta, store, cfg, "pc event")
    assert info["mode"] == "full" and "priorityclasses" in info["reason"]
