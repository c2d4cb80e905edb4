"""The topology histograms of PodTopologySpread and InterPodAffinity
(engine/kernels.py) are contractions over one-hot planes. Each must give
the integers of the scatter-add it replaced, row for row, on real
encodings: node labels with several (key, value) pairs, hard and soft
spread constraints, required and preferred (anti-)affinity with negative
weights, hardPodAffinityWeight > 1, deleted pods, masked padding rows,
every pod of the encoding evaluated as one vmapped chunk. The scatter
forms live here, as the reference.

The gang pass over the same kind of cluster must place every pod where,
and with the annotations, the scatter version of the kernels did
(tests/data/gang_topology_placements.json was recorded with it).
"""

import hashlib
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.engine import TPU32, BatchedScheduler, encode_cluster
from kube_scheduler_simulator_tpu.engine import kernels as K
from kube_scheduler_simulator_tpu.engine.packing import make_unpacker
from kube_scheduler_simulator_tpu.sched.config import SchedulerConfiguration

from helpers import node, pod

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
APPS = ("web", "db", "cache")
SEEDS = (24001, 24002, 24003)
HARD_W = 7
RECORDED = os.path.join(os.path.dirname(__file__), "data", "gang_topology_placements.json")


def _term(rng):
    return {
        "topologyKey": rng.choice((ZONE, HOST)),
        "labelSelector": {"matchLabels": {"app": rng.choice(APPS)}},
    }


def _affinity(rng):
    kind = rng.choice(("req", "anti", "pref", "antipref", "none", "none"))
    if kind == "none":
        return None
    if kind in ("req", "anti"):
        field = "podAffinity" if kind == "req" else "podAntiAffinity"
        return {field: {"requiredDuringSchedulingIgnoredDuringExecution": [_term(rng)]}}
    field = "podAffinity" if kind == "pref" else "podAntiAffinity"
    return {field: {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": rng.randint(1, 100), "podAffinityTerm": _term(rng)}
        for _ in range(rng.randint(1, 2))
    ]}}


def _spread(rng):
    if rng.random() < 0.5:
        return None
    return [{
        "maxSkew": rng.randint(1, 3),
        "topologyKey": rng.choice((ZONE, HOST)),
        "whenUnsatisfiable": rng.choice(("DoNotSchedule", "ScheduleAnyway")),
        "labelSelector": {"matchLabels": {"app": rng.choice(APPS)}},
    } for _ in range(rng.randint(1, 2))]


def topology_cluster(seed):
    """10 nodes (hostname labels, most with one of three zones), 16 bound
    residents (every seventh deleted), 24 pending pods; affinity terms
    and spread constraints drawn per pod."""
    rng = random.Random(seed)
    nodes = []
    for i in range(10):
        labels = {HOST: f"n{i}"}
        if rng.random() < 0.85:
            labels[ZONE] = rng.choice("abc")
        nodes.append(node(f"n{i}", cpu="4", mem="16Gi", labels=labels))
    pods = []
    for i in range(16):
        p = pod(f"r{i}", cpu="200m", ns=rng.choice(("default", "prod")),
                labels={"app": rng.choice(APPS)}, node_name=f"n{rng.randrange(10)}",
                affinity=_affinity(rng))
        if i % 7 == 3:
            p["metadata"]["deletionTimestamp"] = "2024-01-01T00:00:00Z"
        pods.append(p)
    for i in range(24):
        pods.append(pod(f"q{i}", cpu="300m", ns=rng.choice(("default", "default", "prod")),
                        labels={"app": rng.choice(APPS)}, affinity=_affinity(rng),
                        spread=_spread(rng)))
    return nodes, pods


def _config():
    cfg = SchedulerConfiguration.default().to_dict()
    for pc in cfg["profiles"][0]["pluginConfig"]:
        if pc["name"] == "InterPodAffinity":
            pc["args"]["hardPodAffinityWeight"] = HARD_W
    return SchedulerConfiguration.from_dict(cfg)


_ENCODINGS = {}


def _encoding(seed):
    """(arrays, states, NP1): the encoding padded to 48 pod rows and 12
    node rows, with two states: the residents bound, and a mid-pass
    state that also binds about half of the pending pods at random."""
    if seed not in _ENCODINGS:
        nodes, pods = topology_cluster(seed)
        enc = encode_cluster(nodes, pods, _config(), policy=TPU32,
                             node_capacity=12, pod_capacity=48)
        a = make_unpacker(enc)(enc.arrays)
        s0 = enc.state0
        rng = np.random.default_rng(seed)
        asg = np.asarray(s0.assignment).copy()
        for i in range(len(pods)):
            if asg[i] < 0 and rng.random() < 0.5:
                asg[i] = rng.integers(0, len(nodes))
        s1 = s0.replace(assignment=jnp.asarray(asg, s0.assignment.dtype))
        _ENCODINGS[seed] = (a, (s0, s1), enc.aux["n_node_pairs"] + 1)
    return _ENCODINGS[seed]


def _all_pods(fn, a):
    """fn(p) for every pod row, padding included, as one vmapped chunk."""
    P = a.pod_mask.shape[0]
    return jax.vmap(fn)(jnp.arange(P, dtype=jnp.int32))


def _node_masks(a, seed):
    """[P, N] a random node mask per pod (the kernels pass elig/scored)."""
    P, N = a.pod_mask.shape[0], a.node_mask.shape[0]
    m = np.random.default_rng(seed + 7).random((P, N)) < 0.6
    return jnp.asarray(m) & a.node_mask[None, :]


# -- the scatter forms the kernels used before ---------------------------------


def scatter_node_counts(a, s, mm):
    T, N = mm.shape[0], a.node_mask.shape[0]
    tgt = jnp.maximum(s.assignment, 0)
    return jnp.zeros((T, N), jnp.int32).at[:, tgt].add(mm.astype(jnp.int32))


def scatter_spread_pair_counts(a, s, mm, keys, NP1, node_w):
    T = keys.shape[0]
    pairs = a.rel.node_pair[:, jnp.maximum(keys, 0)]  # [N, T]
    cnt_node = scatter_node_counts(a, s, mm)
    return jnp.zeros((T, NP1), jnp.int32).at[jnp.arange(T)[:, None], pairs.T].add(
        cnt_node * node_w[None, :]
    )


def scatter_forward_pair_counts(a, s, m, keys, NP1):
    T = keys.shape[0]
    pair = a.rel.node_pair[jnp.maximum(s.assignment, 0)][:, jnp.maximum(keys, 0)].T
    ok = (keys >= 0)[:, None] & (s.assignment >= 0)[None, :]
    pair_tp = jnp.where(ok, pair, 0)
    return jnp.zeros((T, NP1), jnp.int32).at[jnp.arange(T)[:, None], pair_tp].add(
        m.astype(jnp.int32)
    )


def scatter_pair_nodes(a, keys, node_w, NP1):
    T = keys.shape[0]
    pairs = a.rel.node_pair[:, jnp.maximum(keys, 0)]  # [N, T]
    return jnp.zeros((T, NP1), jnp.int32).at[jnp.arange(T)[:, None], pairs.T].add(
        (node_w[:, None] & (pairs > 0)).T.astype(jnp.int32)
    )


def scatter_term_pair_hist(contrib, pair_ot, NP1, weight=None):
    w = jnp.ones(contrib.shape, jnp.int32) if weight is None else weight.astype(jnp.int32)
    return jnp.zeros(NP1, jnp.int32).at[pair_ot].add(jnp.where(contrib, w, 0))


# -- rewritten histogram == scatter form ---------------------------------------

SPREAD = {
    "hard": ("sph_key", "sph_ctype", "sph_ckey", "sph_cpairs"),
    "soft": ("sps_key", "sps_ctype", "sps_ckey", "sps_cpairs"),
}
IPA = {
    d: tuple(f"{d}_{f}" for f in ("key", "ctype", "ckey", "cpairs", "nsall", "ns"))
    for d in ("ia", "ian", "ipa", "ipan")
}


def _spread_mm(a, s, p, domain):
    key, ct, ck, cp = (getattr(a.rel, f) for f in SPREAD[domain])
    return key[p], K._spread_match(a, s, p, ct[p], ck[p], cp[p])


@pytest.mark.parametrize("state", [0, 1])
@pytest.mark.parametrize("domain", sorted(SPREAD))
@pytest.mark.parametrize("seed", SEEDS)
def test_spread_histograms_equal_scatter(seed, domain, state):
    a, states, NP1 = _encoding(seed)
    s = states[state]
    assert NP1 > 2 and int((~a.pod_mask).sum()) > 0 and bool(a.rel.deleted.any())
    masks = _node_masks(a, seed)

    def both(p):
        keys, mm = _spread_mm(a, s, p, domain)
        w = masks[p]
        new = (
            K._spread_counts(a, s, mm),
            K._pair_counts(a, s, mm, keys, NP1, node_w=w),
            K._pair_nodes(a, keys, w, NP1),
        )
        old = (
            scatter_node_counts(a, s, mm),
            scatter_spread_pair_counts(a, s, mm, keys, NP1, w),
            scatter_pair_nodes(a, keys, w, NP1),
        )
        return new, old, mm.sum()

    new, old, counted = _all_pods(both, a)
    assert int(counted.sum()) > 0
    for n, o in zip(new, old):
        assert n.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(n), np.asarray(o))


@pytest.mark.parametrize("state", [0, 1])
@pytest.mark.parametrize("domain", sorted(IPA))
@pytest.mark.parametrize("seed", SEEDS)
def test_interpod_histograms_equal_scatter(seed, domain, state):
    a, states, NP1 = _encoding(seed)
    s = states[state]
    key, ct, ck, cp, na, nm = (getattr(a.rel, f) for f in IPA[domain])
    if domain in ("ipa", "ipan"):
        weight = (1 if domain == "ipa" else -1) * getattr(a.rel, f"{domain}_weight")
    else:
        weight = jnp.full(key.shape, HARD_W, jnp.int32)

    def both(p):
        m = K._ipa_forward_match(a, s, p, ct, ck, cp, na, nm)
        contrib, pair_ot = K._reverse_terms(a, s, p, key, ct, ck, cp, na, nm)
        new = (
            K._forward_pair_counts(a, s, p, key, ct, ck, cp, na, nm, NP1),
            K._term_pair_hist(contrib, pair_ot, NP1),
            K._term_pair_hist(contrib, pair_ot, NP1, weight=weight),
        )
        old = (
            scatter_forward_pair_counts(a, s, m, key[p], NP1),
            scatter_term_pair_hist(contrib, pair_ot, NP1),
            scatter_term_pair_hist(contrib, pair_ot, NP1, weight),
        )
        return new, old, m.sum() + contrib.sum()

    new, old, counted = _all_pods(both, a)
    assert int(counted.sum()) > 0
    for n, o in zip(new, old):
        np.testing.assert_array_equal(np.asarray(n), np.asarray(o))


@pytest.mark.parametrize(
    "bound", [1, 100, 256, 40_000, 2**31 - 1], ids=lambda b: f"w{b}"
)
def test_weighted_term_histogram_is_exact_mod_2_32(bound):
    """Weights of any int32 size, negative ones included, sum as an int32
    scatter-add does (wrapping modulo 2^32 where it would)."""
    rng = np.random.default_rng(bound)
    P, T, NP1 = 96, 3, 11
    contrib = jnp.asarray(rng.random((P, T)) < 0.7)
    pair_ot = jnp.asarray(rng.integers(0, NP1, (P, T)), jnp.int32)
    weight = jnp.asarray(rng.integers(-bound, bound, (P, T), endpoint=True), jnp.int32)
    got = jax.jit(K._term_pair_hist, static_argnums=2)(contrib, pair_ot, NP1, weight)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(scatter_term_pair_hist(contrib, pair_ot, NP1, weight))
    )


KEY_FIELDS = ("sph_key", "sps_key", "ia_key", "ian_key", "ipa_key", "ipan_key")


@pytest.mark.parametrize("dtype", ["bool", "int32", "int64"])
@pytest.mark.parametrize("field", KEY_FIELDS)
@pytest.mark.parametrize("seed", SEEDS)
def test_node_lookups_equal_gathers(seed, field, dtype):
    """The per-pod histograms reach the nodes through the nodes' one-hot
    pair plane; each lookup equals the gather it replaced, extreme values
    of every integer width included."""
    a, _, NP1 = _encoding(seed)
    keys_all = getattr(a.rel, field)
    P, T = keys_all.shape
    rng = np.random.default_rng(seed)
    if dtype == "bool":
        tables = jnp.asarray(rng.random((P, T, NP1)) < 0.5)
    else:
        info = np.iinfo(dtype)
        vals = rng.integers(info.min, info.max, (P, T, NP1), dtype=dtype, endpoint=True)
        vals[:, :, :2] = [info.min, info.max]
        tables = jnp.asarray(vals)
    node_pair = a.rel.node_pair

    def both(keys, table):
        pairs = node_pair[:, jnp.maximum(keys, 0)]  # [N, T]
        new = (
            K._node_keys(a, keys),
            K._at_node_key_pairs(a, table, keys, NP1),
            K._at_node_pairs(a, table[0], NP1).T,
        )
        old = (pairs > 0, table[jnp.arange(T)[None, :], pairs], table[0][node_pair])
        return new, old

    new, old = jax.vmap(both)(keys_all, tables)
    for n, o in zip(new, old):
        assert n.dtype == o.dtype
        np.testing.assert_array_equal(np.asarray(n), np.asarray(o))


def _user_scatters(jaxpr, found):
    """Scatter equations anywhere in `jaxpr` (sub-jaxprs too), by the
    file of their innermost user frame."""
    from jax._src import source_info_util

    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            frames = list(source_info_util.user_frames(eqn.source_info.traceback))
            found.append(os.path.basename(frames[0].file_name) if frames else "?")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _user_scatters(sub, found)
    return found


def test_pod_chunk_holds_no_kernel_scatter():
    """The gang pass's evaluation chunk (a vmap of the attempt over pods)
    reaches the topology histograms with no scatter from kernels.py."""
    nodes, pods = topology_cluster(SEEDS[0])
    enc = encode_cluster(nodes, pods, _config(), policy=TPU32)
    eng = BatchedScheduler(enc, record=False)
    chunk = jax.vmap(eng._attempt, in_axes=(None, None, None, 0))
    jaxpr = jax.make_jaxpr(chunk)(
        enc.state0, enc.arrays, eng.weights, jnp.arange(8, dtype=jnp.int32)
    ).jaxpr
    assert "kernels.py" not in _user_scatters(jaxpr, [])


def _served_placements(seed):
    from kube_scheduler_simulator_tpu.server.service import SimulatorService

    svc = SimulatorService()
    cfg = svc.scheduler.get_config()
    for pc in cfg["profiles"][0]["pluginConfig"]:
        if pc["name"] == "InterPodAffinity":
            pc["args"]["hardPodAffinityWeight"] = HARD_W
    svc.scheduler.restart(cfg)
    nodes, pods = topology_cluster(seed)
    for n in nodes:
        svc.store.apply("nodes", n)
    for p in pods:
        svc.store.apply("pods", p)
    placements, rounds, _ = svc.scheduler.schedule_gang(record=True)
    out = {}
    for (ns, name), nd in sorted(placements.items()):
        ann = svc.store.get("pods", name, ns)["metadata"].get("annotations") or {}
        digest = hashlib.sha256(json.dumps(ann, sort_keys=True).encode()).hexdigest()[:16]
        out[f"{ns}/{name}"] = [nd, digest]
    return {"rounds": int(rounds), "placements": out}


@pytest.mark.parametrize("seed", SEEDS)
def test_gang_placements_match_recorded(seed):
    with open(RECORDED) as f:
        want = json.load(f)[str(seed)]
    assert _served_placements(seed) == want
