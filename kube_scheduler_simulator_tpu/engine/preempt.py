"""DefaultPreemption (PostFilter) as an incremental-counter kernel.

Semantics (oracle: sched/oracle_plugins.py default_preemption, mirroring
upstream dry-run preemption; reference records the result via the wrapped
PostFilter plugin, simulator/scheduler/plugin/wrappedplugin.go:518-546):
per candidate node, remove every lower-priority pod, check feasibility of
the preemptor, then re-add victims highest-priority-first keeping those
that leave the pod feasible; rank candidate nodes by (min highest-victim
priority, min priority sum, fewest victims, lowest index).

TPU-first structure — the expensive part of the dry run is re-running the
filter stack per (candidate node x reprieve step). Round 1 evaluated every
full `[N]` filter kernel inside that double loop: O(N²·V·F) compute and a
full SchedState pytree merge per step, which is what blew up both compile
and run time (VERDICT round 1). This rewrite splits every state-dependent
filter into:

  * `prepare`  — per preemption call, state-level: label/selector match
    matrices (assignment-independent) and base aggregation counters from
    the *current* assignment. O(P·T) once, matmul/scatter shaped.
  * `node_init` — per candidate node: its counters as they stand.
  * `add_back` — one resident's O(T) counter delta at the candidate node;
    `by=-1` takes it off. The node's lower-priority residents (its slots,
    below) are taken off one by one, then reprieved one by one.
  * `check`    — per reprieve step: feasibility of the preemptor on the
    candidate node from counters alone; no `[N]`-wide intermediates.

State-independent filters (NodeName, NodeUnschedulable, TaintToleration,
NodeAffinity) are evaluated once per call with their ordinary kernels —
victim removal cannot change them.

Per candidate node the dry run reads only that node's lower-priority
residents: once per preemption call, one sort of the P pods keyed by
(node, priority desc, bind order) lays every node's residents out in one
run of the sorted order, in reprieve order — the slot table, slot k of
node n at `order[start[n] + k]` (`slot_table`). The removal and reprieve
loops gather one slot per node per step and walk as many slots as the
fullest node holds residents (D), the candidates are ranked from what
they gathered, and each node's victims land in a `[V]` row (V:
`_victim_bound`); no value is `[N, P]` and no node sorts P pods.

Total cost: O(P·T) prepare + O(P log P) slot table + O(N·D·(T + NP1))
removal and reprieve — versus round 1's O(N²·V·F·N). The reprieve scan
carry is a few KB of counters instead of the full cluster state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .encode import PODS_RES, ClusterArrays, EncodedCluster, SchedState
from .packing import make_unpacker

PREEMPT_NO_LOWER = 0  # "no lower-priority pods to preempt"
PREEMPT_NO_FIT = 1  # "preemption would not make pod schedulable"
PREEMPT_CANDIDATE = 2  # "can preempt k victim(s): ..."
PREEMPT_SELECTED = 3  # "preemption victim(s): ..."
PREEMPT_SILENT = 4  # fits with zero victims: oracle records no message

# Filters whose codes do not read SchedState: safe to evaluate once per
# preemption call on the unmodified state. Every other enabled filter must
# provide a row implementation below. (VolumeBinding/VolumeZone verdicts
# are host-precomputed static tables; NodeVolumeLimits is a pass-through.)
STATELESS_FILTERS = frozenset(
    {
        "NodeName",
        "NodeUnschedulable",
        "TaintToleration",
        "NodeAffinity",
        "VolumeBinding",
        "VolumeZone",
        "NodeVolumeLimits",
    }
)


class _FitRow:
    """NodeResourcesFit under victim removal (oracle fit_filter)."""

    def __init__(self, enc: EncodedCluster):
        pass

    def prepare(self, a: ClusterArrays, state: SchedState, p):
        return ()

    def node_init(self, a, ctx, state, n):
        return {"requested": state.requested[n], "n_pods": state.n_pods[n]}

    def add_back(self, a, ctx, cnt, v, n, by=1):
        return {
            "requested": cnt["requested"] + by * a.pod_req[v],
            "n_pods": cnt["n_pods"] + by,
        }

    def check(self, a, ctx, cnt, p, n):
        req = a.pod_req[p]
        free = a.node_alloc[n] - cnt["requested"]
        fits = ~((req > 0) & (req > free)).any()
        return fits & (cnt["n_pods"] + 1 <= a.node_alloc[n, PODS_RES])


class _PortsRow:
    """NodePorts under victim removal (oracle node_ports_filter)."""

    def __init__(self, enc: EncodedCluster):
        pass

    def prepare(self, a, state, p):
        return ()

    def node_init(self, a, ctx, state, n):
        return {
            "used_pair": state.used_pair[n],
            "used_wild": state.used_wild[n],
            "used_trip": state.used_trip[n],
        }

    def add_back(self, a, ctx, cnt, v, n, by=1):
        return {
            "used_pair": cnt["used_pair"] + by * a.want_pair[v],
            "used_wild": cnt["used_wild"] + by * a.want_wild[v],
            "used_trip": cnt["used_trip"] + by * a.want_trip[v],
        }

    def check(self, a, ctx, cnt, p, n):
        wild = a.want_wild[p] > 0
        trip = a.want_trip[p] > 0
        wild_conflict = (wild & (cnt["used_pair"] > 0)).any()
        trip_conflict = (
            trip & ((cnt["used_trip"] > 0) | (cnt["used_wild"][a.trip_pair] > 0))
        ).any()
        return ~(wild_conflict | trip_conflict)


class _SpreadRow:
    """PodTopologySpread hard constraints under victim removal (oracle
    spread_filter over a mutated NodeInfo; kernels.build_spread_filter is
    the [N]-wide analogue). Counters: matching bound pods per (constraint,
    topology-value) over *eligible* nodes."""

    def __init__(self, enc: EncodedCluster):
        from . import kernels as K

        self.aff_kernel = K.build_node_affinity_filter(enc)
        self.NP1 = enc.aux["n_node_pairs"] + 1
        self.BIG = jnp.iinfo(jnp.int32).max

    def prepare(self, a: ClusterArrays, state: SchedState, p):
        from .encode_rel import match_clauses

        rel = a.rel
        keys = rel.sph_key[p]  # [HC]
        valid = keys >= 0
        # assignment-independent liveness factors of _count_matching_pods
        m_live = (
            match_clauses(rel, rel.sph_ctype[p], rel.sph_ckey[p], rel.sph_cpairs[p])
            & (rel.ns_id == rel.ns_id[p])[None, :]
            & ~rel.deleted[None, :]
            & a.pod_mask[None, :]
        )  # [HC, P]
        pairs_all = rel.node_pair[:, jnp.maximum(keys, 0)]  # [N, HC]
        has_key_all = pairs_all > 0
        has_all = (has_key_all | ~valid[None, :]).all(axis=1)  # [N]
        elig = (self.aff_kernel(a, state, p) == 0) & has_all & a.node_mask
        HC = keys.shape[0]
        hc_ix = jnp.arange(HC)[:, None]
        # which topology values exist on eligible nodes (min domain)
        present = (
            jnp.zeros((HC, self.NP1), jnp.int32)
            .at[hc_ix, pairs_all.T]
            .add((elig[None, :] & has_key_all.T).astype(jnp.int32))
        )
        pmask = (present > 0) & (jnp.arange(self.NP1) > 0)[None, :]
        # base counts from the current assignment (pods on eligible nodes)
        bound = state.assignment >= 0
        tgt = jnp.maximum(state.assignment, 0)
        w = (m_live & bound[None, :] & elig[tgt][None, :]).astype(jnp.int32)
        pair_q = pairs_all[tgt].T  # [HC, P] — value of each pod's node
        base_cnt = (
            jnp.zeros((HC, self.NP1), jnp.int32).at[hc_ix, pair_q].add(w)
        )
        return {
            "keys_valid": valid,
            "m_live": m_live,
            "pairs_all": pairs_all,
            "has_key_all": has_key_all,
            "elig": elig,
            "pmask": pmask,
            "base_cnt": base_cnt,
            "self_add": rel.sph_self[p].astype(jnp.int32),
            "maxskew": rel.sph_skew[p],
            "hc_ix": hc_ix[:, 0],
        }

    def node_init(self, a, ctx, state, n):
        return {"cnt": ctx["base_cnt"]}

    def add_back(self, a, ctx, cnt, v, n, by=1):
        # v sits on node n: its per-constraint contribution lands at node
        # n's topology values
        d = ctx["m_live"][:, v].astype(jnp.int32) * ctx["elig"][n].astype(jnp.int32)
        pairs_n = ctx["pairs_all"][n]
        return {"cnt": cnt["cnt"].at[ctx["hc_ix"], pairs_n].add(by * d)}

    def check(self, a, ctx, cnt, p, n):
        c = cnt["cnt"]
        min_c = jnp.where(ctx["pmask"], c, self.BIG).min(axis=1)
        min_c = jnp.where(ctx["pmask"].any(axis=1), min_c, 0)  # [HC]
        pairs_n = ctx["pairs_all"][n]
        node_cnt = c[ctx["hc_ix"], pairs_n]
        skew = node_cnt + ctx["self_add"] - min_c
        has_key_n = ctx["has_key_all"][n]
        fail = ctx["keys_valid"] & (~has_key_n | (skew > ctx["maxskew"]))
        return ~fail.any()


class _InterpodRow:
    """InterPodAffinity under victim removal (oracle interpod_filter over a
    recomputed cycle state; kernels.build_interpod_filter is the [N]-wide
    analogue). Three counter families: existing pods' required
    anti-affinity vs the incoming pod (by node (key,value) pair), and the
    incoming pod's required anti-affinity / affinity term counts."""

    def __init__(self, enc: EncodedCluster):
        self.NP1 = enc.aux["n_node_pairs"] + 1

    def prepare(self, a: ClusterArrays, state: SchedState, p):
        from .encode_rel import match_clauses, match_clauses_rev

        rel = a.rel
        bound = (state.assignment >= 0) & a.pod_mask
        tgt = jnp.maximum(state.assignment, 0)
        np_assigned = rel.node_pair[tgt]  # [P, K]

        # (1) existing pods' required anti-affinity vs the incoming pod
        rev = match_clauses_rev(rel, rel.ian_ctype, rel.ian_ckey, rel.ian_cpairs, p)
        ns_ok1 = rel.ian_nsall | rel.ian_ns[:, :, rel.ns_id[p]]
        contrib1 = rev & ns_ok1 & (rel.ian_key >= 0)  # [P, T1]
        pair_ot = jnp.take_along_axis(
            np_assigned, jnp.maximum(rel.ian_key, 0), axis=1
        )  # [P, T1]
        pair_ot = jnp.where((rel.ian_key >= 0) & bound[:, None], pair_ot, 0)
        w1 = (contrib1 & bound[:, None] & (pair_ot > 0)).astype(jnp.int32)
        ea_base = jnp.zeros(self.NP1, jnp.int32).at[pair_ot].add(w1)

        # (2)/(3) the incoming pod's required anti-affinity / affinity
        def forward(key_all, ctype, ckey, cpairs, nsall, nsmh):
            key = key_all[p]  # [T]
            valid = key >= 0
            m = (
                match_clauses(rel, ctype[p], ckey[p], cpairs[p])
                & (nsall[p][:, None] | nsmh[p][:, rel.ns_id])
                & a.pod_mask[None, :]
            )  # [T, P]
            pair_tp = np_assigned[:, jnp.maximum(key, 0)].T  # [T, P]
            pair_tp = jnp.where(
                valid[:, None] & bound[None, :], pair_tp, 0
            )
            T = key.shape[0]
            t_ix = jnp.arange(T)
            base = (
                jnp.zeros((T, self.NP1), jnp.int32)
                .at[t_ix[:, None], pair_tp]
                .add((m & bound[None, :]).astype(jnp.int32))
            )
            npair_n = rel.node_pair[:, jnp.maximum(key, 0)]  # [N, T]
            npair_n = jnp.where(valid[None, :], npair_n, 0)
            return {
                "valid": valid,
                "m": m,
                "base": base,
                "npair_n": npair_n,
                "t_ix": t_ix,
            }

        f2 = forward(
            rel.ian_key, rel.ian_ctype, rel.ian_ckey, rel.ian_cpairs,
            rel.ian_nsall, rel.ian_ns,
        )
        f3 = forward(
            rel.ia_key, rel.ia_ctype, rel.ia_ckey, rel.ia_cpairs,
            rel.ia_nsall, rel.ia_ns,
        )
        total3 = (f3["base"] * (jnp.arange(self.NP1) > 0)[None, :]).sum()
        self_all = (rel.ia_self[p] | ~f3["valid"]).all()
        return {
            "contrib1": contrib1,
            "pair_ot": pair_ot,
            "ea_base": ea_base,
            "f2": f2,
            "f3": f3,
            "total3": total3,
            "self_all": self_all,
            "has_terms": f3["valid"].any(),
        }

    def node_init(self, a, ctx, state, n):
        return {
            "ea": ctx["ea_base"],
            "f2": ctx["f2"]["base"],
            "f3": ctx["f3"]["base"],
            "total3": ctx["total3"],
        }

    def add_back(self, a, ctx, cnt, v, n, by=1):
        # (1): v's own anti-affinity contributions come and go with it
        w1 = (ctx["contrib1"][v] & (ctx["pair_ot"][v] > 0)).astype(jnp.int32)
        out = {"ea": cnt["ea"].at[ctx["pair_ot"][v]].add(by * w1)}
        for fk in ("f2", "f3"):
            f = ctx[fk]
            npair_row = f["npair_n"][n]  # [T] — v sits on node n
            d = f["m"][:, v].astype(jnp.int32) * (npair_row > 0)
            out[fk] = cnt[fk].at[f["t_ix"], npair_row].add(by * d)
        out["total3"] = cnt["total3"] + by * (
            ctx["f3"]["m"][:, v].astype(jnp.int32) * (ctx["f3"]["npair_n"][n] > 0)
        ).sum()
        return out

    def check(self, a, ctx, cnt, p, n):
        rel = a.rel
        np_n = rel.node_pair[n]  # [K]
        fail1 = ((cnt["ea"][np_n] > 0) & (np_n > 0)).any()
        f2 = ctx["f2"]
        npair2 = f2["npair_n"][n]
        cnt2 = cnt["f2"][f2["t_ix"], npair2]
        fail2 = (f2["valid"] & (npair2 > 0) & (cnt2 > 0)).any()
        f3 = ctx["f3"]
        npair3 = f3["npair_n"][n]
        cnt3 = cnt["f3"][f3["t_ix"], npair3]
        ok_t = (npair3 > 0) & (cnt3 > 0)
        satisfied = (ok_t | ~f3["valid"]).all()
        # first-pod-in-series special case, gated on the node carrying every
        # requested topology key (upstream satisfyPodAffinity fails such
        # nodes before the special case is reached)
        has_all_keys = ((npair3 > 0) | ~f3["valid"]).all()
        pass3 = satisfied | (
            has_all_keys & (cnt["total3"] == 0) & ctx["self_all"]
        )
        fail3 = ctx["has_terms"] & ~pass3
        return ~(fail1 | fail2 | fail3)


def _vol_rows():
    from .kernels_vol import VolRestrictionsRow, make_vol_limits_row

    return {
        "VolumeRestrictions": VolRestrictionsRow,
        "EBSLimits": make_vol_limits_row("EBSLimits"),
        "GCEPDLimits": make_vol_limits_row("GCEPDLimits"),
        "AzureDiskLimits": make_vol_limits_row("AzureDiskLimits"),
    }


ROW_FILTERS = {
    "NodeResourcesFit": _FitRow,
    "NodePorts": _PortsRow,
    "PodTopologySpread": _SpreadRow,
    "InterPodAffinity": _InterpodRow,
    **_vol_rows(),
}


def _victim_bound(enc: EncodedCluster, filter_names) -> int:
    """Static bound on victims per node: with NodeResourcesFit enabled no
    node ever holds more pods than max(pods capacity, its initial load).

    Rounded UP to the geometric shape bucket: the bound is baked into
    the compiled program (it sizes each node's victim row and caps the
    reprieve), and the raw value moves with the initial per-node load —
    exact, it would recompile as churn shifts pods around.
    Over-approximation is safe: the reprieve walks only the slots the
    fullest node fills, and the rest of each row stays -1."""
    from ..utils.compilecache import shape_bucket

    P = enc.P
    if "NodeResourcesFit" not in filter_names:
        return P
    caps = np.asarray(enc.arrays.node_alloc[:, PODS_RES])
    mask = np.asarray(enc.arrays.node_mask)
    cap_max = int(caps[mask].max()) if mask.any() else 0
    assign0 = np.asarray(enc.state0.assignment)
    bound0 = assign0[assign0 >= 0]
    init_max = int(np.bincount(bound0).max()) if bound0.size else 0
    raw = max(1, min(P, max(cap_max, init_max)))
    return min(P, shape_bucket(raw, lo=1))


def slot_table(state: SchedState, lower, priority, N: int):
    """Each node's `lower` residents in reprieve order (priority desc, then
    bind order asc: oracle NodeInfo.pods insertion order for ties; pod
    index breaks what is left, as a stable per-node sort would) as runs of
    one sorted order: `(order [P], start [N], count [N])`, slot k of node n
    being `order[start[n] + k]` for k < count[n]. One stable sort of P
    keyed by (node, -priority, bound_seq)."""
    node_key = jnp.where(lower, state.assignment, N)  # non-residents last
    order = jnp.lexsort((state.bound_seq, -priority, node_key)).astype(jnp.int32)
    sorted_node = node_key[order]
    nodes = jnp.arange(N, dtype=node_key.dtype)
    start = jnp.searchsorted(sorted_node, nodes, side="left").astype(jnp.int32)
    end = jnp.searchsorted(sorted_node, nodes, side="right").astype(jnp.int32)
    return order, start, end - start


def nominated_victims(victims, nominated, P: int):
    """The `[P]` eviction mask of the nominated node's victims (all
    False when nothing was nominated)."""
    row = victims[jnp.maximum(nominated, 0)]
    row = jnp.where((row >= 0) & (nominated >= 0), row, P)
    return jnp.zeros(P + 1, bool).at[row].set(True)[:P]


def build_preemption(enc: EncodedCluster, filter_names):
    """Returns preempt(a, state, p) -> (pf_code [N] int32, victims [N, V]
    int32, nominated int32). `victims[n]` holds the pod indices node n
    would evict, in reprieve order, -1-padded; `nominated_victims` turns the
    nominated node's into the `[P]` eviction mask. `preempt.victim_slots` is
    V."""
    from . import kernels as K

    BIG = jnp.iinfo(jnp.int32).max
    row_filters = []
    static_kernels = []
    for name in filter_names:
        if name in ROW_FILTERS:
            row_filters.append(ROW_FILTERS[name](enc))
        elif name in STATELESS_FILTERS:
            static_kernels.append(K.FILTER_KERNELS[name][0](enc))
        else:
            raise NotImplementedError(
                f"filter {name!r} has no preemption row implementation and is "
                "not declared state-independent (preempt.STATELESS_FILTERS)"
            )
    V = _victim_bound(enc, filter_names)
    unpack = make_unpacker(enc)

    def preempt(a: ClusterArrays, state: SchedState, p):
        # widen PACKED planes in-trace (no-op when the caller — the
        # engine step — already unpacked; real work when the extender
        # loop or a test jits this closure against the raw encoding)
        a = unpack(a)
        prio_p = a.pod_priority[p]
        lower_all = (
            (state.assignment >= 0) & a.pod_mask & (a.pod_priority < prio_p)
        )  # [P]
        N = a.node_mask.shape[0]
        static_ok = a.node_mask
        for k in static_kernels:
            static_ok = static_ok & (k(a, state, p) == 0)
        ctxs = [rf.prepare(a, state, p) for rf in row_filters]
        order, start, count = slot_table(state, lower_all, a.pod_priority, N)
        P = order.shape[0]
        # the loops walk as many slots as the fullest node has residents:
        # past them every node's slots are empty (the reprieve stops at V,
        # the most any node can hold)
        depth = count.max()
        sum_dt = jax.eval_shape(jnp.sum, a.pod_priority).dtype

        def eval_node(n, lo, cnt_n):
            def slot(k):
                return order[jnp.minimum(lo + k, P - 1)], k < cnt_n

            def feasible(cnts_now):
                ok = static_ok[n]
                for rf, ctx, cnt in zip(row_filters, ctxs, cnts_now):
                    ok = ok & rf.check(a, ctx, cnt, p, n)
                return ok

            def step(cnts_c, v, by):
                return tuple(
                    rf.add_back(a, ctx, cnt, v, n, by)
                    for rf, ctx, cnt in zip(row_filters, ctxs, cnts_c)
                )

            def remove(k, cnts_c):
                v, live = slot(k)
                return jax.tree.map(
                    lambda x, y: jnp.where(live, x, y), step(cnts_c, v, -1), cnts_c
                )

            cnts = tuple(
                rf.node_init(a, ctx, state, n) for rf, ctx in zip(row_filters, ctxs)
            )
            cnts = jax.lax.fori_loop(0, depth, remove, cnts)
            fits = feasible(cnts)

            def reprieve(k, carry):
                cnts_c, victims, maxp, sump = carry
                v, live = slot(k)
                cnts_try = step(cnts_c, v, 1)
                ok = feasible(cnts_try)
                keep = live & ok
                cnts_c = jax.tree.map(
                    lambda x, y: jnp.where(keep, x, y), cnts_try, cnts_c
                )
                out = live & ~ok
                prio = a.pod_priority[v]
                return (
                    cnts_c,
                    victims.at[k].set(jnp.where(out, v, -1)),
                    jnp.where(out, jnp.maximum(maxp, prio), maxp),
                    sump + jnp.where(out, prio, 0).astype(sum_dt),
                )

            _, victims, maxp, sump = jax.lax.fori_loop(
                0,
                jnp.minimum(depth, V),
                reprieve,
                (cnts, jnp.full(V, -1, jnp.int32), jnp.int32(-BIG), jnp.zeros((), sum_dt)),
            )
            has_victims = (victims >= 0).any()
            code = jnp.where(
                cnt_n == 0,
                PREEMPT_NO_LOWER,
                jnp.where(
                    ~fits,
                    PREEMPT_NO_FIT,
                    jnp.where(has_victims, PREEMPT_CANDIDATE, PREEMPT_SILENT),
                ),
            )
            # SILENT: fits with zero surviving victims (possible when the
            # infeasibility came from another node via spread/inter-pod
            # coupling) — the oracle records no message and no candidate.
            victims = jnp.where(code == PREEMPT_CANDIDATE, victims, -1)
            return code.astype(jnp.int32), victims, maxp, sump

        pf_code, victims, maxp, sump = jax.vmap(eval_node)(
            jnp.arange(N), start, count
        )  # [N], [N, V], [N], [N]
        # node choice (oracle rank): min highest-victim priority, then min
        # priority sum, then fewest victims, then lowest node index
        cand = pf_code == PREEMPT_CANDIDATE
        cnt = (victims >= 0).sum(axis=1)
        alive = cand
        for key in (maxp, sump, cnt):
            best = jnp.where(alive, key, BIG).min()
            alive = alive & (key == best)
        nominated = jnp.where(alive.any(), jnp.argmax(alive), -1).astype(jnp.int32)
        pf_code = jnp.where(
            (jnp.arange(N) == nominated) & (nominated >= 0),
            PREEMPT_SELECTED,
            pf_code,
        )
        return pf_code, victims, nominated

    preempt.victim_slots = V
    return preempt


def decode_preemption(
    code: int, enc: EncodedCluster, node_idx: int, victims: "list[str]"
) -> str:
    if code == PREEMPT_NO_LOWER:
        return "no lower-priority pods to preempt"
    if code == PREEMPT_NO_FIT:
        return "preemption would not make pod schedulable"
    if code == PREEMPT_CANDIDATE:
        return f"can preempt {len(victims)} victim(s): " + ", ".join(victims)
    return "preemption victim(s): " + ", ".join(victims)
