"""Per-plugin filter/score kernels over the `[nodes]` axis.

Each kernel replaces one upstream scheduler-framework plugin's per-node
callback (reference: the wrapped plugins' Filter/Score delegation,
simulator/scheduler/plugin/wrappedplugin.go:491-516 and :388-413) with a
single vectorized pass over every node at once.

Contracts:
  * filter kernel: `fn(arrays, state, p) -> codes[N] int32`, 0 = pass,
    >0 = plugin-specific reason code. Codes are decoded host-side via
    `decode(code, enc, node_idx)` into the exact upstream failure messages
    the reference records into the `filter-result` annotation.
  * score kernel: `fn(arrays, state, p) -> raw[N]` in the score dtype,
    plus a normalize mode: None (raw is final), "default"
    (helper.DefaultNormalizeScore), or "default_reverse" (reverse=True).

Builders take the `EncodedCluster` so they can bake static plugin args
(scoring-strategy resources, weights) into the jitted closure — the
analogue of the reference rebuilding the scheduler on config change
(simulator/scheduler/scheduler.go:70-87 RestartScheduler).
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from ..sched.config import MAX_NODE_SCORE
from ..sched.oracle_plugins import BALANCED_SCALE
from .encode import EncodedCluster, PODS_RES, ClusterArrays, SchedState

# ---------------------------------------------------------------------------
# NodeResourcesFit  (oracle: sched/oracle_plugins.py fit_filter/fit_score;
# upstream NodeResourcesFit with all three scoringStrategies —
# LeastAllocated (default), MostAllocated, RequestedToCapacityRatio)
# ---------------------------------------------------------------------------


def build_fit_filter(enc: EncodedCluster):
    R = enc.R

    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        req = a.pod_req[p]  # [R]
        free = a.node_alloc - s.requested  # [N, R]
        insuff = (req > 0)[None, :] & (req[None, :] > free)  # [N, R]
        too_many = s.n_pods + 1 > a.node_alloc[:, PODS_RES]
        # first violating resource in the pod's request-dict order
        rank = jnp.where(insuff, a.pod_req_rank[p][None, :], R + 1)
        first_r = jnp.argmin(rank, axis=1)
        any_insuff = insuff.any(axis=1)
        return jnp.where(
            too_many, 1, jnp.where(any_insuff, 2 + first_r, 0)
        ).astype(jnp.int32)

    return kernel


def decode_fit(code: int, enc: EncodedCluster, node_idx: int) -> str:
    if code == 1:
        return "Too many pods"
    return f"Insufficient {enc.resource_names[code - 2]}"


def build_fit_score(enc: EncodedCluster):
    args = enc.config.plugin_args("NodeResourcesFit")
    strategy = args.get("scoringStrategy") or {}
    resources = strategy.get("resources") or [
        {"name": "cpu", "weight": 1},
        {"name": "memory", "weight": 1},
    ]
    stype = strategy.get("type", "LeastAllocated")
    specs = [
        (enc.resource_names.index(r["name"]), int(r.get("weight", 1)))
        for r in resources
        if r["name"] in enc.resource_names
    ]
    # Resources never seen in the cluster still contribute weight with
    # score 0 (capacity 0), as in the oracle's loop over configured specs.
    zero_weight = sum(
        int(r.get("weight", 1)) for r in resources if r["name"] not in enc.resource_names
    )
    wsum = sum(w for _, w in specs) + zero_weight

    if stype == "RequestedToCapacityRatio":
        from ..sched.oracle_plugins import rtcr_shape

        shape = rtcr_shape(strategy)

        def broken_linear_vec(u: jnp.ndarray) -> jnp.ndarray:
            """helper.BuildBrokenLinearFunction over a [N] utilization
            vector: ascending segments overwrite where u >= x1, ends
            clamp — integer math with Go's trunc-toward-zero division
            (jnp // floors, so negative slopes need the sign fixup)."""
            y = jnp.full_like(u, shape[0][1])
            for (x1, y1), (x2, y2) in zip(shape, shape[1:]):
                prod = (u - x1) * (y2 - y1)
                dx = max(x2 - x1, 1)
                seg = jnp.sign(prod) * (jnp.abs(prod) // dx) + y1
                y = jnp.where(u >= x1, seg.astype(y.dtype), y)
            return jnp.where(u >= shape[-1][0], shape[-1][1], y)

    def kernel(a: ClusterArrays, s: SchedState, p, feasible=None) -> jnp.ndarray:
        total = jnp.zeros(a.node_mask.shape[0], enc.policy.score)
        for r_idx, w in specs:
            cap = a.node_alloc[:, r_idx]
            req = s.s_requested[:, r_idx] + a.pod_sreq[p, r_idx]
            if stype == "RequestedToCapacityRatio":
                # over-capacity / zero-capacity evaluates the shape at
                # max utilization (upstream resourceScoringFunction)
                u = jnp.where(
                    (cap == 0) | (req > cap),
                    100,
                    req * 100 // jnp.maximum(cap, 1),
                ).astype(enc.policy.score)
                r_score = broken_linear_vec(u)
            elif stype == "MostAllocated":
                r_score = req * MAX_NODE_SCORE // jnp.maximum(cap, 1)
                r_score = jnp.where((cap == 0) | (req > cap), 0, r_score)
            else:  # LeastAllocated
                r_score = (cap - req) * MAX_NODE_SCORE // jnp.maximum(cap, 1)
                r_score = jnp.where((cap == 0) | (req > cap), 0, r_score)
            total = total + r_score.astype(enc.policy.score) * w
        if wsum == 0:
            return total
        return total // wsum

    return kernel


# ---------------------------------------------------------------------------
# NodeResourcesBalancedAllocation  (oracle: balanced_allocation_score;
# upstream balancedResourceScorer: 100 * (1 - std of usage fractions))
# ---------------------------------------------------------------------------


def _exact_isqrt64(x: jnp.ndarray) -> jnp.ndarray:
    """floor(sqrt(x)) for int64 x < 2^52, exact: the float64 sqrt of an
    exactly-representable int is correctly rounded, then one-step adjusted.
    Requires jax_enable_x64 (EXACT policy only)."""
    s = jnp.floor(jnp.sqrt(x.astype(jnp.float64))).astype(x.dtype)
    s = jnp.where(s * s > x, s - 1, s)
    s = jnp.where((s + 1) * (s + 1) <= x, s + 1, s)
    return s


def _div_scale_exact(num: jnp.ndarray, den: jnp.ndarray, scale_bits: int) -> jnp.ndarray:
    """floor(num * 2^scale_bits / den) without widening past the input
    dtype: base-256 long division, exact as long as den < 2^(31-8). This
    keeps the int32 (TPU) policy overflow-free — the encoder clamps device
    quantities to 2^23-1 for exactly this reason."""
    den = jnp.maximum(den, 1)
    acc = num // den
    rem = num % den
    for shift in range(0, scale_bits, 8):
        bits = min(8, scale_bits - shift)
        acc = acc * (1 << bits) + (rem * (1 << bits)) // den
        rem = (rem * (1 << bits)) % den
    return acc


def build_balanced_score(enc: EncodedCluster):
    """Quantized-integer balanced allocation (see oracle_plugins.py
    balanced_allocation_score): usage fractions in units of 1/2^16, std
    decided by integer arithmetic so the kernel is bit-identical to the
    oracle. The two-resource default config is exact in both dtype
    policies; the >2-resource variance branch is exact under EXACT (int64 +
    isqrt) and float32-approximate (±1 point) under the 32-bit TPU policy,
    where 48-bit intermediates don't exist."""
    args = enc.config.plugin_args("NodeResourcesBalancedAllocation")
    resources = args.get("resources") or [
        {"name": "cpu", "weight": 1},
        {"name": "memory", "weight": 1},
    ]
    idxs = [
        enc.resource_names.index(r["name"])
        for r in resources
        if r["name"] in enc.resource_names
    ]
    S = BALANCED_SCALE
    S_BITS = S.bit_length() - 1
    exact64 = enc.policy.name == "exact"

    def kernel(a: ClusterArrays, s: SchedState, p, feasible=None) -> jnp.ndarray:
        N = a.node_mask.shape[0]
        if not idxs:
            return jnp.full(N, MAX_NODE_SCORE, enc.policy.score)
        caps = jnp.stack([a.node_alloc[:, i] for i in idxs], axis=1)  # [N, K]
        reqs = jnp.stack(
            [s.s_requested[:, i] + a.pod_sreq[p, i] for i in idxs], axis=1
        )
        incl = caps > 0
        # Clamp requested to capacity BEFORE the long division: fractions
        # cap at 1 anyway (q = S exactly when req >= cap, as in the
        # oracle), and it preserves _div_scale_exact's no-overflow
        # precondition when usage wildly exceeds a tiny capacity.
        q = _div_scale_exact(jnp.minimum(reqs, caps), caps, S_BITS)  # [N, K]
        nf = incl.sum(axis=1).astype(q.dtype)
        # nf == 2 branch: std = |q0 - q1| / (2S); ints stay under 2^24.
        qmax = jnp.where(incl, q, jnp.iinfo(q.dtype).min).max(axis=1)
        qmin = jnp.where(incl, q, jnp.iinfo(q.dtype).max).min(axis=1)
        d = qmax - qmin
        score2 = (200 * S - 100 * d) // (2 * S)
        # general branch: A = nf*Σq² - (Σq)², std = sqrt(A)/(nf*S),
        # score = 100 - ceil(100*sqrt(A)/(nf*S)).
        if exact64:
            q64 = q.astype(jnp.int64)
            nf64 = nf.astype(jnp.int64)
            sum_q = jnp.where(incl, q64, 0).sum(axis=1)
            sum_q2 = jnp.where(incl, q64 * q64, 0).sum(axis=1)
            A = nf64 * sum_q2 - sum_q * sum_q
            x2 = 10000 * A
            D = jnp.maximum(nf64, 1) * S
            # ceil(sqrt(x2)/D) == isqrt(x2-1)//D + 1 for x2 > 0
            k = jnp.where(
                x2 == 0, 0, _exact_isqrt64(jnp.maximum(x2 - 1, 0)) // D + 1
            )
            score_n = (MAX_NODE_SCORE - k).astype(q.dtype)
        else:
            f = q.astype(jnp.float32) / S
            nff = jnp.maximum(nf, 1).astype(jnp.float32)
            mean = jnp.where(incl, f, 0).sum(axis=1) / nff
            var = jnp.where(incl, (f - mean[:, None]) ** 2, 0).sum(axis=1) / nff
            std = jnp.sqrt(var)
            score_n = jnp.floor((1 - std) * MAX_NODE_SCORE).astype(q.dtype)
        score = jnp.where(nf == 2, score2, score_n)
        score = jnp.where(nf < 2, MAX_NODE_SCORE, score)
        return score.astype(enc.policy.score)

    return kernel


# ---------------------------------------------------------------------------
# NodeName / NodeUnschedulable  (oracle: node_name_filter,
# node_unschedulable_filter)
# ---------------------------------------------------------------------------


def build_node_name_filter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        want = a.pod_node_name[p]
        node_ids = jnp.arange(a.node_mask.shape[0], dtype=jnp.int32)
        fail = (want != -1) & (node_ids != want)
        return fail.astype(jnp.int32)

    return kernel


def decode_node_name(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return "node(s) didn't match the requested node name"


def build_node_unschedulable_filter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        fail = a.node_unsched & ~a.pod_tol_unsched[p]
        return fail.astype(jnp.int32)

    return kernel


def decode_node_unschedulable(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return "node(s) were unschedulable"


# ---------------------------------------------------------------------------
# registries — populated further by m3 kernel modules
# ---------------------------------------------------------------------------

# name -> (builder(enc) -> filter kernel, decode(code, enc) -> message)
FILTER_KERNELS: dict[str, tuple[Callable, Callable]] = {
    "NodeResourcesFit": (build_fit_filter, decode_fit),
    "NodeName": (build_node_name_filter, decode_node_name),
    "NodeUnschedulable": (build_node_unschedulable_filter, decode_node_unschedulable),
}

# name -> (builder(enc) -> score kernel, normalize mode)
SCORE_KERNELS: dict[str, tuple[Callable, "str | None"]] = {
    "NodeResourcesFit": (build_fit_score, None),
    "NodeResourcesBalancedAllocation": (build_balanced_score, None),
}

# preFilter plugins that can veto a pod before the per-node loop; name ->
# (builder(enc) -> fn(arrays, state, p) -> code (0 = pass), decode). M2
# plugins never fail prefilter; populated by m3 kernels (NodePorts
# self-conflict etc.).
PREFILTER_KERNELS: dict[str, tuple[Callable, Callable]] = {}

# preFilter plugins whose oracle implementation only caches state and can
# never fail — the engine just records "success" for them.
TRIVIAL_PREFILTER: set[str] = {"NodeResourcesFit"}

# preScore plugins that can fail/skip; name -> (builder, decode). Trivial
# ones (always "success") are listed in TRIVIAL_PRESCORE.
PRESCORE_KERNELS: dict[str, tuple[Callable, Callable]] = {}

TRIVIAL_PRESCORE: set[str] = {
    "TaintToleration",
    "NodeAffinity",
    "NodeResourcesFit",
    "NodeResourcesBalancedAllocation",
}

# postFilter (preemption) kernels; name -> builder. Empty until the
# DefaultPreemption victim-selection kernel lands (SURVEY.md §7 M3).
POSTFILTER_KERNELS: dict[str, Callable] = {}

# Plugins whose kernel builders bake *cluster content* (not just shapes /
# config args) into the compiled closure must register a statics function
# here: name -> fn(enc) -> hashable. `BatchedScheduler.compile_signature`
# folds it in so the serving layer's compiled-engine cache can never reuse
# a program whose baked features went stale (e.g. the NetworkBandwidth
# demo bakes annotation-derived arrays; plugins/networkbandwidth.py).
# In-tree kernels read content only through `arrays`/`state` arguments —
# except the preemption victim bound, which compile_signature already
# includes directly.
COMPILE_STATICS: dict[str, Callable] = {}

# Permit plugins: name -> builder(enc) -> fn(pod_idx, node_idx) ->
# (message, timeout_seconds). Permit runs AFTER node selection and only
# produces the recorded status + wait timeout (the reference records Wait
# statuses and the timeout duration, wrappedplugin.go:549-575 /
# store.go:544-555); it is host-side by design — no in-tree plugin uses
# it, the simulator never actually parks a binding, and keeping it off
# the compiled path means custom permits can use arbitrary Python.
# Enabled permit plugins WITHOUT a registration record plain "success".
PERMIT_PLUGINS: dict[str, Callable] = {}


# ---------------------------------------------------------------------------
# TaintToleration  (oracle: taint_toleration_filter/score/normalize;
# models/objects.py toleration_tolerates_taint)
# ---------------------------------------------------------------------------


def _tolerated(a: ClusterArrays, p) -> jnp.ndarray:
    """[N, T] — is each node taint tolerated by pod p's tolerations?"""
    tk = a.tol_key[p][:, None, None]  # [L, 1, 1]
    tv = a.tol_val[p][:, None, None]
    te = a.tol_effect[p][:, None, None]
    to = a.tol_op[p][:, None, None]
    nk = a.taint_key[None, :, :]  # [1, N, T]
    nv = a.taint_val[None, :, :]
    ne = a.taint_effect[None, :, :]
    valid = to >= 0
    eff_ok = (te == -1) | (te == ne)
    key_ok = (tk == -1) | (tk == nk)
    # Exists always matches; Equal needs the value; unknown ops (2) never
    val_ok = (to == 1) | ((to == 0) & (tv == nv))
    return (valid & eff_ok & key_ok & val_ok).any(axis=0)  # [N, T]


def build_taint_filter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        tolerated = _tolerated(a, p)
        intolerable = (a.taint_effect == 0) | (a.taint_effect == 2)  # NoSchedule|NoExecute
        bad = intolerable & ~tolerated  # [N, T]
        first_bad = jnp.argmax(bad, axis=1)  # first True slot
        return jnp.where(bad.any(axis=1), first_bad + 1, 0).astype(jnp.int32)

    return kernel


def decode_taint(code: int, enc: EncodedCluster, node_idx: int) -> str:
    taint = enc.aux["node_taints"][node_idx][code - 1]
    return (
        "node(s) had untolerated taint "
        f"{{{taint.get('key', '')}: {taint.get('value', '')}}}"
    )


def build_taint_score(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p, feasible=None) -> jnp.ndarray:
        tolerated = _tolerated(a, p)
        prefer = a.taint_effect == 1  # PreferNoSchedule
        return (prefer & ~tolerated).sum(axis=1).astype(enc.policy.score)

    return kernel


# ---------------------------------------------------------------------------
# NodeAffinity / nodeSelector  (oracle: node_affinity_filter/score;
# models/objects.py match_node_selector_term[s], _match_expression)
# ---------------------------------------------------------------------------


def _terms_match(a: ClusterArrays, key, op, vals, num, num_ok, term_valid):
    """[..., N] — per term: AND over expressions, against every node.

    key/op/num/num_ok: [TM, E]; vals: [TM, E, VV]; term_valid: [TM].
    Returns match[TM, N].
    """
    key_safe = jnp.maximum(key, 0)
    nval = a.label_val.T[key_safe]  # [TM, E, N]
    nnum = a.label_num.T[key_safe]
    nnum_ok = a.label_num_ok.T[key_safe]
    present = nval >= 0
    eq_any = (nval[..., None, :] == vals[..., :, None]).any(axis=-2)  # [TM, E, N]
    is_in = present & eq_any
    # upstream labels.Requirement: NotIn matches when the key is ABSENT
    # too (value-id padding is VAL_PAD=-3, never the absent sentinel -1,
    # so eq_any is False for absent keys and ~is_in is exact)
    not_in = ~is_in
    exists = present
    dne = ~present
    num_cmp_ok = present & nnum_ok & num_ok[..., None]
    gt = num_cmp_ok & (nnum > num[..., None])
    lt = num_cmp_ok & (nnum < num[..., None])
    opx = op[..., None]
    m = jnp.where(
        opx == 0, is_in,
        jnp.where(opx == 1, not_in,
        jnp.where(opx == 2, exists,
        jnp.where(opx == 3, dne,
        jnp.where(opx == 4, gt,
        jnp.where(opx == 5, lt, False))))))
    # padded expression slots (key == -1) are neutral for the AND
    m = m | (key == -1)[..., None]
    return m.all(axis=-2) & term_valid[:, None]  # [TM, N]


def build_node_affinity_filter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        # nodeSelector: AND of key == value
        k = a.nsel_key[p]  # [NS]
        k_safe = jnp.maximum(k, 0)
        nval = a.label_val.T[k_safe]  # [NS, N]
        sel_ok = ((nval == a.nsel_val[p][:, None]) | (k == -1)[:, None]).all(axis=0)
        # required terms: OR over terms (pass when no terms)
        tmatch = _terms_match(
            a,
            a.raff_key[p],
            a.raff_op[p],
            a.raff_vals[p],
            a.raff_num[p],
            a.raff_num_ok[p],
            a.raff_term_valid[p],
        )
        req_ok = tmatch.any(axis=0) | ~a.pod_has_raff[p]
        return jnp.where(sel_ok & req_ok, 0, 1).astype(jnp.int32)

    return kernel


def decode_node_affinity(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return "node(s) didn't match Pod's node affinity/selector"


def build_node_affinity_score(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p, feasible=None) -> jnp.ndarray:
        tmatch = _terms_match(
            a,
            a.paff_key[p],
            a.paff_op[p],
            a.paff_vals[p],
            a.paff_num[p],
            a.paff_num_ok[p],
            a.paff_term_valid[p],
        )  # [PR, N]
        w = a.paff_weight[p][:, None]
        return jnp.where(tmatch, w, 0).sum(axis=0).astype(enc.policy.score)

    return kernel


# ---------------------------------------------------------------------------
# NodePorts  (oracle: node_ports_filter/_ports_conflict; prefilter is a
# pure state cache and never fails)
# ---------------------------------------------------------------------------


def build_node_ports_filter(enc: EncodedCluster):
    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        wild = a.want_wild[p] > 0  # [Q]
        trip = a.want_trip[p] > 0  # [V2]
        wild_conflict = (wild[None, :] & (s.used_pair > 0)).any(axis=1)
        trip_conflict = (
            trip[None, :]
            & ((s.used_trip > 0) | (s.used_wild[:, a.trip_pair] > 0))
        ).any(axis=1)
        return (wild_conflict | trip_conflict).astype(jnp.int32)

    return kernel


def decode_node_ports(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return "node(s) didn't have free ports for the requested pod ports"


# ---------------------------------------------------------------------------
# ImageLocality  (oracle: image_locality_score; Ki-unit integer semantics,
# see encode.IMG_* constants)
# ---------------------------------------------------------------------------


def build_image_locality_score(enc: EncodedCluster):
    from .encode import IMG_MAX_CONTAINER_KI, IMG_MIN_KI

    score_dt = enc.policy.score

    def kernel(a: ClusterArrays, s: SchedState, p, feasible=None) -> jnp.ndarray:
        counts = a.pod_img[p].astype(a.img_contrib.dtype)  # [I]
        ss = (a.img_contrib * counts[None, :]).sum(axis=1)  # [N]
        ncont = a.pod_ncont[p].astype(a.img_contrib.dtype)
        maxth = IMG_MAX_CONTAINER_KI * ncont
        ss = jnp.clip(ss, IMG_MIN_KI, jnp.maximum(maxth, IMG_MIN_KI + 1))
        x = ss - IMG_MIN_KI
        den = jnp.maximum(maxth - IMG_MIN_KI, 1)
        # (100*x)//den via two base-10 digits to stay in int32: x <= den
        a1 = x // den
        r = x % den
        d1 = (r * 10) // den
        r2 = (r * 10) % den
        d2 = (r2 * 10) // den
        score = a1 * 100 + d1 * 10 + d2
        # zero-container pods score 0, pinned on both sides (oracle
        # image_locality_score guards num_containers == 0 the same way;
        # unreachable for valid k8s pods, which always have >= 1 container)
        return jnp.where(ncont == 0, 0, score).astype(score_dt)

    return kernel


FILTER_KERNELS.update(
    {
        "TaintToleration": (build_taint_filter, decode_taint),
        "NodeAffinity": (build_node_affinity_filter, decode_node_affinity),
        "NodePorts": (build_node_ports_filter, decode_node_ports),
    }
)
SCORE_KERNELS.update(
    {
        "TaintToleration": (build_taint_score, "default_reverse"),
        "NodeAffinity": (build_node_affinity_score, "default"),
        "ImageLocality": (build_image_locality_score, None),
    }
)
TRIVIAL_PREFILTER.add("NodePorts")


# ---------------------------------------------------------------------------
# Topology histograms (shared by PodTopologySpread and InterPodAffinity).
# Every per-pod count of matching pods by node, or by node (key, value)
# pair, is a contraction of a per-pod 0/1 mask with a one-hot plane built
# from the state alone: the node each pod is bound to, the pair each node
# carries per key, the pair of each bound pod's node per term. Reading a
# histogram back at the nodes is a contraction with the nodes' one-hot
# pair plane too. The planes do not depend on the incoming pod, so under
# the gang pass's pod `vmap` they stay unbatched and a chunk's histograms
# are a few matmuls. Scatter-adds and gathers would give the same
# integers, but the TPU applies colliding scatter updates one after
# another, and a gather with per-pod indices runs element by element
# (docs/performance.md "Topology histograms are contractions"). Operands
# are int8, accumulation int32: every count and every value is exact.
# ---------------------------------------------------------------------------


def _onehot(ids, n: int) -> jnp.ndarray:
    """[..., n] int8 plane, 1 where `ids` equals the column; an id
    outside [0, n) (an unbound pod's -1) gives a zero row."""
    cols = jnp.arange(n, dtype=jnp.int32)
    return (ids.astype(jnp.int32)[..., None] == cols).astype(jnp.int8)


def _contract(spec: str, mask, plane) -> jnp.ndarray:
    """Exact int32 `einsum(spec)` of a 0/1 mask with an int8 plane."""
    return jnp.einsum(
        spec, mask.astype(jnp.int8), plane, preferred_element_type=jnp.int32
    )


def _pod_nodes(a: ClusterArrays, s: SchedState) -> jnp.ndarray:
    """[P, N] one-hot of each pod's node (zero row when unbound)."""
    return _onehot(s.assignment, a.node_mask.shape[0])


def _pod_pairs(a: ClusterArrays, s: SchedState) -> jnp.ndarray:
    """[P, K] pair id of each pod's node per key; 0 when unbound."""
    bound = (s.assignment >= 0)[:, None]
    return jnp.where(bound, a.rel.node_pair[jnp.maximum(s.assignment, 0)], 0)


def _pick_key(per_key, keys) -> jnp.ndarray:
    """[T, M] rows of a per-key table ([T, K, M], or [K, M] for every row)
    at each row's key (key column 0 for a padded row, as the kernels' key
    gathers do)."""
    K = per_key.shape[-2]
    sel = (jnp.maximum(keys, 0)[:, None] == jnp.arange(K))[:, :, None]  # [T, K, 1]
    if per_key.dtype == jnp.bool_:
        return (per_key & sel).any(axis=1)
    return jnp.where(sel, per_key, 0).sum(axis=1, dtype=per_key.dtype)


def _spread_counts(a: ClusterArrays, s: SchedState, mm) -> jnp.ndarray:
    """[T, N] — counted bound pods (`mm` [T, P]) on each node."""
    return _contract("tp,pn->tn", mm, _pod_nodes(a, s))


def _pair_counts(a: ClusterArrays, s: SchedState, mm, keys, NP1: int, node_w=None):
    """[T, NP1] — counted bound pods (`mm` [T, P]) by the pair their node
    carries at row t's key (`keys` [T]; bin 0 where the node lacks it),
    only pods on a node of `node_w` [N] when given. Counted from the pod
    side, so every operand stays 0/1 however large the counts."""
    if node_w is not None:
        on_w = _contract("n,pn->p", node_w, _pod_nodes(a, s)) > 0  # [P]
        mm = mm & on_w[None, :]
    per_key = _contract("tp,pkv->tkv", mm, _onehot(_pod_pairs(a, s), NP1))
    return _pick_key(per_key, keys)


def _pair_nodes(a: ClusterArrays, keys, node_w, NP1: int) -> jnp.ndarray:
    """[T, NP1] — nodes of `node_w` [N] carrying each pair at row t's key;
    bin 0 (the key absent) stays 0."""
    per_key = _contract("n,nkv->kv", node_w, _onehot(a.rel.node_pair, NP1))
    rows = _pick_key(per_key, keys)
    return jnp.where(jnp.arange(NP1)[None, :] > 0, rows, 0)


def _digits(x) -> jnp.ndarray:
    """[..., B] balanced base-256 int8 digits of the integers `x` (B their
    byte width): Σ_j d_j·256^j == x modulo 2^(8B), every d_j in
    [-128, 127]. A contraction of int8 digits stays exact in int32."""
    digits = []
    for _ in range(x.dtype.itemsize):
        d = ((x + 128) & 255) - 128
        digits.append(d)
        x = (x - d) >> 8
    return jnp.stack(digits, axis=-1).astype(jnp.int8)


def _undigits(parts, dtype) -> jnp.ndarray:
    """Σ_j parts[..., j]·256^j in `dtype`, wrapping as its adds do."""
    B = parts.shape[-1]
    scale = jnp.asarray([1 << (8 * j) for j in range(B)], dtype)
    return (parts.astype(dtype) * scale).sum(axis=-1, dtype=dtype)


def _term_pair_hist(contrib, pair_ot, NP1: int, weight=None) -> jnp.ndarray:
    """[NP1] int32 — Σ over (pod, term) of `contrib` [P, T] (0/1), times
    `weight` [P, T] when given, binned by `pair_ot` [P, T] (the pair of
    each pod's node at the term's key). The weights ride in the mask as
    int8 digits, so the sum is exact modulo 2^32, as an int32
    scatter-add's is."""
    plane = _onehot(pair_ot, NP1)  # [P, T, NP1]
    if weight is None:
        return _contract("pt,ptv->v", contrib, plane)
    digits = contrib[..., None].astype(jnp.int8) * _digits(weight.astype(jnp.int32))
    return _undigits(_contract("ptj,ptv->vj", digits, plane), jnp.int32)


def _node_keys(a: ClusterArrays, keys) -> jnp.ndarray:
    """[N, T] — whether each node carries row t's key (`keys` [T]; key
    column 0 for a padded row, as the kernels' key gathers do)."""
    return _pick_key((a.rel.node_pair > 0).T, keys).T


def _at_node_pairs(a: ClusterArrays, table, NP1: int) -> jnp.ndarray:
    """[..., K, N] — `table` [..., NP1] at the pair each node carries per
    key: table[..., node_pair[n, k]]. A gather, run as contractions with
    the nodes' one-hot pair plane; an integer table rides as int8 digits,
    each output taking exactly one term, so the values are exact."""
    plane = _onehot(a.rel.node_pair, NP1)  # [N, K, NP1]
    if table.dtype == jnp.bool_:
        return _contract("...v,nkv->...kn", table, plane) > 0
    parts = _contract("...vj,nkv->...knj", _digits(table), plane)
    return _undigits(parts, table.dtype)


def _at_node_key_pairs(a: ClusterArrays, table, keys, NP1: int) -> jnp.ndarray:
    """[N, T] — `table` [T, NP1] at the pair each node carries at row t's
    key: table[t, node_pair[n, keys[t]]] (key column 0 for a padded row)."""
    return _pick_key(_at_node_pairs(a, table, NP1), keys).T


# ---------------------------------------------------------------------------
# PodTopologySpread  (oracle: spread_pre_filter/spread_filter/
# spread_pre_score/spread_score/spread_normalize). The oracle's
# PreFilter/PreScore dict-building loops become the histograms above and
# gathers back to the nodes.
# ---------------------------------------------------------------------------


def _spread_match(a: ClusterArrays, s: SchedState, p, ctype, ckey, cpairs):
    """[T, P] — per constraint, the bound pods it counts (same namespace
    as pod p, not deleted; oracle _count_matching_pods)."""
    from .encode_rel import match_clauses

    rel = a.rel
    m = match_clauses(rel, ctype, ckey, cpairs)  # [T, P]
    live = (
        (rel.ns_id == rel.ns_id[p])[None, :]
        & ~rel.deleted[None, :]
        & a.pod_mask[None, :]
        & (s.assignment >= 0)[None, :]
    )
    return m & live


def build_spread_filter(enc: EncodedCluster):
    aff_kernel = build_node_affinity_filter(enc)
    NP1 = enc.aux["n_node_pairs"] + 1
    BIG = jnp.iinfo(jnp.int32).max

    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        rel = a.rel
        N = a.node_mask.shape[0]
        keys = rel.sph_key[p]  # [HC]
        HC = keys.shape[0]
        valid = keys >= 0
        has_key = _node_keys(a, keys)  # [N, HC]
        has_all = (has_key | ~valid[None, :]).all(axis=1)  # [N]
        elig = (aff_kernel(a, s, p) == 0) & has_all & a.node_mask
        mm = _spread_match(
            a, s, p, rel.sph_ctype[p], rel.sph_ckey[p], rel.sph_cpairs[p]
        )  # [HC, P]
        val_cnt = _pair_counts(a, s, mm, keys, NP1, node_w=elig)
        present = _pair_nodes(a, keys, elig, NP1)
        pmask = (present > 0) & (jnp.arange(NP1) > 0)[None, :]
        min_c = jnp.where(pmask, val_cnt, BIG).min(axis=1)
        min_c = jnp.where(pmask.any(axis=1), min_c, 0)  # [HC]
        node_cnt = _at_node_key_pairs(a, val_cnt, keys, NP1)  # [N, HC]
        skew = node_cnt + rel.sph_self[p][None, :].astype(jnp.int32) - min_c[None, :]
        fail_skew = skew > rel.sph_skew[p][None, :]
        code_c = jnp.where(
            ~valid[None, :], 0, jnp.where(~has_key, 1, jnp.where(fail_skew, 2, 0))
        )  # [N, HC]
        code = jnp.zeros((N,), jnp.int32)
        for c in reversed(range(HC)):  # the first failing constraint's code
            code = jnp.where(code_c[:, c] != 0, code_c[:, c], code)
        return code

    return kernel


def decode_spread(code: int, enc: EncodedCluster, node_idx: int) -> str:
    if code == 1:
        return (
            "node(s) didn't match pod topology spread constraints "
            "(missing required label)"
        )
    return "node(s) didn't match pod topology spread constraints"


def build_spread_score(enc: EncodedCluster):
    """Raw score: Σ_c count(c) * log-weight(c) in SPREAD_SCALE fixed point,
    plus Σ(maxSkew-1), banker's-rounded — bit-identical to the oracle's
    integer rewrite. Counts stay < 2^31/weight for P ≤ ~50k pods."""
    from ..sched.oracle_plugins import SPREAD_SCALE

    # The score path consumes PreScore state (oracle spread_pre_score →
    # spread_score): with the PreScore plugin disabled, the oracle scores 0
    # and normalizes to 0 — mirror that exactly.
    if "PodTopologySpread" not in enc.config.enabled("preScore"):

        def zero_kernel(a, s, p, feasible):
            return jnp.zeros(a.node_mask.shape[0], enc.policy.score)

        zero_kernel._normalize = lambda a, s, p, raw, feasible: jnp.zeros_like(raw)
        return zero_kernel

    aff_kernel = build_node_affinity_filter(enc)
    NP1 = enc.aux["n_node_pairs"] + 1

    def soft_ignored(a: ClusterArrays, s: SchedState, p, feasible):
        rel = a.rel
        keys = rel.sps_key[p]
        valid = keys >= 0
        has_key = _node_keys(a, keys)  # [N, SC]
        has_all = (has_key | ~valid[None, :]).all(axis=1)
        ignored = feasible & rel.req_all[p] & ~has_all
        return keys, valid, has_key, has_all, ignored

    def kernel(a: ClusterArrays, s: SchedState, p, feasible) -> jnp.ndarray:
        rel = a.rel
        keys, valid, has_key, has_all, ignored = soft_ignored(a, s, p, feasible)
        scored = feasible & ~ignored
        n_scored = scored.sum().astype(jnp.int32)
        count_mask = (
            (aff_kernel(a, s, p) == 0)
            & jnp.where(rel.req_all[p], has_all, True)
            & a.node_mask
        )
        mm = _spread_match(
            a, s, p, rel.sps_ctype[p], rel.sps_ckey[p], rel.sps_cpairs[p]
        )  # [SC, P]
        cnt_node = _spread_counts(a, s, mm)  # [SC, N]
        val_cnt = _pair_counts(a, s, mm, keys, NP1, node_w=count_mask)
        present = _pair_nodes(a, keys, scored, NP1)
        topo_size = ((present > 0) & (jnp.arange(NP1) > 0)[None, :]).sum(axis=1)
        host = rel.sps_host[p]  # [SC]
        w_m = jnp.where(host, n_scored, topo_size)
        w_q = rel.spread_lut[jnp.clip(w_m, 0, rel.spread_lut.shape[0] - 1)]  # [SC]
        node_cnt = _at_node_key_pairs(a, val_cnt, keys, NP1)  # [N, SC]
        val_ok = _at_node_key_pairs(a, present > 0, keys, NP1)
        cnt = jnp.where(host[None, :], cnt_node.T, node_cnt)
        apply = valid[None, :] & has_key & (host[None, :] | val_ok)
        totq = (jnp.where(apply, cnt, 0) * w_q[None, :]).sum(axis=1)
        mssum = jnp.where(apply, rel.sps_skew[p][None, :] - 1, 0).sum(axis=1)
        q, r = totq // SPREAD_SCALE, totq % SPREAD_SCALE
        up = (2 * r > SPREAD_SCALE) | ((2 * r == SPREAD_SCALE) & (q % 2 == 1))
        raw = mssum + q + up.astype(jnp.int32)
        return jnp.where(ignored, 0, raw).astype(enc.policy.score)

    def normalize(a: ClusterArrays, s: SchedState, p, raw, feasible):
        rel = a.rel
        keys = rel.sps_key[p]
        *_, ignored = soft_ignored(a, s, p, feasible)
        live = feasible & ~ignored
        BIG = jnp.iinfo(jnp.int32).max
        minv = jnp.where(live, raw, BIG).min()
        maxv = jnp.where(live, raw, -BIG).max()
        normed = jnp.where(
            maxv == 0,
            MAX_NODE_SCORE,
            MAX_NODE_SCORE * (maxv + minv - raw) // jnp.maximum(maxv, 1),
        )
        normed = jnp.where(ignored, 0, normed)
        active = (keys >= 0).any() & live.any()
        return jnp.where(active, normed, 0).astype(raw.dtype)

    kernel._normalize = normalize
    return kernel


FILTER_KERNELS["PodTopologySpread"] = (build_spread_filter, decode_spread)
SCORE_KERNELS["PodTopologySpread"] = (build_spread_score, "custom")
TRIVIAL_PREFILTER.add("PodTopologySpread")
TRIVIAL_PRESCORE.add("PodTopologySpread")


# ---------------------------------------------------------------------------
# InterPodAffinity  (oracle: interpod_pre_filter/interpod_filter/
# interpod_pre_score/interpod_score/interpod_normalize). Both matching
# directions run on-device: the incoming pod's terms vs every pod
# (match_clauses) and every pod's terms vs the incoming pod
# (match_clauses_rev); topology localization reduces through the node
# (key,value)-pair vocab with the topology histograms above.
# ---------------------------------------------------------------------------


def _ipa_forward_match(a: ClusterArrays, s: SchedState, p, ctype, ckey, cpairs, nsall, nsmh):
    """[T, P] — per incoming term, the bound pods it matches (real, in the
    term's namespaces). A padded term slot matches nothing: its namespace
    masks are empty."""
    from .encode_rel import match_clauses

    rel = a.rel
    m = match_clauses(rel, ctype[p], ckey[p], cpairs[p])  # [T, P]
    bound = (s.assignment >= 0) & a.pod_mask
    ns_ok = nsall[p][:, None] | nsmh[p][:, rel.ns_id]  # [T, P]
    return m & ns_ok & bound[None, :]


def _forward_pair_counts(a, s, p, key_cols, ctype, ckey, cpairs, nsall, nsmh, NP1):
    """[T, NP1] — per incoming term, matching bound pods grouped by the
    (topologyKey, value) pair of their node."""
    m = _ipa_forward_match(a, s, p, ctype, ckey, cpairs, nsall, nsmh)
    return _pair_counts(a, s, m, key_cols[p], NP1)


def _reverse_terms(a: ClusterArrays, s: SchedState, p, key, ctype, ckey, cpairs, nsall, nsmh):
    """(contrib [P, T], pair_ot [P, T]) — which existing pods' terms match
    the incoming pod p and count (bound, key present on their node), and
    the pair of each pod's node at its term's key (pod-independent)."""
    from .encode_rel import match_clauses_rev

    rel = a.rel
    rev = match_clauses_rev(rel, ctype, ckey, cpairs, p)  # [P, T]
    ns_ok = nsall | nsmh[:, :, rel.ns_id[p]]
    pair_ot = jnp.take_along_axis(_pod_pairs(a, s), jnp.maximum(key, 0), axis=1)
    bound = (s.assignment >= 0) & a.pod_mask
    contrib = rev & ns_ok & (key >= 0) & bound[:, None] & (pair_ot > 0)
    return contrib, pair_ot


def build_interpod_filter(enc: EncodedCluster):
    NP1 = enc.aux["n_node_pairs"] + 1

    def kernel(a: ClusterArrays, s: SchedState, p) -> jnp.ndarray:
        rel = a.rel
        # (1) existing pods' required anti-affinity vs the incoming pod
        contrib, pair_ot = _reverse_terms(
            a, s, p, rel.ian_key, rel.ian_ctype, rel.ian_ckey, rel.ian_cpairs,
            rel.ian_nsall, rel.ian_ns,
        )
        ea_cnt = _term_pair_hist(contrib, pair_ot, NP1)  # [NP1]
        ea_node = _at_node_pairs(a, ea_cnt > 0, NP1).T  # [N, K]
        fail1 = (ea_node & (rel.node_pair > 0)).any(axis=1)
        # (2) incoming pod's required anti-affinity
        anti_cnt = _forward_pair_counts(
            a, s, p, rel.ian_key, rel.ian_ctype, rel.ian_ckey, rel.ian_cpairs,
            rel.ian_nsall, rel.ian_ns, NP1,
        )  # [T, NP1]
        key2 = rel.ian_key[p]  # [T]
        hit2 = _at_node_key_pairs(a, anti_cnt > 0, key2, NP1)  # [N, T]
        fail2 = (_node_keys(a, key2) & hit2 & (key2 >= 0)[None, :]).any(axis=1)
        # (3) incoming pod's required affinity
        aff_cnt = _forward_pair_counts(
            a, s, p, rel.ia_key, rel.ia_ctype, rel.ia_ckey, rel.ia_cpairs,
            rel.ia_nsall, rel.ia_ns, NP1,
        )
        key3 = rel.ia_key[p]
        tvalid3 = key3 >= 0
        has_terms = tvalid3.any()
        has3 = _node_keys(a, key3)  # [N, T]
        ok_t = has3 & _at_node_key_pairs(a, aff_cnt > 0, key3, NP1)
        satisfied = (ok_t | ~tvalid3[None, :]).all(axis=1)
        # first-pod-in-series: no term matched anything anywhere AND the
        # pod matches all of its own terms (oracle interpod_filter) — gated
        # on the node carrying every requested topology key (upstream
        # satisfyPodAffinity fails such nodes before the special case)
        total_matches = aff_cnt[:, 1:].sum()
        self_all = (rel.ia_self[p] | ~tvalid3).all()
        has_all_keys = (has3 | ~tvalid3[None, :]).all(axis=1)  # [N]
        pass3 = satisfied | (has_all_keys & (total_matches == 0) & self_all)
        fail3 = has_terms & ~pass3
        return jnp.where(
            fail1, 1, jnp.where(fail2, 2, jnp.where(fail3, 3, 0))
        ).astype(jnp.int32)

    return kernel


def decode_interpod(code: int, enc: EncodedCluster, node_idx: int) -> str:
    return {
        1: "node(s) didn't satisfy existing pods anti-affinity rules",
        2: "node(s) didn't match pod anti-affinity rules",
        3: "node(s) didn't match pod affinity rules",
    }[code]


def build_interpod_score(enc: EncodedCluster):
    """topology_score[(key,val)] accumulated into a node-pair weight array,
    gathered per node (oracle interpod_pre_score/interpod_score)."""
    if "InterPodAffinity" not in enc.config.enabled("preScore"):

        def zero_kernel(a, s, p, feasible):
            return jnp.zeros(a.node_mask.shape[0], enc.policy.score)

        zero_kernel._normalize = lambda a, s, p, raw, feasible: jnp.zeros_like(raw)
        return zero_kernel

    NP1 = enc.aux["n_node_pairs"] + 1
    hard_w = int(
        enc.config.plugin_args("InterPodAffinity").get("hardPodAffinityWeight", 1)
    )
    score_dt = enc.policy.score

    def kernel(a: ClusterArrays, s: SchedState, p, feasible) -> jnp.ndarray:
        rel = a.rel
        wsum = jnp.zeros(NP1, score_dt)
        # incoming pod's preferred terms vs existing pods (weight ±w)
        for key, ct, ck, cp, na, nm, w, sign in (
            (rel.ipa_key, rel.ipa_ctype, rel.ipa_ckey, rel.ipa_cpairs,
             rel.ipa_nsall, rel.ipa_ns, rel.ipa_weight, 1),
            (rel.ipan_key, rel.ipan_ctype, rel.ipan_ckey, rel.ipan_cpairs,
             rel.ipan_nsall, rel.ipan_ns, rel.ipan_weight, -1),
        ):
            cnt = _forward_pair_counts(a, s, p, key, ct, ck, cp, na, nm, NP1)
            wt = (sign * w[p]).astype(score_dt)[:, None]  # [T, 1]
            wsum = wsum + (cnt.astype(score_dt) * wt).sum(axis=0, dtype=score_dt)
        # existing pods' terms vs the incoming pod: preferred ±w, and
        # required affinity at hardPodAffinityWeight
        rev_domains = [
            (rel.ipa_key, rel.ipa_ctype, rel.ipa_ckey, rel.ipa_cpairs,
             rel.ipa_nsall, rel.ipa_ns, rel.ipa_weight, 1),
            (rel.ipan_key, rel.ipan_ctype, rel.ipan_ckey, rel.ipan_cpairs,
             rel.ipan_nsall, rel.ipan_ns, rel.ipan_weight, -1),
        ]
        if hard_w > 0:
            rev_domains.append(
                (rel.ia_key, rel.ia_ctype, rel.ia_ckey, rel.ia_cpairs,
                 rel.ia_nsall, rel.ia_ns, None, hard_w)
            )
        for key, ct, ck, cp, na, nm, w, sign in rev_domains:
            contrib, pair_ot = _reverse_terms(a, s, p, key, ct, ck, cp, na, nm)
            if w is None:
                hist = sign * _term_pair_hist(contrib, pair_ot, NP1)
            else:
                hist = _term_pair_hist(contrib, pair_ot, NP1, weight=sign * w)
            wsum = wsum + hist.astype(score_dt)
        wsum = jnp.where(jnp.arange(NP1) > 0, wsum, 0)  # a key the node lacks adds 0
        return _at_node_pairs(a, wsum, NP1).sum(axis=0, dtype=score_dt)

    def normalize(a, s, p, raw, feasible):
        BIG = jnp.iinfo(jnp.int32).max
        minv = jnp.where(feasible, raw, BIG).min()
        maxv = jnp.where(feasible, raw, -BIG).max()
        diff = maxv - minv
        return jnp.where(
            diff > 0, MAX_NODE_SCORE * (raw - minv) // jnp.maximum(diff, 1), 0
        ).astype(raw.dtype)

    kernel._normalize = normalize
    return kernel


FILTER_KERNELS["InterPodAffinity"] = (build_interpod_filter, decode_interpod)
SCORE_KERNELS["InterPodAffinity"] = (build_interpod_score, "custom")
TRIVIAL_PREFILTER.add("InterPodAffinity")
TRIVIAL_PRESCORE.add("InterPodAffinity")


# ---------------------------------------------------------------------------
# Volume family (kernels_vol.py): static gather tables for VolumeBinding /
# VolumeZone, counter kernels for VolumeRestrictions + volume-count limits.
# ---------------------------------------------------------------------------

from . import kernels_vol as _KV  # noqa: E402

FILTER_KERNELS.update(
    {
        "VolumeBinding": (
            _KV._build_static_table_filter("vb_code"),
            _KV._vol_message,
        ),
        "VolumeZone": (
            _KV._build_static_table_filter("vz_code"),
            _KV._vol_message,
        ),
        "VolumeRestrictions": (
            _KV.build_volume_restrictions_filter,
            _KV.decode_volume_restrictions,
        ),
        "NodeVolumeLimits": (
            _KV.build_node_volume_limits_filter,
            _KV.decode_never,
        ),
        "EBSLimits": (
            _KV._build_volume_limits_filter("EBSLimits"),
            _KV.decode_volume_limits,
        ),
        "GCEPDLimits": (
            _KV._build_volume_limits_filter("GCEPDLimits"),
            _KV.decode_volume_limits,
        ),
        "AzureDiskLimits": (
            _KV._build_volume_limits_filter("AzureDiskLimits"),
            _KV.decode_volume_limits,
        ),
    }
)
PREFILTER_KERNELS["VolumeBinding"] = (
    _KV.build_volume_binding_prefilter,
    _KV.decode_volume_binding_prefilter,
)
# Recorded-but-unfailable prefilters (oracle PREFILTER_PLUGINS lambdas).
TRIVIAL_PREFILTER.update({"VolumeRestrictions", "VolumeZone", "NodeAffinity"})


# ---------------------------------------------------------------------------
# DefaultPreemption (PostFilter) lives in preempt.py — an incremental-
# counter dry run: O(P·T) prepare + O(N·V·(T+NP1)) reprieve, replacing the
# round-1 full-kernel re-evaluation (O(N²·V·F)). Builders take
# (enc, filter_names).
# ---------------------------------------------------------------------------

from .preempt import (  # noqa: E402
    PREEMPT_CANDIDATE,
    PREEMPT_NO_FIT,
    PREEMPT_NO_LOWER,
    PREEMPT_SELECTED,
    PREEMPT_SILENT,
    build_preemption,
    decode_preemption,
)

POSTFILTER_KERNELS["DefaultPreemption"] = build_preemption
