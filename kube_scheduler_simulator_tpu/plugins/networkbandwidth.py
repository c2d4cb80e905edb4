"""NetworkBandwidth — the demo out-of-tree plugin, TPU-native.

Re-derivation of the reference's example custom plugin
(simulator/scheduler/plugin/networkbandwidth/plugin.go:52-186): nodes
carry a bandwidth capacity annotation, pods request ingress/egress
bandwidth via annotations; Filter rejects nodes whose remaining capacity
can't fit the request, Score is the remaining capacity min-max normalized
(plugin.go:159-186).

This module is the user-extensibility proof: importing it registers

  * oracle functions into `sched.oracle_plugins` dispatch tables,
  * a filter kernel + score kernel into `engine.kernels` registries,
  * a preemption row into `engine.preempt.ROW_FILTERS`,

after which any configuration may enable "NetworkBandwidth" by name. The
kernel builders featurize the *raw manifests* themselves (annotations →
arrays) at build time — custom plugins need no changes to the core
featurizer; allocated bandwidth is reduced on-device from
`state.assignment` with one scatter-add per step.

Integer semantics: bandwidth quantities are taken in Mi units (value >>
20, same int32-portability rationale as ImageLocality's Ki units,
sched/oracle_plugins.py) — requests under 1Mi round to zero and count as
"no request" (upstream is byte-granular).
"""

from __future__ import annotations

import numpy as np

from ..sched import oracle_plugins as op
from ..sched.config import MAX_NODE_SCORE
from ..utils.quantity import parse_quantity

NODE_LIMIT_ANNOTATION = "node.kubernetes.io/network-limit"
INGRESS_ANNOTATION = "kubernetes.io/ingress-request"
EGRESS_ANNOTATION = "kubernetes.io/egress-request"

FILTER_MESSAGE = (
    "node does not have enough network bandwidth capacity to schedule pod"
)


def _annotations(obj: dict) -> dict:
    return (obj.get("metadata", {}) or {}).get("annotations") or {}


def _mi(quantity_str: "str | None") -> "int | None":
    if not quantity_str:
        return None
    try:
        return parse_quantity(quantity_str).units >> 20
    except (ValueError, TypeError):
        return None


def pod_bandwidth_mi(pod_obj: dict) -> int:
    ann = _annotations(pod_obj)
    total = 0
    for key in (INGRESS_ANNOTATION, EGRESS_ANNOTATION):
        v = _mi(ann.get(key))
        if v:
            total += v
    return total


def node_limit_mi(node_obj: dict) -> "int | None":
    return _mi(_annotations(node_obj).get(NODE_LIMIT_ANNOTATION))


# -- oracle (per-pod reference semantics) -----------------------------------


def _allocated_mi(ni) -> int:
    return sum(pod_bandwidth_mi(p.obj) for p in ni.pods)


def nb_filter(ctx, pod, ni) -> "str | None":
    limit = node_limit_mi(ni.node.obj)
    if limit is None:
        return None  # node opted out (upstream Skip)
    want = pod_bandwidth_mi(pod.obj)
    if want == 0:
        return None  # no request (upstream Skip)
    if _allocated_mi(ni) + want > limit:
        return FILTER_MESSAGE
    return None


def nb_score(ctx, pod, ni) -> int:
    limit = node_limit_mi(ni.node.obj)
    if limit is None:
        return 0
    return limit - _allocated_mi(ni)


def nb_normalize(ctx, pod, raw: dict) -> dict:
    """Min-max to [0, MAX_NODE_SCORE] (plugin.go:159-186), integer
    floor-div for float-portability (see oracle SPREAD_SCALE note)."""
    if not raw:
        return raw
    lo, hi = min(raw.values()), max(raw.values())
    delta = hi - lo
    return {
        k: (MAX_NODE_SCORE * (v - lo)) // delta if delta > 0 else 0
        for k, v in raw.items()
    }


# -- engine kernels ---------------------------------------------------------


def _featurize(enc):
    """Annotations → arrays, computed by the builder itself (the custom-
    kernel pattern: no core featurizer changes)."""
    N, P = enc.N, enc.P
    node_limit = np.zeros(N, np.int64)
    node_has = np.zeros(N, bool)
    for i, n in enumerate(enc.objects.get("nodes", [])):
        lim = node_limit_mi(n)
        if lim is not None:
            node_limit[i] = lim
            node_has[i] = True
    pod_bw = np.zeros(P, np.int64)
    for i, p in enumerate(enc.pods):
        pod_bw[i] = pod_bandwidth_mi(p)
    return node_limit, node_has, pod_bw


def build_nb_filter(enc):
    import jax.numpy as jnp

    limit_np, has_np, bw_np = _featurize(enc)
    res_dt = enc.policy.res
    limit = jnp.asarray(limit_np, res_dt)
    has = jnp.asarray(has_np)
    bw = jnp.asarray(bw_np, res_dt)
    N = enc.N

    def kernel(a, s, p):
        bound = (s.assignment >= 0) & a.pod_mask
        tgt = jnp.maximum(s.assignment, 0)
        allocated = (
            jnp.zeros(N, bw.dtype).at[tgt].add(bw * bound.astype(bw.dtype))
        )
        want = bw[p]
        fail = has & (want > 0) & (allocated + want > limit)
        return fail.astype(jnp.int32)

    return kernel


def decode_nb(code: int, enc, node_idx: int) -> str:
    return FILTER_MESSAGE


def build_nb_score(enc):
    import jax.numpy as jnp

    limit_np, has_np, bw_np = _featurize(enc)
    score_dt = enc.policy.score
    limit = jnp.asarray(limit_np, score_dt)
    has = jnp.asarray(has_np)
    bw = jnp.asarray(bw_np, score_dt)
    N = enc.N

    def kernel(a, s, p, feasible=None):
        bound = (s.assignment >= 0) & a.pod_mask
        tgt = jnp.maximum(s.assignment, 0)
        allocated = (
            jnp.zeros(N, bw.dtype).at[tgt].add(bw * bound.astype(bw.dtype))
        )
        return jnp.where(has, limit - allocated, 0).astype(score_dt)

    kernel._normalize = _make_normalize(enc)
    return kernel


def _make_normalize(enc):
    import jax.numpy as jnp

    score_dt = enc.policy.score
    BIG = jnp.iinfo(jnp.int32).max

    def normalize(a, s, p, raw, feasible):
        lo = jnp.where(feasible, raw, BIG).min()
        hi = jnp.where(feasible, raw, -BIG).max()
        delta = hi - lo
        scaled = (MAX_NODE_SCORE * (raw - lo)) // jnp.maximum(delta, 1)
        return jnp.where(delta > 0, scaled, 0).astype(score_dt)

    return normalize


class _NBRow:
    """Preemption row: remaining bandwidth under victim removal."""

    def __init__(self, enc):
        import jax.numpy as jnp

        limit_np, has_np, bw_np = _featurize(enc)
        dt = enc.policy.res
        self.limit = jnp.asarray(limit_np, dt)
        self.has = jnp.asarray(has_np)
        self.bw = jnp.asarray(bw_np, dt)
        self.N = enc.N

    def prepare(self, a, state, p):
        import jax.numpy as jnp

        bound = (state.assignment >= 0) & a.pod_mask
        tgt = jnp.maximum(state.assignment, 0)
        allocated = (
            jnp.zeros(self.N, self.bw.dtype)
            .at[tgt]
            .add(self.bw * bound.astype(self.bw.dtype))
        )
        return {"allocated": allocated}

    def node_init(self, a, ctx, state, n):
        return {"alloc_n": ctx["allocated"][n]}

    def add_back(self, a, ctx, cnt, v, n, by=1):
        return {"alloc_n": cnt["alloc_n"] + by * self.bw[v]}

    def check(self, a, ctx, cnt, p, n):
        want = self.bw[p]
        return ~(self.has[n] & (want > 0) & (cnt["alloc_n"] + want > self.limit[n]))


# -- registration -----------------------------------------------------------


def _compile_statics(enc) -> tuple:
    """The content this plugin's builders bake into compiled closures —
    folded into BatchedScheduler.compile_signature so a cached compiled
    engine is never reused after the annotations change."""
    limit_np, has_np, bw_np = _featurize(enc)
    return (limit_np.tobytes(), has_np.tobytes(), bw_np.tobytes())


def register() -> None:
    """Idempotently register oracle + kernels + preemption row."""
    from ..engine import kernels as K
    from ..engine import preempt

    op.FILTER_PLUGINS["NetworkBandwidth"] = nb_filter
    op.SCORE_PLUGINS["NetworkBandwidth"] = (nb_score, nb_normalize)
    K.FILTER_KERNELS["NetworkBandwidth"] = (build_nb_filter, decode_nb)
    K.SCORE_KERNELS["NetworkBandwidth"] = (build_nb_score, "custom")
    K.COMPILE_STATICS["NetworkBandwidth"] = _compile_statics
    preempt.ROW_FILTERS["NetworkBandwidth"] = _NBRow


register()
