"""The batched scheduler: one `lax.scan` over the pod queue.

Each scan step schedules one pod exactly as the upstream framework does
(PreFilter → Filter → PreScore → Score → Normalize → weight → select →
bind; reference call stack SURVEY.md §3.3), but every per-node, per-plugin
evaluation inside the step is a vectorized tensor op over the whole node
axis — the reference's 16-goroutine per-node loop (upstream `Parallelism`,
simulator/scheduler/scheduler.go:153) becomes one XLA kernel launch.

Sequential-parity mode: scanning the queue in PrioritySort order with an
in-scan scatter-update of node state gives bit-identical placements to the
one-pod-at-a-time reference scheduler (pod i sees pod i-1's binding) while
still extracting all the node/plugin parallelism.

The scan carries `SchedState` (requested resources, pod counts,
assignments) and emits dense result tensors; `results()` converts them
host-side into the reference's exact annotation wire format
(sched/results.py) — replacing the reference's result stores + informer
reflector (simulator/scheduler/storereflector/storereflector.go) with the
kernel's own outputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..sched.results import (
    PASSED_FILTER_MESSAGE,
    SUCCESS_MESSAGE,
    PodSchedulingResult,
    record_bind_points,
)
from ..utils import broker as broker_mod
from . import kernels as K
from .encode import EncodedCluster
from .packing import make_unpacker
from .preempt import nominated_victims

class UnsupportedPluginError(NotImplementedError):
    pass


# Trace slot layout of step()'s record=True output, by tuple position.
# Single source of truth for run_chunked's chunk handling and results()'s
# unpacking; step() emits exactly these, in this order.
TRACE_SLOTS_PREEMPT = (
    "pf_codes", "codes", "raw", "final", "sel", "did",
    "pcode", "victims", "nominated",
    "codes2", "raw2", "final2", "sel2", "pcode2", "victims2", "nominated2",
    "final_sel",
)
TRACE_SLOTS_PLAIN = ("pf_codes", "codes", "raw", "final", "sel")
# Slots run_chunked keeps sparsely (fired rows only): the [N, V] victim
# rows plus every retry-attempt tensor — results() reads all of them
# only under did[qi], so event-free pods need no storage or transfer.
TRACE_SPARSE_SLOTS = frozenset(
    TRACE_SLOTS_PREEMPT.index(n)
    for n in ("victims", "victims2", "pcode", "codes2", "raw2", "final2", "pcode2")
)
TRACE_DID_SLOT = TRACE_SLOTS_PREEMPT.index("did")


def victims_by_node_index(victims_row, n_nodes: int) -> "dict[int, list[int]]":
    """Per node, the victim pod indices of a dry run's `[N, V]` rows, in
    the rows' (reprieve) order, -1 padding dropped."""
    rows = np.asarray(victims_row)
    return {n: [int(v) for v in rows[n] if v >= 0] for n in range(n_nodes)}


class _SparseRows:
    """Row-indexable stand-in for a stacked [P, ...] trace tensor that
    materializes only the rows recorded by `run_chunked` (pods whose
    preemption dry-run fired); other rows read as `fill` (zeros; -1,
    no victim, for the victim rows)."""

    def __init__(
        self, rows: "dict[int, np.ndarray]", row_shape: tuple, dtype=bool, fill=0
    ):
        self._rows = rows
        self._zero = np.full(row_shape, fill, dtype)
        self._zero.setflags(write=False)  # shared across misses

    def __getitem__(self, qi: int) -> np.ndarray:
        return self._rows.get(int(qi), self._zero)


def supported_config() -> "SchedulerConfiguration":
    """The default-plugin-order configuration restricted to extension
    points the engine has kernels for today. Grows automatically as kernel
    registries fill in; used by the graft entry point and the benchmark."""
    from ..sched.config import SchedulerConfiguration, default_plugins

    dp = default_plugins()
    star = [{"name": "*"}]

    def keep(point, names):
        return {
            "disabled": star,
            "enabled": [e for e in dp[point] if e["name"] in names],
        }

    plugins = {
        "preFilter": keep(
            "preFilter", set(K.PREFILTER_KERNELS) | K.TRIVIAL_PREFILTER
        ),
        "filter": keep("filter", set(K.FILTER_KERNELS)),
        "postFilter": keep("postFilter", set(K.POSTFILTER_KERNELS)),
        "preScore": keep("preScore", set(K.PRESCORE_KERNELS) | K.TRIVIAL_PRESCORE),
        "score": keep("score", set(K.SCORE_KERNELS)),
    }
    return SchedulerConfiguration.from_dict(
        {"profiles": [{"schedulerName": "default-scheduler", "plugins": plugins}]}
    )


def unsupported_plugins(cfg: "SchedulerConfiguration") -> list[str]:
    """Enabled plugins the engine has no kernel for (the strict-mode check,
    exposed so a config can be validated before the scheduler is rebuilt —
    the lifecycle service's rollback test, reference
    simulator/scheduler/scheduler.go:70-87)."""
    missing = [n for n in cfg.enabled("filter") if n not in K.FILTER_KERNELS]
    missing += [n for n, _ in cfg.score_plugins() if n not in K.SCORE_KERNELS]
    missing += [
        n
        for n in cfg.enabled("preFilter")
        if n not in K.PREFILTER_KERNELS and n not in K.TRIVIAL_PREFILTER
    ]
    missing += [
        n
        for n in cfg.enabled("preScore")
        if n not in K.PRESCORE_KERNELS and n not in K.TRIVIAL_PRESCORE
    ]
    missing += [
        n for n in cfg.enabled("postFilter") if n not in K.POSTFILTER_KERNELS
    ]
    return sorted(set(missing))


class BatchedScheduler:
    """Compiled scheduling engine over one `EncodedCluster`."""

    def __init__(
        self,
        enc: EncodedCluster,
        *,
        record: bool = True,
        strict: bool = True,
        unroll: int = 1,
        preempt_mode: str = "cond",
    ):
        self.enc = enc
        self.record = record
        # lax.scan unroll factor: trades compile time for per-step
        # overhead; useful at large queue lengths with record=False
        self.unroll = unroll
        # preempt_mode: how the PostFilter dry-run is gated per step.
        #   "cond"   — `lax.cond`: the dry-run only executes for pods the
        #              main attempt left unschedulable (the common case
        #              skips it entirely — right for the sequential path).
        #   "masked" — always-run with the outputs select-gated on the
        #              same predicate. Identical placements and trace;
        #              required under `vmap` (sweeps), where batching
        #              would lower the cond to both-branches-run anyway —
        #              making the masking explicit keeps the semantics
        #              defined instead of relying on that lowering.
        if preempt_mode not in ("cond", "masked"):
            raise ValueError(
                f"preempt_mode must be cond|masked, got {preempt_mode!r}"
            )
        self.preempt_mode = preempt_mode
        if enc.policy.name == "exact" and not jax.config.jax_enable_x64:
            raise RuntimeError("EXACT dtype policy requires jax_enable_x64")
        cfg = enc.config
        # All prefilter names emitted into the trace (oracle order); the
        # kernel-backed subset contributes device codes, the trivial subset
        # is always "success".
        self._prefilter_names = [
            n
            for n in cfg.enabled("preFilter")
            if n in K.PREFILTER_KERNELS or n in K.TRIVIAL_PREFILTER
        ]
        self._prefilter_kernel_names = [
            n for n in self._prefilter_names if n in K.PREFILTER_KERNELS
        ]
        self._filter_names = [n for n in cfg.enabled("filter") if n in K.FILTER_KERNELS]
        self._prescore_names = [
            n
            for n in cfg.enabled("preScore")
            if n in K.TRIVIAL_PRESCORE or n in K.PRESCORE_KERNELS
        ]
        self._score_specs = [
            (n, w) for n, w in cfg.score_plugins() if n in K.SCORE_KERNELS
        ]
        if strict:
            missing = unsupported_plugins(cfg)
            if missing:
                raise UnsupportedPluginError(
                    f"no kernel for enabled plugins: {missing} "
                    "(pass strict=False to skip them)"
                )
        self._pf_kernels = [
            K.PREFILTER_KERNELS[n][0](enc) for n in self._prefilter_kernel_names
        ]
        self._f_kernels = [K.FILTER_KERNELS[n][0](enc) for n in self._filter_names]
        self._s_kernels = [K.SCORE_KERNELS[n][0](enc) for n in self._score_specs_names]
        # normalize mode: None | "default" | "default_reverse" | "custom".
        # "custom" plugins attach fn(a, state, p, raw, feasible) as
        # kernel._normalize (PodTopologySpread, InterPodAffinity).
        self._s_normalize = [
            getattr(k, "_normalize", None) if mode == "custom" else mode
            for k, mode in zip(
                self._s_kernels,
                (K.SCORE_KERNELS[n][1] for n in self._score_specs_names),
            )
        ]
        self._postfilter_names = [
            n for n in cfg.enabled("postFilter") if n in K.POSTFILTER_KERNELS
        ]
        # custom permit kernels (K.PERMIT_PLUGINS): record-only handlers
        # invoked at trace-decode time for scheduled pods
        self._permit_handlers = {
            n: K.PERMIT_PLUGINS[n](enc)
            for n in cfg.enabled("permit")
            if n in K.PERMIT_PLUGINS
        }
        self._preempt = (
            K.POSTFILTER_KERNELS["DefaultPreemption"](enc, self._filter_names)
            if "DefaultPreemption" in self._postfilter_names
            else None
        )
        self.weights = jnp.asarray(
            [w for _, w in self._score_specs], enc.policy.score
        )
        # run_fn is the un-jitted program: (arrays, state0, queue, weights)
        # -> (final_state, trace). Exposed for the graft entry point, for
        # vmap over weight variants (Monte-Carlo), and for mesh-sharded jit.
        self.run_fn = self._build_run()
        # jits route through the broker module: the persistent compile
        # cache is armed before the first lowering (utils/broker.py).
        # The audit specs scope the KSS7xx jaxpr auditor: the encoding
        # derives the bucket exemptions (vocab axes) + the f64 waiver
        # (EXACT policy); the plugin-count axes are static by config.
        aud = self.audit_spec()
        self._run = broker_mod.jit(self.run_fn, audit={**aud, "label": "seq.run"})
        self._run_segment = broker_mod.jit(
            self._run_segment_fn, audit={**aud, "label": "seq.segment"}
        )
        # single-pod segments for host-callback (extender) scheduling
        self.attempt_fn = broker_mod.jit(
            lambda arrays, state, weights, p: self._attempt(state, arrays, weights, p),
            audit={**aud, "label": "seq.attempt"},
        )
        self.bind_fn = broker_mod.jit(
            lambda arrays, state, p, sel, qi: self._bind(state, arrays, p, sel, qi),
            audit={**aud, "label": "seq.bind"},
        )
        # the FUSED single-pod step: filter→score→normalize→select→bind
        # in ONE dispatched program — half the per-pod dispatches of the
        # attempt_fn/bind_fn pair wherever control need not return to
        # the host between select and bind (the extender loop's
        # no-extender-interest fast path). The select is the program's
        # own argmax (lowest-index tie-break, identical to the host
        # rule), and an unschedulable pod's bind is the engine's exact
        # no-op, so placements and trace bytes match the split pair.
        self.attempt_bind_fn = broker_mod.jit(
            lambda arrays, state, weights, p, qi: self._attempt_bind(
                state, arrays, weights, p, qi
            ),
            audit={**aud, "label": "seq.step"},
        )
        self._trace = None
        self._final_state = None

    @property
    def _score_specs_names(self) -> list[str]:
        return [n for n, _ in self._score_specs]

    def audit_spec(self) -> dict:
        """Base KSS7xx audit options for this engine's jit sites (the
        `label` is added per site): the encoding scopes the bucket check
        and the EXACT-policy f64 waiver; the plugin-count axes (trace
        tensors stack one slot per enabled kernel) are static under
        churn, so they join the exemptions explicitly."""
        return {
            "enc": self.enc,
            "extra_dims": (
                len(self._score_specs),
                len(self._filter_names),
                len(self._prefilter_kernel_names),
            ),
        }

    # -- compile reuse ------------------------------------------------------

    @staticmethod
    def queue_bucket(n: int) -> int:
        """The padded sequential-scan length for a pending queue of `n`
        pods: the scan is compiled at the geometric bucket above the live
        length and padded with no-op steps (pod index -1), so churn that
        moves the pending count within a bucket reuses the compilation."""
        from ..utils.compilecache import shape_bucket

        return shape_bucket(n, lo=8)

    @staticmethod
    def compile_signature(
        enc: EncodedCluster, record: bool = True, include_queue_len: bool = True
    ) -> tuple:
        """Everything the compiled program bakes in beyond its argument
        shapes: the configuration (kernel selection + static plugin args),
        dtype policy, the resource-vocabulary order (score-resource indices
        are baked), the node-pair vocabulary size, the preemption victim
        bound (derived from node capacities + initial assignment), and the
        full shape/dtype signature of the argument pytrees. Two encodings
        with equal signatures can share one compiled scheduler via
        `retarget` — the serving layer's recompile-avoidance contract.

        Memoized on the encoding (it is pure in the encoding's content):
        repeat signature probes — every pass's engine-cache lookup, and
        `retarget`'s compatibility check against an encoding whose stale
        buffers the delta encoder may have donated since — reuse the
        tuple instead of re-reading device arrays."""
        memo = getattr(enc, "_sig_memo", None)
        if memo is None:
            memo = enc._sig_memo = {}
        mkey = (record, include_queue_len)
        if mkey in memo:
            return memo[mkey]

        from .preempt import _victim_bound

        shapes = tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree.leaves((enc.arrays, enc.state0))
        )
        # PACKED: the word count ceil(n/32) is not injective in the
        # logical lane count, so two encodings with equal leaf shapes can
        # still unpack differently — the logical dims are program statics
        # and must key the compile (and the AOT bundle) themselves.
        packed_dims = tuple(sorted((enc.aux.get("packed_dims") or {}).items()))
        filter_names = [
            n for n in enc.config.enabled("filter") if n in K.FILTER_KERNELS
        ]
        has_preempt = "DefaultPreemption" in enc.config.enabled("postFilter")
        victim_bound = _victim_bound(enc, filter_names) if has_preempt else 0
        # content baked by custom kernels (K.COMPILE_STATICS registry)
        enabled = set(filter_names)
        for point in ("preFilter", "preScore", "score"):
            enabled.update(enc.config.enabled(point))
        custom_statics = tuple(
            (name, K.COMPILE_STATICS[name](enc))
            for name in sorted(enabled & set(K.COMPILE_STATICS))
        )
        sig = (
            enc.config.fingerprint(),
            enc.policy.name,
            tuple(enc.resource_names),
            enc.aux.get("n_node_pairs"),
            victim_bound,
            # the BUCKETED scan length is baked into the sequential
            # program (run() pads the queue to it); gang mode passes the
            # queue as a fixed-[P] order argument and drops this
            # component (GangScheduler.compile_signature)
            BatchedScheduler.queue_bucket(len(enc.queue))
            if include_queue_len
            else None,
            record,
            custom_statics,
            shapes,
            packed_dims,
        )
        memo[mkey] = sig
        return sig

    def retarget(self, enc: EncodedCluster) -> "BatchedScheduler":
        """Point this compiled scheduler at a new encoding with an equal
        compile signature (same shapes + baked statics, different array
        contents). The jitted program is reused; host-side decode tables
        come from the new encoding."""
        if self.compile_signature(enc, self.record) != self.compile_signature(
            self.enc, self.record
        ):
            raise ValueError("encoding is not compile-compatible; rebuild")
        self.enc = enc
        self._trace = None
        self._final_state = None
        return self

    # -- compiled program ---------------------------------------------------

    def _build_run(self):
        enc = self.enc
        N = enc.N
        P = enc.P
        score_dt = enc.policy.score
        NEG = jnp.iinfo(score_dt).min // 2
        record = self.record
        pf_kernels = self._pf_kernels
        f_kernels = self._f_kernels
        s_kernels = self._s_kernels
        s_normalize = self._s_normalize
        preempt_fn = self._preempt
        # PACKED policy: widen the packed cluster planes back to the
        # logical int32/bool form at the top of every exposed closure —
        # inside the trace, so the unpack fuses into the one scheduling
        # dispatch. Identity for EXACT/TPU32 and idempotent (dtype-driven,
        # static at trace time), so internal reuse costs nothing.
        unpack = make_unpacker(enc)
        packed_bf16 = getattr(enc.policy, "packed", False)

        def attempt(state, a, weights, p):
            """One full Filter→Score→Normalize→select pass for pod p."""
            a = unpack(a)
            if pf_kernels:
                pf_codes = jnp.stack([k(a, state, p) for k in pf_kernels])
                pf_ok = (pf_codes == 0).all()
            else:
                pf_codes = jnp.zeros((0,), jnp.int32)
                pf_ok = jnp.bool_(True)
            if f_kernels:
                codes = jnp.stack([k(a, state, p) for k in f_kernels], axis=1)  # [N,F]
            else:
                codes = jnp.zeros((N, 0), jnp.int32)
            feasible = (codes == 0).all(axis=1) & a.node_mask & pf_ok
            if s_kernels:
                raw = jnp.stack(
                    [k(a, state, p, feasible) for k in s_kernels], axis=1
                )  # [N,S]
                finals = []
                for j, mode in enumerate(s_normalize):
                    r = raw[:, j]
                    if mode in ("default", "default_reverse"):
                        mx = jnp.max(jnp.where(feasible, r, 0))
                        scaled = r * K.MAX_NODE_SCORE // jnp.maximum(mx, 1)
                        if mode == "default_reverse":
                            normed = jnp.where(
                                mx == 0, K.MAX_NODE_SCORE, K.MAX_NODE_SCORE - scaled
                            )
                        else:
                            normed = jnp.where(mx == 0, r, scaled)
                    elif callable(mode):
                        normed = mode(a, state, p, r, feasible)  # "custom"
                    else:
                        normed = r
                    if packed_bf16 and not callable(mode):
                        # bf16 score lane (PACKED): integers in [0, 256]
                        # are exactly representable in bfloat16, so the
                        # round-trip is lossless precisely where the
                        # elementwise guard applies it and every other
                        # lane rides through untouched — `final` (hence
                        # every placement and trace byte) is identical
                        # to TPU32 while the normalized plane runs
                        # through bf16 storage.
                        nb = normed.astype(score_dt)
                        safe = (nb >= 0) & (nb <= 256)
                        normed = jnp.where(
                            safe,
                            nb.astype(jnp.bfloat16).astype(score_dt),
                            nb,
                        )
                    finals.append(normed.astype(score_dt) * weights[j])
                final = jnp.stack(finals, axis=1)  # [N,S]
                total = final.sum(axis=1)
            else:
                raw = jnp.zeros((N, 0), score_dt)
                final = raw
                total = jnp.zeros((N,), score_dt)
            masked = jnp.where(feasible, total, NEG)
            sel = jnp.argmax(masked).astype(jnp.int32)
            sel = jnp.where(feasible.any(), sel, -1)
            return pf_codes, codes, raw, final, sel, pf_ok

        def bind(state, a, p, sel, qi):
            # Unschedulable pods scatter-add zeros to row 0 (valid == 0),
            # keeping the node axis exactly [N] for mesh sharding.
            # p < 0 marks a queue-bucket padding step (run() pads the
            # scan to its geometric bucket): every write is gated off so
            # the step is an exact no-op on the carried state.
            a = unpack(a)
            ok = p >= 0
            ps = jnp.maximum(p, 0)
            sel = jnp.where(ok, sel, jnp.int32(-1))
            tgt = jnp.maximum(sel, 0)
            valid = (sel >= 0).astype(a.pod_req.dtype)
            vi = (sel >= 0).astype(jnp.int32)
            return state.replace(
                requested=state.requested.at[tgt].add(a.pod_req[ps] * valid),
                s_requested=state.s_requested.at[tgt].add(a.pod_sreq[ps] * valid),
                n_pods=state.n_pods.at[tgt].add(vi),
                assignment=state.assignment.at[ps].set(
                    jnp.where(ok, sel, state.assignment[ps])
                ),
                used_pair=state.used_pair.at[tgt].add(a.want_pair[ps] * vi),
                used_wild=state.used_wild.at[tgt].add(a.want_wild[ps] * vi),
                used_trip=state.used_trip.at[tgt].add(a.want_trip[ps] * vi),
                used_claims=state.used_claims
                + a.pod_claim[ps].astype(jnp.int32) * vi,
                node_disk_any=state.node_disk_any.at[tgt].add(
                    a.pod_disk_any[ps] * vi
                ),
                node_disk_rw=state.node_disk_rw.at[tgt].add(
                    a.pod_disk_rw[ps] * vi
                ),
                node_vol3=state.node_vol3.at[tgt].add(a.pod_vol3[ps] * vi),
                bound_seq=state.bound_seq.at[ps].set(
                    jnp.where(
                        ok,
                        jnp.where(sel >= 0, jnp.int32(P) + qi, jnp.int32(-1)),
                        state.bound_seq[ps],
                    )
                ),
            )

        def evict_all(state, a, mask):
            """Remove every masked pod from its node (preemption victims;
            oracle Oracle.evict)."""
            a = unpack(a)
            tgtv = jnp.maximum(state.assignment, 0)
            mf = mask.astype(a.pod_req.dtype)[:, None]
            mi = mask.astype(jnp.int32)
            return state.replace(
                requested=state.requested.at[tgtv].add(-(a.pod_req * mf)),
                s_requested=state.s_requested.at[tgtv].add(-(a.pod_sreq * mf)),
                n_pods=state.n_pods.at[tgtv].add(-mi),
                assignment=jnp.where(mask, -1, state.assignment),
                used_pair=state.used_pair.at[tgtv].add(-(a.want_pair * mi[:, None])),
                used_wild=state.used_wild.at[tgtv].add(-(a.want_wild * mi[:, None])),
                used_trip=state.used_trip.at[tgtv].add(-(a.want_trip * mi[:, None])),
                used_claims=state.used_claims
                - mi @ a.pod_claim.astype(jnp.int32),
                node_disk_any=state.node_disk_any.at[tgtv].add(
                    -(a.pod_disk_any * mi[:, None])
                ),
                node_disk_rw=state.node_disk_rw.at[tgtv].add(
                    -(a.pod_disk_rw * mi[:, None])
                ),
                node_vol3=state.node_vol3.at[tgtv].add(-(a.pod_vol3 * mi[:, None])),
                bound_seq=jnp.where(mask, -1, state.bound_seq),
            )

        def attempt_bind(state, a, weights, p, qi):
            """The fused single-pod step (seq.step): one dispatch for
            the whole filter→score→normalize→select→bind chain. The
            attempt outputs ride out unchanged (the host decode reads
            the same tensors the split path returned), and `bind` is
            already an exact no-op for sel == -1."""
            pf_codes, codes, raw, final, sel, pf_ok = attempt(
                state, a, weights, p
            )
            new_state = bind(state, a, p, sel, qi)
            return pf_codes, codes, raw, final, sel, pf_ok, new_state

        # Exposed segment programs: the extender loop (extender_loop.py)
        # schedules pod-by-pod with HTTP callbacks between these device
        # segments (SURVEY.md §7 hard part #6); the gang scheduler's
        # preempt phase (gang.py) reuses attempt/evict with its own bind.
        self._attempt = attempt
        self._attempt_bind = attempt_bind
        self._bind = bind
        self._evict_all = evict_all

        def step(carry, x):
            state, a, weights = carry
            p, qi = x
            # ps is p with queue-bucket padding steps (p == -1) clamped
            # to a safe gather row; their attempt outputs are discarded
            # (sel forced to -1, bind gated, preemption gated).
            ps = jnp.maximum(p, 0)
            pf_codes, codes, raw, final, sel, pf_ok = attempt(state, a, weights, ps)
            sel = jnp.where(p >= 0, sel, jnp.int32(-1))
            if preempt_fn is None:
                state = bind(state, a, p, sel, qi)
                out = (pf_codes, codes, raw, final, sel) if record else sel
                return (state, a, weights), out

            # PostFilter path: when the pod is unschedulable, run the
            # preemption dry-run; on nomination, evict victims and retry the
            # full cycle within the same step (oracle schedule_all re-queues
            # the pod at the queue head — nothing schedules in between).
            do = (sel < 0) & pf_ok & a.pod_mask[ps] & (p >= 0)

            def masked_preempt(st):
                # Always-run form of `with_preempt` below: gate the victim
                # nomination on `do` instead of branching. With nothing
                # nominated, `evict` is all-False, `evict_all` is an exact
                # no-op, and the retry attempt reproduces the main attempt
                # — so binding proceeds from `sel` exactly as the skipped
                # branch would. Retry outputs are zero-gated to match the
                # cond mode's `without` trace bit-for-bit.
                pcode, victims, nominated = preempt_fn(a, st, ps)
                nominated = jnp.where(do, nominated, jnp.int32(-1))
                victims = jnp.where(do, victims, -1)
                pcode = jnp.where(do, pcode, 0)
                evict = nominated_victims(victims, nominated, P)
                st2 = evict_all(st, a, evict)
                _, codes2, raw2, final2, sel2, _ = attempt(st2, a, weights, ps)
                pcode2, victims2, nominated2 = preempt_fn(a, st2, ps)
                return st2, (
                    pcode, victims, nominated, evict,
                    jnp.where(do, codes2, 0),
                    jnp.where(do, raw2, 0),
                    jnp.where(do, final2, 0),
                    jnp.where(do, sel2, jnp.int32(-1)),
                    jnp.where(do, pcode2, 0),
                    jnp.where(do, victims2, -1),
                    jnp.where(do, nominated2, jnp.int32(-1)),
                )

            def with_preempt(st):
                pcode, victims, nominated = preempt_fn(a, st, ps)
                evict = nominated_victims(victims, nominated, P)
                st2 = evict_all(st, a, evict)
                _, codes2, raw2, final2, sel2, _ = attempt(st2, a, weights, ps)
                # retry-failure postfilter (recorded, never evicts — the
                # oracle's retried-set forces Unschedulable on 2nd failure)
                pcode2, victims2, nominated2 = preempt_fn(a, st2, ps)
                return st2, (
                    pcode, victims, nominated, evict,
                    codes2, raw2, final2, sel2, pcode2, victims2, nominated2,
                )

            def without(st):
                V = preempt_fn.victim_slots
                return st, (
                    jnp.zeros(N, jnp.int32), jnp.full((N, V), -1, jnp.int32),
                    jnp.int32(-1), jnp.zeros(P, bool),
                    jnp.zeros_like(codes), jnp.zeros_like(raw),
                    jnp.zeros_like(final), jnp.int32(-1),
                    jnp.zeros(N, jnp.int32), jnp.full((N, V), -1, jnp.int32),
                    jnp.int32(-1),
                )

            if self.preempt_mode == "masked":
                state, extra = masked_preempt(state)
            else:
                state, extra = jax.lax.cond(do, with_preempt, without, state)
            (pcode, victims, nominated, evict,
             codes2, raw2, final2, sel2, pcode2, victims2, nominated2) = extra
            final_sel = jnp.where(do & (nominated >= 0), sel2, sel)
            state = bind(state, a, p, final_sel, qi)
            if record:
                out = (
                    pf_codes, codes, raw, final, sel, do,
                    pcode, victims, nominated,
                    codes2, raw2, final2, sel2, pcode2, victims2, nominated2,
                    final_sel,
                )
            else:
                out = final_sel
            return (state, a, weights), out

        def run_segment(arrays, state, queue_seg, qis, weights):
            # one scan over a queue segment, resuming from `state` with
            # explicit global step indices — the chunked-trace primitive
            # (run_chunked) and the building block of the full run.
            # Packed planes widen ONCE here, outside the scan, so the
            # carry holds the logical arrays and per-step unpacks are
            # static no-ops.
            arrays = unpack(arrays)
            (state, _, _), out = jax.lax.scan(
                step, (state, arrays, weights), (queue_seg, qis), unroll=self.unroll
            )
            return state, out

        def run(arrays, state0, queue, weights):
            # arrays ride through the scan carry untouched; passing them as
            # an argument (not a closure constant) keeps the cluster data
            # out of the compiled executable, so equal-shape problems reuse
            # the compilation.
            return run_segment(
                arrays,
                state0,
                queue,
                jnp.arange(queue.shape[0], dtype=jnp.int32),
                weights,
            )

        self._run_segment_fn = run_segment
        return run

    # -- execution ----------------------------------------------------------

    def run(self, weights: "jnp.ndarray | None" = None):
        """Execute the scan; returns (final_state, trace).

        The queue is padded to its geometric bucket with no-op steps
        (pod index -1) so pending-count churn inside a bucket reuses the
        compiled program — trace rows beyond the live queue are unused
        padding (`results()`/decode iterate the live queue only)."""
        w = self.weights if weights is None else weights
        queue = np.asarray(self.enc.queue, np.int32)
        bucket = self.queue_bucket(len(queue))
        if bucket > len(queue):
            queue = np.concatenate(
                [queue, np.full(bucket - len(queue), -1, np.int32)]
            )
        state, out = self._run(
            self.enc.arrays, self.enc.state0, jnp.asarray(queue), w
        )
        self._final_state = state
        self._trace = out
        return state, out

    def warmup(self) -> "BatchedScheduler":
        """Compile the main program by executing one pass, then drop the
        result — the CompileBroker's speculative-build contract: a later
        pass at an equal compile signature `retarget`s onto this instance
        and runs warm (zero XLA compile on the serving thread)."""
        self.run()
        self._trace = None
        self._final_state = None
        return self

    def run_chunked(self, chunk: int = 64, weights: "jnp.ndarray | None" = None):
        """Execute the scan in queue segments, offloading each segment's
        trace to host memory — the at-scale `record=True` strategy.

        The full-run trace is O(P) stacked per-step tensors; with
        preemption enabled the dominant terms are the per-node retry
        attempt and the two [N, V] victim rows of every pod. Chunking
        bounds device trace memory to `chunk x per-step-trace`; on the
        host those slots are kept sparsely — only the rows of pods whose
        preemption dry-run actually fired (`did`) — so host memory
        scales with the number of preemption events, not P x N. `results()` then decodes
        (optionally a subset of pods; see `results(pods=...)`).

        The trailing partial chunk is padded to the full chunk length
        with no-op steps (pod index -1), so exactly ONE segment program
        compiles regardless of queue length.
        """
        if not self.record:
            raise RuntimeError("engine built with record=False has no trace")
        w = self.weights if weights is None else weights
        enc = self.enc
        queue = np.asarray(enc.queue)
        if len(queue) == 0:
            return self.run(weights)
        state = enc.state0
        has_pf = self._preempt is not None
        sparse_slots = TRACE_SPARSE_SLOTS if has_pf else frozenset()
        n_slots = len(TRACE_SLOTS_PREEMPT if has_pf else TRACE_SLOTS_PLAIN)
        dense: list[list[np.ndarray]] = [[] for _ in range(n_slots)]
        sparse: dict[int, dict[int, np.ndarray]] = {i: {} for i in sparse_slots}
        zero_spec: dict[int, tuple] = {}  # slot -> (row shape, dtype)
        for i in range(0, len(queue), chunk):
            seg = np.asarray(queue[i : i + chunk], np.int32)
            if len(seg) < chunk:
                seg = np.concatenate(
                    [seg, np.full(chunk - len(seg), -1, np.int32)]
                )
            qseg = jnp.asarray(seg)
            qis = jnp.arange(i, i + chunk, dtype=jnp.int32)
            state, out = self._run_segment(enc.arrays, state, qseg, qis, w)
            out = list(out) if isinstance(out, (tuple, list)) else [out]
            # fired-row indices first: event-free chunks transfer nothing
            # from the big per-attempt slots, and the sparse-slot gathers
            # keep host memory proportional to fired rows, not P x N
            fired = (
                np.nonzero(np.asarray(out[TRACE_DID_SLOT]))[0] if has_pf else ()
            )
            to_fetch: dict[int, object] = {}
            for j, x in enumerate(out):
                if j in sparse_slots:
                    if j not in zero_spec:
                        zero_spec[j] = (tuple(x.shape[1:]), np.dtype(str(x.dtype)))
                    if len(fired):
                        to_fetch[j] = x[jnp.asarray(fired)]
                else:
                    to_fetch[j] = x
            # ONE device_get per chunk for the whole trace pytree (plus
            # the `did` probe above) instead of one host sync per slot —
            # the chunked-run decode batching the perf_opt PR pins
            host = jax.device_get(to_fetch)
            for j, x in host.items():
                if j in sparse_slots:
                    for r, k in enumerate(fired):
                        sparse[j][i + int(k)] = x[r]
                else:
                    dense[j].append(np.asarray(x))
        trace = []
        for j in range(n_slots):
            if j in sparse_slots:
                shape, dtype = zero_spec[j]
                fill = -1 if TRACE_SLOTS_PREEMPT[j].startswith("victims") else 0
                trace.append(_SparseRows(sparse[j], shape, dtype, fill))
            else:
                trace.append(np.concatenate(dense[j], axis=0))
        self._final_state = state
        self._trace = tuple(trace)
        return state, self._trace

    def placements(self) -> dict[tuple[str, str], str]:
        """pod (ns, name) → node name ("" = unschedulable). Fast path."""
        if self._final_state is None:
            self.run()
        return self.enc.decode_assignment(self._final_state.assignment)

    # -- trace → reference annotation records -------------------------------

    def _fill_attempt(self, res, codes_row, raw_row, final_row, sel_val, p=None):
        """Fill one Filter→Score attempt into a result record. Returns True
        when the attempt scheduled the pod. `p`: the pod's index, forwarded
        to custom permit handlers (both the first and the post-preemption
        retry attempt pass it; None suppresses permit records)."""
        enc = self.enc
        feasible = []
        for n in range(enc.n_nodes):
            ok = True
            for j, fname in enumerate(self._filter_names):
                c = int(codes_row[n, j])
                if c:
                    res.add_filter(
                        enc.node_names[n],
                        fname,
                        K.FILTER_KERNELS[fname][1](c, enc, n),
                    )
                    ok = False
                    break
                res.add_filter(enc.node_names[n], fname, PASSED_FILTER_MESSAGE)
            if ok:
                feasible.append(n)
        if not feasible:
            res.status = "Unschedulable"
            return False
        for pname in self._prescore_names:
            res.pre_score[pname] = SUCCESS_MESSAGE
        for j, sname in enumerate(self._score_specs_names):
            for n in feasible:
                res.add_score(enc.node_names[n], sname, int(raw_row[n, j]))
                res.add_final_score(enc.node_names[n], sname, int(final_row[n, j]))
        s = int(sel_val)
        res.selected_node = enc.node_names[s]
        res.status = "Scheduled"
        permit = (
            {n: h(p, s) for n, h in self._permit_handlers.items()}
            if self._permit_handlers and p is not None
            else None
        )
        record_bind_points(enc.config, res, permit=permit)
        return True

    def _fill_postfilter(self, res, pcode_row, victims_row):
        """Attach DefaultPreemption messages (oracle default_preemption's
        per-node messages dict). `victims_row`: the dry run's `[N, V]`
        victim pod indices, each node's already in reprieve order
        (priority desc, bind order asc), -1-padded. Returns the victims'
        names by node."""
        enc = self.enc
        victims = victims_by_node_index(victims_row, enc.n_nodes)
        victims_by_node = {}
        for n in range(enc.n_nodes):
            code = int(pcode_row[n])
            names = [
                f"{enc.pod_keys[v][0]}/{enc.pod_keys[v][1]}" for v in victims[n]
            ]
            victims_by_node[n] = names
            if code == K.PREEMPT_SILENT:
                continue
            res.post_filter.setdefault(enc.node_names[n], {})[
                "DefaultPreemption"
            ] = K.decode_preemption(code, enc, n, names)
        return victims_by_node

    def results(
        self, pods: "set[tuple[str, str]] | None" = None
    ) -> list[PodSchedulingResult]:
        """Convert the dense result tensors into the reference's per-pod
        scheduling records (identical to the oracle's output shape).

        `pods`: optional set of (namespace, name) keys — decode only those
        pods' records. The per-pod record is O(N x plugins) host objects
        (the reference's annotation maps enumerate every node), so at
        BASELINE scale full decoding is 1e7+ dict entries; selective
        decode keeps the cost proportional to the pods asked about.
        """
        if not self.record:
            raise RuntimeError("engine built with record=False has no trace")
        if self._trace is None:
            self.run()
        enc = self.enc
        has_pf = self._preempt is not None
        # one batched device_get for every on-device trace tensor (a
        # full `run()` leaves all of them on device; `run_chunked` has
        # already landed them host-side) — not one sync per slot
        vals = list(self._trace)
        dev_idx = [
            i
            for i, x in enumerate(vals)
            if not isinstance(x, (_SparseRows, np.ndarray))
        ]
        if dev_idx:
            fetched = jax.device_get([vals[i] for i in dev_idx])
            for i, v in zip(dev_idx, fetched):
                vals[i] = np.asarray(v)
        if has_pf:
            (pf_codes, codes, raw, final, sel, did, pcode, victims, nominated,
             codes2, raw2, final2, sel2, pcode2, victims2, nominated2,
             final_sel) = vals
        else:
            pf_codes, codes, raw, final, sel = vals
            final_sel = sel
        results = []
        for qi, p in enumerate(enc.queue):
            ns, name = enc.pod_keys[p]
            if pods is not None and (ns, name) not in pods:
                continue
            res = PodSchedulingResult(pod_namespace=ns, pod_name=name)
            pf_failed = False
            for pname in self._prefilter_names:
                if pname in K.PREFILTER_KERNELS:
                    j = self._prefilter_kernel_names.index(pname)
                    c = int(pf_codes[qi, j])
                else:
                    c = 0
                msg = K.PREFILTER_KERNELS[pname][1](c, enc) if c else SUCCESS_MESSAGE
                res.pre_filter_status[pname] = msg
                if c:
                    pf_failed = True
            if pf_failed:
                res.status = "Unschedulable"
                results.append(res)
                continue
            self._fill_attempt(res, codes[qi], raw[qi], final[qi], sel[qi], p)
            if has_pf and bool(did[qi]):
                victims_by_node = self._fill_postfilter(res, pcode[qi], victims[qi])
                nom = int(nominated[qi])
                if nom >= 0:
                    res.status = "Nominated"
                    res.nominated_node = enc.node_names[nom]
                    res.preemption_victims = victims_by_node[nom]
                    results.append(res)
                    # the retry attempt (oracle re-queues the pod at the
                    # head; a second failure is terminally Unschedulable)
                    res2 = PodSchedulingResult(pod_namespace=ns, pod_name=name)
                    res2.pre_filter_status = dict(res.pre_filter_status)
                    ok = self._fill_attempt(
                        res2, codes2[qi], raw2[qi], final2[qi], sel2[qi], p
                    )
                    if not ok:
                        self._fill_postfilter(res2, pcode2[qi], victims2[qi])
                        nom2 = int(nominated2[qi])
                        if nom2 >= 0:
                            res2.nominated_node = enc.node_names[nom2]
                        res2.status = "Unschedulable"
                    results.append(res2)
                else:
                    res.status = "Unschedulable"
                    results.append(res)
            else:
                results.append(res)
        return results
