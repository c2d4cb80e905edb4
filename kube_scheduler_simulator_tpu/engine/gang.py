"""Gang (fixpoint) scheduling: all pending pods per round, in parallel.

The sequential engine (engine.py) is bit-identical to the reference's
one-pod-at-a-time loop but latency-bound: P pods cost P dependent scan
steps regardless of how small each step's tensors are. Gang mode is the
batched-queue design from SURVEY.md §7 M4 ("iterate rounds to fixpoint /
priority-ordered conflict resolution"): per round it

  1. evaluates EVERY pending pod against the round-start state — a
     `vmap` of the same Filter→Score→Normalize pass the sequential
     engine runs, chunked through `lax.map` so the [chunk, N, plugins]
     intermediates stay inside device memory — producing the full
     [P, N] masked score matrix;
  2. resolves conflicts by priority with an inner matching loop over
     that matrix (no kernel re-evaluation): each unmatched pod argmaxes
     over nodes not yet taken this round, the earliest pod in
     PrioritySort queue order wins each node (a scatter-min over queue
     positions — the tensor form of "pod i sees pod i-1's bind"), and
     losers fall back to their next-best feasible node. Every (pod,
     node) pair matched this way was evaluated feasible against the
     round-start state, and one-commit-per-node means same-round
     commits cannot interact through node-local state — so the fallback
     mirrors what the sequential loop would do after an earlier bind
     consumes a node (node-local score deltas move the argmax to the
     next-best node);
  3. scatter-binds the whole matching at once and repeats until a round
     commits nothing (`lax.while_loop`).

Without step 2's fallback, homogeneous pods would all argmax to the
same node and rounds would commit one pod each — the matching commits
up to N pods per round, so rounds ≈ max pods per node.

One pod per node commits per round, so within a round committed pods
cannot interact through node-local state (resources, ports, per-node
volume counts, image locality, balanced allocation). The two
cluster-global state dependences are handled separately:
ReadWriteOncePod claims get their own per-claim conflict resolution in
the matching (at most one claimant commits per round; see `match`),
while the global topology-spread / inter-pod-affinity counts remain the
documented within-round divergence below. Losers re-evaluate next round
against the updated state, exactly as the sequential loop would have
seen it.

Divergence policy (documented, per SURVEY §7 M4):

  * Pods found unschedulable in round r are retried in round r+1 — so a
    pod whose required inter-pod affinity peer sits LATER in the queue
    can schedule here but not in the strict sequential pass (upstream
    would also retry it on the next cluster event; gang mode's rounds
    play the role of that event-driven re-queue).
  * Pods committed in the same round read the same global
    topology-spread / inter-pod-affinity counts; sequential parity for
    those two plugins holds only across rounds, not within one. Pods
    carrying REQUIRED anti-affinity terms are exempted by default:
    `rel_serialize` batches only up to the first placeable carrier in
    queue order and gives the carrier an EXCLUSIVE round (see
    __init__), so required-term coupling is always evaluated against
    committed state, in both directions, with sequential order
    preserved at carrier boundaries.
  * A pod that loses its round re-evaluates against ALL of that round's
    commits — including pods later in the queue that won other nodes —
    so under contention placements are a deterministic greedy fixpoint,
    not the sequential order's. Exact sequential parity is guaranteed
    precisely when no pod loses a round (no two pending pods select the
    same node), e.g. spread-out workloads; the contended cases keep the
    invariants that every commit was feasible when made and node-local
    constraints are never violated.
  * PostFilter (DefaultPreemption) runs as a *phase*, not inline: when
    the round loop settles with pods still pending, those pods (few by
    construction — everything schedulable without eviction has already
    placed) go through a compiled sequential preempt pass (dry-run →
    evict → retry → bind, the same kernels as the sequential engine),
    after which rounds resume; phases repeat until neither makes
    progress. Against a workload where every preemption-needing pod is
    unschedulable without eviction this matches the sequential engine
    exactly; in mixed workloads the phase ordering (all non-evicting
    binds first) is the documented divergence. Non-DefaultPreemption
    postFilter plugins remain unsupported (`skipped_postfilter`).

Scale: rounds needed ≈ max pods targeting one node, not P. The per-round
work is a dense [P, N, plugins] evaluation — the MXU-shaped program the
north star needs (BASELINE.json: 100k pods x 10k nodes x 1k variants).
`run_fn` is pure in (arrays, state0, order, weights) so policy sweeps
vmap over the weight axis and meshes shard the node axis unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import broker as broker_mod
from . import kernels as K
from .encode import EncodedCluster
from .engine import BatchedScheduler
from .packing import make_unpacker

# queue-position value that can never win a scatter-min
_NO_ORDER = jnp.iinfo(jnp.int32).max


class GangScheduler:
    """Fixpoint batch scheduler over one `EncodedCluster`.

    Result records (`results()` / `run_recorded()`): the reference's
    product is the per-pod scheduling trace flushed as 13 annotations
    (reference simulator/scheduler/plugin/resultstore/store.go:129-190).
    A naive gang trace would be [rounds, P, N, plugins] with
    data-dependent rounds, so the record path instead runs the fixpoint
    with a [P] bind-round tensor (`run_tracked`) and then REPLAYS the
    chronology: each pod is re-evaluated ONCE — with full per-plugin
    outputs — against the start state of the round that bound it
    (exactly the state its committing evaluation saw), preempt phases
    are replayed through the sequential engine's record segments (their
    semantics are the sequential step's), and fixpoint leftovers are
    evaluated against the final state (showing why every node fails).
    Total record cost ~= ONE full evaluation of every pod, not
    rounds x P.

    One honest gang-specific caveat: a round's matching can commit a
    pod to its 2nd..k-th best node when an earlier-priority pod takes
    its argmax in the same round, so a record's `selectedNode` is the
    ACTUAL committed node, which may not be the argmax of that record's
    own score table — the score table explains the candidate set, the
    selection explains the commit.
    """

    def __init__(
        self,
        enc: EncodedCluster,
        *,
        strict: bool = True,
        chunk: int = 256,
        max_rounds: "int | None" = None,
        inner_iters: int = 64,
        loop: str = "dynamic",
        static_rounds: "int | None" = None,
        match_width: "int | None" = None,
        compact: bool = True,
        inner_loop: "str | None" = None,
        rel_serialize: bool = True,
        eval_window: "int | None" = None,
    ):
        """loop="dynamic" (default) runs rounds under `lax.while_loop`
        until a round commits nothing. loop="static" runs a FIXED number
        of rounds (`static_rounds`, default ceil(P/N)+4) as a `lax.scan`
        — counted-loop-only control flow, the same structure as the
        sequential engine's scan, which is known to compile on backends
        where dynamic-condition loops have not been observed to. A
        static pass that spends its whole budget with the last round
        still committing AUTO-RESUMES: `run()` executes another pass of
        the same compiled program from the reached state, so the
        per-pass budget bounds wasted no-op rounds (at most ~budget
        past the fixpoint) without ever starving a workload — the
        budget is a quantum, not a cap. An explicit `max_rounds` caps
        the per-pass budget too. In dynamic mode with a BINDING
        `eval_window`, an explicit `max_rounds` is denominated in
        COMMIT rounds (the unit it caps unwindowed, where every counted
        round commits): no-commit window-sweep rounds don't burn it, so
        the cap can never exhaust the loop mid-sweep and strand
        feasible pods (ADVICE r5). Constraint: such an explicit
        `max_rounds` must still cover one full window sweep
        (`max_rounds >= ceil(P/WP)`) — commits reset the window offset
        to 0, so a smaller cap can spend itself entirely on the
        earliest windows and end the pass before later windows were
        ever evaluated; the combination raises `ValueError` (mirroring
        the static-mode validation) instead of silently stranding
        feasible pods.

        With equal `inner_iters` the two modes place identically (the
        extra static iterations/rounds are provably no-ops); a SMALLER
        static `inner_iters` is a different matching depth — losers past
        it retry in a later round against updated state, which can
        change placements (still valid, just a different greedy order).

        `match_width` bounds each pod's per-round candidate list: the
        matching runs over the pod's top-`match_width` scoring feasible
        nodes (one `lax.top_k` per round) instead of the full [P, N]
        matrix. This is the same kind of depth bound as `inner_iters` —
        a pod whose whole candidate list is consumed by earlier-order
        winners waits for the next round's fresh evaluation instead of
        falling back to its (k+1)-th choice — and placements are
        identical to full-width matching whenever every pod commits
        within its k candidates (always true when k == N; `lax.top_k`
        breaks score ties toward lower node indices, matching argmax).
        It keeps the inner loop at [P, k] instead of the [P, N]
        select/argmax chain per inner iteration of full-width matching.
        Default: full width for N <= 512, else 128.

        `inner_loop` picks the matching iteration's control flow
        independently of the outer loop: None (default) follows `loop`;
        "dynamic" runs the matching as a `lax.while_loop` that exits as
        soon as an iteration commits nothing — with equal `inner_iters`
        placements are identical to the scan form (post-settle
        iterations are provably no-ops), but the round stops paying for
        them. The split exists because the matching scan is the round's
        LATENCY floor on real TPU hardware (64 dependent iterations of
        small selects, ~whole-round wall time at the bench shape):
        `loop="static", inner_loop="dynamic"` keeps the outer program
        counted and lets each round's matching quit early.

        `rel_serialize` (default True, effective only when the
        InterPodAffinity filter is enabled) — queue-prefix batching:
        each batched round commits only pods strictly BEFORE the first
        placeable pod carrying REQUIRED anti-affinity terms in queue
        order (positive required affinity is monotone — same-round
        peers can only satisfy it — and bound pods' positive terms
        never block incoming pods, so affinity-only pods stay
        batched); once that prefix is exhausted, the
        carrier takes an EXCLUSIVE round at its argmax node (the
        sequential engine's choice against this state), then batching
        resumes up to the next carrier. Two properties follow:

          * soundness — no required ANTI-affinity term is ever violated
            in the final state, in either direction: the carrier
            evaluates against fully-committed state, and no same-round
            peer can slip under its symmetric anti-affinity (next-round
            matchers are blocked by the kernel's fail1 check once it is
            bound). Positive required terms are satisfied in the final
            state too, but with one residual feasibility-SHAPED
            divergence: two series-starting pods whose required positive
            terms self-match (the first-pod-in-series special case,
            kernels.py) can batch in one round and pass via the
            no-matches-anywhere rule in different topology domains,
            where the sequential engine would co-locate the later with
            the earlier — final-state required terms still hold under
            self-inclusion (the invariant the fuzz checker pins,
            tests/test_engine_fuzz.py), the sequential engine would just
            have produced a more-co-located layout;
          * order fidelity at carrier boundaries — for carriers
            PLACEABLE at their round's start: pods before such a
            carrier bind before it, pods after bind after, as the
            sequential interleaving would. A carrier infeasible at
            round start does not gate the batch (c_min considers
            placeable carriers only), so later-queued pods can commit
            past it; if the same round's commits then make it
            placeable, it binds after pods the sequential engine would
            have placed behind it — soundness unaffected (it still
            evaluates against committed state), order not guaranteed.
            (Without carrier serialization at all, carriers committing
            before earlier-queued matching pods spread over every
            topology domain first and their symmetric terms then block
            those pods everywhere; a fuzz workload measured 22% fewer
            placements than sequential from exactly that —
            tests/test_engine_fuzz.py.)

        Cost: rounds grow by ~one per pending carrier plus chunked
        prefixes, so carrier-heavy queues degrade toward sequential
        rounds (set rel_serialize=False to trade the coupling
        divergence back for throughput); carrier-free workloads (all
        bench gang shapes) pay nothing. Pods whose only rel features
        are PREFERRED terms score against stale counts — a
        score-quality, not feasibility, divergence — and stay batched.

        `compact` (default True) makes each round evaluate only chunks
        that contain still-pending pods: pods are permuted pending-first
        (stable argsort of the pending mask) and settled chunks return
        floor rows through a `lax.cond` — placements are bit-identical
        (settled pods' scores are masked out either way), but total
        evaluation work drops from rounds x P to ~sum of per-round
        pending counts (~P^2/2N on uniform workloads). Turn it off under
        `vmap` (GangSweep does): vmapped `cond` lowers to both-branches
        select, so there is nothing to skip.

        `eval_window` (default None = off; independent of `compact` —
        a binding window routes rounds through its own row-subset
        pipeline and never touches the compacted eval program) bounds
        each round's dense work — eval, top_k, matching — to a window
        of `eval_window` PENDING pods in queue order, rounded UP to the
        chunk boundary (chunk-granular: the effective window is
        ceil(W/chunk)*chunk). It is the chip lever for the eval-bound
        round wall (round-5 measurement: ~95% of a live round is
        evaluation, yet only ~N pods can commit per round, so
        evaluating all pending pays ~P/2N times the useful work), and
        it keeps every tall [P, N] dense construct out of the compiled
        program. Rounds carry a window OFFSET: a commit
        resets it to 0 (earlier-queue pods get first claim on the new
        state), a no-commit round advances to the next window, and a
        full sweep of the pending windows with no commit anywhere —
        against a provably unchanged state — is exactly the unwindowed
        fixpoint signal, so windowed passes can never strand pods
        (test-pinned, including a 1-node budget-exhaustion repro).
        Placements are a different valid greedy order than the
        unwindowed fixpoint (same class of divergence as `match_width`;
        all invariants hold — fuzz-pinned in
        tests/test_engine_fuzz.py). Pure selects (no lax.cond), so the
        same program stays efficient under GangSweep's vmap."""
        self.enc = enc
        self.chunk = int(chunk)
        # fallback depth of the per-round matching: how many next-best
        # hops a loser may take before waiting for a fresh evaluation
        self.inner_iters = int(inner_iters)
        # one-carrier-per-round only matters when the InterPodAffinity
        # kernels actually read the required terms
        self.rel_serialize = bool(rel_serialize) and (
            "InterPodAffinity" in enc.config.enabled("filter")
        )
        if match_width is None:
            # scalable-by-default on EVERY backend: a uniform default keeps placements backend-independent,
            # and the depth bound is the same sanctioned semantics as
            # inner_iters — a pod that exhausts 128 candidates in one
            # round waits for the next round's fresh evaluation
            match_width = enc.N if enc.N <= 512 else 128
        self.match_width = max(1, min(int(match_width), enc.N))
        self.compact = bool(compact)
        if eval_window is not None:
            eval_window = int(eval_window)
            if eval_window < 1:
                raise ValueError(
                    f"eval_window must be >= 1, got {eval_window}"
                )
        self.eval_window = eval_window
        if loop not in ("dynamic", "static"):
            raise ValueError(f"loop must be dynamic|static, got {loop!r}")
        self.loop = loop
        if inner_loop is None:
            inner_loop = loop
        if inner_loop not in ("dynamic", "static"):
            raise ValueError(
                f"inner_loop must be dynamic|static|None, got {inner_loop!r}"
            )
        self.inner_loop = inner_loop
        explicit_budget = static_rounds is not None or max_rounds is not None
        if static_rounds is None:
            # honor an explicit max_rounds as the static budget too.
            # Default per-pass quantum: ~max-pods-per-node rounds plus
            # slack — enough for typical fixpoints in ONE pass; heavy
            # skew just triggers auto-resume passes of the same program.
            static_rounds = (
                max_rounds
                if max_rounds is not None
                else (-(-enc.P // max(1, enc.N))) + 4
            )
        self.static_rounds = int(static_rounds)
        # A binding eval_window spreads the fixpoint sweep across round
        # slots (one window per slot), and every pass restarts its
        # window offset at 0 — so the auto-resume rule's "zero-commit
        # pass == infeasible remainder" proof needs the static budget to
        # cover a COMPLETE sweep (ceil(P/WP) slots). Otherwise a pass
        # could exhaust its quantum mid-sweep with zero commits and the
        # driver would strand feasible later-window pods (code-review
        # r5 repro: 14 infeasible high-priority pods ahead of 2
        # feasible ones at window size 2). The DEFAULT budget is raised
        # to the sweep width; an EXPLICIT static_rounds/max_rounds below
        # it is rejected rather than silently overridden (the cap is a
        # documented per-pass latency contract). Same rule protects
        # GangSweep's per-variant-array form of the resume check.
        self._wp = self.effective_window(enc, self.eval_window, self.chunk)
        if self._wp is not None:
            n_win = -(-enc.P // self._wp)
            if explicit_budget:
                # an explicit cap below a full sweep would void the
                # completeness proof — make the caller choose (bigger
                # budget or bigger window) instead of silently
                # overriding their per-pass latency cap
                if self.static_rounds < n_win and loop == "static":
                    raise ValueError(
                        f"static per-pass budget {self.static_rounds}"
                        f" cannot cover a full eval_window sweep"
                        f" (ceil(P/WP) = {n_win}): raise"
                        f" static_rounds/max_rounds or eval_window"
                    )
                # same rule for the dynamic loop (ADVICE r5 residue):
                # its cap is denominated in COMMIT rounds, and every
                # commit resets the window offset to 0, so a cap below
                # the sweep width can spend itself entirely on the
                # earliest windows and end the pass before later
                # windows were ever evaluated against settled state —
                # feasible pods stranded with no auto-resume backstop.
                # A cap that covers one full sweep is the floor at
                # which "budget exhausted" can't masquerade as
                # "remainder infeasible".
                if (
                    loop == "dynamic"
                    and max_rounds is not None
                    and max_rounds < n_win
                ):
                    raise ValueError(
                        f"dynamic per-pass commit budget"
                        f" max_rounds={max_rounds} cannot cover a full"
                        f" eval_window sweep (ceil(P/WP) = {n_win}):"
                        f" raise max_rounds or eval_window"
                    )
            else:
                self.static_rounds = max(self.static_rounds, n_win)
        # Reuse the sequential engine's compiled-kernel construction and
        # its `attempt` program — gang mode is a different driver around
        # the identical per-pod evaluation.
        self._base = BatchedScheduler(enc, record=False, strict=strict)
        # DefaultPreemption runs as the fixpoint preempt phase (see module
        # docstring); only postFilter plugins without a kernel are skipped.
        self.skipped_postfilter = [
            n
            for n in enc.config.enabled("postFilter")
            if n not in K.POSTFILTER_KERNELS
        ]
        self.weights = self._base.weights
        self.max_rounds = max_rounds
        self.run_fn = self._build_run()
        aud = self.audit_spec()
        self._run = broker_mod.jit(
            self.run_fn, audit={**aud, "label": "gang.run"}
        )
        self._preempt_phase = (
            broker_mod.jit(
                self.preempt_phase_fn,
                audit={**aud, "label": "gang.preempt_phase"},
            )
            if self.preempt_phase_fn is not None
            else None
        )
        # the fused whole-pass program (rounds + preempt alternation,
        # see fixpoint in _build_run): ONE dispatch per untracked
        # dynamic pass, no host readback between phases — and the unit
        # the batch plane vmaps for batch.gang.run
        self._fixpoint = (
            broker_mod.jit(
                self.fixpoint_fn, audit={**aud, "label": "gang.fixpoint"}
            )
            if self.fixpoint_fn is not None
            else None
        )
        self._final_state = None
        self._rounds = None
        # record path (results()) — all built/filled lazily so the
        # default fixpoint program and its compile class stay untouched
        self._run_tracked = None
        self._rec = None
        self._eval_rec = None
        self._replay_round = None
        self._chronology = None
        self._trace = None
        self._recorded_weights = None

    def audit_spec(self) -> dict:
        """Base KSS7xx audit options for the gang jit sites: the
        sequential base engine's spec plus the gang-only static dims
        (evaluation chunk, the chunk-rounded eval window, the static
        round budget — fixed per engine build, never churn-driven)."""
        aud = self._base.audit_spec()
        extra = tuple(aud["extra_dims"]) + tuple(
            int(d)
            for d in (self.chunk, self._wp, self.static_rounds)
            if d
        )
        return {**aud, "extra_dims": extra}

    # -- host-side queue encoding ------------------------------------------

    def order_arrays(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(order, in_queue): order[p] = position of pod p in the
        PrioritySort queue (NO_ORDER when not queued), in_queue[p] bool."""
        P = self.enc.P
        order = np.full((P,), int(_NO_ORDER), np.int32)
        in_q = np.zeros((P,), bool)
        for i, p in enumerate(self.enc.queue):
            order[p] = i
            in_q[p] = True
        return jnp.asarray(order), jnp.asarray(in_q)

    # -- compiled program ---------------------------------------------------

    def _build_run(self):
        enc = self.enc
        N = enc.N
        P = enc.P
        CH = max(1, min(self.chunk, P))
        n_chunks = -(-P // CH)
        P_pad = n_chunks * CH
        attempt = self._base._attempt
        # WP: the chunk-granular window row count (Python int, static;
        # computed once in __init__ so the static-budget clamp and the
        # program builder can never disagree). None when windowing is
        # off or never binds (W >= P) — the builders then use the
        # unwindowed program unchanged.
        WP = self._wp
        # Dynamic-loop livelock guard. Unwindowed, every progressing
        # round commits >= 1 pod, so P+1 bounds the loop. With a
        # binding eval_window, each commit may be preceded by a
        # no-commit sweep over up to ceil(P/WP) windows, so the windowed
        # dynamic loops guard on COMMIT rounds instead of total rounds
        # (w_cond/tw_cond below): sweep rounds never burn the budget,
        # and an explicit max_rounds below the sweep width can no longer
        # exhaust the while_loop mid-sweep and silently strand feasible
        # pods (ADVICE r5 — there is no dynamic-mode auto-resume to
        # catch that; the old rounds-based guard scaled the DEFAULT by
        # the sweep width but an explicit cap still bit). Termination
        # needs no total-rounds bound: between commits the offset sweep
        # reaches its fixpoint signal in <= ceil(P/WP) rounds, and
        # commits are capped, so total rounds <= (cap+1) * sweep width —
        # max_rounds stays a bounded-latency cap, denominated in the
        # same unit the unwindowed loop counts (rounds that commit).
        max_rounds = self.max_rounds if self.max_rounds is not None else P + 1
        inner_iters = self.inner_iters
        MW = self.match_width
        static = self.loop == "static"
        inner_static = self.inner_loop == "static"
        rel_serialize = self.rel_serialize
        # sentinel strictly below any reachable total score (engine.py
        # uses the same NEG for infeasible nodes); also used to mask
        # non-pending pods and taken nodes during the inner matching
        NEG = jnp.iinfo(enc.policy.score).min // 2
        FLOOR = NEG

        compact = self.compact
        W = self.eval_window
        # PACKED-policy widening (engine/packing.py): identity for
        # EXACT/TPU32, idempotent for PACKED — each exposed closure
        # unpacks defensively (faultsweep jits `_bind_all` directly
        # against the raw encoding) while the outer drivers unpack once.
        unpack = make_unpacker(enc)

        def pod_score_row(state, a, weights, p):
            """[N] masked total score of pod p against `state` (NEG
            where infeasible) — the ONE per-pod evaluation body, shared
            by eval_all and eval_rows so windowed and full rounds can
            never diverge in feasibility/scoring semantics."""
            _, codes, raw, final, _, pf_ok = attempt(state, a, weights, p)
            feasible = (codes == 0).all(axis=1) & a.node_mask & pf_ok
            total = final.sum(axis=1) if final.shape[1] else jnp.zeros(
                (N,), enc.policy.score
            )
            return jnp.where(feasible, total, NEG)

        def eval_all(state, a, weights, pending):
            """[P, N] masked total scores (NEG where infeasible),
            evaluated against `state`.

            Chunked vmap: `lax.map` over pod chunks keeps peak memory at
            [CH, N, plugins] instead of [P, N, plugins]; XLA dead-code
            eliminates the unused attempt outputs (codes/raw/final), so
            only the masked score row survives per pod.

            Compaction (`compact`): pods ride through the chunks in
            pending-first order (stable argsort), and a chunk whose
            pods are all settled short-circuits to floor rows via
            `lax.cond` — later rounds pay for their pending count, not
            for P. Settled pods' rows are floor either way (the caller
            masks on `pending`), so placements cannot depend on it.

            Windowed rounds do NOT come through here — they use
            `eval_rows` (no [P+1, N] scatter-back; see below). This
            function stays byte-identical to the chip-proven compact
            program.
            """

            one_pod = pod_score_row

            if not compact:
                ps = jnp.arange(P_pad, dtype=jnp.int32) % P
                ps = ps.reshape(n_chunks, CH)

                def one_chunk(pc):
                    return jax.vmap(
                        lambda p: one_pod(state, a, weights, p)
                    )(pc)

                return jax.lax.map(one_chunk, ps).reshape(P_pad, N)[:P]

            # pending-first permutation; padding rows scatter to row P
            # of a [P+1]-row buffer so they can never clobber a pod row
            row_dt = jax.eval_shape(
                lambda s, aa, w: one_pod(s, aa, w, jnp.int32(0)),
                state, a, weights,
            ).dtype
            perm = jnp.argsort(~pending).astype(jnp.int32)
            n_live = pending.sum()
            if P_pad > P:
                rows = jnp.concatenate(
                    [perm, jnp.full((P_pad - P,), jnp.int32(P))]
                )
                pods_in = jnp.concatenate([perm, perm[: P_pad - P]])
            else:
                rows = perm
                pods_in = perm
            ps = pods_in.reshape(n_chunks, CH)

            def one_chunk(args):
                i, pc = args

                def live(_):
                    return jax.vmap(
                        lambda p: one_pod(state, a, weights, p)
                    )(pc)

                def settled(_):
                    return jnp.full((CH, N), NEG, row_dt)

                return jax.lax.cond(
                    i * CH < n_live, live, settled, None
                )

            flat = jax.lax.map(
                one_chunk, (jnp.arange(n_chunks, dtype=jnp.int32), ps)
            ).reshape(P_pad, N)
            return (
                jnp.full((P + 1, N), NEG, row_dt)
                .at[rows]
                .set(flat)[:P]
            )

        def eval_rows(state, a, weights, rows, n_live):
            """[WP, N] masked total scores for the pod-id rows `rows`
            (the eval window), chunked exactly like eval_all but
            WITHOUT the [P+1, N] scatter-back: every downstream tensor
            of a windowed round is [WP, ...], so per-round dense work
            is bounded by the window, not by P — both the throughput
            lever and the dodge for the chip's refusal of very tall
            [P, N] constructs (round-5 crash bracket: P in
            (8192, 10240] at N=1024)."""

            def one_pod(p):
                return pod_score_row(state, a, weights, p)

            row_dt = jax.eval_shape(lambda: one_pod(jnp.int32(0))).dtype
            w_chunks = WP // CH
            ps = rows.reshape(w_chunks, CH)

            def one_chunk(args):
                i, pc = args

                def live(_):
                    return jax.vmap(one_pod)(pc)

                def settled(_):
                    return jnp.full((CH, N), NEG, row_dt)

                return jax.lax.cond(i * CH < n_live, live, settled, None)

            return jax.lax.map(
                one_chunk, (jnp.arange(w_chunks, dtype=jnp.int32), ps)
            ).reshape(WP, N)

        def bind_all(state, a, mask, sel, order):
            """Scatter-bind every masked pod to its selected node in one
            update (the batched form of engine.py's per-pod `bind`;
            unmasked rows contribute zeros to node row 0)."""
            a = unpack(a)
            tgt = jnp.where(mask, jnp.maximum(sel, 0), 0)
            mf = mask.astype(a.pod_req.dtype)[:, None]
            mi = mask.astype(jnp.int32)
            return state.replace(
                requested=state.requested.at[tgt].add(a.pod_req * mf),
                s_requested=state.s_requested.at[tgt].add(a.pod_sreq * mf),
                n_pods=state.n_pods.at[tgt].add(mi),
                assignment=jnp.where(mask, sel, state.assignment),
                used_pair=state.used_pair.at[tgt].add(a.want_pair * mi[:, None]),
                used_wild=state.used_wild.at[tgt].add(a.want_wild * mi[:, None]),
                used_trip=state.used_trip.at[tgt].add(a.want_trip * mi[:, None]),
                used_claims=state.used_claims
                + mi @ a.pod_claim.astype(jnp.int32),
                node_disk_any=state.node_disk_any.at[tgt].add(
                    a.pod_disk_any * mi[:, None]
                ),
                node_disk_rw=state.node_disk_rw.at[tgt].add(
                    a.pod_disk_rw * mi[:, None]
                ),
                node_vol3=state.node_vol3.at[tgt].add(a.pod_vol3 * mi[:, None]),
                bound_seq=jnp.where(mask, jnp.int32(P) + order, state.bound_seq),
            )

        preempt_fn = self._base._preempt
        evict_all = self._base._evict_all

        def preempt_phase(arrays, state, seg, order, weights):
            """Sequential preempt pass over the pods the round loop left
            pending. `seg`: [K] pod indices in queue (PrioritySort) order,
            -1-padded. Per pod: full attempt → masked preemption dry-run →
            evict victims → retry → bind (the sequential engine's step
            semantics, reference wrappedplugin.go:518-546), expressed with
            the gang module's mask-vector bind so padded rows are exact
            no-ops. Returns (state, pods bound this phase)."""
            a = unpack(arrays)

            def pstep(state, p_raw):
                valid = p_raw >= 0
                p = jnp.maximum(p_raw, 0)
                _, _, _, _, sel, pf_ok = attempt(state, a, weights, p)
                pending = valid & (state.assignment[p] < 0) & a.pod_mask[p]
                do = pending & (sel < 0) & pf_ok
                pcode, vmask, nominated = preempt_fn(a, state, p)
                nominated = jnp.where(do, nominated, jnp.int32(-1))
                vmask = vmask & do
                evict = vmask[jnp.maximum(nominated, 0)] & (nominated >= 0)
                state = evict_all(state, a, evict)
                _, _, _, _, sel2, _ = attempt(state, a, weights, p)
                # an earlier eviction in this phase may have made the pod
                # plainly feasible (sel >= 0): bind it exactly as the
                # sequential loop would
                final_sel = jnp.where(
                    do & (nominated >= 0),
                    sel2,
                    jnp.where(pending, sel, jnp.int32(-1)),
                )
                commit = pending & (final_sel >= 0)
                mask_vec = jnp.zeros((P,), bool).at[p].set(commit)
                sel_vec = jnp.full((P,), -1, jnp.int32).at[p].set(final_sel)
                state = bind_all(state, a, mask_vec, sel_vec, order)
                return state, commit

            state, commits = jax.lax.scan(pstep, state, seg)
            return state, commits.sum().astype(jnp.int32)

        self.preempt_phase_fn = preempt_phase if preempt_fn is not None else None
        # building blocks for the record path (results()): advance a
        # reconstructed state by one round's commits / re-evaluate pods
        self._bind_all = bind_all
        self._eval_attempt = attempt

        def make_round_once(arrays, order, weights):
            """The one dense round (eval → match → bind), shared by the
            default program (`run`) and the bind-round-tracking record
            variant (`run_tracked`) so the two can never drift."""
            in_queue = order != _NO_ORDER
            C = arrays.pod_claim.shape[1]
            pod_claim = arrays.pod_claim.astype(bool)
            # [P] pods carrying required ANTI-affinity terms — the only
            # cluster-global coupling that needs serialization: positive
            # required affinity is monotone in the feasibility sense
            # (same-round peers can only ADD matches, never violate a
            # term; the residual is the self-matching series-start
            # divergence documented in __init__ — batched series
            # starters may split domains sequential would co-locate)
            # and bound pods' positive terms never block incoming pods
            # (upstream's symmetric check exists for anti-affinity
            # only), so affinity-only pods batch freely
            rel_carrier = (
                (arrays.rel.ian_key >= 0).any(axis=1)
                if rel_serialize
                else None
            )

            def make_match_step(order_v, pod_claim_v, rel_carrier_v):
                """Matching iteration over an arbitrary ROW SUBSET of
                the queue: `order_v`/`pod_claim_v`/`rel_carrier_v` are
                the [K]-row views (K == P for full rounds; K == the
                eval window for windowed rounds). Queue positions in
                `order_v` are global, so the per-node/per-claim
                earliest-order winner logic is identical either way."""

                def match_step(taken, claim_taken, sel_acc, vals, idx, c_min):
                    """One matching iteration (shared by both loop
                    modes): argmax over untaken candidates → per-node
                    order winner → per-claim order winner → commit.
                    `vals`/`idx` are the [K, k] top-k candidate
                    scores/node-indices (idx is None in full-width
                    mode, where column position == node)."""
                    node_taken = (
                        taken[idx] if idx is not None else taken[None, :]
                    )
                    m = jnp.where(node_taken, FLOOR, vals)
                    m = jnp.where((sel_acc >= 0)[:, None], FLOOR, m)
                    claim_blocked = (
                        pod_claim_v & claim_taken[None, :]
                    ).any(axis=1)
                    m = jnp.where(claim_blocked[:, None], FLOOR, m)
                    if rel_carrier_v is not None:
                        # queue-prefix batching: the batched matching
                        # may only commit pods strictly BEFORE the
                        # first placeable carrier in queue order —
                        # carriers (and everything behind them) wait,
                        # preserving the sequential interleaving at
                        # carrier boundaries
                        m = jnp.where((order_v >= c_min)[:, None], FLOOR, m)
                    col = jnp.argmax(m, axis=1).astype(jnp.int32)
                    has = (
                        jnp.take_along_axis(m, col[:, None], axis=1)[:, 0]
                        > NEG
                    )
                    cand = (
                        jnp.take_along_axis(idx, col[:, None], axis=1)[:, 0]
                        if idx is not None
                        else col
                    )
                    tgt = jnp.where(has, cand, N)
                    winner = (
                        jnp.full((N + 1,), _NO_ORDER, jnp.int32)
                        .at[tgt]
                        .min(order_v)
                    )
                    commit = has & (winner[jnp.maximum(cand, 0)] == order_v)
                    claim_order = jnp.where(
                        commit[:, None] & pod_claim_v,
                        order_v[:, None],
                        _NO_ORDER,
                    )
                    claim_min = claim_order.min(axis=0)  # [C]
                    claim_ok = jnp.where(
                        pod_claim_v,
                        claim_min[None, :] == order_v[:, None],
                        True,
                    ).all(axis=1)
                    commit = commit & claim_ok
                    sel_acc = jnp.where(commit, cand, sel_acc)
                    taken = taken | (
                        jnp.zeros((N + 1,), bool)
                        .at[jnp.where(commit, cand, N)]
                        .set(True)[:N]
                    )
                    claim_taken = claim_taken | (
                        pod_claim_v & commit[:, None]
                    ).any(axis=0)
                    return taken, claim_taken, sel_acc, commit.any()

                return match_step

            def match(
                scores, order_v=None, pod_claim_v=None, rel_carrier_v=...,
            ):
                """One-commit-per-node matching over the round's masked
                score matrix: argmax → earliest-order winner per node →
                losers retry their next-best untaken node. No kernel
                re-evaluation — pure selects over [P, N].

                ReadWriteOncePod claims are cluster-global, so node
                serialization alone can't protect them: two claimants
                could win different nodes in one round. The matching
                therefore also carries per-claim consumption — a pod
                commits only if it is the earliest-order committer for
                every claim it uses, and consumed claims knock their
                other claimants out of the rest of the round (next
                round's evaluation sees used_claims > 0 and rejects them
                exactly like the sequential engine).

                With `match_width` < N the iteration runs over each
                pod's top-k candidate columns instead of all N nodes
                (see __init__ docstring).

                With `rel_serialize`, rounds respect queue order at
                carrier boundaries: the batched matching commits only
                pods strictly before the first placeable required-term
                carrier, and once the prefix is exhausted the carrier
                takes an EXCLUSIVE round at its argmax node (the
                sequential engine's choice against this state). See
                __init__.

                Row-subset form: `order_v`/`pod_claim_v`/`rel_carrier_v`
                override the full-queue views for windowed rounds (the
                scores' rows are then the window's pods). Defaults keep
                the full-round call sites unchanged."""
                if order_v is None:
                    order_v = order
                if pod_claim_v is None:
                    pod_claim_v = pod_claim
                if rel_carrier_v is ...:
                    rel_carrier_v = rel_carrier
                K_rows = scores.shape[0]
                match_step = make_match_step(
                    order_v, pod_claim_v, rel_carrier_v
                )
                if MW < N:
                    vals, idx = jax.lax.top_k(scores, MW)
                    idx = idx.astype(jnp.int32)
                else:
                    vals, idx = scores, None
                if rel_carrier_v is not None:
                    # non-pending rows are FLOOR, so row_ok means
                    # "pending with at least one feasible node"
                    row_best = vals.max(axis=1)
                    row_ok = row_best > NEG
                    c_min = jnp.where(
                        rel_carrier_v & row_ok, order_v, _NO_ORDER
                    ).min()
                    # exclusive carrier round (see __init__ docstring):
                    # the earliest placeable carrier commits alone, but
                    # only once nothing placeable sits before it in
                    # queue order
                    prefix_exists = (row_ok & (order_v < c_min)).any()
                    have_carrier = (~prefix_exists) & (c_min != _NO_ORDER)
                else:
                    c_min = jnp.int32(_NO_ORDER)
                    have_carrier = None
                taken0 = jnp.zeros((N,), bool)
                claims0 = jnp.zeros((C,), bool)
                sel0 = jnp.full((K_rows,), -1, jnp.int32)

                def run_matching(_):
                    if inner_static:
                        # counted loop: iterations after the matching
                        # settles are no-ops (nothing commits twice)
                        def m_scan(carry, __):
                            taken, claim_taken, sel_acc = carry
                            taken, claim_taken, sel_acc, _ = match_step(
                                taken, claim_taken, sel_acc, vals, idx, c_min
                            )
                            return (taken, claim_taken, sel_acc), None

                        (_, _, sel_acc), _ = jax.lax.scan(
                            m_scan,
                            (taken0, claims0, sel0),
                            None,
                            length=inner_iters,
                        )
                        return sel_acc

                    def m_cond(c):
                        _, _, _, changed, it = c
                        return changed & (it < inner_iters)

                    def m_body(c):
                        taken, claim_taken, sel_acc, _, it = c
                        taken, claim_taken, sel_acc, changed = match_step(
                            taken, claim_taken, sel_acc, vals, idx, c_min
                        )
                        return (
                            taken, claim_taken, sel_acc, changed,
                            it + jnp.int32(1),
                        )

                    _, _, sel_acc, _, _ = jax.lax.while_loop(
                        m_cond,
                        m_body,
                        (taken0, claims0, sel0, jnp.bool_(True), jnp.int32(0)),
                    )
                    return sel_acc

                if rel_carrier_v is None:
                    return run_matching(None)
                # a carrier round's matching is all-FLOOR no-ops; skip
                # it through cond so the static scan doesn't pay
                # inner_iters wasted iterations per carrier (under vmap
                # cond lowers to both-branches select — no worse than
                # always running it)
                sel_acc = jax.lax.cond(
                    have_carrier, lambda _: sel0, run_matching, None
                )
                is_pick = rel_carrier_v & row_ok & (order_v == c_min)
                col = jnp.argmax(vals, axis=1).astype(jnp.int32)
                cand = (
                    jnp.take_along_axis(idx, col[:, None], axis=1)[:, 0]
                    if idx is not None
                    else col
                )
                sel_carrier = jnp.where(is_pick, cand, jnp.int32(-1))
                return jnp.where(have_carrier, sel_carrier, sel_acc)

            def round_once(state, w_idx=None):
                """One dense round.

                With a BINDING window (WP < P) the caller carries a
                window offset `w_idx` and gets back
                (state, w_idx', progressed). The round's dense work —
                eval, top_k, matching — runs on [WP, N] row-subset
                tensors ONLY (window `w_idx` of the pending queue, in
                queue order): per-round cost is bounded by the window
                regardless of P, and the compiled program carries no
                tall [P, N] construct at any P. The offset advance IS
                the fixpoint machinery: a commit resets w_idx to 0
                (earlier-queue pods get first claim on the new state),
                a no-commit round advances to the next window, and a
                full sweep 0..ceil(n_pending/WP)-1 with no commit
                anywhere — swept against a provably unchanged state —
                is exactly the unwindowed full round's
                nothing-can-place signal, so `progressed` goes False.
                Pure selects throughout: no lax.cond, so the same
                program is vmap-efficient (GangSweep) — a vmapped cond
                would pay both branches every round (code-review r5).

                Soundness of skipping earlier windows at offset k > 0:
                those windows' pods ARE queue-before the in-window pods
                — but every one of them was matched against this EXACT
                state earlier in the no-commit streak (a no-commit
                round leaves state bytes unchanged, and any commit
                resets the offset to 0) and could not place, which is
                precisely the condition under which the carrier-prefix
                and priority-order arguments allow batching past them.
                Any change to the offset advance (not resetting on
                commit, resuming mid-sweep across passes) breaks that
                premise — don't."""
                pending = (state.assignment < 0) & in_queue & arrays.pod_mask
                if W is None or WP is None:
                    scores = eval_all(state, arrays, weights, pending)
                    scores = jnp.where(pending[:, None], scores, FLOOR)
                    sel = match(scores)
                    commit = sel >= 0
                    state = bind_all(state, arrays, commit, sel, order)
                    committed = commit.any()
                    if W is None:
                        return state, committed
                    # the window never binds: full rounds with the
                    # windowed carry shape — plain fixpoint signal
                    return state, jnp.int32(0), committed

                n_pending = pending.sum()
                perm = jnp.argsort(
                    jnp.where(pending, order, _NO_ORDER)
                ).astype(jnp.int32)
                n_win = -(-P // WP)  # static sweep bound
                # windows past the sweep bound only occur in static
                # budget slots after the fixpoint — clamp them to the
                # last window (liveness gates their eval to ~nothing)
                k = jnp.minimum(w_idx, jnp.int32(n_win - 1))
                # the last window's start clamps to P-WP (it may overlap
                # the previous — harmless: those rows committed nothing
                # against this same state); liveness uses the SAME
                # clamped start so a clamped window can never
                # floor-skip chunks that hold pending rows
                start = jnp.minimum(k * jnp.int32(WP), jnp.int32(P - WP))
                rows = jax.lax.dynamic_slice_in_dim(perm, start, WP)
                rows_pending = pending[rows]
                n_live = jnp.clip(n_pending - start, 0, jnp.int32(WP))
                scores_w = eval_rows(state, arrays, weights, rows, n_live)
                scores_w = jnp.where(rows_pending[:, None], scores_w, FLOOR)
                sel_w = match(
                    scores_w,
                    order_v=order[rows],
                    pod_claim_v=pod_claim[rows],
                    rel_carrier_v=(
                        None if rel_carrier is None else rel_carrier[rows]
                    ),
                )
                sel = (
                    jnp.full((P,), -1, jnp.int32)
                    .at[rows]
                    .set(jnp.where(rows_pending, sel_w, -1))
                )
                commit = sel >= 0
                state = bind_all(state, arrays, commit, sel, order)
                committed = commit.any()
                # sweep accounting against THIS round's pending count
                # (constant across a no-commit streak, so the streak
                # really does cover every pending window)
                w_max = jnp.maximum(
                    jnp.int32(1),
                    -(-n_pending // jnp.int32(WP)),
                )
                done = (~committed) & (k + 1 >= w_max)
                w_next = jnp.where(committed, jnp.int32(0), w_idx + 1)
                return state, w_next, ~done

            return round_once

        def run(arrays, state0, order, weights):
            """(arrays, state0, order, weights) -> (final_state, rounds).

            `order` comes from `order_arrays()`; passing it as an
            argument (like the sequential engine's queue) keeps the
            compiled program reusable across retargets and lets sweeps
            vmap over `weights` alone.
            """
            arrays = unpack(arrays)
            round_once = make_round_once(arrays, order, weights)

            def cond(carry):
                _, progressed, rounds = carry
                return progressed & (rounds < max_rounds)

            if static:
                # counted outer loop too: the whole program is scans, the
                # same control-flow shape as the sequential engine
                if W is not None:

                    def rw_scan(carry, _):
                        state, w_idx = carry
                        state, w_next, progressed = round_once(
                            state, w_idx
                        )
                        return (state, w_next), progressed

                    (state, _), progressed = jax.lax.scan(
                        rw_scan,
                        (state0, jnp.int32(0)),
                        None,
                        length=self.static_rounds,
                    )
                    return state, progressed.sum().astype(jnp.int32)

                def r_scan(state, _):
                    state, progressed = round_once(state)
                    return state, progressed

                state, progressed = jax.lax.scan(
                    r_scan, state0, None, length=self.static_rounds
                )
                return state, progressed.sum().astype(jnp.int32)

            if W is not None:
                # commit-round budget (see the max_rounds comment above):
                # w_next == 0 identifies a committing round — a commit
                # resets the window offset, a no-commit round advances it
                # past 0

                def w_cond(carry):
                    _, progressed, _, _, commits = carry
                    return progressed & (commits < max_rounds)

                def w_body(carry):
                    state, _, rounds, w_idx, commits = carry
                    state, w_next, progressed = round_once(state, w_idx)
                    commits = commits + (w_next == 0).astype(jnp.int32)
                    return (
                        state, progressed, rounds + jnp.int32(1), w_next,
                        commits,
                    )

                state, _, rounds, _, _ = jax.lax.while_loop(
                    w_cond,
                    w_body,
                    (
                        state0, jnp.bool_(True), jnp.int32(0), jnp.int32(0),
                        jnp.int32(0),
                    ),
                )
                return state, rounds

            def body(carry):
                state, _, rounds = carry
                state, progressed = round_once(state)
                return state, progressed, rounds + jnp.int32(1)

            state, _, rounds = jax.lax.while_loop(
                cond, body, (state0, jnp.bool_(True), jnp.int32(0))
            )
            return state, rounds

        def run_tracked(arrays, state0, order, weights):
            """`run` plus a [P] bind-round tensor (-1 = not bound this
            pass): the record path's reconstruction key — results()
            re-evaluates each pod against the start state of the round
            that bound it. A separate program so the default (chip-
            proven) compile class carries nothing extra; the round body
            is the SAME `make_round_once` closure."""
            arrays = unpack(arrays)
            round_once = make_round_once(arrays, order, weights)
            br0 = jnp.full((P,), -1, jnp.int32)
            if static:
                if W is not None:

                    def rw_scan(carry, r):
                        state, br, w_idx = carry
                        state2, w_next, progressed = round_once(
                            state, w_idx
                        )
                        newly = (
                            (state2.assignment >= 0) & (state.assignment < 0)
                        )
                        br = jnp.where(newly, r, br)
                        return (state2, br, w_next), progressed

                    (state, br, _), progressed = jax.lax.scan(
                        rw_scan,
                        (state0, br0, jnp.int32(0)),
                        jnp.arange(self.static_rounds, dtype=jnp.int32),
                    )
                    return state, progressed.sum().astype(jnp.int32), br

                def r_scan(carry, r):
                    state, br = carry
                    state2, progressed = round_once(state)
                    newly = (state2.assignment >= 0) & (state.assignment < 0)
                    br = jnp.where(newly, r, br)
                    return (state2, br), progressed

                (state, br), progressed = jax.lax.scan(
                    r_scan,
                    (state0, br0),
                    jnp.arange(self.static_rounds, dtype=jnp.int32),
                )
                return state, progressed.sum().astype(jnp.int32), br

            if W is not None:
                # same commit-round budget as the untracked loop

                def tw_cond(carry):
                    _, progressed, _, _, _, commits = carry
                    return progressed & (commits < max_rounds)

                def tw_body(carry):
                    state, _, rounds, br, w_idx, commits = carry
                    state2, w_next, progressed = round_once(state, w_idx)
                    newly = (state2.assignment >= 0) & (state.assignment < 0)
                    br = jnp.where(newly, rounds, br)
                    commits = commits + (w_next == 0).astype(jnp.int32)
                    return (
                        state2, progressed, rounds + jnp.int32(1), br,
                        w_next, commits,
                    )

                state, _, rounds, br, _, _ = jax.lax.while_loop(
                    tw_cond,
                    tw_body,
                    (
                        state0, jnp.bool_(True), jnp.int32(0), br0,
                        jnp.int32(0), jnp.int32(0),
                    ),
                )
                return state, rounds, br

            def t_cond(carry):
                _, progressed, rounds, _ = carry
                return progressed & (rounds < max_rounds)

            def t_body(carry):
                state, _, rounds, br = carry
                state2, progressed = round_once(state)
                newly = (state2.assignment >= 0) & (state.assignment < 0)
                br = jnp.where(newly, rounds, br)
                return state2, progressed, rounds + jnp.int32(1), br

            state, _, rounds, br = jax.lax.while_loop(
                t_cond, t_body, (state0, jnp.bool_(True), jnp.int32(0), br0)
            )
            return state, rounds, br

        self.run_tracked_fn = run_tracked

        def fixpoint(arrays, state0, order, weights):
            """The WHOLE untracked gang pass as one device program:
            rounds-to-fixpoint, then the preempt-phase/resume
            alternation `_drive` used to run as a host loop (with an
            `assignment` readback per iteration — the sync that defeated
            async pipeline overlap and cost 2k+1 dispatches per pass
            with preemption enabled). Control flow is the exact device
            transliteration of the host driver:

              state, rounds = run(state0)
              while True:                      # outer while_loop
                  pending = unbound & queued & real
                  if none pending: break       # phase cond-skipped
                  state, n = preempt_phase(pending in queue order)
                  if n == 0: break             # resume cond-skipped
                  state, r = run(state); rounds += r   # fresh budget

            The phase segment is built on device: a stable argsort of
            `order` masked to pending pods (identical to the host's
            `pending[np.argsort(order[pending])]`), -1-padded to fixed
            length P — pstep rows with p_raw == -1 are exact no-ops, so
            the fixed-length scan replaces the host path's
            pow2-padded-segment recompile family with ONE phase shape.
            Each resume re-enters `run`'s loop with a fresh max_rounds
            commit budget, matching the host driver's per-call budget.

            Caveats: under vmap (batch.gang.run) the two `lax.cond`
            guards lower to both-branches-plus-select, so converged
            sessions in a batch pay (masked, no-op) phase work — the
            GangSweep tradeoff; and the batched while_loops run until
            every session converges. Dynamic loop mode only: the static
            outer scan keeps its host auto-resume driver, and tracked
            (record) passes keep the host chronology driver that the
            byte-parity trace replay is built on."""
            # widen packed planes ONCE, outside the while_loop — the
            # nested run/preempt_phase unpacks become static no-ops
            arrays = unpack(arrays)
            state, rounds = run(arrays, state0, order, weights)
            if preempt_fn is None:
                return state, rounds
            in_queue = order != _NO_ORDER

            def obody(carry):
                state, rounds, _ = carry
                pending = (
                    (state.assignment < 0) & in_queue & arrays.pod_mask
                )
                n_pend = pending.sum().astype(jnp.int32)
                perm = jnp.argsort(
                    jnp.where(pending, order, _NO_ORDER)
                ).astype(jnp.int32)
                seg = jnp.where(
                    jnp.arange(P, dtype=jnp.int32) < n_pend,
                    perm,
                    jnp.int32(-1),
                )
                state, n_bound = jax.lax.cond(
                    n_pend > 0,
                    lambda s: preempt_phase(arrays, s, seg, order, weights),
                    lambda s: (s, jnp.int32(0)),
                    state,
                )
                state, r2 = jax.lax.cond(
                    n_bound > 0,
                    lambda s: run(arrays, s, order, weights),
                    lambda s: (s, jnp.int32(0)),
                    state,
                )
                return state, rounds + r2, n_bound > 0

            state, rounds, _ = jax.lax.while_loop(
                lambda carry: carry[2],
                obody,
                (state, rounds, jnp.bool_(True)),
            )
            return state, rounds

        # the fused one-dispatch pass exists only for the dynamic loop:
        # static mode's auto-resume budget is a host decision by design
        # (backends where while_loop won't compile), and it keeps the
        # host driver.
        self.fixpoint_fn = fixpoint if not static else None
        return run

    # -- execution ----------------------------------------------------------

    def run(self, weights: "jnp.ndarray | None" = None):
        """Execute to fixpoint; returns (final_state, rounds_used).

        Static loop mode auto-resumes: a pass whose whole round budget
        committed (no-op rounds form a suffix, so a pass's rounds ==
        budget means its final budgeted round still made progress) runs
        another pass of the same compiled program from the reached
        state, until a pass reaches its fixpoint mid-budget — the old
        under-budget starvation trap (ADVICE r3) is structurally
        impossible, so there is no `exhausted` flag anymore. An
        infeasible remainder that coincides with an exactly-full budget
        costs at most one extra (no-commit) pass, the same price
        dynamic mode pays for its final empty round.

        With DefaultPreemption enabled the fixpoint alternates with
        preempt phases: rounds settle → the (few) still-pending pods go
        through the compiled sequential preempt pass → rounds resume;
        the host loop stops when a phase binds nothing."""
        return self._drive(weights, chronology=None)

    def warmup(self, record: bool = False) -> "GangScheduler":
        """Compile the fixpoint program (and, with `record=True`, the
        bind-round-tracking variant) by executing one full drive, then
        drop the result — the CompileBroker's speculative-build contract:
        a later pass at an equal compile signature `retarget`s onto this
        instance and runs warm (zero XLA compile on the serving thread)."""
        if record:
            self.run_recorded()
        else:
            self.run()
        self._final_state = None
        self._rounds = None
        self._chronology = None
        self._trace = None
        self._recorded_weights = None
        return self

    def _drive(self, weights, chronology: "list | None"):
        """The ONE host driver behind `run()` and `run_recorded()`:
        gang passes (with the static auto-resume rule) alternating with
        preempt phases. When `chronology` is given, each pass runs the
        bind-round-tracking program and appends its replay entry, each
        phase appends its segment, and fixpoint leftovers append theirs
        — identical control flow either way, so the record path can
        never drift from the default one. (parallel/sweep.py gang_pass
        carries the per-variant-array form of the resume rule — keep
        the two in step.)"""
        w = self.weights if weights is None else weights
        order, in_q = self.order_arrays()
        arrays = self.enc.arrays
        tracked = chronology is not None
        if not tracked and self._fixpoint is not None:
            # the fused whole-pass program: rounds + preempt alternation
            # in ONE dispatch, zero host readbacks before the caller's
            # decode fetch — this is the serving path (async overlap
            # depends on it staying sync-free). `rounds` stays a device
            # scalar; the finish path fetches it with the assignment.
            state, rounds = self._fixpoint(arrays, self.enc.state0, order, w)
            self._final_state = state
            self._rounds = rounds
            return state, rounds
        if tracked and self._run_tracked is None:
            self._run_tracked = broker_mod.jit(
                self.run_tracked_fn,
                audit={**self.audit_spec(), "label": "gang.run_tracked"},
            )
        # the eligibility mask feeds host-side pending counts, which only
        # the static auto-resume, the preempt-phase loop, and the record
        # path read — the plain dynamic path must not pay the two [P]
        # host transfers
        need_pending = (
            self.loop == "static" or self._preempt_phase is not None or tracked
        )
        eligible = (
            np.asarray(in_q) & np.asarray(arrays.pod_mask)
            if need_pending
            else None
        )

        def pending_count(state) -> int:
            return int(((np.asarray(state.assignment) < 0) & eligible).sum())

        def one_pass(state):
            """One compiled pass (+ chronology entry when tracked)."""
            if tracked:
                state, rounds, br = self._run_tracked(arrays, state, order, w)
                chronology.append(
                    (
                        "rounds",
                        np.asarray(br),
                        int(np.asarray(rounds)),
                        np.asarray(state.assignment),
                    )
                )
            else:
                state, rounds = self._run(arrays, state, order, w)
            return state, rounds

        def gang_pass(state):
            state, rounds = one_pass(state)
            if self.loop != "static":
                return state, rounds
            # static auto-resume: continue while the LAST pass used its
            # whole budget (fixpoint not provably reached) and pods are
            # still pending; a pass without progress means the remainder
            # is infeasible, not under-budgeted. An EXPLICIT max_rounds
            # stays a TOTAL cap across passes, matching its hard-cap role
            # in the dynamic loop — never an unbounded-latency trap.
            total = rounds
            committed = last = int(np.asarray(rounds))
            pend = pending_count(state)
            while (
                pend > 0
                and last >= self.static_rounds
                and (self.max_rounds is None or committed < self.max_rounds)
            ):
                state2, r2 = one_pass(state)
                total = total + r2
                last = int(np.asarray(r2))
                committed += last
                pend2 = pending_count(state2)
                state = state2
                if pend2 >= pend:
                    break
                pend = pend2
            return state, total

        state, rounds = gang_pass(self.enc.state0)
        if self._preempt_phase is not None:
            order_np = np.asarray(order)
            while True:
                pending = np.nonzero(
                    (np.asarray(state.assignment) < 0) & eligible
                )[0]
                if pending.size == 0:
                    break
                pending = pending[np.argsort(order_np[pending])]
                if tracked:
                    # recorded even when the phase binds nothing: the
                    # no-progress phase IS the leftovers' failure record
                    chronology.append(("phase", pending.astype(np.int32)))
                # pow2 padding bounds distinct compilations to log2(P)
                pad = 1 << int(pending.size - 1).bit_length()
                seg = np.full((max(pad, 1),), -1, np.int32)
                seg[: pending.size] = pending
                state, n_bound = self._preempt_phase(
                    arrays, state, jnp.asarray(seg), order, w
                )
                if int(np.asarray(n_bound)) == 0:
                    break
                state, r2 = gang_pass(state)
                rounds = rounds + r2
        elif tracked:
            leftovers = np.nonzero(
                (np.asarray(state.assignment) < 0) & eligible
            )[0]
            if leftovers.size:
                chronology.append(("leftover", leftovers.astype(np.int32)))
        self._final_state = state
        self._rounds = rounds
        if tracked:
            self._chronology = chronology
            self._recorded_weights = w
            self._trace = None  # decoded lazily by results()
        return state, rounds

    def placements(self) -> dict[tuple[str, str], str]:
        """pod (ns, name) → node name ("" = unschedulable)."""
        if self._final_state is None:
            self.run()
        return self.enc.decode_assignment(self._final_state.assignment)

    # -- record path (the reference's 13-annotation product) ---------------

    def run_recorded(self, weights: "jnp.ndarray | None" = None):
        """Execute to fixpoint like `run()` — same host driver, the
        bind-round-tracking program — additionally capturing the replay
        chronology the record decode needs: per gang pass, the [P]
        bind-round tensor plus the pass-end assignment snapshot; per
        preempt phase, its pending segment; plus the fixpoint leftovers
        when no preempt phase exists. Returns (state, rounds),
        bit-identical placements to `run()` (test-pinned)."""
        return self._drive(weights, chronology=[])

    def _recorder(self) -> BatchedScheduler:
        """The record-mode base engine the decode borrows: its kernel
        name tables, its `_run_segment` (phase replay), and its
        `results()` (the one definition of the wire format)."""
        if self._rec is None:
            self._rec = BatchedScheduler(self.enc, record=True, strict=False)
        return self._rec

    def _assemble_trace(self) -> tuple:
        """Replay the chronology into the sequential engine's trace slot
        layout ([Q, ...] per-queue-position tensors, sparse for the
        [N, P] victim masks) so `BatchedScheduler.results()` decodes
        gang runs with zero new wire-format code."""
        from .engine import (
            TRACE_SLOTS_PREEMPT,
            TRACE_SPARSE_SLOTS,
            _SparseRows,
        )

        enc = self.enc
        rec = self._recorder()
        arrays = enc.arrays
        wj = self._recorded_weights
        order, _ = self.order_arrays()
        queue = np.asarray(enc.queue)
        Q = len(queue)
        qpos = {int(p): qi for qi, p in enumerate(queue)}
        N, P = enc.N, enc.P
        has_pf = rec._preempt is not None
        nPF = len(rec._prefilter_kernel_names)
        F = len(rec._filter_names)
        S = len(rec._score_specs)
        sdt = np.dtype(jnp.zeros((), enc.policy.score).dtype.name)
        pf_codes = np.zeros((Q, nPF), np.int32)
        codes = np.zeros((Q, N, F), np.int32)
        raw = np.zeros((Q, N, S), sdt)
        final = np.zeros((Q, N, S), sdt)
        sel = np.full((Q,), -1, np.int32)
        if has_pf:
            did = np.zeros((Q,), bool)
            nominated = np.full((Q,), -1, np.int32)
            sel2 = np.full((Q,), -1, np.int32)
            nominated2 = np.full((Q,), -1, np.int32)
            final_sel = np.full((Q,), -1, np.int32)
            sparse: dict[str, dict] = {
                n: {}
                for n in (
                    "pcode", "vmask", "codes2", "raw2", "final2",
                    "pcode2", "vmask2",
                )
            }
        if self._eval_rec is None:
            # ONE compiled chunk evaluator for every round/leftover pod;
            # chunks are padded by repeating the first pod (evaluation
            # is read-only, duplicates are discarded host-side)
            self._eval_rec = broker_mod.jit(
                jax.vmap(rec._attempt, in_axes=(None, None, None, 0)),
                audit={**self.audit_spec(), "label": "gang.eval_record"},
            )
        if self._replay_round is None:
            # the FUSED replay round: evaluate one pod chunk AND
            # scatter-bind the whole round's commits in ONE dispatched
            # program — the replay loop's eval+bind pair collapses to a
            # single dispatch per chunk (the per-pass dispatch-count
            # lever; tests pin the ledger call counts). The eval reads
            # the pre-bind carry exactly like the split form, so the
            # emitted trace rows are byte-identical.
            bind_all = self._bind_all

            def replay_round(state, a, w, pods, mask, selv, order_v):
                pf, cd, rw, fn, _s, _ok = jax.vmap(
                    rec._attempt, in_axes=(None, None, None, 0)
                )(state, a, w, pods)
                return pf, cd, rw, fn, bind_all(state, a, mask, selv, order_v)

            self._replay_round = broker_mod.jit(
                replay_round,
                audit={**self.audit_spec(), "label": "gang.replay_round"},
            )
        CH = max(1, min(128, P))

        def write_rows(chunk, pf, cd, rw, fn, assign_after):
            pf, cd, rw, fn = (np.asarray(x) for x in (pf, cd, rw, fn))
            for j, p in enumerate(chunk):
                qi = qpos[int(p)]
                pf_codes[qi] = pf[j]
                codes[qi] = cd[j]
                raw[qi] = rw[j]
                final[qi] = fn[j]
                if assign_after is not None:
                    committed = np.int32(assign_after[int(p)])
                    sel[qi] = committed
                    if has_pf:
                        final_sel[qi] = committed

        def record_eval(state, pod_ids, assign_after):
            for i in range(0, len(pod_ids), CH):
                chunk = pod_ids[i : i + CH]
                padded = np.full((CH,), chunk[0], np.int32)
                padded[: len(chunk)] = chunk
                pf, cd, rw, fn, _s, _ok = self._eval_rec(
                    state, arrays, wj, jnp.asarray(padded)
                )
                write_rows(chunk, pf, cd, rw, fn, assign_after)

        state = enc.state0
        for entry in self._chronology:
            kind = entry[0]
            if kind == "rounds":
                _, br, n_rounds, assign_after = entry
                for r in range(n_rounds):
                    pods_r = np.nonzero(br == r)[0].astype(np.int32)
                    if pods_r.size == 0:
                        continue
                    mask = np.zeros((P,), bool)
                    mask[pods_r] = True
                    selv = np.where(mask, assign_after, -1).astype(np.int32)
                    # all chunks evaluate against the round's pre-bind
                    # state; the LAST chunk rides the fused program,
                    # which also commits the whole round's binds —
                    # dispatches per round: ceil(|round|/CH), not +1
                    head = ((pods_r.size - 1) // CH) * CH
                    if head:
                        record_eval(state, pods_r[:head], assign_after)
                    chunk = pods_r[head:]
                    padded = np.full((CH,), chunk[0], np.int32)
                    padded[: len(chunk)] = chunk
                    pf, cd, rw, fn, state = self._replay_round(
                        state, arrays, wj, jnp.asarray(padded),
                        jnp.asarray(mask), jnp.asarray(selv), order,
                    )
                    write_rows(chunk, pf, cd, rw, fn, assign_after)
            elif kind == "phase":
                # the sequential engine's record segments replay the
                # phase pod-by-pod (phase semantics ARE the sequential
                # step's — engine.py step() vs preempt_phase pstep)
                for p in entry[1]:
                    qi = qpos[int(p)]
                    state, out = rec._run_segment(
                        arrays,
                        state,
                        jnp.asarray([int(p)], queue.dtype),
                        jnp.asarray([qi], jnp.int32),
                        wj,
                    )
                    vals = dict(zip(TRACE_SLOTS_PREEMPT, out))
                    pf_codes[qi] = np.asarray(vals["pf_codes"])[0]
                    codes[qi] = np.asarray(vals["codes"])[0]
                    raw[qi] = np.asarray(vals["raw"])[0]
                    final[qi] = np.asarray(vals["final"])[0]
                    sel[qi] = int(np.asarray(vals["sel"])[0])
                    did[qi] = bool(np.asarray(vals["did"])[0])
                    nominated[qi] = int(np.asarray(vals["nominated"])[0])
                    sel2[qi] = int(np.asarray(vals["sel2"])[0])
                    nominated2[qi] = int(np.asarray(vals["nominated2"])[0])
                    final_sel[qi] = int(np.asarray(vals["final_sel"])[0])
                    if did[qi]:
                        for nm in sparse:
                            sparse[nm][qi] = np.asarray(vals[nm])[0]
            else:  # leftover (no preempt phase configured)
                record_eval(state, entry[1], None)

        if not has_pf:
            return (pf_codes, codes, raw, final, sel)
        row_shapes = {
            "pcode": ((N,), np.int32),
            "vmask": ((N, P), bool),
            "codes2": ((N, F), np.int32),
            "raw2": ((N, S), sdt),
            "final2": ((N, S), sdt),
            "pcode2": ((N,), np.int32),
            "vmask2": ((N, P), bool),
        }
        by_name = {
            "pf_codes": pf_codes, "codes": codes, "raw": raw,
            "final": final, "sel": sel, "did": did,
            "nominated": nominated, "sel2": sel2,
            "nominated2": nominated2, "final_sel": final_sel,
        }
        trace = []
        for i, name in enumerate(TRACE_SLOTS_PREEMPT):
            if i in TRACE_SPARSE_SLOTS:
                shape, dtype = row_shapes[name]
                trace.append(_SparseRows(sparse[name], shape, dtype))
            else:
                trace.append(by_name[name])
        return tuple(trace)

    def results(self, pods: "set[tuple[str, str]] | None" = None):
        """The reference's per-pod scheduling records for a gang run
        (13-annotation wire format, decoded by the sequential engine's
        `results()` — one definition of the format). Runs
        `run_recorded()` first when needed."""
        if self._chronology is None:
            self.run_recorded()
        if self._trace is None:
            self._trace = self._assemble_trace()
        rec = self._recorder()
        rec._trace = self._trace
        rec._final_state = self._final_state
        return rec.results(pods)

    @staticmethod
    def compile_signature(enc: EncodedCluster) -> tuple:
        """Everything the compiled gang program bakes in. Unlike the
        sequential scan, the queue rides in as a fixed-[P] `order`
        argument, so two encodings differing only in pending-queue
        length share one compilation."""
        return BatchedScheduler.compile_signature(
            enc, record=False, include_queue_len=False
        )

    @staticmethod
    def effective_window(
        enc: EncodedCluster, eval_window: "int | None", chunk: int = 256
    ) -> "int | None":
        """The chunk-granular window row count the compiled program
        actually uses — None when windowing is off or never binds
        (eval_window >= P). THIS, not the raw eval_window value, is
        what program identity depends on: cache keys canonicalized on
        it never recompile for raw windows that round to the same WP."""
        if eval_window is None:
            return None
        ch = max(1, min(int(chunk), enc.P))
        wp = min(-(-min(int(eval_window), enc.P) // ch) * ch, enc.P)
        return None if wp >= enc.P else wp

    def retarget(self, enc: EncodedCluster) -> "GangScheduler":
        """Point at a compile-compatible new encoding (see
        BatchedScheduler.retarget)."""
        if self.compile_signature(enc) != self.compile_signature(self.enc):
            raise ValueError("encoding is not compile-compatible; rebuild")
        # keep the base engine's host-side decode tables in sync
        self._base.enc = enc
        self.enc = enc
        self._final_state = None
        self._rounds = None
        # record state is per-encoding. _run_tracked survives (its
        # shapes are part of the signature just checked); the recorder
        # and its chunk evaluator bake enc-derived statics via their own
        # kernel constructors, so rebuild them lazily (jit is lazy and
        # the persistent compile cache absorbs the repeat).
        self._chronology = None
        self._trace = None
        self._recorded_weights = None
        self._rec = None
        self._eval_rec = None
        self._replay_round = None
        return self
