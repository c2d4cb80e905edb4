"""Cross-tenant continuous batching: the micro-batch dispatch plane.

PR 6 built the session plane (thousands of tenants, one shared
CompileBroker, bucket-compatible sessions already sharing warm
executables) and PR 12 killed the compile wall — but every scheduling
pass still drove the device ONE SESSION AT A TIME, so aggregate
decisions/s/process was flat in session count. Inference serving solved
exactly this shape with iteration-level batching (Orca; vLLM's batched
serving loop): stack compatible requests onto one device program and
keep occupancy high. Here the batch axis is the already-vmapped sweep
axis (parallel/sweep.py) — the kernel machinery existed, this module is
the missing serving plane.

How a window forms (docs/sessions.md "Continuous batching"):

  * Device-driving sequential passes that arrive inside a collection
    window (``KSS_BATCH_WINDOW_MS``) and are **batch-compatible** — the
    same engine kind, compile signature, shape bucket and device epoch,
    i.e. the exact broker key warm-engine sharing already uses — enroll
    in one window. The first enrollee is the window's LEADER; it waits
    out the window (or until ``KSS_BATCH_MAX_SESSIONS`` fills it) and
    then executes every enrolled pass as ONE broker-jitted program:
    ``vmap(run_fn)`` over a leading session axis, the `parallel/sweep.py`
    pattern with sessions where the sweep has policy variants.
  * The batch axis is padded to its geometric bucket (slot 0 replayed;
    results discarded — the sweep's ``valid=False`` analogue) so batch
    fills 3, 5..8 reuse the 4- and 8-wide compilations.
  * Results scatter back per-session: each enrollee receives ITS slice
    of the final state + trace and decodes/writes back on its own
    thread, under its own session context and pass id — placements and
    trace bytes are BYTE-IDENTICAL to solo dispatch (parity-pinned in
    tests/test_batchplane.py and `make batch-smoke`).

Fairness is a hard contract: a lone tenant never waits more than one
window — the leader's wait is bounded by
``min(KSS_BATCH_WINDOW_MS, KSS_BATCH_MAX_WAIT_MS)`` and a window that
closes with one enrollee is told to dispatch SOLO (today's path,
``soloFallbacks``) rather than pay a vmapped program for nothing.
Windows close on the timer, never on a quorum, so semaphore waiters
(the ``KSS_MAX_CONCURRENT_PASSES`` collection point, server/sessions.py)
can never deadlock against the window: a window with no second arrival
always flushes. Incompatible passes — different broker key, extender
mode, a recorded gang pass (its trace replay is per-session host work),
a session-scoped (or process) fault plane, an escalated device rung —
fall back to solo dispatch, counted per-session.

Gang passes batch too (``batch.gang.run``): the fused `gang.fixpoint`
program made one gang pass ONE broker-keyed dispatch, so bucket- and
window-compatible gang passes stack exactly like sequential ones — the
batch axis rides `vmap` over (arrays, state0, order, weights) and each
session gets back its (final state, rounds) slice. The vmapped
while_loops run until every session's fixpoint converges; converged
sessions' extra rounds are masked no-ops and the program's `lax.cond`
guards lower to both-branches-plus-select (the GangSweep tradeoff,
docs/performance.md).

Failure containment: ANY error inside the batched execution (compile
failure, device fault, a torn stack) marks every enrollee solo and each
falls back to today's dispatch on its own thread — with its own
resilience ladder (retry → shrink → CPU, eager fallback). The batch
plane can degrade throughput, never correctness.

Accounting: ``batchedPasses`` / ``batchWindows`` / ``batchOccupancySum``
/ ``soloFallbacks`` phases counters (utils/metrics.py — per-session for
passes/fallbacks, on the plane's default registry for windows/occupancy),
a ``fleet.batchOccupancy`` Perfetto counter track, ``batch.execute``
complete-events, and per-tenant program-ledger attribution: the ONE
``batch.seq.run`` call a window dispatches fans its session attribution
out to every enrolled tenant (`ProgramLedger.attribute_sessions`), so
`calls` counts device dispatches while per-session counts stay passes
served.

`POST /api/v1/admin/drain` flushes partially-filled windows before
snapshotting (`begin_drain`): a draining process must not sit out a
collection window, and new enrollments shed to solo immediately.
"""

from __future__ import annotations

import os
import threading
import time

from ..utils import locking, telemetry
from ..utils import broker as broker_mod
from ..utils import ledger as ledger_mod
from ..utils.compilecache import shape_bucket
from ..utils.envcheck import env_truthy

# the KSS7xx audit labels (and program-ledger keys) of the two batched
# program kinds: the vmapped sequential scan and the vmapped gang
# fixpoint (engine/gang.py `gang.fixpoint` — the fused rounds +
# preempt-alternation pass — over a leading session axis)
BATCH_SEQ_LABEL = "batch.seq.run"
BATCH_GANG_LABEL = "batch.gang.run"

# how long a follower waits on the leader's execution before giving up
# and dispatching solo. The leader ALWAYS signals (results or error) in
# a finally block, so this bound only matters if the leader thread is
# killed mid-execution — generous because a cold chip compile of the
# batched program can legitimately take minutes.
_FOLLOWER_TIMEOUT_S = 600.0

# batched programs kept warm, FIFO-evicted: each entry holds one vmapped
# jit + its template engine, keyed (broker key, batch bucket) — the same
# bound spirit as the broker's warm-engine LRU
_PROGRAM_CAP = 8


def _env_float_ms(name: str, default_ms: float) -> float:
    """A window knob in milliseconds (lenient like the broker's ladder
    knobs: a malformed value must not take the serving stack down).
    The env READ stays module-local so KSS1xx can tie the name to its
    reader; the coercion is the broker's shared helper."""
    return broker_mod._coerce_env_number(
        os.environ.get(name, ""), default_ms, float, 0.0
    )


def _env_int(name: str, default: int, minimum: int) -> int:
    return broker_mod._coerce_env_number(
        os.environ.get(name, ""), default, int, minimum
    )


def from_env(metrics=None) -> "BatchPlane | None":
    """The serving plane's constructor: an armed `BatchPlane` when
    ``KSS_BATCH`` is truthy, else None (batching is off by default —
    the historical one-session-at-a-time dispatch)."""
    if not env_truthy(os.environ.get("KSS_BATCH")):
        return None
    window_ms = _env_float_ms("KSS_BATCH_WINDOW_MS", 5.0)
    max_wait_ms = _env_float_ms("KSS_BATCH_MAX_WAIT_MS", window_ms)
    max_sessions = _env_int("KSS_BATCH_MAX_SESSIONS", 8, 1)
    return BatchPlane(
        window_ms=window_ms,
        max_wait_ms=max_wait_ms,
        max_sessions=max_sessions,
        metrics=metrics,
    )


class _Enrollee:
    """One pass enrolled in a window: its decode engine (carrying the
    encoding), padded queue, and the slot the leader scatters into."""

    __slots__ = (
        "engine", "queue", "session_id", "metrics",
        "done", "state", "trace", "error", "abandoned", "trace_id",
    )

    def __init__(self, engine, queue, session_id, metrics):
        self.engine = engine
        self.queue = queue
        self.session_id = session_id
        self.metrics = metrics
        self.done = threading.Event()
        self.state = None
        self.trace = None
        # the enrolling request's distributed-trace id, captured on the
        # submit thread: the ONE vmapped window dispatch links back to
        # every enrolled tenant's request trace (docs/observability.md)
        self.trace_id = telemetry.current_trace_id()
        self.error: "Exception | None" = None
        # set (under the plane lock) by a follower whose done-wait
        # expired: it is about to dispatch solo, so the late leader
        # must not count or attribute its pass as batched
        self.abandoned = False


class _Window:
    """One collection window for one batch key. `full` wakes the leader
    early when KSS_BATCH_MAX_SESSIONS enrollees arrived; `closed` stops
    late joiners (they open a successor window instead)."""

    __slots__ = ("key", "kind", "items", "closed", "full")

    def __init__(self, key, kind="seq"):
        self.key = key
        # "seq" | "gang" — which batched program the window dispatches.
        # Uniform per window by construction: the key's leading element
        # is the engine kind, so mixed-kind enrollment cannot happen.
        self.kind = kind
        self.items: "list[_Enrollee]" = []
        self.closed = False
        self.full = threading.Event()


@locking.guard_inferred
class BatchPlane:
    """The micro-batch dispatch plane (module docstring). One instance
    per SessionManager, shared by every session's SchedulerService."""

    def __init__(
        self,
        *,
        window_ms: float = 5.0,
        max_wait_ms: "float | None" = None,
        max_sessions: int = 8,
        metrics=None,
    ):
        self.window_s = max(0.0, float(window_ms)) / 1000.0
        wait_s = (
            self.window_s
            if max_wait_ms is None
            else max(0.0, float(max_wait_ms)) / 1000.0
        )
        # the fairness bound: the leader's collection wait — and with it
        # any enrollee's added latency — never exceeds one window
        self.wait_s = min(self.window_s, wait_s)
        self.max_sessions = max(1, int(max_sessions))
        # window/occupancy counters land here (the default session's
        # registry — the broker's fallback-attribution precedent);
        # per-pass counters land on each enrollee's own registry
        self.metrics = metrics
        self._lock = locking.make_lock("batchplane.windows")
        self._open: "dict[object, _Window]" = {}
        self._programs: "dict[tuple, dict]" = {}
        self._draining = False

    # -- observability --------------------------------------------------------

    def stats(self) -> dict:
        """Config + live-window stats for the session-plane stats block
        (`GET /api/v1/metrics` sessions.batching)."""
        with self._lock:
            return {
                "armed": True,
                "windowMs": round(self.window_s * 1000.0, 3),
                "maxWaitMs": round(self.wait_s * 1000.0, 3),
                "maxSessions": self.max_sessions,
                "openWindows": len(self._open),
                "warmPrograms": len(self._programs),
                "draining": self._draining,
            }

    # -- drain (docs/resilience.md) -------------------------------------------

    def begin_drain(self) -> None:
        """Flush every partially-filled window NOW and shed new
        enrollments to solo dispatch: a draining server must not sit
        out a collection window before snapshotting. Idempotent."""
        with self._lock:
            self._draining = True
            windows = list(self._open.values())
            self._open.clear()
        for win in windows:
            win.full.set()  # wakes the leader; it closes + executes/solos

    # -- the collection point -------------------------------------------------

    def submit(self, key, engine, queue, *, metrics, session_id=None,
               kind="seq"):
        """Enroll one pass under batch `key` (the broker engine key:
        kind, compile signature, queue bucket, device epoch). Blocks
        until the window executes, then returns
        ``(final_state_slice, trace_slice)`` for THIS pass — or None,
        meaning the caller must dispatch solo (lone window, draining,
        or a failed batched execution). `engine` is the caller's
        decode-engine instance; its encoding supplies the stacked
        arrays and its program shape defines the window's dispatch.

        ``kind="seq"`` (the default): `queue` is the bucket-padded pod
        queue, the program vmaps `run_fn`, and the trace slice is the
        record trace. ``kind="gang"``: `queue` is the [P] PrioritySort
        order tensor, the program vmaps `fixpoint_fn`
        (``batch.gang.run``), and the second slice is the pass's
        (rounds-to-fixpoint scalar, preempt-phase counts)."""
        me = _Enrollee(engine, queue, session_id, metrics)
        with self._lock:
            if self._draining:
                return None
            win = self._open.get(key)
            if win is not None and (
                win.closed or len(win.items) >= self.max_sessions
            ):
                win = None  # missed it: open the successor window
            if win is None:
                win = _Window(key, kind)
                win.items.append(me)
                self._open[key] = win
                leader = True
                if len(win.items) >= self.max_sessions:
                    # max_sessions=1: the window is born full — close it
                    # immediately rather than taxing the pass one window
                    win.full.set()
            else:
                win.items.append(me)
                leader = False
                if len(win.items) >= self.max_sessions:
                    win.full.set()
        t0 = time.perf_counter()
        if leader:
            # the leader IS the window timer: it always wakes after one
            # window even if no second pass ever arrives — the no-
            # deadlock contract for semaphore waiters queued behind it
            win.full.wait(self.wait_s)
            with self._lock:
                win.closed = True
                if self._open.get(key) is win:
                    del self._open[key]
                items = list(win.items)
            if len(items) == 1:
                # lone tenant: dispatch solo, don't pay a vmapped
                # program for a batch of one (the fairness contract)
                telemetry.complete(
                    "batch.enroll", t0, time.perf_counter(), fill=1,
                    leader=True, outcome="solo",
                )
                return None
            self._execute(win.kind, key, items)
        else:
            if not me.done.wait(_FOLLOWER_TIMEOUT_S):
                # leader lost (killed thread, a compile beyond even the
                # generous bound): dispatch solo — and mark the slot so
                # a LATE leader can't also count this pass as batched
                # (it would be double-counted: batched AND solo)
                with self._lock:
                    if not me.done.is_set():
                        me.abandoned = True
        batched = (
            not me.abandoned and me.error is None and me.state is not None
        )
        telemetry.complete(
            "batch.enroll", t0, time.perf_counter(),
            fill=len(win.items), leader=leader,
            outcome="error" if me.error is not None else (
                "batched" if batched else "solo"
            ),
        )
        if not batched:
            return None
        return me.state, me.trace

    # -- batched execution ----------------------------------------------------

    def _program(self, kind, key, bucket: int, engine):
        """The vmapped program for (key, batch bucket), built once and
        kept warm (FIFO-bounded). For ``seq`` windows it is built from
        a fresh signature-equal template engine (masked preemption —
        the vmappable form of the solo cond path); for ``gang`` windows
        it vmaps the enrollee engine's own fused `fixpoint_fn` — the
        identical program text solo dispatch runs, so batched slices
        cannot diverge from solo placements. Returns (vrun, fresh)."""
        from ..engine.engine import BatchedScheduler

        with self._lock:
            entry = self._programs.get((key, bucket))
            if entry is not None:
                return entry["vrun"], False
        # build OUTSIDE the plane lock: kernel construction allocates
        # device constants and other windows' enrollment must not wait
        # on it. A concurrent duplicate build of the same (key, bucket)
        # is tolerated — last one wins, XLA's caches dedupe the compile.
        import jax

        if kind == "gang":
            run_fn = engine.fixpoint_fn
            aud = engine.audit_spec()
            label = BATCH_GANG_LABEL
        else:
            template = BatchedScheduler(
                engine.enc, record=True, strict=True, preempt_mode="masked"
            )
            run_fn = template.run_fn
            aud = template.audit_spec()
            label = BATCH_SEQ_LABEL
        # the batch axis joins the audit's static dims (it is pow2 by
        # construction; KSS713 would otherwise read fills 3/5/6/7 as
        # off-bucket) — the sweep's variant-axis waiver, scoped tighter
        aud["extra_dims"] = tuple(aud.get("extra_dims", ())) + (bucket,)
        vrun = broker_mod.jit(
            jax.vmap(run_fn, in_axes=(0, 0, 0, 0)),
            audit={**aud, "label": label},
        )
        # only `vrun` is cached, not the template engine: the program
        # closure retains what it retains (the build encoding, via
        # run_fn's kernel closures — exactly what a warm solo engine in
        # the broker's LRU pins), but the template's host-side decode
        # tables and trace state need not ride along. Bounded by
        # _PROGRAM_CAP, FIFO-evicted, same spirit as the broker's warm
        # map.
        with self._lock:
            self._programs[(key, bucket)] = {"vrun": vrun}
            while len(self._programs) > _PROGRAM_CAP:
                self._programs.pop(next(iter(self._programs)))
        return vrun, True

    def _execute(self, kind, key, items: "list[_Enrollee]") -> None:
        """Run one closed window as ONE device dispatch and scatter the
        slices back. Never raises: any failure marks every enrollee
        solo (their own dispatch ladders take over)."""
        try:
            self._execute_inner(kind, key, items)
        except Exception as e:  # noqa: BLE001 — contained: everyone solos
            for it in items:
                it.error = e
            telemetry.instant(
                "batch.error", fill=len(items), error=type(e).__name__
            )
        finally:
            for it in items:
                it.done.set()

    def _execute_inner(self, kind, key, items: "list[_Enrollee]") -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        B = len(items)
        # pad the batch axis to its geometric bucket by replaying slot 0
        # (results discarded — the sweep's valid=False analogue), so
        # fills 3 and 5..8 reuse the 4- and 8-wide compilations
        bucket = shape_bucket(B, lo=2)
        padded = items + [items[0]] * (bucket - B)
        vrun, fresh = self._program(kind, key, bucket, items[0].engine)
        t0 = time.perf_counter()
        arrays_b = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[it.engine.enc.arrays for it in padded],
        )
        state_b = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[it.engine.enc.state0 for it in padded],
        )
        # seq: the bucket-padded pod queue; gang: the [P] order tensor
        queue_b = jnp.asarray(np.stack([it.queue for it in padded]))
        weights_b = jnp.stack([it.engine.weights for it in padded])
        state_out, trace_out = vrun(arrays_b, state_b, queue_b, weights_b)
        dt = time.perf_counter() - t0
        for i, it in enumerate(items):
            it.state = jax.tree.map(lambda x, i=i: x[i], state_out)
            # gang's second output is (rounds, preempt counts, matching
            # iterations);
            # tree.map slices every shape identically
            it.trace = jax.tree.map(lambda x, i=i: x[i], trace_out)
        # -- accounting -----------------------------------------------------
        # enrollees whose done-wait already expired are dispatching solo
        # and must not ALSO be counted/attributed as batched (the
        # double-count a lost leader would otherwise cause)
        with self._lock:
            served = [it for it in items if not it.abandoned]
        if fresh:
            # first call of a fresh program IS its compile (jit is
            # lazy) — book it ONCE, on the leader, as an engine build;
            # followers book nothing (a compile wall must never inflate
            # executeSeconds — the same split the solo path keeps)
            leader_metrics = items[0].metrics
            if leader_metrics is not None:
                leader_metrics.record_engine_build(dt)
        for it in served:
            if it.metrics is not None:
                it.metrics.record_batching(batched_passes=1)
                if kind == "gang":
                    it.metrics.record_gang(batched_passes=1)
                if not fresh:
                    it.metrics.record_phase_seconds(execute=dt)
        if self.metrics is not None:
            self.metrics.record_batching(windows=1, occupancy=B)
        telemetry.counter("fleet.batchOccupancy", float(B))
        # span links: the one device dispatch names every enrolled
        # tenant's request trace — the N-tenants-one-dispatch
        # attribution, navigable from either end in the merged export
        links = sorted({it.trace_id for it in items if it.trace_id})
        extra = {"links": links} if links else {}
        telemetry.complete(
            "batch.execute", t0, time.perf_counter(),
            tid=telemetry.DEVICE_TID, fill=B, bucket=bucket, **extra,
        )
        # per-tenant ledger attribution: the window's ONE device
        # dispatch was recorded (by the AuditedJit/Bundled wrapper)
        # under the LEADER's session context; fan the attribution out
        # to every other enrolled tenant so /debug/programs answers
        # per-session truthfully (calls = dispatches, session counts =
        # passes served)
        if ledger_mod.ledger_enabled():
            label = BATCH_GANG_LABEL if kind == "gang" else BATCH_SEQ_LABEL
            others = [it.session_id for it in served[1:]]
            if others:
                ledger_mod.LEDGER.attribute_sessions(label, others)
