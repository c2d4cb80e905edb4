"""CompileBroker — the one owner of engine compilation on the serving path.

BENCH_r05 put the steady-state cost where the kernels no longer are: a
full-default-set compile is ~30 s against a ~0.03 s warm pass, and every
shape-bucket crossing in a churn run re-paid that compile *synchronously
on the request thread*. The broker turns compilation into a managed,
predictable resource with three jobs:

  1. **Dedupe** — concurrent requests for the same (program, bucket) key
     resolve to ONE build: the first caller compiles, everyone else
     blocks on the in-flight build and shares the result (unit-tested:
     two threads, one compile).
  2. **Persistent-cache routing** — every engine jit in the repo goes
     through `broker.jit`, which arms the repo-local persistent XLA
     compile cache (utils/compilecache.py) before the first lowering, so
     repeat compiles of identical programs are disk hits across
     processes and sessions.
  3. **Prediction** — `speculate()` runs compile work on a background
     worker thread. The serving layer arms it when live object counts
     drift past a watermark of the current shape bucket
     (`adjacent_bucket_targets`, default 80%), so a bucket crossing
     finds a warm executable in the broker instead of stalling the
     request thread for the full XLA compile.

Accounting (surfaced through `SchedulingMetrics.record_compile` into the
`/api/v1/metrics` phases block and the bench headline):

  * ``compileHits``           — requests served from the warm-engine map
                                (including waits on an in-flight build:
                                the caller did not compile);
  * ``compileMisses``         — request-thread builds (the synchronous
                                compile the tentpole eliminates from the
                                steady state);
  * ``speculativeCompiles``   — background builds completed;
  * ``stallSeconds``          — request-thread seconds blocked on any
                                compile (own miss builds + in-flight
                                waits).

``KSS_NO_SPECULATIVE_COMPILE=1`` disables the background worker for
deterministic profiling (docs/performance.md); dedupe and the warm-engine
map stay on.

Run supervision (the robustness PR, docs/resilience.md): the serving
layer reaches the broker through `get_resilient`, which adds the compile
WATCHDOG + DEGRADATION LADDER on top of `get`'s dedupe:

  * each build attempt runs under a deadline (``KSS_COMPILE_DEADLINE_S``;
    0/unset = no watchdog) — a wedged XLA compile can't be interrupted
    from Python, so the watchdog abandons the compile thread and treats
    the attempt as failed (the detached thread's late result is
    discarded);
  * failed/timed-out attempts retry with exponential backoff
    (``KSS_COMPILE_RETRIES`` more attempts, base ``KSS_COMPILE_BACKOFF_S``),
    each retry counted as ``compileRetries``;
  * a key whose ladder is exhausted enters a COOLDOWN
    (``KSS_COMPILE_COOLDOWN_PASSES`` calls served degraded without
    re-paying the deadline+retry storm), and `CompileUnavailable` tells
    the caller to run the pass eagerly (`eager_execution` makes
    `broker.jit` a pass-through, so the same engine pass executes
    un-jitted — slow, but it completes);
  * the fault plane (utils/faultinject.py) wires into the build attempt
    (``compile_slow`` / ``compile_fail``) and the speculative worker
    (``worker_crash``) so every rung is testable on CPU.

A crashed speculative worker no longer dies silently: the crash is
logged once, counted (``brokerWorkerCrashes``), and speculation
self-disables for the broker.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
import time
import weakref
from contextlib import contextmanager, nullcontext as _null_context

from . import bundles as bundles_mod
from . import envcheck, faultinject, locking, telemetry
from . import ledger as ledger_mod
from .compilecache import enable_compile_cache

_log = logging.getLogger("kube_scheduler_simulator_tpu.broker")

_jit_cache_armed = False

# thread-local eager-execution switch: inside `eager_execution()`, `jit`
# returns the raw function — the degradation ladder's last rung builds
# engines whose every "compiled" program is plain eager JAX
_eager = threading.local()


@contextmanager
def eager_execution():
    """Make `broker.jit` a pass-through on THIS thread for the block:
    engines constructed inside run un-jitted (no XLA compile to fail or
    wedge). Thread-local, so a degraded request never leaks eagerness
    into concurrent passes or the speculation worker."""
    prev = getattr(_eager, "on", False)
    _eager.on = True
    try:
        yield
    finally:
        _eager.on = prev


def eager_active() -> bool:
    return getattr(_eager, "on", False)


def jaxpr_audit_enabled() -> bool:
    """The KSS7xx runtime-audit switch (analysis/jaxpr_audit.py), read
    at JIT-WRAP time — engine construction — like the lock witness's
    creation-time contract."""
    return envcheck.env_truthy(os.environ.get("KSS_JAXPR_AUDIT"))


def jit(fn, audit=None, **kw):
    """`jax.jit` with the persistent compile cache armed first — the
    single jit entry point for the engines (engine/engine.py,
    engine/gang.py, parallel/sweep.py, engine/extender_loop.py), so every
    program they lower is eligible for cross-process disk-cache hits.

    Inside `eager_execution()` this returns `fn` itself (jit kwargs like
    donate_argnums are compile-time hints with no eager meaning): the
    degradation ladder's eager rung.

    `audit` (a dict — keys documented atop analysis/jaxpr_audit.py:
    label/enc/extra_dims/exempt/allow_f64) names and scopes the site
    for the KSS7xx jaxpr auditor; under ``KSS_JAXPR_AUDIT=1`` the
    returned callable audits each new argument signature's ClosedJaxpr
    before executing (docs/static-analysis.md)."""
    global _jit_cache_armed
    if eager_active():
        return fn
    import jax

    if not _jit_cache_armed:
        # respect an entry point that already armed the cache (conftest,
        # bench) — re-arming would reset its min-compile-time threshold
        if not jax.config.jax_compilation_cache_dir:
            enable_compile_cache()
        _jit_cache_armed = True
    jitted = jax.jit(fn, **kw)
    audit_on = jaxpr_audit_enabled()
    ledger_on = ledger_mod.ledger_enabled()
    bundles_on = bundles_mod.bundles_enabled()
    if bundles_on:
        # the AOT bundle store (utils/bundles.py): the first call of
        # each signature deserializes a persisted executable — or
        # AOT-compiles and persists one — instead of letting jit
        # re-lower. When the ledger is also armed, the bundle wrapper
        # IS its dispatch path (it already splits resolve cost into
        # deserialize vs lowering/backend), so the AuditedJit below
        # runs audit-only — two AOT dispatch caches would double-pay
        # every first call.
        jitted = bundles_mod.BundledJit(
            jitted, kw, audit,
            ledger=ledger_mod.LEDGER if ledger_on else None,
        )
    if audit_on or (ledger_on and not bundles_on):
        from ..analysis.jaxpr_audit import AuditedJit

        # ONE wrapper serves both program observers: the KSS7xx audit
        # and the performance ledger (utils/ledger.py) share the
        # first-signature hook and the per-site audit labels
        return AuditedJit(
            jitted,
            kw,
            audit,
            audit_enabled=audit_on,
            ledger=(
                ledger_mod.LEDGER if (ledger_on and not bundles_on) else None
            ),
        )
    return jitted


class CompileDeadlineExceeded(RuntimeError):
    """One build attempt overran KSS_COMPILE_DEADLINE_S (the compile
    thread is abandoned; its late result, if any, is discarded).
    `thread` is the abandoned builder, so the broker can refuse to
    re-probe a key while a previous probe is still stuck in XLA."""

    def __init__(self, msg: str, thread: "threading.Thread | None" = None):
        super().__init__(msg)
        self.thread = thread


class CompileUnavailable(RuntimeError):
    """The compile ladder is exhausted (retries spent or cooldown
    active): the caller must serve the pass another way — the serving
    layer's eager fallback (server/service.py)."""


def _coerce_env_number(raw: str, default, convert, minimum):
    """The shared lenient-knob coercion: malformed or out-of-range
    values fall back to the default — a typo must never disarm a
    ladder. Env READS stay module-local (`_env_number` here, its twin
    in utils/devices.py) so the KSS1xx env-registry analyzer can tie
    each KSS_* name to its reader; only the coercion is shared."""
    try:
        v = convert(raw) if raw else default
    except ValueError:
        return default
    return v if v >= minimum else default


def _env_number(name: str, default, convert, minimum):
    """A ladder knob from the environment (lenient, see
    `_coerce_env_number`)."""
    return _coerce_env_number(os.environ.get(name, ""), default, convert, minimum)


def compile_deadline_s() -> float:
    """Per-attempt compile deadline from KSS_COMPILE_DEADLINE_S; 0 (the
    default) disables the watchdog — no extra thread per compile."""
    return _env_number("KSS_COMPILE_DEADLINE_S", 0.0, float, 0.0)


def compile_retry_limit() -> int:
    """Extra build attempts after the first failure
    (KSS_COMPILE_RETRIES, default 2)."""
    return _env_number("KSS_COMPILE_RETRIES", 2, int, 0)


def compile_backoff_s() -> float:
    """Base of the exponential retry backoff (KSS_COMPILE_BACKOFF_S,
    default 0.05): retry i sleeps base * 2**(i-1)."""
    return _env_number("KSS_COMPILE_BACKOFF_S", 0.05, float, 0.0)


def compile_cooldown_passes() -> int:
    """How many `get_resilient` calls a ladder-exhausted key serves
    degraded before re-probing compilation (KSS_COMPILE_COOLDOWN_PASSES,
    default 3)."""
    return _env_number("KSS_COMPILE_COOLDOWN_PASSES", 3, int, 1)


def cooldown_ttl_s() -> float:
    """Wall-clock bound on how long a cooldown entry may linger UNTOUCHED
    before it expires (KSS_COMPILE_COOLDOWN_TTL_S, default 300, 0 = never).
    Cooldowns drain per `get_resilient` call of their own scope, so a
    tenant that simply stops sending traffic (idle, evicted, abandoned)
    would otherwise pin `health()` — and with it `/api/v1/readyz` — in
    the degraded state forever. An expired entry is pruned; the scope's
    next pass re-probes compilation exactly as a spent cooldown would."""
    return _env_number("KSS_COMPILE_COOLDOWN_TTL_S", 300.0, float, 0.0)


def _call_with_deadline(build, deadline_s: float, make_exc=None,
                        thread_name: str = "kss-compile-attempt"):
    """Run `build()` with a watchdog: on timeout the builder thread is
    abandoned (a wedged XLA compile cannot be interrupted from Python)
    and the timeout exception raises on the caller. The abandoned
    thread's result — engine or exception — is discarded. `make_exc`
    maps the abandoned thread to the exception to raise (default:
    `CompileDeadlineExceeded` carrying the thread); the execution
    ladder's dispatch watchdog (utils/devices.run_with_deadline) reuses
    this machinery with its own exception type."""
    if deadline_s <= 0:
        return build()
    box: dict = {}
    done = threading.Event()

    def runner():
        try:
            box["engine"] = build()
        except BaseException as e:  # noqa: BLE001 — relayed to the caller
            box["error"] = e
        done.set()

    th = threading.Thread(target=runner, name=thread_name, daemon=True)
    th.start()
    if not done.wait(deadline_s):
        if make_exc is not None:
            raise make_exc(th)
        raise CompileDeadlineExceeded(
            f"compile exceeded KSS_COMPILE_DEADLINE_S={deadline_s}s",
            thread=th,
        )
    if "error" in box:
        raise box["error"]
    return box["engine"]


def speculation_enabled_default() -> bool:
    """Speculative background compilation default: on, unless the
    profiling kill switch KSS_NO_SPECULATIVE_COMPILE is set (any truthy
    spelling `envcheck` validates — the two must agree, or a 'validated'
    kill switch silently does nothing)."""
    return not envcheck.env_truthy(
        os.environ.get("KSS_NO_SPECULATIVE_COMPILE")
    )


# the occupancy watermark of a shape bucket: past it the next bucket up
# is compiled ahead; a count that fits the next bucket down within it is
# re-encoded there (engine/delta.py `DeltaEncoder`)
BUCKET_WATERMARK = 0.8


def adjacent_bucket_targets(
    live: int, bucket: int, *, lo: int = 8, up_frac: float = BUCKET_WATERMARK
) -> list[int]:
    """The shape buckets worth pre-compiling for, given `live` objects in
    the current `bucket`: the next power-of-two UP once occupancy passes
    the watermark (default 80% — arrivals will cross soon), and the next
    bucket DOWN once the live count would fit it with the same headroom
    (shrink passes re-encode at the smaller bucket). Empty when the count
    sits comfortably inside its bucket — the steady state arms nothing."""
    if bucket <= 0 or live < 0:
        return []
    out: list[int] = []
    if live >= up_frac * bucket:
        out.append(bucket * 2)
    half = bucket // 2
    if half >= lo and live <= up_frac * half:
        out.append(half)
    return out


class _Inflight:
    """One in-progress build: waiters block on `ev`. When it fires,
    `engine` is the built engine — or None, meaning the builder failed
    and the waiter should retry the build itself (`get`'s loop)."""

    __slots__ = ("ev", "engine")

    def __init__(self):
        self.ev = threading.Event()
        self.engine = None


@locking.guard_inferred
class CompileBroker:
    """Warm-engine map + in-flight dedupe + background speculation.

    Keys are opaque tuples (the serving layer uses
    ``(kind, compile_signature, ...)``); values are compiled engine
    instances the caller `retarget`s onto fresh encodings. STRICTLY one
    broker per `SchedulerService`: engines are stateful (`retarget`
    mutates them), and only the owning service's pass lock serializes
    their use — sharing a broker across services would let one service's
    retarget corrupt another's in-flight pass.
    """

    def __init__(
        self,
        metrics=None,
        capacity: int = 8,
        speculative: "bool | None" = None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.metrics = metrics
        self.capacity = capacity
        self.speculative = (
            speculation_enabled_default() if speculative is None else bool(speculative)
        )
        self._lock = locking.make_lock("broker.lock")
        self._idle = threading.Condition(self._lock)
        self._engines: "dict[tuple, object]" = {}  # LRU via dict order
        self._inflight: "dict[tuple, _Inflight]" = {}
        self._tokens: set = set()  # speculation dedupe (queued/running)
        self._tasks: list = []
        self._worker: "threading.Thread | None" = None
        self._busy = 0  # speculation tasks queued or running
        # degradation ladder: keys whose compile ladder is exhausted →
        # remaining get_resilient calls served degraded without retrying.
        # Keyed (scope, key): with a SHARED broker (the session plane),
        # each session's ladder exhaustion cools down that session only
        # — the bulkhead that keeps one tenant's storm from degrading
        # its neighbors' identical-shape compiles (docs/sessions.md).
        # Values are (remaining passes, last-touch monotonic stamp): the
        # stamp lets health() expire entries whose scope stopped issuing
        # passes (cooldown_ttl_s) instead of reporting not-ready forever
        self._cooldown: "dict[tuple, tuple[int, float]]" = {}
        # watchdog-abandoned builder threads per (scope, key): while any
        # is still alive (a truly wedged XLA compile), re-probing would
        # leak ANOTHER stuck thread every cooldown cycle — the probe is
        # refused instead, bounding the leak at one batch per key
        self._abandoned: "dict[tuple, list[threading.Thread]]" = {}
        # per-key engine leases: warm engines are STATEFUL (retarget
        # mutates them), so callers sharing this broker across services
        # hold the key's lease for the whole dispatch→finish window of a
        # pass (server/service.py). Bounded by shape diversity, like the
        # warm map's keyspace.
        self._leases: "dict[tuple, threading.RLock]" = {}
        # speculative crashes drawn from a SESSION-scoped fault plane,
        # per scope: contained (worker survives, health stays ready) but
        # visible — one tenant's chaos must not read as replica sickness
        self._scoped_crashes: "dict[object, int]" = {}
        self._crash_logged = False
        # local counters (mirrored into self.metrics when present)
        self.compile_hits = 0
        self.compile_misses = 0
        self.speculative_compiles = 0
        self.stall_seconds = 0.0
        self.compile_retries = 0
        self.worker_crashes = 0
        # speculations skipped by the HBM headroom gate
        # (KSS_SPEC_MEM_HEADROOM_BYTES, utils/fleetstats.py)
        self.spec_mem_skips = 0
        _live_brokers.add(self)

    # -- accounting ---------------------------------------------------------

    def _note(
        self, hits=0, misses=0, speculative=0, stall_s=0.0,
        retries=0, worker_crashes=0, metrics=None,
    ) -> None:
        """Count into the broker-local aggregates, mirroring into
        `metrics` when given (per-session attribution on a shared
        broker) or `self.metrics` otherwise."""
        with self._lock:
            self.compile_hits += hits
            self.compile_misses += misses
            self.speculative_compiles += speculative
            self.stall_seconds += stall_s
            self.compile_retries += retries
            self.worker_crashes += worker_crashes
            total_stall = self.stall_seconds
        if misses or speculative:
            # cold-start accounting (utils/ledger.py): the process's
            # first engine compile just completed on this broker
            ledger_mod.COLD_START.mark("firstCompile")
        if stall_s:
            # Perfetto counter track: cumulative request-thread stall
            # alongside the compile spans (no-op when tracing is off)
            telemetry.counter("stallSeconds", total_stall)
        sink = metrics if metrics is not None else self.metrics
        if sink is not None:
            if hits or misses or speculative or stall_s:
                sink.record_compile(
                    hits=hits, misses=misses, speculative=speculative,
                    stall_s=stall_s,
                )
            if retries or worker_crashes:
                sink.record_resilience(
                    retries=retries, worker_crashes=worker_crashes
                )

    def stats(self) -> dict:
        with self._lock:
            return {
                "compileHits": self.compile_hits,
                "compileMisses": self.compile_misses,
                "speculativeCompiles": self.speculative_compiles,
                "stallSeconds": round(self.stall_seconds, 6),
                "compileRetries": self.compile_retries,
                "brokerWorkerCrashes": self.worker_crashes,
                "scopedWorkerCrashes": sum(self._scoped_crashes.values()),
                "speculationMemSkips": self.spec_mem_skips,
            }

    @staticmethod
    def _cooldown_expired(entry: "tuple[int, float]") -> bool:
        ttl = cooldown_ttl_s()
        return ttl > 0 and (time.monotonic() - entry[1]) > ttl

    def _prune_cooldowns_locked(self) -> None:
        """Under self._lock: drop cooldown entries untouched past the
        TTL — their scope stopped issuing passes, so nothing else would
        ever drain them (the next pass of that scope, if one ever comes,
        re-probes compilation like a spent cooldown)."""
        for ck in [
            k for k, e in self._cooldown.items() if self._cooldown_expired(e)
        ]:
            del self._cooldown[ck]

    def health(self) -> dict:
        """The readiness view (`GET /api/v1/readyz`): a broker with any
        key in an active compile cooldown, or whose speculative worker
        has crashed (speculation self-disabled), is DEGRADED — an
        external load balancer should drain the replica rather than
        route fresh tenants at a sick compile plane. Stale cooldowns
        (scope went quiet, `cooldown_ttl_s`) are pruned first: an idle or
        evicted tenant's exhausted ladder must not drain the replica
        forever."""
        with self._lock:
            self._prune_cooldowns_locked()
            cooling = len(self._cooldown)
            stuck = sum(
                1
                for threads in self._abandoned.values()
                if any(t.is_alive() for t in threads)
            )
            return {
                "cooldownKeys": cooling,
                "stuckCompiles": stuck,
                "workerCrashed": self.worker_crashes > 0,
                "speculative": self.speculative,
                "warmEngines": len(self._engines),
            }

    def drop_scope(self, scope) -> None:
        """Purge a deleted session's namespaced ladder state so a dead
        tenant cannot keep health() degraded forever: its cooldown
        entries, its per-scope crash tally, and its dead
        abandoned-builder bookkeeping. A STILL-ALIVE wedged builder
        thread stays visible — that compile is burning a real CPU
        whatever happened to the tenant that started it (health()
        self-clears when the thread finally dies)."""
        with self._lock:
            for ck in [k for k in self._cooldown if k[0] == scope]:
                del self._cooldown[ck]
            self._scoped_crashes.pop(scope, None)
            for ck in list(self._abandoned):
                if ck[0] != scope:
                    continue
                alive = [t for t in self._abandoned[ck] if t.is_alive()]
                if alive:
                    self._abandoned[ck] = alive
                else:
                    del self._abandoned[ck]

    # -- AOT bundle scope ----------------------------------------------------

    def _scoped_build(self, key: tuple, build, metrics=None):
        """Wrap `build` so the engine key and the building service's
        metrics registry ride the AOT-bundle thread-local while it runs
        (utils/bundles.py): every program jit-WRAPPED inside the build
        keys its bundle on the broker key — (kind, compile signature,
        window) + the device-epoch suffix — and every bundle event
        attributes to the right tenant. The wrap is a closure (not a
        with-block here) so the scope follows the build onto whatever
        thread actually runs it (the watchdog's builder thread, the
        speculation worker)."""
        sink = metrics if metrics is not None else self.metrics

        def scoped():
            with bundles_mod.build_scope(key, sink):
                return build()

        return scoped

    # -- warm-engine map ----------------------------------------------------

    def _store_locked(self, key: tuple, engine) -> None:
        self._engines.pop(key, None)
        self._engines[key] = engine
        while len(self._engines) > self.capacity:
            old = next(iter(self._engines))
            self._engines.pop(old)
            # retire the evicted key's lease with it (unless a pass is
            # mid-flight holding it — then the entry stays until the key
            # is rebuilt and evicted again), keeping _leases bounded by
            # the warm map's keyspace instead of lifetime shape diversity
            lk = self._leases.get(old)
            if lk is not None and lk.acquire(blocking=False):
                lk.release()
                del self._leases[old]

    def peek(self, key: tuple):
        """The cached engine for `key` (no build, no counters), or None."""
        with self._lock:
            return self._engines.get(key)

    def lease(self, key: tuple) -> "threading.RLock":
        """The per-key engine lease. Warm engines are stateful (`retarget`
        mutates them in place), so when several services share one broker
        (the session plane), each holds the key's lease across its pass's
        dispatch→finish window — two bucket-compatible tenants share the
        executable, never a concurrent mutation of it. Re-entrant, so a
        single-service broker's uncontended pass costs one lock probe."""
        with self._lock:
            lk = self._leases.get(key)
            if lk is None:
                lk = self._leases[key] = locking.make_rlock("broker.lease")
            return lk

    def get(self, key: tuple, build, info: "dict | None" = None, metrics=None):
        """The engine for `key`: warm from the map (hit), shared from an
        in-flight build (hit + stall), or built by THIS caller via
        `build()` (miss + stall). `build` must return the engine fully
        compiled — its wall time IS the stall being accounted.

        `info`, when given, is filled with ``{"source": "hit" | "wait" |
        "miss", "wait_s": seconds}`` — `wait_s` is the time THIS caller
        spent blocked on someone else's in-flight compile, which callers
        must exclude from their own execute-phase accounting (it is
        already booked as stallSeconds)."""
        build = self._scoped_build(key, build, metrics)
        while True:
            with self._lock:
                eng = self._engines.get(key)
                if eng is not None:
                    self._engines[key] = self._engines.pop(key)  # recency
                    mine = None
                else:
                    fl = self._inflight.get(key)
                    if fl is None:
                        fl = _Inflight()
                        self._inflight[key] = fl
                        mine = True
                    else:
                        mine = False
            if mine is None:
                if info is not None:
                    info.update(source="hit", wait_s=0.0)
                self._note(hits=1, metrics=metrics)
                return eng
            if mine:
                t0 = time.perf_counter()
                try:
                    with telemetry.span("compile.build", key=str(key)):
                        eng = build()
                except BaseException:
                    with self._lock:
                        self._inflight.pop(key, None)
                    fl.ev.set()  # engine stays None: waiters retry
                    raise
                with self._lock:
                    self._store_locked(key, eng)
                    self._inflight.pop(key, None)
                fl.engine = eng
                fl.ev.set()
                if info is not None:
                    info.update(source="miss", wait_s=0.0)
                self._note(
                    misses=1, stall_s=time.perf_counter() - t0, metrics=metrics
                )
                return eng
            # someone else (request thread or speculation worker) is
            # compiling this key: wait and share — no second compile
            t0 = time.perf_counter()
            with telemetry.span("compile.wait", key=str(key)):
                fl.ev.wait()
            if fl.engine is not None:
                wait_s = time.perf_counter() - t0
                if info is not None:
                    info.update(source="wait", wait_s=wait_s)
                self._note(hits=1, stall_s=wait_s, metrics=metrics)
                return fl.engine
            # the builder failed; loop — this caller may build it now

    # -- run supervision (watchdog + degradation ladder) --------------------

    def _attempt_build(self, build):
        """One supervised build attempt: the fault plane's compile sites
        fire inside the watchdog window (an injected compile_slow must
        be able to trip the deadline, exactly like a wedged XLA compile)."""

        def attempt():
            plane = faultinject.active()
            if plane is not None:
                plane.delay("compile_slow")
                plane.maybe_raise("compile_fail")
            return build()

        return _call_with_deadline(attempt, compile_deadline_s())

    def get_resilient(
        self,
        key: tuple,
        build,
        info: "dict | None" = None,
        *,
        metrics=None,
        scope=None,
    ):
        """`get` under run supervision — the serving path's entry point
        (docs/resilience.md). Semantics on top of `get`:

          * each request-thread build attempt runs under the
            KSS_COMPILE_DEADLINE_S watchdog and the fault plane's
            compile sites;
          * a failed/timed-out attempt retries with exponential backoff,
            up to KSS_COMPILE_RETRIES extra attempts (each counted as a
            compileRetry);
          * when the ladder is exhausted the key enters a cooldown of
            KSS_COMPILE_COOLDOWN_PASSES calls and `CompileUnavailable`
            raises — the caller serves the pass eagerly instead
            (`eager_execution`). A speculative background build landing
            the key warm ends the cooldown early.

        `metrics` attributes the hit/miss/stall/retry counters to the
        calling service's registry (defaults to the broker's own);
        `scope` namespaces the cooldown + abandoned-builder state — on a
        SHARED broker each session's ladder exhaustion degrades that
        session only (the bulkhead, docs/sessions.md), while the warm
        map and in-flight dedupe stay cross-scope (the shared-executable
        win). Without a deadline, retries, faults, or failures this is
        exactly `get` (same dedupe, same counters)."""
        build = self._scoped_build(key, build, metrics)
        ck = (scope, key)
        while True:
            cooled = False
            with self._lock:
                eng = self._engines.get(key)
                if eng is not None:
                    self._engines[key] = self._engines.pop(key)  # recency
                    self._cooldown.pop(ck, None)  # warm ends the cooldown
                    mine = None
                else:
                    entry = self._cooldown.get(ck)
                    if entry is not None and self._cooldown_expired(entry):
                        # untouched past the TTL: expire — this scope's
                        # return after a quiet spell re-probes compile
                        del self._cooldown[ck]
                        entry = None
                    if entry is not None:
                        remaining = entry[0]
                        if remaining > 1:
                            self._cooldown[ck] = (
                                remaining - 1, time.monotonic()
                            )
                        else:
                            # cooldown spent: the NEXT call re-probes
                            self._cooldown.pop(ck, None)
                        cooled = True
                        mine = False
                    elif self._stuck_locked(ck):
                        # an abandoned builder is STILL inside XLA: a
                        # re-probe would leak another thread — stay
                        # degraded until the stuck compile dies
                        self._cooldown[ck] = (
                            compile_cooldown_passes(), time.monotonic()
                        )
                        cooled = True
                        mine = False
                    else:
                        fl = self._inflight.get(key)
                        if fl is None:
                            fl = _Inflight()
                            self._inflight[key] = fl
                            mine = True
                        else:
                            mine = False
            if mine is None:
                if info is not None:
                    info.update(source="hit", wait_s=0.0)
                self._note(hits=1, metrics=metrics)
                return eng
            if cooled:
                raise CompileUnavailable(
                    f"compile for {key!r} is cooling down after ladder "
                    f"exhaustion; serve degraded"
                )
            if mine:
                return self._build_resilient(
                    key, fl, build, info, metrics=metrics, ck=ck
                )
            # share someone else's in-flight build, like `get`
            t0 = time.perf_counter()
            with telemetry.span("compile.wait", key=str(key)):
                fl.ev.wait()
            if fl.engine is not None:
                wait_s = time.perf_counter() - t0
                if info is not None:
                    info.update(source="wait", wait_s=wait_s)
                self._note(hits=1, stall_s=wait_s, metrics=metrics)
                return fl.engine
            # builder failed: loop — the cooldown it set (or a free
            # slot) decides this caller's fate

    def _stuck_locked(self, ck: tuple) -> bool:
        """Under self._lock: prune dead abandoned builders for the
        (scope, key) pair; True when one is still running (the wedged
        compile persists)."""
        alive = [t for t in self._abandoned.get(ck, ()) if t.is_alive()]
        if alive:
            self._abandoned[ck] = alive
            return True
        self._abandoned.pop(ck, None)
        return False

    def _build_resilient(
        self, key: tuple, fl: _Inflight, build, info, metrics=None, ck=None
    ):
        """The retry ladder for the caller that owns the in-flight slot."""
        if ck is None:
            ck = (None, key)
        t0 = time.perf_counter()
        attempts = 1 + compile_retry_limit()
        backoff = compile_backoff_s()
        eng = None
        err: "Exception | None" = None
        try:
            for i in range(attempts):
                if i:
                    self._note(retries=1, metrics=metrics)
                    telemetry.instant(
                        "compile.retry", key=str(key), attempt=i + 1
                    )
                    if backoff > 0:
                        time.sleep(backoff * (2 ** (i - 1)))
                try:
                    with telemetry.span(
                        "compile.build", key=str(key), attempt=i + 1
                    ):
                        eng = self._attempt_build(build)
                    break
                except Exception as e:  # noqa: BLE001 — each rung retries
                    err = e
                    th = getattr(e, "thread", None)
                    if th is not None:
                        with self._lock:
                            self._abandoned.setdefault(ck, []).append(th)
                        telemetry.instant(
                            "compile.deadline_abandoned", key=str(key)
                        )
        except BaseException:
            # non-Exception escape (KeyboardInterrupt, SystemExit):
            # release the slot exactly like `get`'s miss path
            with self._lock:
                self._inflight.pop(key, None)
            fl.ev.set()
            raise
        if eng is None:
            with self._lock:
                self._inflight.pop(key, None)
                self._cooldown[ck] = (
                    compile_cooldown_passes(), time.monotonic()
                )
            fl.ev.set()  # engine stays None: waiters re-enter the ladder
            self._note(stall_s=time.perf_counter() - t0, metrics=metrics)
            telemetry.instant("compile.ladder_exhausted", key=str(key))
            raise CompileUnavailable(
                f"compile ladder exhausted for {key!r} after {attempts} "
                f"attempts: {type(err).__name__}: {err}"
            ) from err
        with self._lock:
            self._store_locked(key, eng)
            self._inflight.pop(key, None)
        fl.engine = eng
        fl.ev.set()
        if info is not None:
            info.update(source="miss", wait_s=0.0)
        self._note(misses=1, stall_s=time.perf_counter() - t0, metrics=metrics)
        return eng

    # -- speculation --------------------------------------------------------

    def speculate(self, token, task, metrics=None) -> bool:
        """Queue `task` for the background worker. `task()` runs off the
        request thread and returns ``(key, build)`` — or None to skip —
        after which the worker builds and stores the engine (skipping
        keys already warm or in flight). `token` dedupes while the task
        is queued/running. Returns False when speculation is disabled or
        the token is already pending. `metrics` attributes the eventual
        speculativeCompiles count to the ARMING service's registry (on a
        shared broker, the session that armed the build)."""
        if not self.speculative:
            return False
        # the HBM headroom gate (utils/fleetstats.py, docs/
        # observability.md): with KSS_SPEC_MEM_HEADROOM_BYTES set, a
        # device whose free HBM is below the floor SKIPS speculation —
        # a background build's XLA workspace must never be the
        # allocation that OOMs a serving process. Counted + marked so
        # memory-shed speculation is visible, never silent.
        from . import fleetstats

        if not fleetstats.speculation_memory_ok():
            with self._lock:
                self.spec_mem_skips += 1
            telemetry.instant(
                "compile.speculation_skipped", reason="hbm-headroom",
                token=str(token),
            )
            return False
        # the causal pass id + session + distributed-trace id of the
        # ARMING request thread (and its thread-locally scoped fault
        # plane, the session bulkhead) travel with the task: the worker
        # re-enters them all, so a speculative build's telemetry spans
        # name the pass/session/trace that armed it and its faults draw
        # from the arming session's plane
        armed_by = telemetry.current_pass_id()
        armed_session = telemetry.current_session_id()
        armed_trace = telemetry.current_trace_id()
        armed_plane = faultinject.scoped_active()
        with self._lock:
            if token in self._tokens:
                return False
            self._tokens.add(token)
            self._tasks.append(
                (
                    token, task, armed_by, armed_session, armed_trace,
                    armed_plane, metrics,
                )
            )
            self._busy += 1
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._work, name="kss-compile-broker", daemon=True
                )
                self._worker.start()
        return True

    def _work(self) -> None:
        while True:
            with self._lock:
                if not self._tasks:
                    self._worker = None
                    return
                (
                    token, task, armed_by, armed_session, armed_trace,
                    armed_plane, armed_metrics,
                ) = self._tasks.pop(0)
            try:
                scope = (
                    faultinject.scoped(armed_plane)
                    if armed_plane is not None
                    else _null_context()
                )
                with scope, telemetry.pass_context(
                    armed_by
                ), telemetry.session_context(
                    armed_session
                ), telemetry.trace_context(armed_trace), telemetry.span(
                    "compile.speculative", token=str(token)
                ):
                    plane = faultinject.active()
                    if plane is not None:
                        plane.maybe_raise("worker_crash")
                    res = task()
                    if res is not None:
                        key, build = res
                        self._background_build(key, build, metrics=armed_metrics)
            except BaseException as e:  # noqa: BLE001 — speculation never fails a run
                if armed_plane is not None:
                    # the crash came from a SESSION-scoped fault plane
                    # (the arming tenant's private chaos spec): contain
                    # it to that tenant — the shared worker stays up and
                    # the broker stays ready for every other session
                    # (docs/sessions.md bulkheads)
                    self._contain_scoped_crash(e, armed_session)
                else:
                    self._contain_worker_crash(e)
            finally:
                with self._lock:
                    self._tokens.discard(token)
                    self._busy -= 1
                    self._idle.notify_all()

    def _contain_worker_crash(self, exc: BaseException) -> None:
        """A crashed speculative task/worker: logged ONCE per broker,
        counted (brokerWorkerCrashes), and speculation self-disables —
        the worker must degrade visibly, never die silently. Dedupe and
        the warm-engine map stay on; already-queued tasks still drain
        (their tokens must clear) but no new speculation is accepted."""
        if not self._crash_logged:
            self._crash_logged = True
            _log.warning(
                "speculative compile worker crashed (%s: %s); "
                "disabling speculation for this broker",
                type(exc).__name__, exc,
            )
        self.speculative = False
        self._note(worker_crashes=1)

    def _contain_scoped_crash(self, exc: BaseException, scope) -> None:
        """A speculative task crashed under a SESSION's private fault
        plane: counted per scope (visible in stats), logged once per
        scope, but the shared worker keeps running, broker-level
        `worker_crashes` stays 0, and health() stays ready — one
        tenant's chaos spec must not drain the replica or cost its
        neighbors speculation."""
        with self._lock:
            first = scope not in self._scoped_crashes
            self._scoped_crashes[scope] = self._scoped_crashes.get(scope, 0) + 1
        if first:
            _log.warning(
                "speculative build crashed under session %r's fault plane "
                "(%s: %s); contained to that session",
                scope, type(exc).__name__, exc,
            )

    def _background_build(self, key: tuple, build, metrics=None) -> None:
        build = self._scoped_build(key, build, metrics)
        with self._lock:
            if key in self._engines or key in self._inflight:
                return  # already warm / being compiled — nothing to do
            fl = _Inflight()
            self._inflight[key] = fl
        try:
            # the fault plane's compile sites cover background builds
            # too (a failed speculative compile is a NORMAL outcome —
            # contained here, not a worker crash)
            plane = faultinject.active()
            if plane is not None:
                plane.delay("compile_slow")
                plane.maybe_raise("compile_fail")
            eng = build()
        except BaseException:  # noqa: BLE001
            with self._lock:
                self._inflight.pop(key, None)
            fl.ev.set()  # engine stays None: any waiter retries
            return
        with self._lock:
            self._store_locked(key, eng)
            self._inflight.pop(key, None)
        fl.engine = eng
        fl.ev.set()
        self._note(speculative=1, metrics=metrics)

    def quiesce(self, timeout: "float | None" = None) -> bool:
        """The ORDERLY-exit drain (server drain / graceful shutdown,
        docs/resilience.md): stop accepting new speculation, then
        out-wait any background build still inside XLA — the same
        teardown hazard the atexit hook bounds as a last resort
        (`_drain_live_brokers`), handled here on the graceful path so a
        drained process exits 0 instead of racing the C++ compiler
        threads at interpreter teardown. True when fully quiesced."""
        self.speculative = False
        return self.drain(timeout=timeout)

    def drain(self, timeout: "float | None" = None) -> bool:
        """Block until the speculation queue is empty and no task is
        running — then flush any in-flight AOT bundle writes
        (utils/bundles.py): a drained process must not abandon a
        serialized executable mid-save, and flushing AFTER the worker
        settles covers the bundles its last build enqueued. True on
        success, False on timeout. The 'after warm-up' fence the
        perf-smoke crossing gate stands on."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._busy:
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                self._idle.wait(remaining)
        remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
        return bundles_mod.flush(timeout=remaining)


# Every live broker, so interpreter exit can quiesce speculation first:
# a speculative compile still inside XLA when Python tears down dies as
# std::terminate / a segfault from the C++ compiler threads — the
# process "crashes" on a run that SUCCEEDED. Exit must out-wait any
# in-flight background build (bounded: a truly wedged compile must not
# turn exit into a hang — past the timeout we accept the teardown race
# rather than never exiting).
_live_brokers: "weakref.WeakSet" = weakref.WeakSet()
_ATEXIT_DRAIN_TIMEOUT_S = 30.0


@atexit.register
def _drain_live_brokers() -> None:
    for broker in list(_live_brokers):
        broker.speculative = False  # no new work while exiting
        broker.drain(timeout=_ATEXIT_DRAIN_TIMEOUT_S)
