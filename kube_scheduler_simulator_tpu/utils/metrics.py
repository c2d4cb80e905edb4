"""In-framework scheduling metrics + profiler wiring (SURVEY.md §5).

The reference has no metrics beyond echo request logging — its *product*
is the decision trace. Here the BASELINE metric (scheduling decisions per
second per chip) is a first-class counter: every scheduling pass reports
into a process-wide `SchedulingMetrics` registry that the serving layer
exposes (`GET /api/v1/metrics`, an extension route) and benchmarks read
directly.

`profile_trace` wraps `jax.profiler.trace` so a pass can be captured for
TensorBoard/XProf without the caller importing jax.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

from . import locking, telemetry

# The /api/v1/metrics JSON document's schema version: bumped whenever a
# field changes meaning or disappears (additions don't bump it). v2
# introduced the version stamp itself, uptimeSeconds, and the
# histograms block; v3 marks the observatory document shape — the
# `coldStart` (phase accounting, timeToFirstPassSeconds) and `programs`
# (per-program ledger summary) blocks the serving layer attaches; v4
# marks the SLO plane shape (docs/observability.md): the `slo` block
# (per-objective compliance + alert states, utils/slo.py) and the
# histogram `exemplars` entries the OpenMetrics exposition attaches to
# buckets. The `batching` block (continuous batching, PR 14) is a pure
# ADDITION — per this contract it does not bump the version.
METRICS_SCHEMA_VERSION = 4

# Exemplar capture (docs/observability.md): histogram observations
# remember the causal pass id of a recent observation per bucket, so
# `?format=openmetrics` can link a latency bucket straight to its
# Perfetto span. On by default (one dict write per observation); any
# FALSY spelling of KSS_EXEMPLARS disables capture entirely.
_EXEMPLARS_VAR = "KSS_EXEMPLARS"


def exemplars_enabled() -> bool:
    from .envcheck import FALSY

    raw = os.environ.get(_EXEMPLARS_VAR)
    if not raw:
        return True  # unset/empty = the default: capture on
    return raw.strip().lower() not in FALSY


class Histogram:
    """A fixed-bucket histogram in the Prometheus style: per-bucket
    observation counts over strictly increasing upper bounds plus an
    implicit +Inf overflow, a running sum, a total count, and the most
    recent EXEMPLAR per bucket (the observation's causal pass id — the
    OpenMetrics hook that links a bucket to its Perfetto span,
    docs/observability.md). NOT itself thread-safe —
    `SchedulingMetrics` guards every observation and read with its own
    lock."""

    __slots__ = ("bounds", "counts", "sum", "count", "exemplars")

    def __init__(self, bounds: "tuple[float, ...]"):
        bounds = tuple(float(b) for b in bounds)
        if not bounds or any(
            b2 <= b1 for b1, b2 in zip(bounds, bounds[1:])
        ):
            raise ValueError(
                f"histogram bounds must be strictly increasing: {bounds}"
            )
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # [-1] is the +Inf bucket
        self.sum = 0.0
        self.count = 0
        # parallel to counts: the latest exemplar landing in each
        # bucket — {"labels": {...}, "value": v, "timestamp": wall}
        self.exemplars: "list[dict | None]" = [None] * (len(bounds) + 1)

    def observe(self, value: float, exemplar: "dict | None" = None) -> None:
        v = float(value)
        idx = bisect.bisect_left(self.bounds, v)
        self.counts[idx] += 1
        self.sum += v
        self.count += 1
        if exemplar is not None:
            self.exemplars[idx] = {
                "labels": dict(exemplar),
                "value": v,
                "timestamp": round(time.time(), 3),
            }

    def _bucket_keys(self) -> "list[str]":
        return [repr(b) for b in self.bounds] + ["+Inf"]

    def snapshot(self) -> dict:
        """JSON shape (the /api/v1/metrics histograms block): CUMULATIVE
        bucket counts keyed by upper bound, Prometheus-style, plus the
        per-bucket exemplars (NON-cumulative: an exemplar belongs to
        the bucket its observation landed in)."""
        cum = 0
        buckets = {}
        for bound, n in zip(self.bounds, self.counts):
            cum += n
            buckets[repr(bound)] = cum
        buckets["+Inf"] = self.count
        out = {
            "buckets": buckets,
            "sum": round(self.sum, 9),
            "count": self.count,
        }
        exemplars = {
            key: dict(ex)
            for key, ex in zip(self._bucket_keys(), self.exemplars)
            if ex is not None
        }
        if exemplars:
            out["exemplars"] = exemplars
        return out

    def state_dict(self) -> dict:
        return {
            "bounds": list(self.bounds),
            "counts": list(self.counts),
            "sum": self.sum,
            "count": self.count,
            "exemplars": [
                dict(ex) if ex is not None else None for ex in self.exemplars
            ],
        }

    def load_state(self, state: dict) -> None:
        """Restore `state_dict` output. A checkpoint written with
        different bucket bounds cannot be re-bucketed exactly — it is
        ignored (fresh histogram) rather than loaded wrong. Exemplar
        state written before the SLO PR is simply absent and those
        slots restart empty."""
        if tuple(float(b) for b in state.get("bounds", ())) != self.bounds:
            return
        counts = state.get("counts")
        if not isinstance(counts, list) or len(counts) != len(self.counts):
            return
        self.counts = [int(c) for c in counts]
        self.sum = float(state.get("sum", 0.0))
        self.count = int(state.get("count", 0))
        exemplars = state.get("exemplars")
        if isinstance(exemplars, list) and len(exemplars) == len(self.exemplars):
            self.exemplars = [
                dict(ex) if isinstance(ex, dict) else None for ex in exemplars
            ]


# Default bucket bounds. Pass latency and compile stalls are wall-clock
# host seconds (sub-ms warm passes up to ~minute-scale cold compiles);
# time-to-reschedule is SIMULATED seconds (lifecycle disruption scale).
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)
TTS_BUCKETS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 600.0)

# (JSON key in the histograms block, Prometheus metric name, bounds,
# help text) — the ONE place the histogram families are defined, so the
# JSON snapshot, the exposition text, and the checkpoint state can't
# drift apart.
HISTOGRAM_FAMILIES = (
    (
        "passLatencySeconds",
        "kss_pass_latency_seconds",
        LATENCY_BUCKETS,
        "Wall-clock latency of one scheduling pass.",
    ),
    (
        "compileStallSeconds",
        "kss_compile_stall_seconds",
        LATENCY_BUCKETS,
        "Request-thread seconds blocked on one compile (miss builds and "
        "in-flight waits).",
    ),
    (
        "timeToRescheduleSeconds",
        "kss_time_to_reschedule_seconds",
        TTS_BUCKETS,
        "Simulated seconds an evicted pod spent pending before its "
        "re-bind.",
    ),
)


def _new_histograms() -> dict:
    return {
        key: Histogram(bounds) for key, _, bounds, _ in HISTOGRAM_FAMILIES
    }


@dataclass
class PassRecord:
    """One scheduling pass (one engine execution over the queue)."""

    mode: str  # "sequential" | "gang" | "extender"
    pods: int  # queue length scheduled over
    scheduled: int  # pods that received a node
    wall_s: float
    rounds: int = 0  # gang mode only

    @property
    def decisions_per_s(self) -> float:
        return self.pods / self.wall_s if self.wall_s > 0 else 0.0


@dataclass
class SchedulingMetrics:
    """Thread-safe rolling pass statistics (the decisions/sec/chip
    counter from BASELINE.json, kept in-framework)."""

    keep: int = 256
    _lock: threading.Lock = field(
        default_factory=lambda: locking.make_lock("metrics.registry"),
        repr=False,
    )
    _passes: list[PassRecord] = field(default_factory=list, repr=False)
    _pass_count: int = 0  # monotonic; _passes is a bounded window of it
    _total_pods: int = 0
    _total_scheduled: int = 0
    _total_wall_s: float = 0.0
    # disruption counters (lifecycle/ chaos runs): evictions caused by
    # injected faults, how many of those pods found a node again, and
    # the simulated time each spent pending before its re-bind
    _evicted: int = 0
    _rescheduled: int = 0
    _tts_sum_s: float = 0.0  # sum of time-to-reschedule, sim seconds
    _tts_max_s: float = 0.0
    _tts_count: int = 0
    # phase-timing breakdown (perf_opt PR: where a pass's wall-clock
    # goes — encode vs compile vs execute vs decode) plus the encode-path
    # counters that prove the incremental encoder is carrying churn
    # (docs/performance.md). encodeSeconds includes cache probes;
    # compileSeconds is engine-build time (jit compile included).
    _phase_s: dict = field(
        default_factory=lambda: {
            "encode": 0.0, "compile": 0.0, "execute": 0.0, "decode": 0.0
        },
        repr=False,
    )
    _encode_counts: dict = field(
        default_factory=lambda: {"delta": 0, "full": 0, "cached": 0, "empty": 0},
        repr=False,
    )
    # full re-encodes forced by a KSS_DTYPE_POLICY flip landing on a
    # delta encoder retaining the other policy's widths
    _encode_policy_misses: int = 0
    _engine_builds: int = 0
    # compile-broker counters (utils/broker.py): warm-engine hits vs
    # request-thread synchronous compiles, background speculative builds,
    # and request-thread seconds blocked on ANY compile — the stall the
    # predictive warm-up service exists to eliminate
    _compile_hits: int = 0
    _compile_misses: int = 0
    _speculative_compiles: int = 0
    _stall_s: float = 0.0
    # run-supervision / degradation-ladder counters (docs/resilience.md):
    # compile retries after a failed/timed-out build, passes served by
    # the un-jitted eager fallback, passes that ran degraded at all, and
    # speculative-worker crashes contained by the hardened broker loop
    _compile_retries: int = 0
    _eager_fallbacks: int = 0
    _degraded_passes: int = 0
    _worker_crashes: int = 0
    # execution-ladder counters (the runtime device-fault ladder,
    # docs/resilience.md): dispatch attempts re-run after a device
    # fault, passes that escalated to the mid-process CPU failover, and
    # mesh rebuilds over a shrunken surviving-device set
    _dispatch_retries: int = 0
    _device_failovers: int = 0
    _mesh_shrinks: int = 0
    # AOT-bundle counters (utils/bundles.py, KSS_AOT_BUNDLES=1):
    # executables deserialized from / serialized to the on-disk bundle
    # store, bundles present but rejected (version/fingerprint/checksum
    # mismatch — the silent fallback), and the cumulative deserialize
    # wall, kept DISTINCT from stallSeconds' compile wall
    _bundle_loads: int = 0
    _bundle_saves: int = 0
    _bundle_bypasses: int = 0
    _aot_deserialize_s: float = 0.0
    # cross-tenant continuous-batching counters (server/batchplane.py,
    # KSS_BATCH=1): passes served by a batched device dispatch and
    # passes that fell back to solo dispatch land on each SESSION's own
    # registry; windows executed and the cumulative window fill (the
    # occupancy numerator — mean fill = batchOccupancySum/batchWindows,
    # derived in the snapshot's `batching` block) land on the plane's
    # default registry
    _batched_passes: int = 0
    _batch_windows: int = 0
    _batch_occupancy_sum: int = 0
    _solo_fallbacks: int = 0
    # gang-engine counters (engine/gang.py, server/batchplane.py):
    # cumulative commit rounds the device-resident fixpoint used
    # (rounds/pass = gangFixpointRounds / gang passes) and gang passes
    # served by a batched dispatch (batch.gang.run) — both per-session
    _gang_fixpoint_rounds: int = 0
    _batched_gang_passes: int = 0
    # latency-distribution state (the observability PR): Prometheus-style
    # histograms behind the same lock as the counters, rendered into the
    # JSON snapshot's `histograms` block and the exposition text
    _hist: dict = field(default_factory=_new_histograms, repr=False)
    # uptime epoch of this registry (monotonic; NOT checkpointed — a
    # resumed run's uptime is the new process's)
    _born_monotonic: float = field(default_factory=time.monotonic, repr=False)
    # the SLO plane (utils/slo.py): the session id labeling this
    # registry's alerts (set once by the owning SchedulerService), the
    # plane itself (env-derived and cached on the raw KSS_SLO_* strings,
    # or an explicit PUT/test override), and the cache key
    session_id: "str | None" = None
    _slo_plane: "object | None" = field(default=None, repr=False)
    _slo_override: bool = field(default=False, repr=False)
    _slo_env_key: "tuple | None" = field(default=None, repr=False)
    # ratio-objective bookkeeping: a degraded/eager pass's BAD event is
    # emitted by record_resilience (mid-pass), and these counters make
    # the pass's own record() skip the matching GOOD event — one event
    # per pass, so an all-degraded run reads compliance 0.0, not 0.5
    _slo_skip_eager: int = field(default=0, repr=False)
    _slo_skip_degraded: int = field(default=0, repr=False)

    # -- the SLO plane (utils/slo.py) ----------------------------------------

    def slo_plane(self):
        """The registry's SLO plane, or None (the default: plane off).
        An explicit override (`set_slo_plane` — the per-session PUT
        route, checkpoint restore, tests) wins; otherwise the plane is
        built from the KSS_SLO_* environment and rebuilt only when
        those raw strings change (the telemetry `active()` pattern)."""
        from . import slo as slo_mod

        with self._lock:
            if self._slo_override:
                return self._slo_plane
            key = slo_mod.env_key()
            if self._slo_env_key == key:
                return self._slo_plane
            plane = (
                slo_mod.SloPlane(session_id=self.session_id)
                if slo_mod.enabled()
                else None
            )
            self._slo_env_key = key
            self._slo_plane = plane
            return plane

    def set_slo_plane(self, plane) -> None:
        """Install `plane` regardless of the environment (None = plane
        explicitly off) — the per-session PUT /slo override."""
        with self._lock:
            self._slo_plane = plane
            self._slo_override = True

    def clear_slo_override(self) -> None:
        """Drop any explicit plane; the environment rules again."""
        with self._lock:
            self._slo_plane = None
            self._slo_override = False
            self._slo_env_key = None

    def slo_tick(self, sim_t: float) -> None:
        """Advance the plane's clock to simulated time `sim_t` (the
        lifecycle engine's per-batch call): windows slide and alerts
        evaluate on the run's own timeline. No-op with the plane off."""
        plane = self.slo_plane()
        if plane is not None:
            plane.tick_sim(sim_t)

    def record_pending_age(
        self, p90_s: float, max_s: "float | None" = None
    ) -> None:
        """The pending-age observation point (fed by the fleet
        observatory's per-pass age percentiles, utils/fleetstats.py —
        the one place queue age is already measured): one SLO event
        per sampled pass, judged against the pendingAge threshold."""
        plane = self.slo_plane()
        if plane is not None:
            plane.observe("pendingAge", value=float(p90_s))

    def _hist_exemplar(self, pass_id: "int | None" = None) -> "dict | None":
        """The exemplar attached to a histogram observation: the causal
        pass id (`span_id` — the id every one of the pass's Perfetto
        spans carries as args.pass) plus the session label. None when
        capture is disabled (KSS_EXEMPLARS) or no pass is in context."""
        if not exemplars_enabled():
            return None
        pid = pass_id if pass_id is not None else telemetry.current_pass_id()
        if pid is None:
            return None
        ex = {"span_id": str(pid)}
        sid = telemetry.current_session_id() or self.session_id
        if sid is not None:
            ex["session"] = sid
        return ex

    def record(self, rec: PassRecord, pass_id: "int | None" = None) -> None:
        exemplar = self._hist_exemplar(pass_id)
        with self._lock:
            self._passes.append(rec)
            if len(self._passes) > self.keep:
                self._passes = self._passes[-self.keep :]
            self._pass_count += 1
            self._total_pods += rec.pods
            self._total_scheduled += rec.scheduled
            self._total_wall_s += rec.wall_s
            self._hist["passLatencySeconds"].observe(
                rec.wall_s, exemplar=exemplar
            )
            # this pass's ratio events: a degraded/eager pass already
            # emitted its BAD event from record_resilience — consume
            # the skip so the pass contributes exactly one event
            eager_ok = self._slo_skip_eager <= 0
            if not eager_ok:
                self._slo_skip_eager -= 1
            degraded_ok = self._slo_skip_degraded <= 0
            if not degraded_ok:
                self._slo_skip_degraded -= 1
        # cold-start accounting (utils/ledger.py): every pass — any
        # registry, any driver — lands here, so the FIRST one that
        # actually placed a pod closes the process's
        # timeToFirstPassSeconds window (latched; one dict probe per
        # pass afterwards). Empty passes don't count: the headline is
        # time-to-first-SCHEDULED-pod, not time-to-first-no-op.
        if rec.scheduled > 0:
            from .ledger import COLD_START

            COLD_START.mark("firstPass")
        # SLO observation points (utils/slo.py), outside the lock: one
        # passLatency event per pass, plus the GOOD half of the
        # eager-fallback / degraded-pass ratio objectives — skipped for
        # a pass whose bad event record_resilience already emitted
        plane = self.slo_plane()
        if plane is not None:
            plane.observe("passLatency", value=rec.wall_s)
            if eager_ok:
                plane.observe("eagerFallback", good=True)
            if degraded_ok:
                plane.observe("degradedPass", good=True)

    def record_disruption(
        self,
        evicted: int = 0,
        rescheduled: int = 0,
        times_to_reschedule_s: "list[float] | None" = None,
    ) -> None:
        """One fault-injection event's disruption tally: pods evicted by
        the fault, pods re-bound afterwards, and per-pod simulated
        time-to-reschedule for the re-binds that happened this event."""
        times = [float(t) for t in times_to_reschedule_s or ()]
        exemplar = self._hist_exemplar() if times else None
        with self._lock:
            self._evicted += int(evicted)
            self._rescheduled += int(rescheduled)
            for t in times:
                self._tts_sum_s += t
                self._tts_max_s = max(self._tts_max_s, t)
                self._tts_count += 1
                self._hist["timeToRescheduleSeconds"].observe(
                    t, exemplar=exemplar
                )
        if times:
            plane = self.slo_plane()
            if plane is not None:
                for t in times:
                    plane.observe("timeToReschedule", value=t)

    def record_encode(self, mode: str, seconds: float = 0.0) -> None:
        """One encode attempt: `mode` is the path that served it
        (``delta`` / ``full`` / ``cached`` / ``empty``); `seconds` is the
        host time it took (including event replay / cache probes)."""
        with self._lock:
            if mode not in self._encode_counts:
                self._encode_counts[mode] = 0
            self._encode_counts[mode] += 1
            self._phase_s["encode"] += float(seconds)

    def record_encode_policy_miss(self) -> None:
        """One full re-encode whose only trigger was a dtype-policy flip
        (the fallback ladder protecting the width contract)."""
        with self._lock:
            self._encode_policy_misses += 1

    def record_engine_build(self, seconds: float = 0.0) -> None:
        """One compiled-engine construction (the recompile proxy: a
        warm churn pass retargets instead and never lands here)."""
        with self._lock:
            self._engine_builds += 1
            self._phase_s["compile"] += float(seconds)

    def record_compile(
        self,
        *,
        hits: int = 0,
        misses: int = 0,
        speculative: int = 0,
        stall_s: float = 0.0,
    ) -> None:
        """Compile-broker accounting: `hits` served warm (including waits
        on an in-flight build), `misses` compiled synchronously on the
        request thread, `speculative` background builds completed,
        `stall_s` request-thread seconds blocked on compilation."""
        exemplar = self._hist_exemplar() if stall_s > 0 else None
        with self._lock:
            self._compile_hits += int(hits)
            self._compile_misses += int(misses)
            self._speculative_compiles += int(speculative)
            self._stall_s += float(stall_s)
            if stall_s > 0:
                self._hist["compileStallSeconds"].observe(
                    float(stall_s), exemplar=exemplar
                )

    def record_resilience(
        self,
        *,
        retries: int = 0,
        eager_fallbacks: int = 0,
        degraded_passes: int = 0,
        worker_crashes: int = 0,
        dispatch_retries: int = 0,
        device_failovers: int = 0,
        mesh_shrinks: int = 0,
    ) -> None:
        """Degradation-ladder accounting (docs/resilience.md): `retries`
        compile attempts re-run after a failure or deadline, `degraded_passes`
        passes that could not be served by a compiled engine,
        `eager_fallbacks` of those that the un-jitted eager rung served,
        `worker_crashes` speculative-worker crashes the broker contained.
        The execution ladder's rungs land here too: `dispatch_retries`
        device dispatches re-run after a device fault, `mesh_shrinks`
        engine rebuilds over a shrunken surviving-device mesh, and
        `device_failovers` passes that escalated to the mid-process CPU
        failover rung."""
        with self._lock:
            self._compile_retries += int(retries)
            self._eager_fallbacks += int(eager_fallbacks)
            self._degraded_passes += int(degraded_passes)
            self._worker_crashes += int(worker_crashes)
            self._dispatch_retries += int(dispatch_retries)
            self._device_failovers += int(device_failovers)
            self._mesh_shrinks += int(mesh_shrinks)
            # arm the ratio-objective skips: the enclosing pass's
            # record() must not also count a GOOD event for a pass
            # whose bad event lands right here
            self._slo_skip_eager += int(eager_fallbacks)
            self._slo_skip_degraded += int(degraded_passes)
        # the bad halves of the ratio objectives (utils/slo.py),
        # emitted immediately — a terminally-degraded pass that never
        # reaches record() still burns its budget
        if eager_fallbacks or degraded_passes:
            plane = self.slo_plane()
            if plane is not None:
                if eager_fallbacks:
                    plane.observe(
                        "eagerFallback", good=False, count=int(eager_fallbacks)
                    )
                if degraded_passes:
                    plane.observe(
                        "degradedPass", good=False, count=int(degraded_passes)
                    )

    def record_bundles(
        self,
        *,
        loads: int = 0,
        saves: int = 0,
        bypasses: int = 0,
        deserialize_s: float = 0.0,
    ) -> None:
        """AOT-bundle-store accounting (utils/bundles.py): `loads`
        executables deserialized from disk instead of compiled, `saves`
        bundles written, `bypasses` bundles present but rejected (the
        silent fall-back-to-compile path), `deserialize_s` wall seconds
        spent deserializing — never booked as compile stall."""
        with self._lock:
            self._bundle_loads += int(loads)
            self._bundle_saves += int(saves)
            self._bundle_bypasses += int(bypasses)
            self._aot_deserialize_s += float(deserialize_s)

    def record_batching(
        self,
        *,
        batched_passes: int = 0,
        windows: int = 0,
        occupancy: int = 0,
        solo_fallbacks: int = 0,
    ) -> None:
        """Continuous-batching accounting (server/batchplane.py):
        `batched_passes` passes this registry's session had served by a
        batched device dispatch, `solo_fallbacks` passes that fell back
        to solo dispatch (incompatible, lone window, fault-scoped, or a
        failed batched execution), `windows` batched windows executed
        and `occupancy` the window's fill — the latter two recorded on
        the plane's default registry."""
        with self._lock:
            self._batched_passes += int(batched_passes)
            self._batch_windows += int(windows)
            self._batch_occupancy_sum += int(occupancy)
            self._solo_fallbacks += int(solo_fallbacks)

    def record_gang(
        self, *, fixpoint_rounds: int = 0, batched_passes: int = 0
    ) -> None:
        """Gang-engine accounting: `fixpoint_rounds` commit rounds the
        pass's device-resident fixpoint used (engine/gang.py — booked at
        decode, where the rounds scalar is fetched with the assignment
        anyway), `batched_passes` gang passes this registry's session
        had served by a cross-tenant batched dispatch
        (server/batchplane.py ``batch.gang.run``)."""
        with self._lock:
            self._gang_fixpoint_rounds += int(fixpoint_rounds)
            self._batched_gang_passes += int(batched_passes)

    def record_phase_seconds(
        self, execute: float = 0.0, decode: float = 0.0
    ) -> None:
        """Per-pass execute (compiled program) / decode (results +
        write-backs) wall seconds."""
        with self._lock:
            self._phase_s["execute"] += float(execute)
            self._phase_s["decode"] += float(decode)

    @contextmanager
    def time_pass(self, mode: str):
        """Context manager: `ctx.done(pods, scheduled, rounds=...)` inside
        the block stamps the pass; wall time is measured around it."""
        holder = {}

        class _Ctx:
            @staticmethod
            def done(pods: int, scheduled: int, rounds: int = 0):
                holder["args"] = (pods, scheduled, rounds)

        t0 = time.perf_counter()
        yield _Ctx
        wall = time.perf_counter() - t0
        pods, scheduled, rounds = holder.get("args", (0, 0, 0))
        self.record(PassRecord(mode, pods, scheduled, wall, rounds))

    def snapshot(self) -> dict:
        with self._lock:
            recent = self._passes[-16:]
            doc = {
                "schemaVersion": METRICS_SCHEMA_VERSION,
                "uptimeSeconds": round(
                    time.monotonic() - self._born_monotonic, 3
                ),
                "passes": self._pass_count,
                "totalPods": self._total_pods,
                "totalScheduled": self._total_scheduled,
                "totalWallSeconds": round(self._total_wall_s, 6),
                "decisionsPerSecond": round(
                    self._total_pods / self._total_wall_s, 2
                )
                if self._total_wall_s > 0
                else 0.0,
                "recent": [
                    {
                        "mode": r.mode,
                        "pods": r.pods,
                        "scheduled": r.scheduled,
                        "wallSeconds": round(r.wall_s, 6),
                        "decisionsPerSecond": round(r.decisions_per_s, 2),
                        "rounds": r.rounds,
                    }
                    for r in recent
                ],
                "disruption": {
                    "evicted": self._evicted,
                    "rescheduled": self._rescheduled,
                    "meanTimeToRescheduleS": round(
                        self._tts_sum_s / self._tts_count, 6
                    )
                    if self._tts_count
                    else 0.0,
                    "maxTimeToRescheduleS": round(self._tts_max_s, 6),
                },
                "phases": {
                    "encodeSeconds": round(self._phase_s["encode"], 6),
                    "compileSeconds": round(self._phase_s["compile"], 6),
                    "executeSeconds": round(self._phase_s["execute"], 6),
                    "decodeSeconds": round(self._phase_s["decode"], 6),
                    "deltaEncodes": self._encode_counts.get("delta", 0),
                    "fullEncodes": self._encode_counts.get("full", 0),
                    "cachedEncodes": self._encode_counts.get("cached", 0),
                    "emptyEncodes": self._encode_counts.get("empty", 0),
                    "encodePolicyMisses": self._encode_policy_misses,
                    "engineBuilds": self._engine_builds,
                    "compileHits": self._compile_hits,
                    "compileMisses": self._compile_misses,
                    "speculativeCompiles": self._speculative_compiles,
                    "stallSeconds": round(self._stall_s, 6),
                    "compileRetries": self._compile_retries,
                    "eagerFallbacks": self._eager_fallbacks,
                    "degradedPasses": self._degraded_passes,
                    "brokerWorkerCrashes": self._worker_crashes,
                    "dispatchRetries": self._dispatch_retries,
                    "deviceFailovers": self._device_failovers,
                    "meshShrinks": self._mesh_shrinks,
                    "bundleLoads": self._bundle_loads,
                    "bundleSaves": self._bundle_saves,
                    "bundleBypasses": self._bundle_bypasses,
                    "aotDeserializeSeconds": round(self._aot_deserialize_s, 6),
                    "batchedPasses": self._batched_passes,
                    "batchWindows": self._batch_windows,
                    "batchOccupancySum": self._batch_occupancy_sum,
                    "soloFallbacks": self._solo_fallbacks,
                    "gangFixpointRounds": self._gang_fixpoint_rounds,
                    "batchedGangPasses": self._batched_gang_passes,
                },
                # derived continuous-batching view (server/batchplane.py):
                # mean window fill — a ratio, so it lives outside the
                # cumulative `phases` counters the checkpoint carries
                "batching": {
                    "batchOccupancy": round(
                        self._batch_occupancy_sum / self._batch_windows, 3
                    )
                    if self._batch_windows
                    else 0.0,
                },
                "histograms": {
                    key: h.snapshot() for key, h in self._hist.items()
                },
            }
        # the SLO block (schema v4, utils/slo.py) attaches OUTSIDE the
        # registry lock — the plane has its own lock, and the two never
        # nest (lock-order discipline, docs/static-analysis.md)
        plane = self.slo_plane()
        doc["slo"] = plane.summary() if plane is not None else {"enabled": False}
        return doc

    def reset(self) -> None:
        with self._lock:
            self._passes.clear()
            self._pass_count = 0
            self._total_pods = 0
            self._total_scheduled = 0
            self._total_wall_s = 0.0
            self._evicted = 0
            self._rescheduled = 0
            self._tts_sum_s = 0.0
            self._tts_max_s = 0.0
            self._tts_count = 0
            self._phase_s = {
                "encode": 0.0, "compile": 0.0, "execute": 0.0, "decode": 0.0
            }
            self._encode_counts = {
                "delta": 0, "full": 0, "cached": 0, "empty": 0
            }
            self._encode_policy_misses = 0
            self._engine_builds = 0
            self._compile_hits = 0
            self._compile_misses = 0
            self._speculative_compiles = 0
            self._stall_s = 0.0
            self._compile_retries = 0
            self._eager_fallbacks = 0
            self._degraded_passes = 0
            self._worker_crashes = 0
            self._dispatch_retries = 0
            self._device_failovers = 0
            self._mesh_shrinks = 0
            self._bundle_loads = 0
            self._bundle_saves = 0
            self._bundle_bypasses = 0
            self._aot_deserialize_s = 0.0
            self._batched_passes = 0
            self._batch_windows = 0
            self._batch_occupancy_sum = 0
            self._solo_fallbacks = 0
            self._gang_fixpoint_rounds = 0
            self._batched_gang_passes = 0
            self._slo_skip_eager = 0
            self._slo_skip_degraded = 0
            self._hist = _new_histograms()
            self._born_monotonic = time.monotonic()

    # -- checkpointing (lifecycle/checkpoint.py) -----------------------------

    # counter fields a lifecycle checkpoint carries: everything cumulative
    # (the bounded `recent` pass window is cosmetic and stays out)
    _STATE_FIELDS = (
        "_pass_count", "_total_pods", "_total_scheduled", "_total_wall_s",
        "_evicted", "_rescheduled", "_tts_sum_s", "_tts_max_s", "_tts_count",
        "_encode_policy_misses",
        "_engine_builds", "_compile_hits", "_compile_misses",
        "_speculative_compiles", "_stall_s", "_compile_retries",
        "_eager_fallbacks", "_degraded_passes", "_worker_crashes",
        "_dispatch_retries", "_device_failovers", "_mesh_shrinks",
        "_bundle_loads", "_bundle_saves", "_bundle_bypasses",
        "_aot_deserialize_s",
        "_batched_passes", "_batch_windows", "_batch_occupancy_sum",
        "_solo_fallbacks", "_gang_fixpoint_rounds", "_batched_gang_passes",
    )

    def state_dict(self) -> dict:
        """The cumulative counters as one JSON-able dict — what a
        lifecycle checkpoint persists so a resumed run's final metrics
        report the WHOLE run, not just the post-resume suffix. With the
        SLO plane armed, its window + alert state rides along
        (`_slo`), so a drained/resumed process keeps burning the same
        error budget instead of starting a fresh one."""
        with self._lock:
            out = {f: getattr(self, f) for f in self._STATE_FIELDS}
            out["_phase_s"] = dict(self._phase_s)
            out["_encode_counts"] = dict(self._encode_counts)
            out["_histograms"] = {
                key: h.state_dict() for key, h in self._hist.items()
            }
        plane = self.slo_plane()
        if plane is not None:
            out["_slo"] = plane.state_dict()
        return out

    def load_state(self, state: dict) -> None:
        """Restore counters written by `state_dict` (unknown keys are
        ignored so old checkpoints stay loadable across counter growth;
        histogram state written before the telemetry PR is simply
        absent and those distributions restart empty). A checkpointed
        SLO plane is restored when it was an explicit override OR the
        environment still arms the plane — an operator who turned
        KSS_SLO off must not have a checkpoint re-arm it."""
        slo_state = state.get("_slo")
        with self._lock:
            for f in self._STATE_FIELDS:
                if f in state:
                    setattr(self, f, state[f])
            for key in ("_phase_s", "_encode_counts"):
                if isinstance(state.get(key), dict):
                    getattr(self, key).update(state[key])
            hists = state.get("_histograms")
            if isinstance(hists, dict):
                for key, h in self._hist.items():
                    if isinstance(hists.get(key), dict):
                        h.load_state(hists[key])
        if isinstance(slo_state, dict):
            from . import slo as slo_mod

            explicit = bool(
                (slo_state.get("config") or {}).get("explicit")
            )
            if explicit or slo_mod.enabled():
                plane = slo_mod.SloPlane.from_state(slo_state)
                if plane.session_id is None:
                    plane.session_id = self.session_id
                # an explicit (PUT-override) plane restores as an
                # override; an env-derived one restores into the env
                # cache slot instead — a later KSS_SLO_* change must
                # still rebuild/disarm it, exactly as before the resume
                with self._lock:
                    self._slo_plane = plane
                    self._slo_override = explicit
                    self._slo_env_key = None if explicit else slo_mod.env_key()


# process-wide shared registry for ad-hoc callers (benchmarks, scripts).
# Serving-layer services each own a SchedulingMetrics instance instead
# (server/service.py) so per-server numbers stay attributable when
# several services share a process.
GLOBAL = SchedulingMetrics()


# -- Prometheus exposition ----------------------------------------------------

# (metric name, help, snapshot path) — counters straight off the JSON
# snapshot. Metric names are STABLE (docs/observability.md's table): a
# rename is a breaking change for every scrape config pointed here.
_PROM_COUNTERS = (
    ("kss_passes_total", "Scheduling passes executed.", ("passes",)),
    ("kss_pods_total", "Pods evaluated across all passes.", ("totalPods",)),
    ("kss_scheduled_total", "Pods that received a node.", ("totalScheduled",)),
    (
        "kss_pass_wall_seconds_total",
        "Wall-clock seconds spent inside scheduling passes.",
        ("totalWallSeconds",),
    ),
    (
        "kss_evicted_total",
        "Pods evicted by injected lifecycle faults.",
        ("disruption", "evicted"),
    ),
    (
        "kss_rescheduled_total",
        "Evicted pods that found a node again.",
        ("disruption", "rescheduled"),
    ),
    (
        "kss_encode_policy_misses_total",
        "Full re-encodes forced by a dtype-policy flip.",
        ("phases", "encodePolicyMisses"),
    ),
    (
        "kss_engine_builds_total",
        "Compiled-engine constructions (the recompile proxy).",
        ("phases", "engineBuilds"),
    ),
    (
        "kss_compile_hits_total",
        "Engine requests served warm by the CompileBroker.",
        ("phases", "compileHits"),
    ),
    (
        "kss_compile_misses_total",
        "Engine requests compiled synchronously on the request thread.",
        ("phases", "compileMisses"),
    ),
    (
        "kss_speculative_compiles_total",
        "Background speculative compiles completed.",
        ("phases", "speculativeCompiles"),
    ),
    (
        "kss_compile_retries_total",
        "Compile attempts re-run after a failure or deadline.",
        ("phases", "compileRetries"),
    ),
    (
        "kss_eager_fallbacks_total",
        "Passes served by the un-jitted eager rung.",
        ("phases", "eagerFallbacks"),
    ),
    (
        "kss_degraded_passes_total",
        "Passes that could not be served by a compiled engine.",
        ("phases", "degradedPasses"),
    ),
    (
        "kss_broker_worker_crashes_total",
        "Speculative-worker crashes contained by the broker.",
        ("phases", "brokerWorkerCrashes"),
    ),
    (
        "kss_stall_seconds_total",
        "Request-thread seconds blocked on any compile.",
        ("phases", "stallSeconds"),
    ),
    (
        "kss_dispatch_retries_total",
        "Device dispatches re-run after a device fault.",
        ("phases", "dispatchRetries"),
    ),
    (
        "kss_device_failovers_total",
        "Passes that escalated to the mid-process CPU failover rung.",
        ("phases", "deviceFailovers"),
    ),
    (
        "kss_mesh_shrinks_total",
        "Engine rebuilds over a shrunken surviving-device mesh.",
        ("phases", "meshShrinks"),
    ),
    (
        "kss_bundle_loads_total",
        "Engine executables deserialized from the AOT bundle store.",
        ("phases", "bundleLoads"),
    ),
    (
        "kss_bundle_saves_total",
        "Engine executables serialized into the AOT bundle store.",
        ("phases", "bundleSaves"),
    ),
    (
        "kss_bundle_bypasses_total",
        "Bundles present but rejected (fell back to a fresh compile).",
        ("phases", "bundleBypasses"),
    ),
    (
        "kss_aot_deserialize_seconds_total",
        "Wall seconds spent deserializing AOT bundles (not compile stall).",
        ("phases", "aotDeserializeSeconds"),
    ),
    (
        "kss_batched_passes_total",
        "Passes served by a cross-tenant batched device dispatch.",
        ("phases", "batchedPasses"),
    ),
    (
        "kss_batch_windows_total",
        "Batched collection windows executed as one device dispatch.",
        ("phases", "batchWindows"),
    ),
    (
        "kss_batch_occupancy_total",
        "Cumulative batched-window fill (mean occupancy numerator).",
        ("phases", "batchOccupancySum"),
    ),
    (
        "kss_solo_fallbacks_total",
        "Passes that fell back from the batch plane to solo dispatch.",
        ("phases", "soloFallbacks"),
    ),
    (
        "kss_gang_fixpoint_rounds_total",
        "Commit rounds used by device-resident gang fixpoint passes.",
        ("phases", "gangFixpointRounds"),
    ),
    (
        "kss_batched_gang_passes_total",
        "Gang passes served by a cross-tenant batched dispatch.",
        ("phases", "batchedGangPasses"),
    ),
)


def _fmt_value(v) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_prometheus(
    snapshot: dict,
    extra_gauges: "dict | None" = None,
    openmetrics: bool = False,
) -> str:
    """Render a `SchedulingMetrics.snapshot()` document in the
    Prometheus text exposition format (version 0.0.4): counters,
    gauges, and the histogram families, with stable metric names.
    `extra_gauges` maps metric name -> (help, value) for serving-stack
    extras (the encoding-cache capacity). `openmetrics` attaches the
    snapshot's histogram exemplars to bucket samples
    (``# {span_id="…"} value ts`` — the OpenMetrics exemplar syntax);
    the serving route appends the terminating ``# EOF`` itself, after
    the observatory families."""
    return _render_prometheus(
        [({}, snapshot, extra_gauges)], openmetrics=openmetrics
    )


def render_prometheus_sessions(
    entries: "list[tuple[dict, dict, dict | None]]",
    global_counters: "dict | None" = None,
    global_gauges: "dict | None" = None,
    openmetrics: bool = False,
) -> str:
    """Multi-tenant exposition (docs/sessions.md): one document, each
    family declared ONCE, every sample labeled per entry. `entries` is
    ``[(labels, snapshot, extra_gauges), ...]`` — the session plane
    passes ``{"session": id}`` labels so one scrape covers every tenant.
    `global_counters`/`global_gauges` map name -> (help, value) for
    server-wide unlabeled extras (the SSE drop counter, session counts)."""
    return _render_prometheus(
        entries,
        global_counters=global_counters,
        global_gauges=global_gauges,
        openmetrics=openmetrics,
    )


_WORKER_ID_VAR = "KSS_WORKER_ID"

# lazily-compiled sample-line splitter for `label_exposition` (re stays
# off the import path, like _PROM_SAMPLE_RE below)
_LABEL_INJECT_RE = None


def worker_id(env: "dict | None" = None) -> "str | None":
    """The process's fleet worker identity (``KSS_WORKER_ID``), or None
    outside a fleet. The router launches each worker with a distinct id
    so every exposition self-labels (docs/fleet.md); the value must be
    Prometheus-label-safe (envcheck validates the charset at boot)."""
    env = os.environ if env is None else env
    wid = (env.get(_WORKER_ID_VAR) or "").strip()
    return wid or None


def label_exposition(text: str, labels: "dict[str, str]") -> str:
    """Inject `labels` into EVERY sample line of a text exposition
    (0.0.4 or OpenMetrics) — the fleet's `worker` label, applied after
    the whole document (sessions + ledger + observatory + SLO families)
    is assembled, so no renderer needs to thread the label through.
    Comment lines (`# HELP`/`# TYPE`/`# EOF`) and OpenMetrics exemplar
    suffixes (everything after the sample's value separator) pass
    through untouched."""
    if not labels or not text:
        return text
    global _LABEL_INJECT_RE
    if _LABEL_INJECT_RE is None:
        import re

        _LABEL_INJECT_RE = re.compile(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?( .*)$"
        )
    extra = ",".join(f'{k}="{v}"' for k, v in labels.items())
    out: list[str] = []
    for line in text.split("\n"):
        if not line or line.startswith("#"):
            out.append(line)
            continue
        # split the metric name + optional {label body} off the front;
        # the rest of the line (value, timestamp, exemplar) is opaque
        m = _LABEL_INJECT_RE.match(line)
        if m is None:
            out.append(line)
            continue
        name, body, rest = m.group(1), m.group(2), m.group(3)
        inner = body[1:-1] if body else ""
        merged = f"{inner},{extra}" if inner else extra
        out.append(f"{name}{{{merged}}}{rest}")
    return "\n".join(out)


def _fmt_exemplar(ex: dict) -> str:
    """One OpenMetrics exemplar suffix: ``# {labels} value [timestamp]``
    appended to a histogram bucket sample line."""
    labels = ",".join(
        f'{k}="{v}"' for k, v in (ex.get("labels") or {}).items()
    )
    out = f" # {{{labels}}} {_fmt_value(ex.get('value', 0.0))}"
    ts = ex.get("timestamp")
    if ts is not None:
        out += f" {_fmt_value(ts)}"
    return out


def _label_body(labels: dict, extra: "tuple | None" = None) -> str:
    items = list(labels.items()) + list(extra or ())
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in items) + "}"


def _render_prometheus(
    entries,
    global_counters: "dict | None" = None,
    global_gauges: "dict | None" = None,
    openmetrics: bool = False,
) -> str:
    lines: list[str] = []

    def family(name: str, mtype: str, help_text: str) -> None:
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {mtype}")

    def walk(snapshot: dict, path: tuple):
        v = snapshot
        for p in path:
            v = v.get(p, 0) if isinstance(v, dict) else 0
        return v if isinstance(v, (int, float)) else 0

    for name, help_text, path in _PROM_COUNTERS:
        family(name, "counter", help_text)
        for labels, snapshot, _extra in entries:
            lines.append(
                f"{name}{_label_body(labels)} "
                f"{_fmt_value(walk(snapshot, path))}"
            )

    family(
        "kss_encodes_total",
        "counter",
        "Cluster encodes by the path that served them.",
    )
    for labels, snapshot, _extra in entries:
        phases = snapshot.get("phases", {})
        for mode, key in (
            ("delta", "deltaEncodes"),
            ("full", "fullEncodes"),
            ("cached", "cachedEncodes"),
            ("empty", "emptyEncodes"),
        ):
            lines.append(
                f"kss_encodes_total{_label_body(labels, (('mode', mode),))} "
                f"{_fmt_value(phases.get(key, 0))}"
            )
    family(
        "kss_phase_seconds_total",
        "counter",
        "Pass wall-clock by phase (encode/compile/execute/decode).",
    )
    for labels, snapshot, _extra in entries:
        phases = snapshot.get("phases", {})
        for phase in ("encode", "compile", "execute", "decode"):
            lines.append(
                f"kss_phase_seconds_total"
                f"{_label_body(labels, (('phase', phase),))} "
                f"{_fmt_value(phases.get(phase + 'Seconds', 0.0))}"
            )

    family("kss_uptime_seconds", "gauge", "Seconds since this registry was born.")
    for labels, snapshot, _extra in entries:
        lines.append(
            f"kss_uptime_seconds{_label_body(labels)} "
            f"{_fmt_value(snapshot.get('uptimeSeconds', 0.0))}"
        )
    family(
        "kss_metrics_schema_version",
        "gauge",
        "Schema version of the /api/v1/metrics JSON document.",
    )
    for labels, snapshot, _extra in entries:
        lines.append(
            f"kss_metrics_schema_version{_label_body(labels)} "
            f"{_fmt_value(snapshot.get('schemaVersion', METRICS_SCHEMA_VERSION))}"
        )
    # per-entry extra gauges: each family declared once (help from the
    # first entry carrying it), then one labeled sample per entry
    extra_names: list[str] = []
    for _labels, _snapshot, extra in entries:
        for name in extra or ():
            if name not in extra_names:
                extra_names.append(name)
    for name in extra_names:
        help_text = next(
            extra[name][0]
            for _l, _s, extra in entries
            if extra and name in extra
        )
        family(name, "gauge", help_text)
        for labels, _snapshot, extra in entries:
            if extra and name in extra:
                lines.append(
                    f"{name}{_label_body(labels)} "
                    f"{_fmt_value(extra[name][1])}"
                )
    for name, (help_text, value) in (global_counters or {}).items():
        family(name, "counter", help_text)
        lines.append(f"{name} {_fmt_value(value)}")
    for name, (help_text, value) in (global_gauges or {}).items():
        family(name, "gauge", help_text)
        lines.append(f"{name} {_fmt_value(value)}")

    for key, name, _, help_text in HISTOGRAM_FAMILIES:
        carrying = [
            (labels, snapshot.get("histograms", {}).get(key))
            for labels, snapshot, _extra in entries
        ]
        carrying = [(labels, h) for labels, h in carrying if h]
        if not carrying:
            continue
        family(name, "histogram", help_text)
        for labels, h in carrying:
            exemplars = h.get("exemplars") or {}
            for le, cum in h["buckets"].items():
                line = (
                    f"{name}_bucket{_label_body(labels, (('le', le),))} "
                    f"{_fmt_value(cum)}"
                )
                if openmetrics and le in exemplars:
                    line += _fmt_exemplar(exemplars[le])
                lines.append(line)
            lines.append(f"{name}_sum{_label_body(labels)} {_fmt_value(h['sum'])}")
            lines.append(
                f"{name}_count{_label_body(labels)} {_fmt_value(h['count'])}"
            )
    return "\n".join(lines) + "\n"


def render_histogram(
    name: str,
    help_text: str,
    snapshot: dict,
    labels: "dict[str, str] | None" = None,
    openmetrics: bool = False,
) -> str:
    """One standalone histogram family in exposition text, from a
    `Histogram.snapshot()` document — the router's request-latency
    families live OUTSIDE any `SchedulingMetrics` registry, so they
    can't ride `_render_prometheus`'s HISTOGRAM_FAMILIES walk. Same
    line grammar: `_bucket{le=...}` (+ OpenMetrics exemplar suffix when
    asked), `_sum`, `_count`. Caller snapshots under its own lock."""
    labels = labels or {}
    lines = [
        f"# HELP {name} {help_text}",
        f"# TYPE {name} histogram",
    ]
    exemplars = snapshot.get("exemplars") or {}
    for le, cum in snapshot["buckets"].items():
        line = (
            f"{name}_bucket{_label_body(labels, (('le', le),))} "
            f"{_fmt_value(cum)}"
        )
        if openmetrics and le in exemplars:
            line += _fmt_exemplar(exemplars[le])
        lines.append(line)
    lines.append(f"{name}_sum{_label_body(labels)} {_fmt_value(snapshot['sum'])}")
    lines.append(
        f"{name}_count{_label_body(labels)} {_fmt_value(snapshot['count'])}"
    )
    return "\n".join(lines) + "\n"


_PROM_SAMPLE_RE = None  # compiled lazily (re import kept off the hot path)


def parse_prometheus_text(text: str) -> dict:
    """A real text-format (0.0.4) parse of an exposition document —
    what the observability smoke and the endpoint tests scrape through,
    so a malformed render can't pass as 'looks about right'. Returns
    ``{family: {"type", "help", "samples": [(name, labels, value)]}}``
    with labels as a dict. Raises ValueError on: unparseable lines,
    samples without a preceding TYPE, duplicate TYPE lines, histogram
    families with non-monotonic cumulative buckets, a missing/out-of-
    order +Inf bucket, or +Inf disagreeing with `_count`.

    OpenMetrics round-trip (the `?format=openmetrics` contract): a
    histogram bucket sample may carry an exemplar suffix
    (``# {labels} value [timestamp]``), collected into the family's
    ``"exemplars"`` list as ``(sample_name, labels, exemplar_labels,
    exemplar_value)``; a malformed exemplar, or one on a non-bucket
    sample, raises. A terminating ``# EOF`` line is accepted."""
    global _PROM_SAMPLE_RE
    import re

    if _PROM_SAMPLE_RE is None:
        _PROM_SAMPLE_RE = (
            re.compile(
                r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"  # metric name
                r"(?:\{(.*)\})?"  # optional label body
                r"\s+(-?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?|NaN|[+-]?Inf)"
                r"(?:\s+-?\d+)?$"  # optional timestamp
            ),
            re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"\s*(?:,|$)'),
        )
    sample_re, label_re = _PROM_SAMPLE_RE
    families: dict = {}

    def family_of(sample_name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            base = sample_name[: -len(suffix)] if sample_name.endswith(suffix) else None
            if base and families.get(base, {}).get("type") == "histogram":
                return base
        return sample_name

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            parts = line[len("# HELP ") :].split(" ", 1)
            families.setdefault(parts[0], {"type": None, "help": None, "samples": []})
            families[parts[0]]["help"] = parts[1] if len(parts) > 1 else ""
            continue
        if line.startswith("# TYPE "):
            parts = line[len("# TYPE ") :].split()
            if len(parts) != 2 or parts[1] not in (
                "counter", "gauge", "histogram", "summary", "untyped",
            ):
                raise ValueError(f"line {lineno}: malformed TYPE line {line!r}")
            fam = families.setdefault(
                parts[0], {"type": None, "help": None, "samples": []}
            )
            if fam["type"] is not None:
                raise ValueError(f"line {lineno}: duplicate TYPE for {parts[0]}")
            fam["type"] = parts[1]
            continue
        if line.startswith("#"):
            continue  # comment (incl. the OpenMetrics "# EOF" terminator)
        # an OpenMetrics exemplar rides the sample line after " # " —
        # but '#' is legal inside quoted label values, so the split
        # point is the first " # " whose PREFIX is a complete sample (a
        # mid-label '#' leaves an unparseable prefix and is skipped).
        # Splits are tried BEFORE the whole-line match: the label
        # regex's greedy braces would otherwise swallow an exemplar's
        # label body into the sample's
        exemplar_part = None
        m = None
        pos = line.find(" # ")
        while pos != -1:
            cand = sample_re.match(line[:pos])
            if cand is not None:
                m = cand
                exemplar_part = line[pos + 3 :]
                break
            pos = line.find(" # ", pos + 1)
        if m is None:
            m = sample_re.match(line)
        if not m:
            raise ValueError(f"line {lineno}: unparseable sample {line!r}")
        name, label_body, raw_value = m.group(1), m.group(2), m.group(3)
        labels = {}
        if label_body:
            consumed = sum(
                len(lm.group(0)) for lm in label_re.finditer(label_body)
            )
            if consumed != len(label_body):
                raise ValueError(
                    f"line {lineno}: malformed label body {label_body!r}"
                )
            labels = {
                lm.group(1): lm.group(2) for lm in label_re.finditer(label_body)
            }
        value = float(raw_value.replace("Inf", "inf"))
        fam_name = family_of(name)
        fam = families.get(fam_name)
        if fam is None or fam["type"] is None:
            raise ValueError(
                f"line {lineno}: sample {name!r} has no preceding TYPE"
            )
        fam["samples"].append((name, labels, value))
        if exemplar_part is not None:
            if fam["type"] != "histogram" or not name.endswith("_bucket"):
                raise ValueError(
                    f"line {lineno}: exemplar on non-bucket sample {name!r}"
                )
            em = re.match(
                r"^\{(.*)\}\s+(-?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?)"
                r"(?:\s+(-?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?))?$",
                exemplar_part,
            )
            if not em:
                raise ValueError(
                    f"line {lineno}: malformed exemplar {exemplar_part!r}"
                )
            ex_body = em.group(1)
            consumed = sum(
                len(lm.group(0)) for lm in label_re.finditer(ex_body)
            )
            if ex_body and consumed != len(ex_body):
                raise ValueError(
                    f"line {lineno}: malformed exemplar labels {ex_body!r}"
                )
            ex_labels = {
                lm.group(1): lm.group(2) for lm in label_re.finditer(ex_body)
            }
            fam.setdefault("exemplars", []).append(
                (name, labels, ex_labels, float(em.group(2)))
            )

    # histogram semantics: cumulative monotone buckets, +Inf last and
    # equal to _count — validated PER LABEL SET (minus `le`), so a
    # multi-session exposition (one series per `session` label,
    # docs/sessions.md) checks each tenant's distribution independently
    for fam_name, fam in families.items():
        if fam["type"] != "histogram":
            continue
        groups: dict = {}

        def series_of(labels: dict) -> tuple:
            return tuple(
                sorted((k, v) for k, v in labels.items() if k != "le")
            )

        for name, labels, value in fam["samples"]:
            g = groups.setdefault(
                series_of(labels), {"buckets": [], "counts": []}
            )
            if name == fam_name + "_bucket":
                g["buckets"].append((labels.get("le"), value))
            elif name == fam_name + "_count":
                g["counts"].append(value)
        for g in groups.values():
            buckets, counts = g["buckets"], g["counts"]
            if not buckets or not counts:
                raise ValueError(
                    f"histogram {fam_name}: missing buckets or _count"
                )
            if buckets[-1][0] != "+Inf":
                raise ValueError(f"histogram {fam_name}: +Inf bucket not last")
            prev = -1.0
            for le, cum in buckets:
                if cum < prev:
                    raise ValueError(
                        f"histogram {fam_name}: non-monotonic bucket le={le}"
                    )
                prev = cum
            if buckets[-1][1] != counts[0]:
                raise ValueError(
                    f"histogram {fam_name}: +Inf bucket {buckets[-1][1]} != "
                    f"_count {counts[0]}"
                )
    return families


@contextmanager
def profile_trace(log_dir: str):
    """Capture a JAX profiler trace (TensorBoard/XProf format) around the
    block — per-phase device timing for any pass run inside."""
    import jax

    with jax.profiler.trace(log_dir):
        yield


# Published per-chip peaks, keyed by `jax.Device.device_kind`. Source:
# Google Cloud documentation, "TPU v5e" (bf16 peak, HBM bandwidth). The
# scheduling kernels are f32/int32 elementwise+reduce, so measured MFU
# is expected to be ~0: the point of reporting it is to make
# "latency-bound, negligible MFU" a measured number. An accelerator
# kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9},
}


def device_peak(device) -> "dict | None":
    """`PEAKS` entry of `device` (a `jax.Device`); None for a CPU.
    Raises KeyError for an accelerator kind with no published peak."""
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it to utils/metrics.PEAKS"
        ) from None


def cost_analysis(jitted, *args) -> "dict | None":
    """FLOPs + bytes of one execution of `jitted(*args)` from XLA's own
    compiled-program cost model.

    Routed through the program ledger's shared AOT probe
    (`utils/ledger.aot_probe` — the same lower/compile/cost path the
    serving-side ledger wrapper times), which shares the jit
    compilation cache, so calling this after the program already ran is
    cheap. Returns {"flops": float, "bytes": float} or None when the
    backend doesn't expose a cost model — callers must treat None as
    "unavailable", never as zero work."""
    from .ledger import aot_probe

    probe = aot_probe(jitted, args)
    if probe is None:
        return None
    _compiled, info, _traced = probe
    if info["flops"] is None:
        return None
    return {"flops": info["flops"], "bytes": info["bytes"]}


def mfu(flops: "float | None", seconds: float, device) -> "float | None":
    """Model-FLOPs-utilization of `flops` of useful work in `seconds`
    against `device`'s published peak (`device_peak`); None on a CPU or
    without a cost-model number."""
    if not flops or seconds <= 0:
        return None
    peak = device_peak(device)
    if peak is None:
        return None
    return flops / seconds / peak["flops_per_s"]


def cost_fields(
    jitted, args: tuple, seconds: "float | None" = None,
    device=None, per: str = "", label: "str | None" = None,
    variants: "int | None" = None,
) -> dict:
    """The shared cost-telemetry block of every bench program: run
    `cost_analysis`, and when it answers emit `flops`/`bytes` (suffixed
    `_per_<per>` when given) plus — with a measured wall `seconds` —
    `flops_per_s` and, given the `device` it ran on, `mfu`. Empty dict when
    the backend exposes no cost model (callers merge it and move on).

    `label` additionally records the probe into the process ledger
    (`utils/ledger.LEDGER`) so bench and the serving path share one
    accounting. `variants` marks a VMAPPED program: the emitted
    `flops` stays the whole-program cost-model number, and
    `flops_per_variant` spells out the per-variant share — the MFU
    denominator note every headline carries (docs/benchmarking.md:
    the cost model's vmapped totals have been observed inconsistent
    with variants x the single-variant program, BENCH_r05_chip)."""
    if label is not None:
        from .ledger import LEDGER

        info = LEDGER.observe(label, jitted, args)
        cost = (
            {"flops": info["flops"], "bytes": info["bytes"]} if info else None
        )
    else:
        cost = cost_analysis(jitted, *args)
    if not cost:
        return {}
    sfx = f"_per_{per}" if per else ""
    out = {f"flops{sfx}": cost["flops"], f"bytes{sfx}": cost["bytes"]}
    if variants and variants > 1:
        out["flops_per_variant"] = cost["flops"] / variants
        out["variants"] = variants
    if seconds is not None and seconds > 0:
        out["flops_per_s"] = round(cost["flops"] / seconds, 1)
        m = mfu(cost["flops"], seconds, device) if device is not None else None
        if m is not None:
            out["mfu"] = m
    return out
