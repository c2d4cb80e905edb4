"""Simulator services: scheduler lifecycle + scheduling passes + the
export/import/reset composites over one `ResourceStore`.

This is the analogue of the reference's DI-wired service layer
(simulator/server/di/di.go:44-78) collapsed to plain constructors:

  * `SchedulerService` owns the scheduler lifecycle — current
    KubeSchedulerConfiguration, restart-with-new-config with rollback on a
    config the engine cannot run (reference:
    simulator/scheduler/scheduler.go:70-91), and the batched scheduling
    pass itself.
  * Scheduling results are written straight back onto the pod objects in
    the store — `spec.nodeName` plus the 13 result annotations — replacing
    the reference's informer-hooked store reflector
    (simulator/scheduler/storereflector/storereflector.go:54-119): the
    batched engine's outputs ARE the record, so there is no informer race
    and no conflict-retry loop.
  * Preemption victims are deleted from the store, mirroring the upstream
    scheduler's API-delete of victims.
  * `SimulatorService` composes store + scheduler with export / import /
    reset (reference: simulator/export/export.go:187-263,
    simulator/reset/reset.go:57-84).

Divergence (documented): the reference scheduler is a long-running loop
that drains a watch-fed queue one pod at a time; here a scheduling pass is
an explicit, synchronous batch (`schedule()`), optionally auto-triggered
after imports/CRUD by the HTTP layer. One pass schedules every pending pod
in PrioritySort order with identical placement semantics.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import ExitStack

import jax

from ..engine import BatchedScheduler
from ..engine.delta import DeltaEncoder
from ..engine.encode import EncodingCache, policy_from_env
from ..engine.engine import unsupported_plugins
from ..models.snapshot import export_snapshot, import_snapshot
from ..models.store import ResourceStore
from ..sched.config import SchedulerConfiguration
from ..sched.extender import ExtenderService
from ..sched.results import PodSchedulingResult
from ..utils import broker as broker_mod
from ..utils import devices as devices_mod
from ..utils import faultinject, fleetstats, locking
from ..utils import ledger as ledger_mod
from ..utils import metrics as metrics_mod
from ..utils import telemetry
from ..utils.broker import (
    CompileBroker,
    CompileUnavailable,
    adjacent_bucket_targets,
    eager_execution,
)


class InvalidSchedulerConfiguration(ValueError):
    pass


class EngineDegraded(RuntimeError):
    """The degradation ladder is fully exhausted: compilation kept
    failing AND the un-jitted eager fallback failed too. The HTTP layer
    maps this to 503 + Retry-After (docs/resilience.md)."""


# The gang engine's evaluation-chunk size on the SERVING path. Placements
# are chunk-invariant (chunking only batches the per-round evaluation);
# what the chunk sets is the granularity of compact mode's skip-settled
# cond — a live round evaluates ceil(pending / chunk) chunks of
# [chunk x N] kernels. Churn-heavy serving passes have 1-2 pending pods,
# so the warm-pass floor is ONE chunk's evaluation: 64 measures ~3.5x
# faster than the 256 default at the lifecycle-probe shape (520 bound +
# 2 pending x 64 nodes: 141 ms -> 40 ms) while bulk passes keep the same
# total work. Must accompany every GangScheduler build AND every
# effective_window computation here, or engine-cache keys drift.
GANG_CHUNK = 64


def gang_chunk() -> int:
    """The serving-path gang evaluation chunk: ``KSS_GANG_CHUNK`` when
    set (>= 1), else the measured `GANG_CHUNK` default. Placements are
    chunk-invariant, so this is a pure performance knob — compact
    mode's skip-settled granularity on both the fused fixpoint and the
    record path's replay evaluation. Lenient coercion (the broker's
    ladder-knob rule: a malformed value must not take serving down);
    read per pass so the knob is honored without a restart — the chunk
    is part of the engine signature, so a changed value simply keys a
    new engine."""
    return broker_mod._coerce_env_number(
        os.environ.get("KSS_GANG_CHUNK", ""), GANG_CHUNK, int, 1
    )


class SchedulerServiceDisabled(RuntimeError):
    """An external scheduler is enabled, so the internal scheduler
    service refuses config/scheduling calls (reference
    scheduler.go:55 ErrServiceDisabled; the disabled service is built
    when ExternalSchedulerEnabled, scheduler.go:58-61)."""

    def __init__(self):
        super().__init__(
            "an external scheduler is enabled: scheduler service is disabled"
        )


class SchedulingPassHandle:
    """An in-flight scheduling pass: dispatched, not yet resolved.

    `begin_pass`/`begin_gang_pass` return one with the pass lock HELD —
    device execution (and the occasional compile, normally served warm
    by the broker) proceeds while the caller does other host-side work.
    `resolve()` performs the deferred tail — result decode (one batched
    device transfer), store write-backs, pass metrics — releases the
    lock, and returns the number of pods scheduled. Callers MUST resolve
    (or `abandon`) exactly once before starting another pass; the
    lifecycle engine's async pipeline is the canonical driver."""

    def __init__(self, service, mode: str, finish, encode_info, pass_id=None):
        self._service = service
        self._finish = finish
        self._done = False
        self.mode = mode
        # the encode path that served the dispatch (delta/full/cached/…)
        self.encode_info = encode_info
        # causal id of this pass in the service's monotonic sequence —
        # every telemetry span of the pass (including the broker's
        # speculative builds it arms) carries it (utils/telemetry.py)
        self.pass_id = pass_id
        self.scheduled: "int | None" = None

    def resolve(self) -> int:
        if self._done:
            return self.scheduled or 0
        try:
            self.scheduled = self._finish()
        finally:
            self._done = True
            self._service._unlease_engine()
            self._service._schedule_lock.release()
        return self.scheduled

    def abandon(self) -> None:
        """Release the pass lock WITHOUT the deferred write-backs (error
        paths only — the store is left without this pass's results)."""
        if not self._done:
            self._done = True
            self._service._unlease_engine()
            self._service._schedule_lock.release()


@locking.guard_inferred
class SchedulerService:
    """Scheduler lifecycle + batched scheduling passes."""

    def __init__(
        self,
        store: ResourceStore,
        initial_config: "SchedulerConfiguration | None" = None,
        metrics: "metrics_mod.SchedulingMetrics | None" = None,
        disabled: bool = False,
        broker: "CompileBroker | None" = None,
        session_id: "str | None" = None,
        fault_plane=None,
    ):
        self.store = store
        # multi-tenant session plane (docs/sessions.md): the session id
        # labels this service's telemetry spans (SSE filtering, the
        # Prometheus `session` label) and namespaces its cooldowns on a
        # SHARED broker; the optional per-session fault plane
        # (utils/faultinject.FaultPlane) rules this service's passes
        # only — the bulkhead that confines a tenant's injected storm
        self.session_id = session_id
        self.fault_plane = fault_plane
        # the engine lease held across the current pass's dispatch→finish
        # window (broker.lease — cross-session engine serialization);
        # at most one, since _schedule_lock serializes passes
        self._engine_lease: "threading.RLock | None" = None
        # external-scheduler mode: the service exists (the HTTP layer
        # still routes to it) but refuses config and scheduling calls
        self.disabled = disabled
        # per-service pass counters: embedded/test use may run several
        # services in one process, and a process-wide registry would
        # interleave their numbers (ADVICE r3). Each service defaults to
        # its own instance; the serving shell reads it through
        # GET /api/v1/metrics. Pass `metrics=metrics_mod.GLOBAL` to opt
        # into the shared process registry.
        self.metrics = (
            metrics if metrics is not None else metrics_mod.SchedulingMetrics()
        )
        # the SLO plane labels this registry's alerts by tenant
        # (utils/slo.py); a shared/pre-labeled registry keeps its label
        if self.metrics.session_id is None and session_id is not None:
            self.metrics.session_id = session_id
        self._initial = initial_config or SchedulerConfiguration.default()
        self._config = self._initial
        self._lock = locking.make_lock("service.state")
        # whole-pass serialization (held across dispatch→resolve for
        # async passes — see SchedulingPassHandle)
        self._schedule_lock = locking.make_lock("service.schedule")
        # ALL compiled engines (sequential / gang / extender, keyed by
        # kind + compile signature) live in the CompileBroker: it dedupes
        # concurrent builds, counts hits/misses/stall seconds into this
        # service's metrics, and hosts the predictive background compiles
        # `_maybe_speculate` arms (utils/broker.py)
        self.broker = broker if broker is not None else CompileBroker(
            metrics=self.metrics
        )
        # speculation arming memory: one background compile per
        # (bucket, target) pair — cleared when the live bucket moves
        self._spec_bucket: "int | None" = None
        self._spec_armed: set = set()
        # the incremental encoding stack (docs/performance.md):
        #   * EncodingCache — bounded LRU keyed (latest rv, config
        #     identity): back-to-back passes over an unchanged store
        #     reuse the encoding verbatim, across recent configs;
        #     capacity from KSS_ENCODING_CACHE_CAP (default 8, surfaced
        #     in /api/v1/metrics as encodingCacheCapacity);
        #   * DeltaEncoder — on a cache miss, replays the store's event
        #     log into the retained encoding with device scatter
        #     updates, falling back to a full re-encode when it can't
        #     prove exactness. The lifecycle event loop leans on this
        #     for its O(Δ) steady state.
        self.encoding_cache_capacity = self._encoding_cache_cap_from_env()
        self._enc_cache = EncodingCache(capacity=self.encoding_cache_capacity)
        self._delta = DeltaEncoder()
        # the last _encode_current outcome ({"mode": ..., ...}) — read
        # by the lifecycle engine to stamp per-pass encode modes
        self.last_encode_info: "dict | None" = None
        # monotonic pass sequence (telemetry causality): advanced under
        # the schedule lock, so ids order exactly like passes do
        self._pass_seq = 0
        # -- execution ladder state (docs/resilience.md) -----------------
        # the rung this service currently dispatches on:
        #   "device" — the healthy default;
        #   "shrunk" — a device was lost, engines rebuilt over the
        #              surviving mesh (self.mesh) under a bumped epoch;
        #   "cpu"    — mid-process CPU failover: every pass re-encodes
        #              and runs on the CPU backend.
        # Rungs latch: once escalated, later passes run there directly
        # instead of re-walking the ladder per pass.
        self._device_rung = "device"
        self._dispatch_device = None  # default-device override per rung
        self._lost_devices: set = set()
        # joins broker keys once non-zero, so rebuilt engines never
        # collide with a warm engine compiled for a dead device
        self._device_epoch = 0
        # the cross-tenant micro-batch dispatch plane
        # (server/batchplane.py), shared across every session of one
        # SessionManager and assigned by it at session wiring time;
        # None (the default) = solo dispatch, the historical path
        self.batch_plane = None
        # per-pass batching bookkeeping (both only touched inside the
        # schedule-lock window): whether THIS pass already counted its
        # soloFallbacks tick (supervised-dispatch retries re-enter the
        # dispatch closure — one pass must count once), and the reusable
        # decode-engine for batched passes, keyed by broker sig (the
        # broker's retarget pattern: construction is paid on signature
        # change, not per pass)
        self._batch_fallback_counted = False
        self._batch_decode_cache: "tuple | None" = None
        self._batch_gang_decode_cache: "tuple | None" = None
        self.extender_service = ExtenderService(self._config.extenders)

    def _next_pass_id(self) -> int:
        """The next causal pass id — call only with `_schedule_lock`
        held (passes are serialized, so the increment is exact; the
        state lock makes the counter safe for out-of-pass readers like
        `next_pass_id_hint` — guarded-state contract KSS6xx)."""
        with self._lock:
            self._pass_seq += 1
            return self._pass_seq

    def next_pass_id_hint(self) -> int:
        """The pass id the NEXT pass will carry — exact only while the
        caller is the sole driver of this service (the lifecycle engine
        is: it owns its service and runs single-threaded). Used to stamp
        host-side work that FEEDS the next pass (event application under
        the async pipeline) with that pass's causal id."""
        with self._lock:
            return self._pass_seq + 1

    def pass_seq(self) -> int:
        """The completed-pass counter, read under the state lock — the
        session checkpoint writer's accessor (the counter is
        lock-claimed state, KSS6xx: the KSS_RACE_CHECK witness caught
        the bare cross-class read on the live snapshot path)."""
        with self._lock:
            return self._pass_seq

    def restore_pass_seq(self, n: int) -> None:
        """Restore the pass counter from a session checkpoint (the
        restored service has no pass in flight; the state lock makes
        the publication safe for concurrent hint readers)."""
        with self._lock:
            self._pass_seq = int(n)

    def encode_info(self) -> "dict | None":
        """The last pass's encode-path outcome, read under the state
        lock (the lifecycle engine stamps per-pass encodeMode from it
        AFTER the pass released the schedule lock — a bare read there is
        exactly what the KSS_RACE_CHECK witness flags)."""
        with self._lock:
            return self.last_encode_info

    def current_extender_service(self) -> ExtenderService:
        """The live extender service, read under the state lock
        (restart() swaps it there) — the HTTP extender proxy's
        accessor."""
        with self._lock:
            return self.extender_service

    def _session_scope(self) -> ExitStack:
        """The per-pass bulkhead contexts (docs/sessions.md): spans
        emitted inside carry this service's session id, and the
        session's private fault plane (when it has one) shadows the
        process plane on this thread for the duration. Empty for
        sessionless services — the historical behavior."""
        stack = ExitStack()
        if self.session_id is not None:
            stack.enter_context(telemetry.session_context(self.session_id))
        if self.fault_plane is not None:
            stack.enter_context(faultinject.scoped(self.fault_plane))
        return stack

    def _lease_engine(self, sig: tuple) -> None:
        """Hold `sig`'s engine lease for the rest of this pass: warm
        engines in a SHARED broker are stateful (retarget mutates them),
        so two bucket-compatible sessions may share the executable but
        never a concurrent mutation of it. Released by the pass finish
        (or any error path) via `_unlease_engine`."""
        lease = self.broker.lease(sig)
        lease.acquire()
        self._engine_lease = lease

    def _unlease_engine(self) -> None:
        """Release the held engine lease, if any (idempotent — finish
        paths and outer error handlers may both call it)."""
        lease, self._engine_lease = self._engine_lease, None
        if lease is not None:
            lease.release()

    @staticmethod
    def _encoding_cache_cap_from_env() -> int:
        """EncodingCache capacity: KSS_ENCODING_CACHE_CAP when it parses
        to a positive integer, else the default 8 (a bad value must not
        take the serving stack down — the cache is an optimization)."""
        raw = os.environ.get("KSS_ENCODING_CACHE_CAP", "")
        try:
            cap = int(raw) if raw else 8
        except ValueError:
            return 8
        return cap if cap >= 1 else 8

    # -- configuration lifecycle -------------------------------------------

    @property
    def config(self) -> SchedulerConfiguration:
        with self._lock:
            return self._config

    def get_config(self) -> dict:
        if self.disabled:
            raise SchedulerServiceDisabled()
        with self._lock:
            config = self._config
        return config.to_dict()

    def restart(self, new_config: "dict | SchedulerConfiguration") -> None:
        """Swap in a new configuration; on an unusable one, keep the old
        (reference RestartScheduler rolls back to oldSchedulerCfg,
        scheduler.go:70-87)."""
        if self.disabled:
            raise SchedulerServiceDisabled()
        if not isinstance(new_config, SchedulerConfiguration):
            new_config = SchedulerConfiguration.from_dict(new_config)
        missing = unsupported_plugins(new_config)
        if missing:
            raise InvalidSchedulerConfiguration(
                f"no kernel for enabled plugins: {missing}"
            )
        with self._lock:
            self._config = new_config
            self.extender_service = ExtenderService(new_config.extenders)

    def reset(self) -> None:
        """Restore the boot-time configuration (reference
        ResetScheduler, scheduler.go:89-91 — which goes through
        RestartScheduler and hence errors when disabled)."""
        if self.disabled:
            raise SchedulerServiceDisabled()
        with self._lock:
            self._config = self._initial
            self.extender_service = ExtenderService(self._initial.extenders)

    # -- scheduling ---------------------------------------------------------

    def schedule(self) -> list[PodSchedulingResult]:
        """One batched sequential scheduling pass over the store's state.

        Encodes the cluster, runs the engine, writes `spec.nodeName` and
        the 13 result annotations back onto pod objects, and deletes
        preemption victims. Returns the per-pod records. Passes are
        serialized — concurrent HTTP triggers queue up rather than
        interleaving their write-backs. For bulk throughput without
        per-plugin records, see `schedule_gang`.
        """
        if self.disabled:
            raise SchedulerServiceDisabled()
        with self._schedule_lock, self._session_scope():
            try:
                # one config read per pass: encode, branch, and label must
                # all see the same configuration even if restart() lands
                # mid-pass
                with self._lock:
                    config = self._config
                mode = "extender" if config.extenders else "sequential"
                pass_id = self._next_pass_id()
                with telemetry.pass_context(pass_id), telemetry.span(
                    f"pass.{mode}", pass_id=pass_id
                ):
                    with self.metrics.time_pass(mode) as ctx:
                        results = self._schedule_locked(config)
                        # a preempting pod yields two records (Nominated +
                        # retry): count distinct pods so decisions/sec isn't
                        # inflated
                        ctx.done(
                            pods=len(
                                {(r.pod_namespace, r.pod_name) for r in results}
                            ),
                            scheduled=sum(
                                1 for r in results if r.status == "Scheduled"
                            ),
                        )
                return results
            finally:
                # error paths between dispatch and finish (eager-ladder
                # exhaustion, device faults) must not strand the lease
                self._unlease_engine()

    def schedule_gang(
        self, record: bool = True, window: "int | None" = None
    ) -> "tuple[dict, int, list[PodSchedulingResult] | None]":
        """Gang pass with pass serialization; returns
        ({(ns, name): node | ""}, rounds, results).

        `record=True` (default — the annotations ARE the product,
        reference resultstore/store.go:129-190) runs the record path:
        the 13 result annotations are written back onto every queued
        pod exactly like the sequential pass, and the per-pod records
        are returned. `record=False` is the bulk-throughput opt-out
        (results is None, only nodeName is written back).

        `window` passes GangScheduler's eval_window through (the
        at-scale round-cost lever — docs/gang-scheduler.md); placements
        are a valid greedy order of the windowed contract."""
        if self.disabled:
            raise SchedulerServiceDisabled()
        if window is not None and int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        with self._schedule_lock, self._session_scope():
            try:
                return self._schedule_gang_timed(record, window)
            finally:
                self._unlease_engine()

    def _schedule_gang_timed(self, record: bool, window: "int | None" = None):
        with self._lock:
            config = self._config
        if config.extenders:
            raise ValueError(
                "gang mode does not support extenders; use sequential mode"
            )
        pass_id = self._next_pass_id()
        with telemetry.pass_context(pass_id), telemetry.span(
            "pass.gang", pass_id=pass_id
        ):
            with self.metrics.time_pass("gang") as ctx:
                placements, rounds, results = self._schedule_gang_locked(
                    config, record, window
                )
                ctx.done(
                    pods=len(placements),
                    scheduled=sum(1 for v in placements.values() if v),
                    rounds=rounds,
                )
        return placements, rounds, results

    def _schedule_gang_locked(self, config, record: bool, window=None):
        """Gang pass: encode, run to fixpoint, write results back."""
        t0 = time.perf_counter()
        disp = self._gang_dispatch(config, record, window)
        if disp is not None:
            telemetry.complete(
                "device.execute",
                t0,
                time.perf_counter(),
                tid=telemetry.DEVICE_TID,
                mode="gang",
            )
        if disp is None:
            return {}, 0, ([] if record else None)
        return self._gang_finish(disp, record)

    def _fire_device_dispatch(self) -> None:
        """The fault plane's device-dispatch site (``device_error`` /
        ``device_lost`` / ``dispatch_hang``, utils/faultinject.py):
        fired once per pass dispatch, upstream of engine acquisition,
        under the KSS_DISPATCH_DEADLINE_S watchdog (an injected hang
        must trip the deadline exactly like a wedged real dispatch). An
        injected device fault escalates through the EXECUTION ladder
        (`_supervised_dispatch`) — retried, mesh-shrunk, then failed
        over to CPU; on the CPU rung the sites no longer fire (they
        model the accelerator, and that rung no longer touches it)."""
        if self._device_rung == "cpu":
            return
        plane = faultinject.active()
        if plane is None:
            return

        def probe():
            plane.delay("dispatch_hang")
            plane.maybe_raise("device_error")
            plane.maybe_raise("device_lost")

        devices_mod.run_with_deadline(probe, devices_mod.dispatch_deadline_s())

    # -- the execution ladder (docs/resilience.md) --------------------------

    @property
    def device_rung(self) -> str:
        """The execution ladder rung this service dispatches on
        (``device`` / ``shrunk`` / ``cpu``) — surfaced by
        GET /api/v1/metrics as ``deviceRung``. Read under the state
        lock: rung transitions publish under it (`_try_shrink`,
        `_engage_cpu_failover`), and the metrics scrape must not block
        on a whole pass to observe them."""
        with self._lock:
            return self._device_rung

    def _epoch_sig(self, sig: tuple) -> tuple:
        """Append the device epoch to a broker key once any escalation
        happened: a rebuilt engine must never collide with a warm engine
        compiled for a dead (or abandoned) device. Epoch 0 keys stay
        byte-identical to the historical shape, so bucket-compatible
        sessions keep sharing executables."""
        if self._device_epoch:
            return sig + (("devepoch", self._device_epoch),)
        return sig

    def _run_rung(self, once):
        """Run one dispatch attempt on the current rung: under the
        rung's default-device override (a shrink survivor or a CPU
        device) when one is set, inline otherwise."""
        if self._dispatch_device is not None:
            with jax.default_device(self._dispatch_device):
                return once()
        return once()

    def _invalidate_encodings(self) -> None:
        """Escalation invalidates every retained encoding: the cached /
        delta-retained arrays live on the device that just failed, so
        the next `_encode_current` must re-encode from the store under
        the new rung's placement."""
        self._enc_cache = EncodingCache(capacity=self.encoding_cache_capacity)
        self._delta = DeltaEncoder()
        # the batched-pass decode engines retain their encodings too —
        # on the failed device; drop them (batch eligibility already
        # excludes escalated rungs, this just releases the dead buffers)
        self._batch_decode_cache = None
        self._batch_gang_decode_cache = None

    def _try_shrink(self) -> bool:
        """The ladder's mesh-shrink rung: mark the dispatch device lost,
        rebuild the (replicas, nodes) mesh over the survivors (the
        replicas axis absorbs the loss — parallel/mesh.surviving_mesh),
        bump the engine epoch so the broker rebuilds on the new
        topology, and re-encode. False when nothing survives (single
        device, or backend enumeration itself failing) — the caller's
        cue to fall straight to the CPU rung."""
        try:
            all_devices = jax.devices()
        except Exception:  # noqa: BLE001 — a dead backend can't enumerate
            return False
        if len(all_devices) <= 1:
            return False
        # the faulted device is the one dispatches were landing on: the
        # rung's override when set, else the process default (devices[0])
        faulted = (
            self._dispatch_device
            if self._dispatch_device is not None
            else all_devices[0]
        )
        self._lost_devices.add(faulted)
        survivors = [d for d in all_devices if d not in self._lost_devices]
        if not survivors:
            return False
        from ..parallel.mesh import surviving_mesh

        try:
            # validates that a (replicas, nodes) topology exists over
            # the survivors (odd counts fall to node_shards=1) — the
            # rung's actual effect is the dispatch-device pin + epoch
            mesh = surviving_mesh(self._lost_devices, devices=all_devices)
        except ValueError:
            return False
        # rung state publishes under the state lock so out-of-pass
        # readers (the deviceRung scrape) see it without the pass lock
        with self._lock:
            self._dispatch_device = survivors[0]
            self._device_rung = "shrunk"
            self._device_epoch += 1
        self._invalidate_encodings()
        self.metrics.record_resilience(mesh_shrinks=1)
        telemetry.instant(
            "dispatch.mesh_shrink",
            survivors=len(survivors),
            replicas=mesh.shape["replicas"],
        )
        return True

    def _engage_cpu_failover(self, err: "Exception | None") -> None:
        """The ladder's last rung: re-encode on the CPU backend and run
        the SAME pass there. Same placements, same trace bytes; only
        latency degrades, and the pass is counted (`deviceFailovers`) so
        it can never pass for a device run. Latches for the rest of the
        process. With no usable CPU backend
        the ladder is truly exhausted: EngineDegraded, the 503 path."""
        cpus = devices_mod.cpu_devices()
        if not cpus:
            raise EngineDegraded(
                f"device ladder exhausted ({err}) and no CPU backend is "
                f"available for the failover rung"
            ) from err
        with self._lock:
            self._device_rung = "cpu"
            self._dispatch_device = cpus[0]
            self._device_epoch += 1
        self._invalidate_encodings()
        self.metrics.record_resilience(device_failovers=1)
        telemetry.instant("dispatch.cpu_failover", reason=str(err))

    def _supervised_dispatch(self, once):
        """Walk the execution ladder around one dispatch closure
        (docs/resilience.md). `once` is the FULL dispatch — encode,
        engine acquisition through the broker, device run — so every
        escalation re-encodes and rebuilds on the new rung's devices:

          1. up to 1 + KSS_DISPATCH_RETRIES attempts on the current
             rung (each re-run counted as ``dispatchRetries``);
          2. one mesh shrink (drop the faulted device, rebuild over the
             survivors under a bumped epoch) and one attempt there;
          3. CPU failover: the same pass, re-encoded and re-run on the
             CPU backend (``deviceFailovers``).

        Only device faults (`utils/devices.is_device_fault`) escalate;
        every other exception propagates untouched. Rungs latch — a
        failed-over service dispatches straight on CPU next pass."""
        if self._device_rung == "cpu":
            return self._run_rung(once)
        last: "Exception | None" = None
        for attempt in range(1 + devices_mod.dispatch_retries()):
            if attempt:
                self.metrics.record_resilience(dispatch_retries=1)
                telemetry.instant("dispatch.retry", attempt=attempt + 1)
            try:
                return self._run_rung(once)
            except Exception as e:  # noqa: BLE001 — classified below
                if not devices_mod.is_device_fault(e):
                    raise
                last = e
                self._unlease_engine()
        if self._device_rung == "device" and self._try_shrink():
            try:
                return self._run_rung(once)
            except Exception as e:  # noqa: BLE001 — classified below
                if not devices_mod.is_device_fault(e):
                    raise
                last = e
                self._unlease_engine()
        self._engage_cpu_failover(last)
        return self._run_rung(once)

    def _fleet_sample(self, enc, state, mode: str) -> None:
        """One fleet-observatory sample over this pass's encoded
        tensors + final engine state (utils/fleetstats.py): per-device
        HBM, the live-buffer census, and the jitted cluster-quality
        reductions. Read-only over the pass's arrays — placements are
        byte-identical with stats on or off (sampling-invariance,
        test-pinned) — and never-raise: observability must not fail a
        pass. No-op unless KSS_FLEET_STATS armed a recorder."""
        rec = fleetstats.active()
        if rec is None:
            return
        try:
            rec.sample_pass(self, enc, state, mode)
        except Exception:  # noqa: BLE001 — a failed sample never fails a pass
            pass

    def _eager_fallback(self, build, err: Exception):
        """The degradation ladder's last rung (docs/resilience.md): run
        the SAME engine pass un-jitted. Inside `eager_execution`,
        `broker.jit` is a pass-through, so `build()` constructs an engine
        whose programs execute eagerly — no XLA compile to fail or wedge.
        The engine is NOT stored in the broker's warm map (it is not
        compiled); the pass completes slowly instead of not at all."""
        t0 = time.perf_counter()
        try:
            with telemetry.span("pass.eager_fallback", reason=str(err)):
                with eager_execution():
                    engine = build()
        except Exception as e:
            self.metrics.record_resilience(degraded_passes=1)
            raise EngineDegraded(
                f"compile ladder exhausted ({err}) and eager fallback "
                f"failed: {type(e).__name__}: {e}"
            ) from e
        self.metrics.record_resilience(degraded_passes=1, eager_fallbacks=1)
        self.metrics.record_phase_seconds(execute=time.perf_counter() - t0)
        # downstream finish steps (the gang record decode) lazily create
        # MORE jits on this engine — they must stay on the eager rung too
        engine._kss_eager_fallback = True
        return engine

    def _count_solo_fallback(self) -> None:
        """One ``soloFallbacks`` tick per PASS: the supervised dispatch
        re-enters the dispatch closure on device-fault retries and
        ladder rungs, and a retried pass must not inflate the counter
        (per-pass flag, reset by the dispatch wrappers under the
        schedule lock)."""
        if self.batch_plane is None or self._batch_fallback_counted:
            return
        self._batch_fallback_counted = True
        self.metrics.record_batching(solo_fallbacks=1)

    def _gang_dispatch(self, config, record: bool, window=None):
        """One gang dispatch under the execution ladder: the full
        encode + engine-acquire + run closure walks
        `_supervised_dispatch`, so a device fault anywhere inside is
        retried, mesh-shrunk, or failed over to CPU — with the SAME
        pass re-encoded and re-run, never a changed answer."""
        self._batch_fallback_counted = False
        return self._supervised_dispatch(
            lambda: self._gang_dispatch_once(config, record, window)
        )

    def _gang_dispatch_once(self, config, record: bool, window=None):
        """Encode + execute one gang pass, engine served by the broker;
        returns an opaque tuple for `_gang_finish`, or None when nothing
        is schedulable. Everything downstream of this (decode,
        write-backs) is deferrable — the async pipeline's split point."""
        from ..engine.gang import GangScheduler

        enc = self._encode_current(config)
        if enc is None:
            return None
        self._fire_device_dispatch()
        chunk = gang_chunk()
        # the window joins the broker key as the CANONICAL chunk-rounded
        # value program identity actually depends on (raw windows that
        # round to the same WP share one compilation)
        sig = self._epoch_sig((
            "gang",
            GangScheduler.compile_signature(enc),
            GangScheduler.effective_window(enc, window, chunk),
        ))
        if not record:
            # the fused fixpoint made the whole pass one broker-keyed
            # program, so gang passes enroll in the batch plane exactly
            # like sequential ones (batch.gang.run)
            disp = self._maybe_batched_gang_dispatch(sig, enc, chunk, window)
            if disp is not None:
                return disp
        else:
            # record passes stay solo: the byte-parity trace replay is
            # per-session host work by design (docs/performance.md)
            self._count_solo_fallback()
        # cross-session serialization of the (possibly shared) engine:
        # held until _gang_finish (docs/sessions.md)
        self._lease_engine(sig)
        t0 = time.perf_counter()
        holder: dict = {}

        def build():
            g = GangScheduler(
                enc, strict=True, chunk=chunk, eval_window=window
            )
            # jit is lazy: the first drive IS the XLA compile, so the
            # broker's miss wall time is the true request-thread stall
            if record:
                g.run_recorded()
            else:
                g.run()
            holder["ran"] = True
            return g

        broker_info: dict = {}
        try:
            gang = self.broker.get_resilient(
                sig, build, info=broker_info,
                metrics=self.metrics, scope=self.session_id,
            )
        except CompileUnavailable as e:
            # the ladder's last rung: the SAME pass, un-jitted (build
            # runs the engine, so the finish path is identical)
            return (enc, self._eager_fallback(build, e))
        if not holder.get("ran"):
            gang.retarget(enc)
            if record:
                gang.run_recorded()
            else:
                gang.run()
        dt = time.perf_counter() - t0
        # a fresh build's first run IS the XLA compile (jit is lazy)
        if holder.get("ran"):
            self.metrics.record_engine_build(dt)
        else:
            # time spent blocked on someone else's in-flight compile is
            # already booked as stallSeconds — keep it out of execute
            self.metrics.record_phase_seconds(
                execute=max(0.0, dt - broker_info.get("wait_s", 0.0))
            )
        self._maybe_speculate(enc, config, "gang", record=record, window=window)
        return (enc, gang)

    def _gang_finish(self, disp, record: bool):
        """The deferred tail of a gang pass: decode (ONE batched device
        transfer for the assignment diff), victim deletes, write-backs.
        Releases the pass's engine lease on every exit."""
        try:
            return self._gang_finish_inner(disp, record)
        finally:
            self._unlease_engine()

    def _gang_finish_inner(self, disp, record: bool):
        import numpy as np

        enc, gang = disp
        t_decode = time.perf_counter()
        if record and getattr(gang, "_kss_eager_fallback", False):
            # a degraded pass's record decode lazily builds its replay
            # programs (_recorder/_assemble_trace) — those compiles must
            # run un-jitted too, or the "slow but completes" guarantee
            # dies right here on the same wedged compiler
            with eager_execution():
                results = gang.results()
        else:
            results = gang.results() if record else None
        # preemption victims: pre-bound pods the preempt phase evicted.
        # They are NOT in placements (decode covers queued pods only), so
        # diff the full [P] assignment exactly like the sequential path —
        # upstream preemption deletes victims through the API. One
        # device_get fetches both sides of the diff; placements decode
        # reads the already-landed `after` rows (no second sync).
        before, after = jax.device_get(
            (enc.state0.assignment, gang._final_state.assignment)
        )
        before = np.asarray(before)
        after = np.asarray(after)
        placements = gang.enc.decode_assignment(after)
        rounds = int(np.asarray(gang._rounds))
        # booked here, not at dispatch: the rounds scalar stays on
        # device until this finish-path fetch (async overlap depends on
        # the dispatch staying sync-free)
        self.metrics.record_gang(fixpoint_rounds=rounds)
        for p_idx in np.nonzero((before >= 0) & (after < 0))[0]:
            ns, name = enc.pod_keys[int(p_idx)]
            self.store.delete("pods", name, ns)
        if results is not None:
            # the sequential write-back rule: last record per pod wins
            # (a nominated pod's retry overwrites its first record)
            for res in results:
                patch: dict = {
                    "metadata": {
                        "name": res.pod_name,
                        "namespace": res.pod_namespace,
                        "annotations": res.to_annotations(),
                    }
                }
                sel = placements.get((res.pod_namespace, res.pod_name), "")
                if sel:
                    patch["spec"] = {"nodeName": sel}
                if (
                    self.store.get("pods", res.pod_name, res.pod_namespace)
                    is not None
                ):
                    self.store.apply("pods", patch)
        else:
            for (ns, name), node_name in placements.items():
                if not node_name:
                    continue
                if self.store.get("pods", name, ns) is not None:
                    self.store.apply(
                        "pods",
                        {
                            "metadata": {"name": name, "namespace": ns},
                            "spec": {"nodeName": node_name},
                        },
                    )
        self.metrics.record_phase_seconds(
            decode=time.perf_counter() - t_decode
        )
        self._fleet_sample(enc, gang._final_state, "gang")
        return placements, rounds, results

    def _encode_current(self, config) -> "object | None":
        """Encode the store's current pending state under the pass's
        single config read (shared by the sequential and gang passes);
        None when nothing is schedulable.

        Three tiers, cheapest first: the (latest rv, config) LRU serves
        byte-unchanged stores verbatim; the delta encoder replays the
        store's events into the retained encoding (O(Δ)); a full
        `encode_cluster` covers everything the delta path can't prove
        exact. Encode wall time + the path taken land in the metrics'
        phase breakdown."""
        t0 = time.perf_counter()
        # the dtype policy is re-read each pass, and both cache tiers are
        # policy-aware: the LRU keys on the policy name, and the delta
        # encoder falls back to a full re-encode when its retained
        # tensors carry another policy's widths — a KSS_DTYPE_POLICY flip
        # can never serve a stale encoding or scatter into a wrong-width
        # tensor (counted as encodePolicyMisses)
        policy = policy_from_env()
        cache_key = (self.store.latest_rv(), policy.name)
        cached = self._enc_cache.get(cache_key, config)
        if cached is not EncodingCache.MISS:
            # published under the state lock: out-of-pass readers
            # (`encode_info`) must not race the write (KSS6xx)
            with self._lock:
                self.last_encode_info = {"mode": "cached"}
            self.metrics.record_encode("cached", time.perf_counter() - t0)
            telemetry.complete(
                "pass.encode", t0, time.perf_counter(), mode="cached"
            )
            return cached
        self._delta.policy = policy
        enc, info = self._delta.encode(self.store, config)
        if info.get("reason") == "dtype-policy-change":
            self.metrics.record_encode_policy_miss()
        self._enc_cache.put(cache_key, config, enc)
        with self._lock:
            self.last_encode_info = info
        self.metrics.record_encode(info["mode"], time.perf_counter() - t0)
        telemetry.complete(
            "pass.encode", t0, time.perf_counter(), mode=info["mode"]
        )
        if enc is not None:
            # cold-start accounting (utils/ledger.py): the process's
            # first real cluster encode just landed (latched)
            ledger_mod.COLD_START.mark("firstEncode")
        return enc

    # -- predictive compilation --------------------------------------------

    def _maybe_speculate(
        self, enc, config, kind: str, record: bool = False, window=None
    ) -> None:
        """The watermark trigger of the predictive warm-up service: when
        the live pod count drifts past 80% of the current pod-capacity
        bucket (or would fit the next bucket down with the same
        headroom), hand the broker a background task that re-encodes the
        cluster at the adjacent bucket and compiles the matching engine
        — so the eventual bucket crossing finds a warm executable
        instead of stalling the request thread for the XLA compile.
        Armed once per (bucket, target); disabled entirely by
        KSS_NO_SPECULATIVE_COMPILE=1 (docs/performance.md)."""
        broker = self.broker
        if not broker.speculative:
            return
        targets = adjacent_bucket_targets(
            enc.n_pods, enc.P, lo=self._delta.pod_lo
        )
        if not targets:
            return
        if self._spec_bucket != enc.P:
            # the live bucket moved: re-arm (each pair speculates once)
            self._spec_bucket = enc.P
            self._spec_armed = set()
        for target in targets:
            token = (kind, id(config), enc.N, target, window, bool(record))
            if token in self._spec_armed:
                continue
            self._spec_armed.add(token)
            broker.speculate(
                token,
                self._speculation_task(config, kind, record, window, target),
                metrics=self.metrics,
            )

    def _speculation_task(self, config, kind: str, record: bool, window, target: int):
        """A broker background task: encode the CURRENT store at the
        predicted pod-capacity bucket and return (key, build) for an
        engine warmed at those shapes. Runs entirely off the request
        thread (the store is internally locked; encode + compile are
        pure); a cluster that outgrew the prediction by the time the
        worker runs simply skips."""
        store = self.store
        policy = self._delta.policy
        node_lo = self._delta.node_lo
        pod_lo = self._delta.pod_lo
        # the device epoch at ARMING time: a speculative build must key
        # like the passes it serves (a failover between arming and the
        # worker running simply wastes the stale build)
        epoch = self._device_epoch

        def _sig(base: tuple) -> tuple:
            return base + (("devepoch", epoch),) if epoch else base

        def task():
            from ..engine.encode import encode_cluster
            from ..utils.compilecache import capacity_buckets

            nodes = store.list("nodes")
            pods = store.list("pods")
            if not nodes or not pods or len(pods) > target:
                return None
            if kind == "seq" and not any(
                not (p.get("spec") or {}).get("nodeName") for p in pods
            ):
                # an empty pending queue would bake a zero-length scan —
                # useless for serving the crossing
                return None
            ncap, _ = capacity_buckets(
                len(nodes), len(pods), node_lo=node_lo, pod_lo=pod_lo
            )
            enc_s = encode_cluster(
                nodes,
                pods,
                config,
                policy=policy,
                priorityclasses=store.list("priorityclasses"),
                namespaces=store.list("namespaces"),
                pvcs=store.list("pvcs"),
                pvs=store.list("pvs"),
                storageclasses=store.list("storageclasses"),
                node_capacity=ncap,
                pod_capacity=target,
            )
            if kind == "gang":
                from ..engine.gang import GangScheduler

                chunk = gang_chunk()
                sig = _sig((
                    "gang",
                    GangScheduler.compile_signature(enc_s),
                    GangScheduler.effective_window(enc_s, window, chunk),
                ))

                def build():
                    return GangScheduler(
                        enc_s, strict=True, chunk=chunk, eval_window=window
                    ).warmup(record=record)

            else:
                sig = _sig(("seq", BatchedScheduler.compile_signature(enc_s)))

                def build():
                    return BatchedScheduler(
                        enc_s, record=True, strict=True
                    ).warmup()

            return sig, build

        return task

    # -- async (pipelined) passes ------------------------------------------

    def begin_pass(self) -> SchedulingPassHandle:
        """Dispatch one sequential pass and return without the decode /
        write-back tail: device execution proceeds while the caller does
        other host-side work; `handle.resolve()` finishes the pass (one
        batched device transfer, store write-backs, pass metrics) and
        returns the scheduled count. The pass lock stays held until
        resolve — see SchedulingPassHandle."""
        if self.disabled:
            raise SchedulerServiceDisabled()
        self._schedule_lock.acquire()
        try:
            with self._session_scope():
                with self._lock:
                    config = self._config
                mode = "extender" if config.extenders else "sequential"
                pass_id = self._next_pass_id()
                t0 = time.perf_counter()
                with telemetry.pass_context(pass_id), telemetry.span(
                    f"pass.{mode}.dispatch", pass_id=pass_id
                ):
                    disp = self._seq_dispatch(config)
                info = self.last_encode_info
                # the originating request's distributed-trace id: resolve
                # may run on a different thread, so the handle carries it
                armed_trace = telemetry.current_trace_id()
        except BaseException:
            self._unlease_engine()
            self._schedule_lock.release()
            raise

        def finish() -> int:
            # the in-flight window: device execution of THIS pass ran
            # from dispatch until now, overlapping whatever host work
            # the caller did in between — the one span shape that lands
            # on the synthetic device track and can OVERLAP host spans
            telemetry.complete(
                "device.execute",
                t0,
                time.perf_counter(),
                tid=telemetry.DEVICE_TID,
                pass_id=pass_id,
                mode=mode,
                trace=armed_trace,
            )
            with self._session_scope(), telemetry.trace_context(
                armed_trace
            ), telemetry.pass_context(
                pass_id
            ), telemetry.span(f"pass.{mode}.resolve", pass_id=pass_id):
                results = [] if disp is None else self._seq_finish(disp)
                scheduled = sum(
                    1 for r in results if r.status == "Scheduled"
                )
            # distinct pods, like the synchronous pass (a preempting pod
            # yields two records); the explicit pass_id keeps the
            # latency histogram's exemplar causal outside pass_context
            self.metrics.record(
                metrics_mod.PassRecord(
                    mode,
                    len({(r.pod_namespace, r.pod_name) for r in results}),
                    scheduled,
                    time.perf_counter() - t0,
                ),
                pass_id=pass_id,
            )
            return scheduled

        return SchedulingPassHandle(self, mode, finish, info, pass_id=pass_id)

    def begin_gang_pass(
        self, record: bool = False, window: "int | None" = None
    ) -> SchedulingPassHandle:
        """Gang-mode `begin_pass` (see above): dispatch now, decode /
        write-backs at `resolve()`."""
        if self.disabled:
            raise SchedulerServiceDisabled()
        if window is not None and int(window) < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self._schedule_lock.acquire()
        try:
            with self._session_scope():
                with self._lock:
                    config = self._config
                if config.extenders:
                    raise ValueError(
                        "gang mode does not support extenders; use "
                        "sequential mode"
                    )
                pass_id = self._next_pass_id()
                t0 = time.perf_counter()
                with telemetry.pass_context(pass_id), telemetry.span(
                    "pass.gang.dispatch", pass_id=pass_id
                ):
                    disp = self._gang_dispatch(config, record, window)
                info = self.last_encode_info
                armed_trace = telemetry.current_trace_id()
        except BaseException:
            self._unlease_engine()
            self._schedule_lock.release()
            raise

        def finish() -> int:
            telemetry.complete(
                "device.execute",
                t0,
                time.perf_counter(),
                tid=telemetry.DEVICE_TID,
                pass_id=pass_id,
                mode="gang",
                trace=armed_trace,
            )
            if disp is None:
                self.metrics.record(
                    metrics_mod.PassRecord(
                        "gang", 0, 0, time.perf_counter() - t0
                    ),
                    pass_id=pass_id,
                )
                return 0
            with self._session_scope(), telemetry.trace_context(
                armed_trace
            ), telemetry.pass_context(
                pass_id
            ), telemetry.span("pass.gang.resolve", pass_id=pass_id):
                placements, rounds, _results = self._gang_finish(disp, record)
            scheduled = sum(1 for v in placements.values() if v)
            self.metrics.record(
                metrics_mod.PassRecord(
                    "gang",
                    len(placements),
                    scheduled,
                    time.perf_counter() - t0,
                    rounds,
                ),
                pass_id=pass_id,
            )
            return scheduled

        return SchedulingPassHandle(self, "gang", finish, info, pass_id=pass_id)

    def _schedule_locked(self, config) -> list[PodSchedulingResult]:
        # the synchronous pass's device window, on the synthetic device
        # track like the async handle's (encode + engine execution live
        # inside the dispatch): pass/session/trace ids stamp from the
        # ambient contexts — the request thread runs the whole pass
        t0 = time.perf_counter()
        disp = self._seq_dispatch(config)
        if disp is None:
            return []  # nothing schedulable: no device work to record
        telemetry.complete(
            "device.execute",
            t0,
            time.perf_counter(),
            tid=telemetry.DEVICE_TID,
            mode="extender" if config.extenders else "sequential",
        )
        return self._seq_finish(disp)

    def _seq_dispatch(self, config):
        """One sequential dispatch under the execution ladder (see
        `_gang_dispatch`): device faults inside the closure escalate
        through retry → mesh shrink → CPU failover."""
        self._batch_fallback_counted = False
        return self._supervised_dispatch(
            lambda: self._seq_dispatch_once(config)
        )

    def _seq_dispatch_once(self, config):
        """Encode + execute one sequential pass (engine via the broker);
        returns an opaque tuple for `_seq_finish`, or None when nothing
        is schedulable. Trace decode and write-backs are deferred to the
        finish — the async pipeline's split point."""
        enc = self._encode_current(config)
        if enc is None:
            return None
        self._fire_device_dispatch()
        if config.extenders:
            # host-callback loop: device segments + extender HTTP calls,
            # with the same compiled-program reuse as the batch path.
            # Inherently synchronous (the extenders answer over HTTP
            # mid-pass), so the run happens here; only write-backs defer.
            # Extender-touched passes are never batch-eligible (the
            # mid-pass HTTP callbacks are per-session): solo, counted.
            self._count_solo_fallback()
            from ..engine.extender_loop import ExtenderScheduler

            sig = self._epoch_sig(
                ("ext", BatchedScheduler.compile_signature(enc))
            )
            self._lease_engine(sig)
            holder: dict = {}
            # one extender-service read per pass, under the state lock
            # (restart() swaps it there — guarded-state contract KSS6xx):
            # the whole pass runs against one consistent service
            with self._lock:
                ext_service = self.extender_service

            def build():
                t0 = time.perf_counter()
                es = ExtenderScheduler(enc, ext_service)
                holder["built_s"] = time.perf_counter() - t0
                return es

            try:
                ext_sched = self.broker.get_resilient(
                    sig, build, metrics=self.metrics, scope=self.session_id
                )
            except CompileUnavailable as e:
                ext_sched = self._eager_fallback(build, e)
            else:
                if "built_s" in holder:
                    self.metrics.record_engine_build(holder["built_s"])
                else:
                    ext_sched.retarget(enc, ext_service)
            t0 = time.perf_counter()
            results = ext_sched.run()
            self.metrics.record_phase_seconds(execute=time.perf_counter() - t0)
            return ("ext", enc, ext_sched, results)
        # reuse the previous pass's compiled program when the encoding
        # is compile-compatible (same padded shapes + baked statics)
        sig = self._epoch_sig(("seq", BatchedScheduler.compile_signature(enc)))
        # cross-tenant continuous batching (server/batchplane.py): a
        # batch-compatible pass may be served by ONE device dispatch
        # shared with other sessions' concurrent passes; None falls
        # through to today's solo dispatch
        disp = self._maybe_batched_dispatch(sig, enc)
        if disp is not None:
            return disp
        self._lease_engine(sig)
        t0 = time.perf_counter()
        holder = {}

        def build():
            s = BatchedScheduler(enc, record=True, strict=True)
            # jit is lazy: the first run IS the XLA compile, so the
            # broker's miss wall time is the true request-thread stall
            s.run()
            holder["ran"] = True
            return s

        broker_info: dict = {}
        try:
            sched = self.broker.get_resilient(
                sig, build, info=broker_info,
                metrics=self.metrics, scope=self.session_id,
            )
        except CompileUnavailable as e:
            return ("batch", enc, self._eager_fallback(build, e), None)
        if not holder.get("ran"):
            sched.retarget(enc)
            sched.run()
        dt = time.perf_counter() - t0
        # a fresh build's first run IS the XLA compile (jit is
        # lazy): book it as compile; warm passes book as execute —
        # minus any wait on an in-flight compile (that is stallSeconds)
        if holder.get("ran"):
            self.metrics.record_engine_build(dt)
        else:
            self.metrics.record_phase_seconds(
                execute=max(0.0, dt - broker_info.get("wait_s", 0.0))
            )
        self._maybe_speculate(enc, config, "seq")
        return ("batch", enc, sched, None)

    def _maybe_batched_dispatch(self, sig: tuple, enc):
        """Try to serve this sequential pass through the cross-tenant
        batch plane (server/batchplane.py): eligible passes enroll in a
        collection window under the broker key `sig` and come back with
        their slice of ONE vmapped device dispatch — placements and
        trace bytes identical to solo. Returns the same opaque tuple
        `_seq_dispatch_once` builds, or None for solo dispatch.

        Ineligible (counted ``soloFallbacks``): a session-scoped or
        process fault plane (injected faults are per-tenant semantics a
        shared dispatch would conflate — the bulkhead contract), or an
        escalated device rung (rung overrides pin dispatch devices per
        session; escalated sessions also key differently via the epoch
        suffix). A window that closes with one enrollee, a draining
        plane, or a failed batched execution also return solo — the
        plane can degrade throughput, never correctness."""
        import numpy as np

        plane = self.batch_plane
        if plane is None:
            return None
        if (
            self.fault_plane is not None
            or faultinject.active() is not None
            or self.device_rung != "device"
        ):
            self._count_solo_fallback()
            return None
        # the decode-engine for THIS pass: its jitted programs are never
        # invoked (the batch slice lands in _final_state and _trace
        # before results() could trigger a run), so it costs kernel
        # closures, not an XLA compile — and a signature-stable session
        # reuses the previous pass's instance via retarget (the broker's
        # warm-engine pattern), paying construction only when the
        # bucket/config actually moves
        cached = self._batch_decode_cache
        if cached is not None and cached[0] == sig:
            engine = cached[1].retarget(enc)
        else:
            engine = BatchedScheduler(enc, record=True, strict=True)
            self._batch_decode_cache = (sig, engine)
        queue = np.asarray(enc.queue, np.int32)
        bucket = BatchedScheduler.queue_bucket(len(queue))
        if bucket > len(queue):
            queue = np.concatenate(
                [queue, np.full(bucket - len(queue), -1, np.int32)]
            )
        out = plane.submit(
            sig, engine, queue,
            metrics=self.metrics, session_id=self.session_id,
        )
        if out is None:
            self._count_solo_fallback()
            return None
        engine._final_state, engine._trace = out
        return ("batch", enc, engine, None)

    def _maybe_batched_gang_dispatch(self, sig: tuple, enc, chunk, window):
        """Gang-pass twin of `_maybe_batched_dispatch`: enroll this gang
        pass's fused fixpoint in the batch plane (`batch.gang.run` — the
        vmapped `gang.fixpoint` over the session axis) and come back
        with this session's slice of ONE device dispatch. Returns the
        same `(enc, gang)` tuple `_gang_dispatch_once` builds for solo,
        so `_gang_finish` is oblivious to how the pass was served, or
        None for solo dispatch.

        Same ineligibility rules as the sequential path (fault planes,
        escalated rungs, lone windows, draining or failed planes — all
        counted ``soloFallbacks``); additionally, record passes never
        reach here (`_gang_dispatch_once` keeps them solo: the trace
        replay is per-session host work by design)."""
        import numpy as np

        from ..engine.gang import GangScheduler

        plane = self.batch_plane
        if plane is None:
            return None
        if (
            self.fault_plane is not None
            or faultinject.active() is not None
            or self.device_rung != "device"
        ):
            self._count_solo_fallback()
            return None
        # the decode-engine for THIS pass (never dispatched solo: the
        # batch slice lands in _final_state/_rounds before anything
        # could trigger a run) — signature-stable sessions reuse it via
        # retarget, exactly like the sequential decode cache
        cached = self._batch_gang_decode_cache
        if cached is not None and cached[0] == sig:
            gang = cached[1].retarget(enc)
        else:
            gang = GangScheduler(
                enc, strict=True, chunk=chunk, eval_window=window
            )
            self._batch_gang_decode_cache = (sig, gang)
        if gang.fixpoint_fn is None:
            # no fused program for this configuration (static loop):
            # nothing to vmap — solo dispatch
            self._count_solo_fallback()
            return None
        # the PrioritySort queue rides the batch axis as the [P] order
        # tensor (the gang program's queue encoding — fixed length, so
        # bucket-compatible sessions stack without padding logic)
        order, _ = gang.order_arrays()
        out = plane.submit(
            sig, gang, np.asarray(order, np.int32),
            metrics=self.metrics, session_id=self.session_id,
            kind="gang",
        )
        if out is None:
            self._count_solo_fallback()
            return None
        gang._final_state, gang._rounds = out
        return (enc, gang)

    def _seq_finish(self, disp) -> list[PodSchedulingResult]:
        """The deferred tail of a sequential pass: trace decode (batched
        device transfers inside `results()`), victim deletes, write-backs.
        Releases the pass's engine lease on every exit."""
        try:
            return self._seq_finish_inner(disp)
        finally:
            self._unlease_engine()

    def _seq_finish_inner(self, disp) -> list[PodSchedulingResult]:
        import numpy as np

        kind, enc, engine, results = disp
        # one consistent extender service for the whole finish (swapped
        # under the state lock by restart() — KSS6xx)
        with self._lock:
            ext_service = self.extender_service
        t0 = time.perf_counter()
        if kind == "ext":
            final_assignment = engine.final_state.assignment
        elif getattr(engine, "_kss_eager_fallback", False):
            # same trap the gang record decode has: any jit `results()`
            # creates lazily must stay on a degraded pass's eager rung
            # (today the sequential engine jits everything in __init__,
            # but this guard keeps that an implementation detail)
            with eager_execution():
                results = engine.results()
            final_assignment = engine._final_state.assignment
        else:
            results = engine.results()
            final_assignment = engine._final_state.assignment
        self.metrics.record_phase_seconds(decode=time.perf_counter() - t0)

        # preemption victims: pre-bound pods that lost their node (upstream
        # preemption deletes victims through the API). ONE device_get for
        # both sides of the diff instead of two separate host syncs; the
        # placements decode reads the already-landed `after` rows.
        t_decode = time.perf_counter()
        before, after = jax.device_get((enc.state0.assignment, final_assignment))
        before = np.asarray(before)
        after = np.asarray(after)
        placements = enc.decode_assignment(after)
        for p_idx in np.nonzero((before >= 0) & (after < 0))[0]:
            ns, name = enc.pod_keys[int(p_idx)]
            self.store.delete("pods", name, ns)

        # write results back onto the pod objects (last record per pod wins
        # — a nominated pod's retry attempt overwrites its first record,
        # like the reference's sequential annotation updates)
        for res in results:
            annotations = res.to_annotations()
            annotations.update(
                ext_service.annotations_for(res.pod_namespace, res.pod_name)
            )
            patch: dict = {
                "metadata": {
                    "name": res.pod_name,
                    "namespace": res.pod_namespace,
                    "annotations": annotations,
                }
            }
            sel = placements.get((res.pod_namespace, res.pod_name), "")
            if sel:
                patch["spec"] = {"nodeName": sel}
            if self.store.get("pods", res.pod_name, res.pod_namespace) is not None:
                self.store.apply("pods", patch)
            # flushed results are purged, like the reference reflector's
            # DeleteData after AddStoredResultToPod (storereflector.go:70-119)
            ext_service.delete_data(res.pod_namespace, res.pod_name)
        self.metrics.record_phase_seconds(
            decode=time.perf_counter() - t_decode
        )
        self._fleet_sample(
            enc,
            engine.final_state if kind == "ext" else engine._final_state,
            "extender" if kind == "ext" else "sequential",
        )
        return results


@locking.guard_inferred
class SimulatorService:
    """Store + scheduler + snapshot composites (the DI container analogue).

    `external_scheduler_enabled` mirrors the reference's
    EXTERNAL_SCHEDULER_ENABLED (simulator.go:75-80: the internal
    scheduler is never started): the scheduler service is built disabled,
    and pod binds arriving through the resource CRUD surface (an external
    scheduler setting `spec.nodeName`) are recorded into the service's
    metrics as mode="external" passes."""

    def __init__(
        self,
        initial_config: "SchedulerConfiguration | None" = None,
        external_scheduler_enabled: bool = False,
        broker: "CompileBroker | None" = None,
        session_id: "str | None" = None,
        fault_plane=None,
    ):
        self.store = ResourceStore()
        self._controllers_lock = locking.make_lock("service.controllers")
        self.external_scheduler_enabled = external_scheduler_enabled
        # replayable JSONL trace of the most recent lifecycle chaos run
        # (run_lifecycle; served by GET /api/v1/lifecycle/trace)
        self.last_lifecycle_trace: "list[dict] | None" = None
        self.scheduler = SchedulerService(
            self.store,
            initial_config,
            disabled=external_scheduler_enabled,
            broker=broker,
            session_id=session_id,
            fault_plane=fault_plane,
        )
        if external_scheduler_enabled:
            # key -> last-seen bound state; a recorded external bind is
            # specifically the pending→bound TRANSITION, so pods imported
            # or replicated already-bound never count as scheduler
            # activity (they enter the map as bound on their ADDED event)
            self._ext_seen: dict[tuple[str, str], bool] = {}
            self._ext_lock = locking.make_lock("service.external")
            self.store.subscribe(self._record_external_bind)
        self.store.snapshot_initial()

    def _record_external_bind(self, ev) -> None:
        """Store subscriber (external mode only): a pod the simulator has
        seen pending that now carries a nodeName is an external
        scheduler's bind — count it. All such transitions are external
        here by construction (the internal engine is disabled)."""
        if ev.kind != "pods":
            return
        meta = (ev.obj or {}).get("metadata", {})
        key = (meta.get("namespace", "default"), meta.get("name", ""))
        with self._ext_lock:
            if ev.event_type == "DELETED":
                self._ext_seen.pop(key, None)
                return
            bound = bool(((ev.obj or {}).get("spec", {}) or {}).get("nodeName"))
            if bound and self._ext_seen.get(key) is False:
                self.scheduler.metrics.record(
                    metrics_mod.PassRecord(
                        mode="external", pods=1, scheduled=1, wall_s=0.0
                    )
                )
            self._ext_seen[key] = bound

    def run_controllers(self) -> int:
        """Run the deterministic controller subset (deployment →
        replicaset expansion, PV binding; controllers/steps.py) to a
        fixpoint over the store. The reference's controller subset runs
        CONTINUOUSLY against its apiserver (simulator/controller/
        controller.go:31-46 — create a Deployment, get Pods); here the
        serving shell invokes this after every resource mutation, which
        is the same convergence expressed deterministically. Returns the
        rounds executed (0 when nothing the controllers read exists —
        the cheap early-exit that keeps bulk pod/node loads O(N)).
        Fixpoints are serialized: concurrent request threads must not
        interleave partial reconciles (one thread's freshly created pods
        racing another's round)."""
        store = self.store
        if (
            store.count("deployments") == 0
            and store.count("replicasets") == 0
            and (store.count("pvcs") == 0 or store.count("pvs") == 0)
        ):
            return 0
        from ..controllers.steps import run_to_fixpoint

        with self._controllers_lock:
            return run_to_fixpoint(store)

    # -- export / import / reset -------------------------------------------

    def export(self) -> dict:
        """Export resources + config. In external mode the config is not
        exported (reference export.go:400-412 tolerates
        ErrServiceDisabled and omits it)."""
        try:
            cfg = self.scheduler.get_config()
        except SchedulerServiceDisabled:
            cfg = None
        return export_snapshot(self.store, cfg)

    def import_(self, snapshot: dict, ignore_err: bool = False) -> list[str]:
        """Restart the scheduler with the imported config (unless absent),
        then apply resources in dependency order (reference
        export.go:246-263 Import). In external mode the config restart is
        skipped, resources still apply (export.go:251-257)."""
        cfg = snapshot.get("schedulerConfig")
        if cfg:
            try:
                self.scheduler.restart(cfg)
            except SchedulerServiceDisabled:
                pass
        _, errors = import_snapshot(self.store, snapshot, ignore_err=ignore_err)
        return errors

    def reset(self) -> None:
        """Reset resources, and the scheduler config unless disabled
        (reference reset.go:80 tolerates ErrServiceDisabled)."""
        # note: no _ext_seen maintenance needed here — store.reset()
        # dispatches DELETED + re-ADDED events through the subscriber,
        # which rebuilds the map (clearing afterwards would wipe the
        # pending-state of boot-snapshot pods and undercount their
        # later external binds)
        self.store.reset()
        try:
            self.scheduler.reset()
        except SchedulerServiceDisabled:
            pass

    # -- lifecycle / chaos runs --------------------------------------------

    def run_lifecycle(self, spec: "dict | object") -> dict:
        """Run one cluster-lifecycle chaos timeline (lifecycle/engine.py)
        over its OWN isolated store — like the /api/v1/scenario route, the
        serving store is never mutated — while the passes and disruption
        tallies flow into THIS service's scheduler metrics, so
        `GET /api/v1/metrics` reflects lifecycle activity. The run's
        replayable JSONL trace is retained on `last_lifecycle_trace` for
        `GET /api/v1/lifecycle/trace`."""
        from ..lifecycle.engine import LifecycleEngine
        from ..scenario.chaos import ChaosSpec

        if not isinstance(spec, ChaosSpec):
            spec = ChaosSpec.from_dict(spec)
        engine = LifecycleEngine(spec, metrics=self.scheduler.metrics)
        result = engine.run()
        self.last_lifecycle_trace = engine.trace
        return result
