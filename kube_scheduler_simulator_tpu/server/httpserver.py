"""The simulator HTTP server — the reference's full API surface.

Routes (reference: simulator/server/server.go:42-57):

    GET  /api/v1/schedulerconfiguration      current config (200)
    POST /api/v1/schedulerconfiguration      restart w/ new config (202)
    PUT  /api/v1/reset                       reset resources + config (202)
    GET  /api/v1/export                      ResourcesForImport JSON (200)
    POST /api/v1/import                      apply snapshot (200)
    GET  /api/v1/listwatchresources          list+watch stream (SSE-style)
    POST /api/v1/extender/<verb>/<id>        extender proxy (extender.py)

Two deliberate extensions (the reference exposes resource CRUD through its
embedded kube-apiserver, which this framework replaces with the in-process
typed store — SURVEY.md §2 #3):

    GET/PUT            /api/v1/resources/<kind>
    GET/DELETE         /api/v1/resources/<kind>/<ns>/<name>  (or /<name>)
    POST               /api/v1/schedule      run one batched scheduling pass
    GET                /api/v1/metrics       scheduling-pass counters
                                             (decisions/sec, utils/metrics.py;
                                             ?format=prometheus for text
                                             exposition)
    GET                /api/v1/debug/trace   flight-recorder window as Chrome
                                             trace-event JSON (Perfetto)
    POST               /api/v1/debug/profile arm/disarm a jax.profiler capture
    GET                /api/v1/events        live telemetry SSE stream
                                             (docs/observability.md)
    GET                /api/v1/timeseries    fleet & memory observatory
                                             sample window (per-pass HBM
                                             + cluster-quality samples,
                                             utils/fleetstats.py;
                                             KSS_FLEET_STATS=1)
    GET                /api/v1/alerts        SLO burn-rate alerts: active
                                             + per-objective status +
                                             the bounded transition
                                             history ring (utils/slo.py;
                                             KSS_SLO=1 or a PUT /slo
                                             override)
    GET/PUT            /api/v1/slo           the session's SLO objective
                                             set: GET status, PUT a
                                             declarative per-tenant
                                             override over the KSS_SLO_*
                                             defaults
    POST               /api/v1/lifecycle     run a ChaosSpec chaos timeline
                                             (lifecycle/engine.py, isolated store)
    GET                /api/v1/lifecycle/trace   last run's JSONL event trace
    GET                /  (or /ui)           built-in dashboard (webui.py)

The multi-tenant session plane (docs/sessions.md — server/sessions.py):

    GET/POST           /api/v1/sessions      list / create sessions
    GET/DELETE         /api/v1/sessions/<id> session info / destroy
    POST               /api/v1/sessions/<id>/fork    branch a session
    POST               /api/v1/sessions/<id>/evict   snapshot to disk now
    *                  /api/v1/sessions/<id>/<any route above>
                                             every route in this file,
                                             scoped to that session's
                                             store/scheduler/metrics

    GET                /api/v1/healthz       liveness (always 200)
    GET                /api/v1/readyz        readiness: 503 while the
                                             shared compile broker is
                                             cooldown-saturated or its
                                             worker crashed, or while
                                             the server is draining
                                             (state "draining", distinct
                                             from "cooldown-saturated")
    POST               /api/v1/admin/drain   begin the zero-loss drain
                                             (docs/resilience.md): shed
                                             new requests, finish
                                             in-flight passes, snapshot
                                             every session, quiesce the
                                             broker; GET reports status

Legacy (un-prefixed) routes operate on the implicit `default` session.
Admission control (session limit, per-session pending-pod quota, the
bounded concurrent-pass semaphore) sheds with the same structured 503 +
Retry-After as compile degradation.

The watch stream mirrors the reference's wire shape — a sequence of JSON
objects `{"Kind": ..., "EventType": ..., "Obj": {...}}` flushed per event
(simulator/resourcewatcher/streamwriter/streamwriter.go:18-51), with the
same `<kind>LastResourceVersion` query parameters and list-as-ADDED replay
when a version is absent (resourcewatcher.go:94-120). A stale version gets
a relist (the 410-Gone analogue) instead of silently dropped events.

Implementation is stdlib-only (ThreadingHTTPServer): the serving shell has
no third-party dependencies, matching the zero-install environment.
"""

from __future__ import annotations

import json
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..models.store import KINDS, NAMESPACED, StaleResourceVersion
from ..utils import bundles as bundles_mod
from ..utils import fleetstats, locking
from ..utils import ledger as ledger_mod
from ..utils import metrics as metrics_mod
from ..utils import slo as slo_mod
from ..utils import telemetry
from ..utils.broker import CompileDeadlineExceeded, CompileUnavailable
from .replication import ReplicationPlane
from .service import (
    EngineDegraded,
    InvalidSchedulerConfiguration,
    SchedulerServiceDisabled,
    SimulatorService,
)
from .sessions import (
    DEFAULT_SESSION_ID,
    ServerSaturated,
    SessionBusy,
    SessionLimitExceeded,
    SessionManager,
    SessionQuotaExceeded,
    UnknownSession,
)

# Retry-After hint (seconds) on 503 degradation responses: long enough
# for a compile cooldown window to elapse, short enough that a client
# retry lands while the engine is probably healthy again.
DEGRADED_RETRY_AFTER_S = 2

# Bound of each SSE subscriber's event queue: past it the consumer is
# provably slower than the span source, and the subscriber is
# DISCONNECTED (drops counted in sseDroppedEvents) rather than served a
# silently gap-ridden stream (docs/sessions.md).
SSE_QUEUE_MAX = 4096

# kind → (watch wire name, lastResourceVersion query param); reference
# resourcewatcher.go:22-30 + handler/watcher.go:27-34 (note the singular
# "namespaceLastResourceVersion").
WATCH_KINDS = {
    "pods": ("pods", "podsLastResourceVersion"),
    "nodes": ("nodes", "nodesLastResourceVersion"),
    "pvs": ("persistentvolumes", "pvsLastResourceVersion"),
    "pvcs": ("persistentvolumeclaims", "pvcsLastResourceVersion"),
    "storageclasses": ("storageclasses", "scsLastResourceVersion"),
    "priorityclasses": ("priorityclasses", "pcsLastResourceVersion"),
    "namespaces": ("namespaces", "namespaceLastResourceVersion"),
}

# API routes that carry no `http.request` span: the trace export must not
# hold an open span of its own, and a stream never ends to close one
UNSPANNED_ROUTES = ("GET debug/trace", "GET events", "GET listwatchresources")


def route_template(method: str, path: str) -> "str | None":
    """The route a request addresses, as a low-cardinality template for
    its `http.request` span: the method and the path with its variable
    segments replaced — session and checkpoint ids, object namespaces
    and names, extender ids (`PUT /api/v1/resources/pods`,
    `DELETE /api/v1/resources/pods/{namespace}/{name}`). None for the
    routes in `UNSPANNED_ROUTES`."""
    parts = [p for p in urlparse(path).path.split("/") if p]
    if parts[:2] != ["api", "v1"]:
        if parts in ([], ["ui"]):
            return f"{method} /" + "/".join(parts)
        return f"{method} (not found)"
    prefix, rest = ["api", "v1"], parts[2:]
    if rest[:1] == ["sessions"] and len(rest) > 1:
        prefix += ["sessions", "{id}"]
        rest = rest[2:]
    if rest[:2] == ["admin", "checkpoints"] and len(rest) > 2:
        rest = rest[:2] + ["{id}"]
    elif rest[:1] == ["resources"] and len(rest) > 2:
        rest = rest[:2] + (["{namespace}", "{name}"] if len(rest) > 3 else ["{name}"])
    elif rest[:1] == ["extender"] and len(rest) > 2:
        rest = rest[:2] + ["{id}"]
    if f"{method} " + "/".join(rest) in UNSPANNED_ROUTES:
        return None
    return f"{method} /" + "/".join(prefix + rest)


@locking.guard_inferred
class SimulatorServer:
    """Owns the HTTP server thread over one `SimulatorService`."""

    def __init__(
        self,
        service: "SimulatorService | None" = None,
        host: str = "127.0.0.1",
        port: int = 1212,
        auto_schedule: bool = False,
        extender_service=None,
        cors_allowed_origins: "list[str] | None" = None,
        session_config: "dict | None" = None,
    ):
        self.service = service or SimulatorService()
        self.auto_schedule = auto_schedule
        self.extender_service = extender_service
        self.cors_allowed_origins = cors_allowed_origins or []
        # the multi-tenant session plane (server/sessions.py): adopts
        # self.service as the implicit `default` session and owns the
        # SHARED CompileBroker + admission knobs. `session_config`
        # overrides the KSS_* environment (tests, embedded drivers).
        self.sessions = SessionManager(self.service, **(session_config or {}))
        # the fleet durability plane's shipper (server/replication.py):
        # dormant until the router pushes a peer topology through
        # POST /api/v1/admin/replication. Registered with the manager so
        # drain ships one last round and sync-mode journal appends ride
        # the acknowledging thread to the ring successors.
        self.replication = ReplicationPlane(
            self.sessions, env=(session_config or {}).get("env")
        )
        self.sessions.set_replication(self.replication)
        # SSE subscriber accounting (the satellite hardening): live
        # subscriber count against the manager's cap, and the events
        # dropped on slow consumers (surfaced as sseDroppedEvents)
        self._sse_lock = locking.make_lock("http.sse")
        self._sse_subs = 0
        self._sse_dropped = 0
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.httpd.daemon_threads = True
        self._thread: "threading.Thread | None" = None
        # one scenario/sweep run at a time over this server (KEP-140's
        # one-scenario-at-a-time; each request thread would otherwise
        # drive the device concurrently)
        self._scenario_lock = locking.make_lock("http.scenario")
        # POST /api/v1/debug/profile arming state: the active jax
        # profiler capture's log dir, or None (at most one per process —
        # jax.profiler is a process-wide singleton)
        self._profile_lock = locking.make_lock("http.profile")
        self._profile_dir: "str | None" = None
        # graceful-drain state (docs/resilience.md): begin_drain flips
        # `draining` (readyz 503 + request shedding) and runs the
        # session-plane drain on a background thread; `drain_done`
        # fires when every session is snapshotted and the broker is
        # quiesced — the CLI's SIGTERM path waits on it and exits 0
        self._drain_lock = locking.make_lock("http.drain")
        self._drain_thread: "threading.Thread | None" = None
        self._drain_result: "dict | None" = None
        self.drain_done = threading.Event()
        # birth stamp for healthz/readyz uptimeSeconds (docs/fleet.md):
        # the fleet router's probe reads structured health bodies, so
        # liveness, identity, and load ride the endpoints it already
        # polls instead of a second status surface
        self._started_monotonic = time.monotonic()

    def health_doc(self) -> dict:
        """The shared healthz/readyz body fields: worker identity
        (KSS_WORKER_ID, None outside a fleet), uptime, drain state, and
        the resident-session count — everything the fleet router's
        prober needs from the one endpoint it already polls."""
        return {
            "workerId": metrics_mod.worker_id(),
            "uptimeSeconds": round(
                time.monotonic() - self._started_monotonic, 3
            ),
            "draining": self.draining,
            "activeSessions": len(self.sessions.live_services()),
        }

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self):
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def shutdown(self):
        self.sessions.shutdown()
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)

    # -- graceful drain (docs/resilience.md) --------------------------------

    @property
    def draining(self) -> bool:
        return self.sessions.is_draining()

    def begin_drain(self, deadline_s: "float | None" = None) -> bool:
        """Start the zero-loss drain on a background thread (the route
        and the SIGTERM handler both call this; neither may block for
        the drain deadline). False when a drain is already running —
        begin is idempotent, the first caller wins."""
        with self._drain_lock:
            # shed + readyz flip NOW — an atomic test-and-set under the
            # MANAGER's lock (the flag is its claimed state, KSS6xx)
            if not self.sessions.begin_draining():
                return False
            self._drain_thread = threading.Thread(
                target=self._drain_run,
                args=(deadline_s,),
                name="kss-drain",
                daemon=True,
            )
            self._drain_thread.start()
            return True

    def _drain_run(self, deadline_s: "float | None") -> None:
        try:
            self._drain_result = self.sessions.drain(deadline_s)
        except Exception as e:  # noqa: BLE001 — a failed drain must not hang exit
            self._drain_result = {"error": f"{type(e).__name__}: {e}"}
        finally:
            self.drain_done.set()

    def drain(self, deadline_s: "float | None" = None, timeout=None) -> dict:
        """Synchronous drain: begin (if not already begun) and wait for
        completion. Embedded drivers and tests use this; the serving
        CLI prefers begin_drain + waiting on `drain_done`."""
        self.begin_drain(deadline_s)
        self.drain_done.wait(timeout)
        return self.drain_status()

    def drain_status(self) -> dict:
        return {
            "draining": self.draining,
            "done": self.drain_done.is_set(),
            "result": self._drain_result,
        }

    def maybe_schedule(self, service: "SimulatorService | None" = None):
        """Post-mutation convergence for the mutated session: the
        controller subset always runs to fixpoint (the reference's
        continuously-running controllers — POST a Deployment, GET its
        Pods), then a scheduling pass follows when --auto-schedule is
        on."""
        svc = service if service is not None else self.service
        svc.run_controllers()
        if self.auto_schedule and not svc.scheduler.disabled:
            # auto-passes obey the same bounded-concurrency semaphore as
            # explicit /schedule; at saturation the pass is SKIPPED (the
            # pod stays pending — the next mutation or an explicit
            # schedule converges it) rather than 503-failing the CRUD
            # that triggered it, and rather than queueing unboundedly
            # behind the device
            if svc.scheduler._schedule_lock.locked():
                # a pass is already converging this session: skip, don't
                # queue on its lock while holding a global slot
                return
            try:
                with self.sessions.pass_slot():
                    svc.scheduler.schedule()
            except ServerSaturated:
                pass

    # -- SSE subscriber accounting ------------------------------------------

    def sse_acquire(self) -> bool:
        """Claim one SSE subscriber slot against the cap
        (KSS_SSE_MAX_SUBSCRIBERS); False = saturated, the route sheds."""
        with self._sse_lock:
            if self._sse_subs >= self.sessions.sse_max_subscribers:
                return False
            self._sse_subs += 1
            return True

    def sse_release(self) -> None:
        with self._sse_lock:
            self._sse_subs -= 1

    def sse_count_drop(self, n: int = 1) -> None:
        with self._sse_lock:
            self._sse_dropped += n

    @property
    def sse_dropped(self) -> int:
        with self._sse_lock:
            return self._sse_dropped


def _make_handler(server: SimulatorServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        # -- plumbing -------------------------------------------------------

        def log_message(self, fmt, *args):  # quiet by default
            pass

        def _cors_headers(self):
            """CORS per the configured allowlist (reference: echo CORS
            middleware fed by CORS_ALLOWED_ORIGIN_LIST, server.go:29-32)."""
            origin = self.headers.get("Origin")
            if origin and origin in server.cors_allowed_origins:
                self.send_header("Access-Control-Allow-Origin", origin)
                self.send_header("Access-Control-Allow-Credentials", "true")

        def _json(self, code: int, payload=None, headers: "dict | None" = None):
            body = b"" if payload is None else json.dumps(payload).encode()
            self.send_response(code)
            self._cors_headers()
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for name, value in (headers or {}).items():
                self.send_header(name, value)
            self.end_headers()
            if body:
                self.wfile.write(body)

        def _error(
            self,
            code: int,
            msg: str,
            kind: str = "",
            detail: str = "",
            headers: "dict | None" = None,
        ):
            """Structured JSON error: `error` is the human line, `kind`
            the machine-matchable class (exception name or an HTTP-ish
            label), `detail` optional context. `message` mirrors `error`
            for pre-existing clients of the old single-key shape."""
            self._json(
                code,
                {
                    "error": msg,
                    "kind": kind or ("client-error" if code < 500 else "server-error"),
                    "detail": detail,
                    "message": msg,
                },
                headers=headers,
            )

        def _degraded(self, e: Exception):
            """Engine-degradation failures (compile deadline exhausted
            with the eager rung unable to serve) map to 503 + a
            Retry-After hint: the condition is load/compile-shaped and
            retryable, not a client error (docs/resilience.md)."""
            return self._error(
                503,
                str(e),
                kind=type(e).__name__,
                detail="engine degraded: compile ladder exhausted; retry "
                "after the cooldown",
                headers={"Retry-After": str(DEGRADED_RETRY_AFTER_S)},
            )

        def _body(self):
            """Parse the request body: JSON first, YAML fallback — the
            dashboard's editor submits the same YAML a kubectl user would
            paste (reference web: Monaco YAML editor)."""
            length = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(length) if length else b""
            if not raw:
                return None
            try:
                return json.loads(raw)
            except json.JSONDecodeError:
                import yaml

                return yaml.safe_load(raw)

        # -- dispatch -------------------------------------------------------

        def do_GET(self):  # noqa: N802 (stdlib casing)
            self._route("GET")

        def do_POST(self):  # noqa: N802
            self._route("POST")

        def do_PUT(self):  # noqa: N802
            self._route("PUT")

        def do_DELETE(self):  # noqa: N802
            self._route("DELETE")

        def do_OPTIONS(self):  # noqa: N802 — CORS preflight
            self.send_response(204)
            self._cors_headers()
            self.send_header(
                "Access-Control-Allow-Methods", "GET, POST, PUT, DELETE"
            )
            self.send_header("Access-Control-Allow-Headers", "Content-Type")
            self.send_header("Content-Length", "0")
            self.end_headers()

        def end_headers(self):  # noqa: N802 (stdlib casing)
            # distributed tracing (docs/observability.md): report the
            # worker-side wall for this request so the router can split
            # request latency into net vs worker without a second probe.
            # Gated on propagation so untraced runs stay byte-identical.
            t0 = getattr(self, "_kss_t0", None)
            if t0 is not None and telemetry.propagate_enabled():
                self.send_header(
                    "X-KSS-Worker-Seconds",
                    f"{time.perf_counter() - t0:.6f}",
                )
            self._kss_t0 = None
            super().end_headers()

        def _route(self, method: str):
            # distributed-trace adoption chokepoint: EVERY api call
            # funnels through here, so parsing the router-minted
            # traceparent once and entering trace_context makes pass,
            # compile, and device.execute spans carry the originating
            # request's trace id (docs/observability.md). Malformed or
            # absent headers degrade to untraced — never an error.
            self._kss_t0 = time.perf_counter()
            tid = None
            if telemetry.propagate_enabled():
                tid = telemetry.parse_traceparent(
                    self.headers.get("traceparent")
                )
            if tid is None:
                return self._route_spanned(method)
            with telemetry.trace_context(tid):
                return self._route_spanned(method)

        def _route_spanned(self, method: str):
            # one `http.request` span per API call while tracing is on,
            # named by its route template, never by an object's name
            route = (
                route_template(method, self.path)
                if telemetry.enabled()
                else None
            )
            if route is None:
                return self._route_inner(method)
            with telemetry.span("http.request", method=method, route=route):
                return self._route_inner(method)

        def _route_inner(self, method: str):
            url = urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            try:
                if method == "GET" and parts in ([], ["ui"]):
                    from .webui import PAGE

                    body = PAGE.encode()
                    self.send_response(200)
                    self._cors_headers()
                    self.send_header("Content-Type", "text/html; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return None
                if parts[:2] != ["api", "v1"]:
                    return self._error(404, "not found")
                rest = parts[2:]
                if rest == ["healthz"] and method == "GET":
                    # structured liveness (docs/fleet.md): the status
                    # code contract is unchanged (always 200); the body
                    # carries identity + uptime + drain state so the
                    # fleet prober needs no second endpoint
                    doc = {"ok": True}
                    doc.update(server.health_doc())
                    return self._json(200, doc)
                if rest == ["readyz"] and method == "GET":
                    return self._readyz()
                if rest == ["admin", "drain"]:
                    if method == "POST":
                        started = server.begin_drain()
                        doc = server.drain_status()
                        doc["started"] = started
                        return self._json(202, doc)
                    if method == "GET":
                        return self._json(200, server.drain_status())
                    return self._error(405, "method not allowed")
                if rest[:2] == ["admin", "checkpoints"] and method == "GET":
                    # the cross-host checkpoint transport's read side
                    # (docs/fleet.md): list sessions + payload digests,
                    # or fetch one as a digest-guarded transport unit.
                    # Deliberately ABOVE the draining shed — the router
                    # re-homes a draining/drained worker's sessions by
                    # fetching them from here.
                    if len(rest) == 2:
                        return self._json(
                            200, server.sessions.checkpoint_index()
                        )
                    if len(rest) == 3:
                        unit = server.sessions.checkpoint_unit(rest[2])
                        if unit is None:
                            return self._error(
                                404,
                                f"no checkpoint for session {rest[2]!r}",
                                kind="UnknownSession",
                            )
                        return self._json(200, unit)
                if rest == ["admin", "replication"]:
                    # router-pushed replication topology (docs/fleet.md);
                    # answerable while draining — membership pushes race
                    # rolling drains and must not bounce
                    if method == "POST":
                        return self._json(
                            200,
                            server.replication.configure(self._body() or {}),
                        )
                    if method == "GET":
                        return self._json(200, server.replication.stats())
                    return self._error(405, "method not allowed")
                if rest == ["admin", "adopt"] and not server.draining:
                    # session adoption (docs/fleet.md). The body selects
                    # the mode; an empty body is the legacy shared-dir
                    # re-scan of KSS_SESSION_DIR. Body-carried modes:
                    #   {"checkpoints": [unit...]}           adopt live
                    #   {"checkpoints": [...], "replica": 1} hold passively
                    #   {"journalAppend": {...}}             sync-mode entry
                    #   {"promote": [sid...]}                replica -> live
                    # All idempotent: known ids are skipped, digests are
                    # verified before anything lands. A DRAINING server
                    # falls through to the shed below — it must not
                    # admit tenants its own drain will never snapshot.
                    if method == "POST":
                        body = self._body()
                        if not body:
                            return self._json(
                                200,
                                {"adopted": server.sessions.adopt_snapshots()},
                            )
                        if not isinstance(body, dict):
                            return self._error(
                                400,
                                "adopt body must be a JSON object",
                                kind="BadAdoptBody",
                            )
                        try:
                            doc = {}
                            if body.get("checkpoints") is not None:
                                doc.update(
                                    server.sessions.receive_checkpoints(
                                        body["checkpoints"],
                                        replica=bool(body.get("replica")),
                                    )
                                )
                            if body.get("journalAppend") is not None:
                                doc.update(
                                    server.sessions.append_replica_journal(
                                        body["journalAppend"]
                                    )
                                )
                            if body.get("promote") is not None:
                                doc.update(
                                    server.sessions.promote_replicas(
                                        body["promote"] or None
                                    )
                                )
                        except ValueError as e:
                            return self._error(
                                400, str(e), kind="BadAdoptBody"
                            )
                        if not doc:
                            return self._error(
                                400,
                                "adopt body carries none of checkpoints/"
                                "journalAppend/promote",
                                kind="BadAdoptBody",
                            )
                        return self._json(200, doc)
                    return self._error(405, "method not allowed")
                if server.draining and not (
                    method == "GET" and rest == ["metrics"]
                ):
                    # the zero-loss drain path (docs/resilience.md):
                    # new work is shed with the same structured 503 +
                    # Retry-After shape as admission control, while
                    # in-flight passes finish and sessions snapshot.
                    # Health, readiness, drain status, and the legacy
                    # metrics scrape stay answerable — an operator must
                    # be able to watch the drain complete.
                    return self._error(
                        503,
                        "server is draining; retry against another replica",
                        kind="ServerDraining",
                        detail="graceful drain in progress: new requests "
                        "are shed, in-flight passes finish, sessions "
                        "snapshot to disk",
                        headers={"Retry-After": str(DEGRADED_RETRY_AFTER_S)},
                    )
                if rest and rest[0] == "sessions":
                    return self._sessions_route(method, rest[1:], url)
                # legacy (un-prefixed) surface: the implicit default
                # session — sid None marks the legacy entry, which the
                # metrics route uses to scrape EVERY session at once
                return self._api(method, rest, url, server.service, None)
            except BrokenPipeError:
                raise
            except UnknownSession as e:
                return self._error(404, str(e), kind="UnknownSession")
            except (
                SessionLimitExceeded,
                SessionQuotaExceeded,
                ServerSaturated,
            ) as e:
                # admission control sheds with the SAME structured 503 +
                # Retry-After shape as compile degradation: overload is
                # a retryable service condition (docs/sessions.md)
                return self._error(
                    503,
                    str(e),
                    kind=type(e).__name__,
                    detail="admission control: load shed; retry after the "
                    "hinted backoff",
                    headers={"Retry-After": str(e.retry_after_s)},
                )
            except SessionBusy as e:
                return self._error(409, str(e), kind="SessionBusy")
            except SchedulerServiceDisabled as e:
                # reference schedulerconfig.go:32-34: external-scheduler
                # mode answers config/scheduling calls with 400
                return self._error(400, str(e), kind="SchedulerServiceDisabled")
            except InvalidSchedulerConfiguration as e:
                return self._error(500, str(e), kind="InvalidSchedulerConfiguration")
            except (EngineDegraded, CompileUnavailable, CompileDeadlineExceeded) as e:
                # the degradation ladder's terminal failures are
                # retryable service conditions, not server bugs: 503
                return self._degraded(e)
            except Exception as e:  # noqa: BLE001 — boundary
                return self._error(
                    500,
                    f"{type(e).__name__}: {e}",
                    kind=type(e).__name__,
                    detail="unhandled error at the API boundary",
                )

        # -- session plane --------------------------------------------------

        def _readyz(self):
            """Readiness for external load balancers: not-ready while
            the SHARED broker is cooldown-saturated (some session's
            compile ladder is exhausted and cooling) or its speculative
            worker crashed — a sick compile plane should be drained, not
            handed fresh tenants. A DRAINING server is also not-ready,
            with the distinct ``state: "draining"`` (docs/resilience.md)
            so orchestrators can tell an intentional rolling-restart
            drain from a sick compile plane."""
            if server.draining:
                doc = {
                    "ready": False,
                    "state": "draining",
                    "reasons": ["server is draining"],
                    "drain": server.drain_status(),
                }
                doc.update(server.health_doc())
                return self._json(
                    503, doc, headers={"Retry-After": str(DEGRADED_RETRY_AFTER_S)}
                )
            health = server.sessions.broker.health()
            reasons = []
            if health["cooldownKeys"]:
                reasons.append(
                    f"{health['cooldownKeys']} compile key(s) in cooldown"
                )
            if health["stuckCompiles"]:
                reasons.append(
                    f"{health['stuckCompiles']} wedged compile thread(s)"
                )
            if health["workerCrashed"]:
                reasons.append("speculative compile worker crashed")
            doc = {
                "ready": not reasons,
                "state": "cooldown-saturated" if reasons else "ready",
                "reasons": reasons,
                "broker": health,
            }
            doc.update(server.health_doc())
            if reasons:
                return self._json(
                    503, doc, headers={"Retry-After": str(DEGRADED_RETRY_AFTER_S)}
                )
            return self._json(200, doc)

        def _sessions_route(self, method: str, rest: list[str], url):
            mgr = server.sessions
            if not rest:
                if method == "GET":
                    return self._json(
                        200,
                        {
                            "sessions": mgr.list_info(),
                            "broker": mgr.broker.stats(),
                            "limits": mgr.stats(),
                        },
                    )
                if method == "POST":
                    body = self._body() or {}
                    if not isinstance(body, dict):
                        return self._error(400, "session spec must be a mapping")
                    try:
                        sess, errors = mgr.create(
                            name=body.get("name"),
                            snapshot=body.get("snapshot"),
                            fault_inject=body.get("faultInject"),
                            slo=body.get("slo"),
                            # explicit id: the fleet router pins the id
                            # it hashed onto this worker (docs/fleet.md)
                            session_id=body.get("id"),
                        )
                    except ValueError as e:
                        # a malformed faultInject spec is the client's
                        # input (admission errors raise their own types)
                        return self._error(400, str(e))
                    doc = sess.info()
                    doc["errors"] = errors
                    return self._json(201, doc)
                return self._error(405, "method not allowed")
            sid, sub = rest[0], rest[1:]
            if not sub:
                if method == "GET":
                    return self._json(200, mgr.info(sid))
                if method == "DELETE":
                    try:
                        mgr.delete(sid)
                    except ValueError as e:
                        return self._error(400, str(e))
                    return self._json(200, {"deleted": sid})
                return self._error(405, "method not allowed")
            if sub == ["fork"] and method == "POST":
                body = self._body() or {}
                sess = mgr.fork(sid, name=(body or {}).get("name"))
                return self._json(201, sess.info())
            if sub == ["evict"] and method == "POST":
                try:
                    path = mgr.evict(sid)
                except ValueError as e:
                    return self._error(400, str(e))
                return self._json(200, {"evicted": sid, "snapshot": path})
            # any other sub-path: the full API surface scoped to this
            # session (restoring it from its snapshot if evicted). The
            # `using` window registers the request with the manager so
            # the idle sweeper cannot evict the service out from under a
            # mutation it is about to acknowledge
            with mgr.using(sid) as sess:
                return self._api(method, sub, url, sess.service, sid)

        # -- the per-session API surface ------------------------------------

        def _api(self, method: str, rest: list[str], url, svc, sid):
            """Every route of the original single-tenant surface, bound
            to `svc` — the default session for legacy paths (sid None)
            or the session addressed by /api/v1/sessions/<sid>/...."""
            if rest == ["schedulerconfiguration"]:
                return self._scheduler_config(method, svc)
            if rest == ["reset"] and method == "PUT":
                svc.reset()
                return self._json(202)
            if rest == ["export"] and method == "GET":
                return self._json(200, svc.export())
            if rest == ["import"] and method == "POST":
                body = self._body() or {}
                # bulk pod entry obeys the same per-session quota as
                # one-at-a-time CRUD (docs/sessions.md)
                server.sessions.admit_import(svc, body)
                errs = svc.import_(body)
                server.maybe_schedule(svc)
                return self._json(200, {"errors": errs})
            if rest == ["listwatchresources"] and method == "GET":
                return self._list_watch(parse_qs(url.query), svc)
            if rest == ["metrics"] and method == "GET":
                return self._metrics(parse_qs(url.query), svc, sid)
            if rest == ["alerts"] and method == "GET":
                # the SLO plane's alert surface (utils/slo.py,
                # docs/observability.md): active alerts + per-objective
                # status for the addressed session (legacy route: every
                # live session), and the process-wide bounded history
                # ring of pending -> firing -> resolved transitions.
                # Unarmed servers answer an honest empty document.
                return self._alerts(svc, sid)
            if rest == ["slo"]:
                # per-session SLO objectives: GET the current status,
                # PUT a declarative override (docs/observability.md) —
                # the per-tenant knob over the KSS_SLO_* defaults
                return self._slo(method, svc, sid)
            if rest == ["debug", "trace"] and method == "GET":
                # the flight recorder's retained window as Chrome
                # trace-event JSON — loadable as-is in Perfetto
                # (docs/observability.md). With tracing off the
                # document is empty but still loadable, and
                # otherData.tracingEnabled says why. Process-global:
                # every session's spans share the one ring (each span
                # carries its session id in args).
                rec = telemetry.active()
                events = rec.snapshot() if rec is not None else []
                doc = telemetry.chrome_trace(
                    events, dropped=rec.dropped if rec is not None else 0
                )
                doc["otherData"]["tracingEnabled"] = rec is not None
                # monotonic-clock sample for the router's merged-trace
                # offset handshake (docs/observability.md): the router
                # brackets this fetch with its own clock and estimates
                # offset = midpoint - clockUs
                doc["otherData"]["clockUs"] = telemetry.clock_us()
                return self._json(200, doc)
            if rest == ["debug", "programs"] and method == "GET":
                # the per-program performance ledger (utils/ledger.py,
                # docs/observability.md): every broker-jitted program's
                # compile wall (lowering/backend split), cost-model
                # FLOPs/bytes, memory bytes, call count, dispatch
                # seconds, sampled warm wall, and derived MFU — keyed
                # (site label, compile fingerprint), with per-session
                # call attribution. Nested routes filter to programs
                # the addressed session's passes dispatched. Armed by
                # KSS_PROGRAM_LEDGER=1; unarmed servers answer an
                # empty (but honest) document.
                doc = ledger_mod.LEDGER.snapshot(session=sid)
                doc["enabled"] = ledger_mod.ledger_enabled()
                return self._json(200, doc)
            if rest == ["timeseries"] and method == "GET":
                # the fleet & memory observatory's sample window
                # (utils/fleetstats.py, docs/observability.md): per-pass
                # device-HBM + cluster-quality samples from the bounded
                # ring. Nested session routes (and ?session= on the
                # legacy route) filter to one tenant's samples; ?limit=N
                # keeps the last N, ?sinceSeq=K resumes past a seen
                # sequence number. Unarmed servers answer an empty (but
                # honest) document.
                q = parse_qs(url.query)
                session_filter = sid or q.get("session", [None])[0]
                rec = fleetstats.active()
                samples = rec.snapshot() if rec is not None else []
                if session_filter is not None:
                    samples = [
                        s
                        for s in samples
                        if s.get("session") == session_filter
                    ]
                for param, key in (("sinceSeq", "since"), ("limit", "limit")):
                    raw = q.get(param, [None])[0]
                    if raw is None:
                        continue
                    try:
                        n = int(raw)
                    except ValueError:
                        return self._error(
                            400, f"{param} must be an integer, got {raw!r}"
                        )
                    if key == "since":
                        samples = [s for s in samples if s["seq"] > n]
                    elif n >= 0:
                        samples = samples[-n:] if n else []
                return self._json(
                    200,
                    {
                        "enabled": rec is not None,
                        "capacity": rec.capacity if rec is not None else 0,
                        "emitted": rec.emitted if rec is not None else 0,
                        "dropped": rec.dropped if rec is not None else 0,
                        "samples": samples,
                    },
                )
            if rest == ["debug", "profile"] and method == "POST":
                return self._debug_profile(self._body() or {})
            if rest == ["events"] and method == "GET":
                q = parse_qs(url.query)
                # nested routes filter to their session; the legacy
                # stream carries everything unless ?session= narrows it
                session_filter = sid or q.get("session", [None])[0]
                if sid is None and session_filter is not None:
                    # validate + resolve so the metrics feed is the
                    # filtered session's, not the default's
                    svc = server.sessions.get(session_filter).service
                return self._events_stream(q, svc, session_filter)
            if rest == ["schedule"] and method == "POST":
                mode = parse_qs(url.query).get("mode", ["sequential"])[0]
                if mode not in ("sequential", "gang"):
                    return self._error(
                        400, f"unknown scheduling mode {mode!r}"
                    )
                if svc.scheduler._schedule_lock.locked():
                    # this session already has a pass in flight: shed
                    # NOW, before claiming a concurrent-pass slot —
                    # queued same-session requests would otherwise sit
                    # on the global slots doing no device work, starving
                    # every other tenant (the semaphore bounds device
                    # concurrency, not waiting-room depth)
                    raise ServerSaturated(
                        f"session {svc.scheduler.session_id or 'default'!r} "
                        f"already has a pass in flight; retry later"
                    )
                if mode == "gang":
                    # records default ON (the annotations are the
                    # product); ?record=0 is the bulk opt-out;
                    # ?window=W passes eval_window through (the
                    # at-scale round-cost lever)
                    q = parse_qs(url.query)
                    rec_q = q.get("record", ["1"])[0]
                    record = rec_q not in ("0", "false", "no")
                    window = None
                    if "window" in q:
                        try:
                            window = int(q["window"][0])
                        except ValueError:
                            return self._error(
                                400,
                                f"window must be an integer, got"
                                f" {q['window'][0]!r}",
                            )
                    try:
                        with server.sessions.pass_slot():
                            placements, rounds, results = (
                                svc.scheduler.schedule_gang(
                                    record=record, window=window
                                )
                            )
                    except ValueError as e:
                        # known-unsupported combination (extenders
                        # configured) is the client's request, not a
                        # server fault
                        return self._error(400, str(e))
                    body = {
                        "mode": "gang",
                        "rounds": rounds,
                        "scheduled": sum(
                            1 for v in placements.values() if v
                        ),
                        "unschedulable": sum(
                            1 for v in placements.values() if not v
                        ),
                    }
                    if results is not None:
                        body["results"] = [
                            {
                                "namespace": r.pod_namespace,
                                "name": r.pod_name,
                                "status": r.status,
                                "selectedNode": r.selected_node,
                            }
                            for r in results
                        ]
                    return self._json(200, body)
                with server.sessions.pass_slot():
                    results = svc.scheduler.schedule()
                return self._json(
                    200,
                    {
                        "scheduled": sum(
                            1 for r in results if r.status == "Scheduled"
                        ),
                        "results": [
                            {
                                "namespace": r.pod_namespace,
                                "name": r.pod_name,
                                "status": r.status,
                                "selectedNode": r.selected_node,
                            }
                            for r in results
                        ],
                    },
                )
            if rest == ["scenario"] and method == "POST":
                # one-shot KEP-140 scenario / KEP-159 sweep run over
                # the serving shell: the body is a batch-job spec
                # (scenario/batch.py — operations + schedulerConfig,
                # or a sweep snapshot + weightVariants). Runs against
                # its OWN isolated store (KEP-140's one-scenario-at-
                # a-time pre-cleaned cluster, README.md:600-610), not
                # the server's; synchronous, returns the result doc.
                # Concurrent scenario requests serialize (KEP: one
                # scenario at a time; run_job additionally holds the
                # process-wide device lock for sweep jobs) and take a
                # concurrent-pass slot — scenario storms shed like any
                # other device-driving overload.
                from ..scenario.batch import BatchJob, run_job

                try:
                    spec = self._body() or {}
                    if not isinstance(spec, dict):
                        return self._error(400, "spec must be a mapping")
                    job = BatchJob.from_spec(
                        spec.get("name", "http-scenario"), spec
                    )
                except (ValueError, KeyError, AttributeError, TypeError) as e:
                    return self._error(400, f"{type(e).__name__}: {e}")
                # scenario lock FIRST (blocking, holding nothing), slot
                # second: waiting on the one-timeline-at-a-time lock
                # while holding a global pass slot would starve other
                # sessions' device work
                with server._scenario_lock, server.sessions.pass_slot():
                    return self._json(200, run_job(job))
            if rest == ["lifecycle"] and method == "POST":
                # one-shot cluster-lifecycle chaos run: the body is a
                # ChaosSpec (scenario/chaos.py — seeded fault schedule
                # + arrival processes + optional snapshot). Runs over
                # its OWN isolated store (svc.run_lifecycle), the
                # serving store is untouched; synchronous, returns the
                # result document WITH the replayable trace inline.
                # Serialized with scenario runs (one device-driving
                # timeline at a time); metrics flow into the addressed
                # session's registry.
                from ..scenario.chaos import ChaosSpec

                try:
                    spec = ChaosSpec.from_dict(self._body() or {})
                except (ValueError, KeyError, TypeError) as e:
                    return self._error(400, f"{type(e).__name__}: {e}")
                try:
                    # same ordering rationale as the scenario route
                    with server._scenario_lock, server.sessions.pass_slot():
                        result = svc.run_lifecycle(spec)
                        # read under the lock: a concurrent run must
                        # not swap ITS trace into THIS response
                        result["trace"] = svc.last_lifecycle_trace
                except ValueError as e:
                    # a spec that parses but can't build a run (bad
                    # snapshot, unusable scheduler config) is the
                    # client's input, not a server fault
                    return self._error(400, str(e))
                return self._json(200, result)
            if rest == ["lifecycle", "trace"] and method == "GET":
                # the last run's replayable event trace as JSONL
                # (application/x-ndjson), byte-identical across
                # re-runs of the same seeded spec
                trace = svc.last_lifecycle_trace
                if trace is None:
                    return self._error(404, "no lifecycle run yet")
                from ..lifecycle.engine import trace_jsonl

                body = trace_jsonl(trace).encode()
                self.send_response(200)
                self._cors_headers()
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return None
            if rest and rest[0] == "extender":
                return self._extender(method, rest[1:], svc)
            if rest and rest[0] == "resources":
                return self._resources(
                    method, rest[1:], parse_qs(url.query), svc
                )
            return self._error(404, "not found")

        # -- handlers -------------------------------------------------------

        def _scheduler_config(self, method: str, svc):
            if method == "GET":
                return self._json(200, svc.scheduler.get_config())
            if method == "POST":
                # only .profiles (+ .extenders) are honored, reference
                # convertConfigurationForSimulator semantics (config parse
                # enforces this downstream)
                svc.scheduler.restart(self._body() or {})
                return self._json(202)
            return self._error(405, "method not allowed")

        def _resources(self, method: str, rest: list[str], q: dict, svc):
            if not rest or rest[0] not in KINDS:
                return self._error(404, f"unknown kind {rest[:1]}")
            kind = rest[0]
            if len(rest) == 1:
                if method == "GET":
                    return self._json(200, {"items": svc.store.list(kind)})
                if method in ("POST", "PUT"):
                    body = self._body() or {}
                    if kind == "pods":
                        # per-session pending-pod quota: shed BEFORE the
                        # store mutation (docs/sessions.md)
                        server.sessions.admit_pod(svc, body)
                    obj = svc.store.apply(kind, body)
                    server.maybe_schedule(svc)
                    return self._json(201, obj)
            else:
                if len(rest) == 3:
                    namespace, name = rest[1], rest[2]
                elif len(rest) == 2:
                    namespace, name = "default", rest[1]
                else:
                    return self._error(404, "bad resource path")
                if method == "GET":
                    obj = svc.store.get(kind, name, namespace)
                    if obj is None:
                        return self._error(404, "not found")
                    if q.get("format", [None])[0] == "yaml":
                        import yaml

                        body = yaml.safe_dump(
                            obj, sort_keys=False, default_flow_style=False
                        ).encode()
                        self.send_response(200)
                        self._cors_headers()
                        self.send_header(
                            "Content-Type", "application/yaml; charset=utf-8"
                        )
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return None
                    return self._json(200, obj)
                if method == "PUT":
                    # item-path PUT = wholesale replace (kubectl replace):
                    # fields absent from the body are removed — the YAML
                    # editor's save semantics. Collection POST/PUT keeps
                    # the SSA-style merge.
                    obj = self._body() or {}
                    meta = obj.get("metadata", {}) or {}
                    if meta.get("name") and meta["name"] != name:
                        return self._error(
                            400,
                            f"body names {meta['name']!r}, path names {name!r}",
                        )
                    meta["name"] = name
                    if NAMESPACED.get(kind):
                        # a body namespace differing from the path would
                        # silently replace a DIFFERENT object; reject it
                        # like the name mismatch above
                        if meta.get("namespace") and meta["namespace"] != namespace:
                            return self._error(
                                400,
                                f"body namespace {meta['namespace']!r} does "
                                f"not match path namespace {namespace!r}",
                            )
                        meta["namespace"] = namespace
                    obj["metadata"] = meta
                    if kind == "pods":
                        # quota-metered like a collection apply, plus the
                        # replace-only transition: a body omitting
                        # spec.nodeName UNBINDS a bound pod back into the
                        # pending queue (replace deletes absent fields)
                        server.sessions.admit_pod(svc, obj, replace=True)
                    out = svc.store.replace(kind, obj)
                    server.maybe_schedule(svc)
                    return self._json(200, out)
                if method == "DELETE":
                    ok = svc.store.delete(kind, name, namespace)
                    if not ok:
                        return self._error(404, "not found")
                    server.maybe_schedule(svc)
                    return self._json(200)
            return self._error(405, "method not allowed")

        def _extender(self, method: str, rest: list[str], svc):
            ext = (
                server.extender_service
                or svc.scheduler.current_extender_service()
            )
            if method != "POST" or len(rest) != 2:
                return self._error(404, "bad extender path")
            verb, id_str = rest
            out = ext.handle(verb, int(id_str), self._body())
            return self._json(200, out)

        # -- telemetry plane ------------------------------------------------

        def _debug_profile(self, body: dict):
            """Arm / disarm a `jax.profiler` trace capture over HTTP
            (docs/observability.md): ``{"action": "start", "logDir":
            optional}`` begins a TensorBoard/XProf capture of everything
            the process runs next; ``{"action": "stop"}`` ends it. One
            capture at a time — jax.profiler is process-global."""
            import jax

            action = body.get("action")
            if action == "start":
                with server._profile_lock:
                    if server._profile_dir is not None:
                        return self._error(
                            409,
                            f"profile already running into "
                            f"{server._profile_dir!r}; stop it first",
                        )
                    log_dir = body.get("logDir")
                    if not log_dir:
                        import tempfile

                        log_dir = tempfile.mkdtemp(prefix="kss-profile-")
                    jax.profiler.start_trace(log_dir)
                    server._profile_dir = log_dir
                return self._json(
                    200, {"profiling": True, "logDir": log_dir}
                )
            if action == "stop":
                with server._profile_lock:
                    if server._profile_dir is None:
                        return self._error(409, "no profile running")
                    log_dir, server._profile_dir = server._profile_dir, None
                    jax.profiler.stop_trace()
                return self._json(
                    200, {"profiling": False, "logDir": log_dir}
                )
            return self._error(
                400, f"action must be start|stop, got {action!r}"
            )

        def _alerts(self, svc, sid):
            """GET /api/v1/alerts (+ nested session form): the SLO
            plane's judgement surface — per-objective status (burn
            rates, compliance, alert state) for the addressed session
            (legacy route: every live session), the currently
            pending/firing alerts, and the bounded history ring of
            transitions. Statuses evaluate BEFORE the history snapshot
            so a just-crossed threshold's transition is in both."""
            if sid is None:
                planes = [
                    (session_id, service.scheduler.metrics.slo_plane())
                    for session_id, service in server.sessions.live_services()
                ]
            else:
                planes = [(sid, svc.scheduler.metrics.slo_plane())]
            sessions_doc: dict = {}
            active: list = []
            enabled = False
            for session_id, plane in planes:
                if plane is None:
                    continue
                enabled = True
                # status() evaluates first: alert states are current,
                # and any transition lands in the ring before the
                # history snapshot below
                sessions_doc[session_id or DEFAULT_SESSION_ID] = (
                    plane.status()
                )
                active.extend(plane.active_alerts())
            log = slo_mod.alert_log()
            history = log.snapshot()
            if sid is not None:
                history = [
                    ev for ev in history if ev.get("session") == sid
                ]
            return self._json(
                200,
                {
                    "enabled": enabled,
                    "active": active,
                    "sessions": sessions_doc,
                    "history": history,
                    "historyEmitted": log.emitted,
                    "historyDropped": log.dropped,
                    "counters": log.counters(),
                },
            )

        def _slo(self, method: str, svc, sid):
            """GET/PUT /api/v1/slo (+ nested session form): the
            per-tenant objective override (docs/observability.md). PUT
            installs an explicit plane for the session — objectives
            layered over the defaults, optional window/burn/hold
            overrides — that survives eviction and drain through the
            metrics checkpoint state; ``{"reset": true}`` returns the
            session to the KSS_SLO_* environment's plane, and
            ``{"enabled": false}`` disarms it."""
            metrics = svc.scheduler.metrics
            session = sid or DEFAULT_SESSION_ID

            def plane_doc():
                plane = metrics.slo_plane()
                if plane is None:
                    return {"enabled": False, "session": session}
                return plane.status()

            if method == "GET":
                return self._json(200, plane_doc())
            if method != "PUT":
                return self._error(405, "method not allowed")
            body = self._body() or {}
            if not isinstance(body, dict):
                return self._error(400, "SLO spec must be a mapping")
            if body.get("reset"):
                metrics.clear_slo_override()
                return self._json(200, plane_doc())
            try:
                plane = slo_mod.plane_from_put_spec(body, session)
            except (ValueError, TypeError) as e:
                return self._error(400, str(e))
            metrics.set_slo_plane(plane)
            if plane is None:  # {"enabled": false}: explicitly disarmed
                return self._json(200, {"enabled": False, "session": session})
            return self._json(200, plane.status())

        def _metrics(self, q: dict, svc, sid):
            """GET /api/v1/metrics (+ per-session nested form): the
            session's counter snapshot as JSON, or Prometheus text with
            a `session` label on every sample (`?format=openmetrics`
            additionally attaches histogram bucket exemplars — the
            pass-id link into the Perfetto trace — and terminates with
            `# EOF`). The LEGACY (un-prefixed) Prometheus scrape
            renders EVERY live session in one document — the one
            endpoint an external Prometheus points at
            (docs/sessions.md)."""
            fmt = q.get("format", ["json"])[0]
            doc = None
            if fmt == "json" or sid is not None:
                # the legacy prometheus scrape (sid None) re-snapshots
                # every live session inside its consistent cut below —
                # don't pay a discarded extra snapshot per scrape
                doc = svc.scheduler.metrics.snapshot()
                # serving-stack configuration alongside the counters:
                # the encoding-cache bound (KSS_ENCODING_CACHE_CAP)
                doc["encodingCacheCapacity"] = (
                    svc.scheduler.encoding_cache_capacity
                )
                doc["sessionId"] = sid or DEFAULT_SESSION_ID
                # server-wide SSE hardening counter (the satellite): how
                # many events were dropped disconnecting slow subscribers
                doc["sseDroppedEvents"] = server.sse_dropped
                # execution-ladder + drain state (docs/resilience.md):
                # which rung this session's service dispatches on, and
                # the server-wide drain view
                doc["deviceRung"] = svc.scheduler.device_rung
                doc["draining"] = server.draining
                doc["drainedSessions"] = server.sessions.drained_sessions()
                # the observatory blocks (schema v3, utils/ledger.py):
                # process-wide cold-start phase accounting (boot probe →
                # first encode → first compile → first pass, summarized
                # as timeToFirstPassSeconds) and the per-program ledger
                # summary (full detail at GET /api/v1/debug/programs)
                doc["coldStart"] = ledger_mod.COLD_START.snapshot()
                doc["programs"] = ledger_mod.LEDGER.totals()
                # the AOT bundle store (utils/bundles.py): process-wide
                # load/save/bypass counts + the deserialize wall — the
                # per-session attribution rides the phases block
                doc["bundles"] = bundles_mod.STORE.stats()
                # fleet identity (docs/fleet.md): which worker served
                # this scrape — present only inside a fleet, so the
                # single-process document shape is unchanged
                wid = metrics_mod.worker_id()
                if wid is not None:
                    doc["workerId"] = wid
            if fmt in ("prometheus", "openmetrics"):
                openmetrics = fmt == "openmetrics"

                def entry(session_id, snapshot, cache_cap):
                    return (
                        {"session": session_id},
                        snapshot,
                        {
                            "kss_encoding_cache_capacity": (
                                "Capacity of the per-service encoding "
                                "cache (KSS_ENCODING_CACHE_CAP).",
                                cache_cap,
                            )
                        },
                    )

                if sid is None:
                    # the scrape endpoint: every LIVE session, labeled,
                    # from ONE consistent cut — no per-id re-lookup to
                    # race a concurrent DELETE into a scrape-wide 404,
                    # and no restore (scrapes must not defeat idle
                    # eviction; an evicted session's counters live in
                    # its snapshot file until the next real touch)
                    cut = server.sessions.live_services()
                    entries = [
                        entry(
                            session_id,
                            service.scheduler.metrics.snapshot(),
                            service.scheduler.encoding_cache_capacity,
                        )
                        for session_id, service in cut
                    ]
                    slo_planes = [
                        (session_id, service.scheduler.metrics.slo_plane())
                        for session_id, service in cut
                    ]
                else:
                    entries = [entry(sid, doc, doc["encodingCacheCapacity"])]
                    slo_planes = [(sid, svc.scheduler.metrics.slo_plane())]
                mgr_stats = server.sessions.stats()
                global_counters = {
                    "kss_sse_dropped_events_total": (
                        "Events dropped disconnecting slow SSE "
                        "subscribers.",
                        server.sse_dropped,
                    ),
                    "kss_session_evictions_total": (
                        "Idle sessions snapshotted to disk.",
                        mgr_stats["evictions"],
                    ),
                    "kss_drained_sessions_total": (
                        "Sessions snapshotted by the graceful drain "
                        "path.",
                        mgr_stats["drainedSessions"],
                    ),
                }
                if mgr_stats["journal"]["armed"] or mgr_stats[
                    "replication"
                ].get("armed"):
                    # the durability-plane families exist only where the
                    # plane does: a standalone unarmed server keeps its
                    # honest kss_fleet_-free exposition (fleet workers
                    # always journal — the router arms them)
                    global_counters["kss_fleet_replications_total"] = (
                        "Session transport units acknowledged by ring "
                        "successors (server/replication.py).",
                        mgr_stats["replication"].get("shippedUnits", 0),
                    )
                    global_counters["kss_fleet_journal_bytes_total"] = (
                        "Write-ahead session journal bytes appended "
                        "(server/durability.py).",
                        mgr_stats["journal"]["bytes"],
                    )
                text = metrics_mod.render_prometheus_sessions(
                    entries,
                    openmetrics=openmetrics,
                    global_counters=global_counters,
                    global_gauges={
                        "kss_sessions_live": (
                            "Sessions resident in memory.",
                            mgr_stats["live"],
                        ),
                        "kss_sessions_evicted": (
                            "Sessions evicted to disk snapshots.",
                            mgr_stats["evicted"],
                        ),
                        "kss_server_draining": (
                            "1 while the graceful drain is in progress.",
                            1 if mgr_stats["draining"] else 0,
                        ),
                    },
                )
                # the per-program ledger families (kss_program_*, one
                # series per (program, fingerprint) — utils/ledger.py);
                # empty string while the ledger has recorded nothing
                text += ledger_mod.LEDGER.render_prometheus()
                # the fleet observatory families (kss_device_hbm_* /
                # kss_fleet_*, utils/fleetstats.py) from the freshest
                # samples; empty while stats are off or unsampled
                text += fleetstats.render_prometheus()
                # the SLO plane families (kss_slo_* / kss_alert_*,
                # utils/slo.py): per-(objective, session) gauges from
                # every live plane — evaluated at scrape time so alert
                # states are current — plus the process-wide alert-ring
                # counters (always present, so dashboards can pin them)
                text += slo_mod.render_prometheus_planes(slo_planes)
                # the fleet's worker label (KSS_WORKER_ID): injected
                # into every sample AFTER the whole document — sessions,
                # ledger, observatory, and SLO families alike — is
                # assembled, so one rewrite covers every renderer
                wid = metrics_mod.worker_id()
                if wid is not None:
                    text = metrics_mod.label_exposition(
                        text, {"worker": wid}
                    )
                if openmetrics:
                    # the OpenMetrics terminator — LAST, after every
                    # appended observatory family
                    text += "# EOF\n"
                body = text.encode()
                self.send_response(200)
                self._cors_headers()
                self.send_header(
                    "Content-Type",
                    "application/openmetrics-text; version=1.0.0; "
                    "charset=utf-8"
                    if openmetrics
                    else "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return None
            if fmt != "json":
                return self._error(400, f"unknown metrics format {fmt!r}")
            return self._json(200, doc)

        def _events_stream(self, q: dict, svc, session_filter: "str | None"):
            """GET /api/v1/events: live telemetry over SSE
            (text/event-stream), reusing the listwatch chunked plumbing.
            Two event types (docs/observability.md):

              * ``metrics`` — a full `SchedulingMetrics` snapshot; one is
                sent immediately on connect (the stream always yields at
                least one event) and again whenever the counters change;
              * ``span`` — each flight-recorder event as it is emitted
                (requires `KSS_TRACE=1`);
              * ``fleet`` — each fleet-observatory sample (requires
                `KSS_FLEET_STATS=1`);
              * ``alert`` — each SLO alert transition (utils/slo.py;
                requires an armed plane). Without any switch the stream
                carries metrics events only.

            With `session_filter` (a nested /sessions/<id>/events route,
            or ?session= on the legacy route) only that session's spans
            flow; metrics snapshots are the addressed session's.

            Robustness (the satellite hardening): subscriber count is
            capped (KSS_SSE_MAX_SUBSCRIBERS → 503 past it), and a slow
            consumer whose bounded queue overflows is DISCONNECTED —
            with the drop counted in `sseDroppedEvents` — instead of
            silently receiving a gap-ridden interleaving. A comment
            heartbeat (``:``) flows on idle so a vanished client is
            detected and the subscription reclaimed."""
            if not server.sse_acquire():
                return self._error(
                    503,
                    f"SSE subscriber limit reached "
                    f"({server.sessions.sse_max_subscribers}, "
                    f"KSS_SSE_MAX_SUBSCRIBERS)",
                    kind="SSESubscriberLimit",
                    headers={"Retry-After": str(DEGRADED_RETRY_AFTER_S)},
                )
            rec = telemetry.active()
            fleet_rec = fleetstats.active()
            # bounded feed: a slow/stalled client must not accumulate
            # every span the process emits (the unbounded growth the
            # ring buffer exists to prevent) — past the bound the
            # consumer is provably too slow and gets disconnected
            events: "queue.Queue" = queue.Queue(maxsize=SSE_QUEUE_MAX)
            overflowed = threading.Event()

            def feed(ev: dict) -> None:
                if overflowed.is_set():
                    return  # already condemned; don't count more drops
                if (
                    session_filter is not None
                    and (ev.get("args") or {}).get("session") != session_filter
                ):
                    return  # another tenant's span: filtered, not a drop
                try:
                    events.put_nowait(("span", ev))
                except queue.Full:
                    server.sse_count_drop()
                    overflowed.set()

            def fleet_feed(sample: dict) -> None:
                # the fleet observatory's samples ride the same stream
                # as `fleet` events (docs/observability.md) — the
                # dashboard's Observability-tab sparkline source
                if overflowed.is_set():
                    return
                if (
                    session_filter is not None
                    and sample.get("session") != session_filter
                ):
                    return
                try:
                    events.put_nowait(("fleet", sample))
                except queue.Full:
                    server.sse_count_drop()
                    overflowed.set()

            def alert_feed(ev: dict) -> None:
                # SLO alert transitions ride the stream as `alert`
                # events (utils/slo.py) — the dashboard's Alerts-panel
                # source; the ring exists regardless of arming, so the
                # subscription is unconditional and simply idle when no
                # plane is armed
                if overflowed.is_set():
                    return
                if (
                    session_filter is not None
                    and ev.get("session") != session_filter
                ):
                    return
                try:
                    events.put_nowait(("alert", ev))
                except queue.Full:
                    server.sse_count_drop()
                    overflowed.set()

            alerts = slo_mod.alert_log()
            if rec is not None:
                rec.subscribe(feed)
            if fleet_rec is not None:
                fleet_rec.subscribe(fleet_feed)
            alerts.subscribe(alert_feed)
            try:
                self.send_response(200)
                self._cors_headers()
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-store")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def push(event: str, payload) -> None:
                    data = (
                        f"event: {event}\n"
                        f"data: {json.dumps(payload)}\n\n"
                    ).encode()
                    self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
                    self.wfile.flush()

                def counters():
                    snap = svc.scheduler.metrics.snapshot()
                    snap.pop("uptimeSeconds", None)  # changes every read
                    return snap

                last = counters()
                push("metrics", last)
                idle = 0
                checked = time.monotonic()
                while not overflowed.is_set():
                    try:
                        ev = events.get(timeout=1.0)
                    except queue.Empty:
                        ev = None
                    # counters are re-checked on a wall-clock cadence in
                    # BOTH branches: continuous span traffic must not
                    # starve the metrics feed
                    now_t = time.monotonic()
                    if now_t - checked >= 1.0:
                        checked = now_t
                        now = counters()
                        if now != last:
                            last = now
                            push("metrics", now)
                            idle = 0
                    if ev is not None:
                        idle = 0
                        push(*ev)
                        continue
                    idle += 1
                    if idle >= 15:
                        idle = 0
                        # SSE comment line: a spec-legal heartbeat
                        self.wfile.write(b"3\r\n:\n\n\r\n")
                        self.wfile.flush()
                # overflow: fall through — closing the connection IS the
                # disconnect (the client reconnects and re-syncs from a
                # fresh metrics snapshot)
            except (BrokenPipeError, ConnectionResetError):
                pass
            finally:
                if rec is not None:
                    rec.unsubscribe(feed)
                if fleet_rec is not None:
                    fleet_rec.unsubscribe(fleet_feed)
                alerts.unsubscribe(alert_feed)
                server.sse_release()

        # -- watch stream ---------------------------------------------------

        def _list_watch(self, q: dict, svc):
            store = svc.store
            # validate every lastResourceVersion BEFORE the 200/chunked
            # headers go out — past that point errors can't be reported
            last_rvs: dict[str, "int | None"] = {}
            for kind, (_, param) in WATCH_KINDS.items():
                raw = q.get(param, [None])[0]
                if raw is None:
                    last_rvs[kind] = None
                    continue
                try:
                    last_rvs[kind] = int(raw)
                except ValueError:
                    return self._error(400, f"bad {param}: {raw!r}")
                # a version older than the retained log cannot be resumed
                # (deletions in the gap would be lost): 410 Gone, client
                # relists from scratch — the reference apiserver behavior
                try:
                    store.events_since(kind, last_rvs[kind])
                except StaleResourceVersion as e:
                    return self._error(410, str(e))
            events: "queue.Queue" = queue.Queue()
            store.subscribe(events.put)
            try:
                self.send_response(200)
                self._cors_headers()
                self.send_header("Content-Type", "application/json")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def push(ev):
                    wire, _ = WATCH_KINDS[ev.kind]
                    data = (
                        json.dumps(
                            {
                                "Kind": wire,
                                "EventType": ev.event_type,
                                "Obj": ev.obj,
                            }
                        ).encode()
                        + b"\n"  # one JSON object per line
                    )
                    self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
                    self.wfile.flush()

                # initial replay per kind: events since the client's last
                # seen version, or the full list as ADDED (reference
                # doListAndWatch, resourcewatcher.go:94-120)
                seen: dict[str, int] = {}
                for kind, (wire, param) in WATCH_KINDS.items():
                    last = last_rvs[kind]
                    if last is not None:
                        try:
                            replay = store.events_since(kind, last)
                        except StaleResourceVersion:
                            # pruned between validation and here: nothing
                            # safe to send — drop the stream so the client
                            # reconnects and gets the 410
                            return
                    else:
                        replay = store.list_as_added(kind)
                    for ev in replay:
                        push(ev)
                        seen[kind] = max(seen.get(kind, 0), ev.resource_version)
                # live stream until the client disconnects; events that
                # raced into the queue during replay are deduped by rv.
                # An idle stream sends a blank-line heartbeat every ~15s so
                # a vanished client is detected and the handler thread +
                # subscription are reclaimed (consumers skip blank lines).
                idle = 0
                while True:
                    try:
                        ev = events.get(timeout=1.0)
                    except queue.Empty:
                        idle += 1
                        if idle >= 15:
                            idle = 0
                            self.wfile.write(b"1\r\n\n\r\n")
                            self.wfile.flush()
                        continue
                    idle = 0
                    if ev.kind not in WATCH_KINDS:
                        continue  # workload kinds are stored, not watched
                    if ev.resource_version <= seen.get(ev.kind, 0):
                        continue
                    push(ev)
            except (BrokenPipeError, ConnectionResetError):
                pass
            finally:
                store.unsubscribe(events.put)

    return Handler
