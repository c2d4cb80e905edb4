"""The unified telemetry plane: a span flight recorder over every
concurrent machinery in the serving stack.

Three machineries interleave on the serving path — the async
double-buffered pipeline (dispatch→resolve passes), the CompileBroker's
background workers (speculative builds, watchdog-abandoned compiles),
and the lifecycle engine's discrete-event loop — and `/api/v1/metrics`
only ever showed their *aggregate* counters. This module records the
interleavings themselves: structured spans in a lock-cheap bounded ring
buffer (a flight recorder: always the most recent window, never
unbounded growth), exported as Chrome trace-event JSON that Perfetto /
`chrome://tracing` load directly, and streamed live over SSE
(`GET /api/v1/events`).

Span model
----------

  * `span(name, **attrs)` — context manager; emits a `B` (begin) event
    at entry and a matching `E` (end) at exit on the calling thread's
    track. Nesting follows the `with` structure, so B/E are balanced
    per thread by construction (test-pinned).
  * `complete(name, start_s, end_s, *, tid=...)` — one `X` (complete)
    event over an explicit interval, usable for windows that do NOT
    nest on a host thread: the async pipeline's device-execute window
    (dispatch→resolve) lands on the synthetic `DEVICE_TID` track, where
    its overlap with host-side event application is *visible* as
    overlapping tracks in Perfetto and *assertable* from the exported
    intervals (tests/test_async_pipeline.py).
  * `instant(name, **attrs)` — a point event (`i`), used for injected
    faults (utils/faultinject.py) and sim-time correlation marks.

Causality: every span/instant carries the current **pass id** — a
monotonic per-service counter threaded through `SchedulerService` via
the thread-local `pass_context`. Background work triggered *by* a pass
(the broker's speculative builds, eager fallbacks) re-enters the arming
pass's context on the worker thread, so a speculative compile's spans
name the pass that armed it.

Cost model: tracing is **off by default** and near-zero-cost when off —
`span()` returns a shared no-op context manager after one env probe
(`KSS_TRACE`, cached on the raw string exactly like
utils/faultinject.py), no allocation, no lock on the ring, no JAX
import. tests/test_telemetry_mirror.py pins that off path. The ring
capacity is `KSS_TRACE_RING_CAP` events (default 65536); past it the
oldest events are overwritten — the flight-recorder contract.

Timestamps are `time.perf_counter()` microseconds: monotonic, shared
across threads, the unit Chrome trace events use (`ts`/`dur`).

Profiler mirror: while a recorder is active, every `span()` also holds
a `jax.profiler.TraceAnnotation` of the same name for its lifetime, so
a `jax.profiler` capture shows the program's spans as host events on
the capture's own clock, beside the device's operations. The mirrored
events carry the stat ``cat=kss`` (`MIRROR_STAT`), which tells them
from XLA's own host events. `complete()` windows are emitted after the
fact and are not mirrored.

Distributed tracing (docs/observability.md "Distributed tracing"): the
fleet router mints a W3C-traceparent-style context per inbound request
and stamps it on every proxied hop; workers adopt the header at the
HTTP chokepoint via `trace_context`, so every span a request causes —
across processes — carries one ``args["trace"]`` id, and
`merged_chrome_trace` joins per-process exports into one Perfetto
document with per-track monotonic-clock offsets. Propagation rides the
same arming as the recorder (`KSS_TRACE_PROPAGATE`, default on when
KSS_TRACE is truthy): with tracing off nothing is minted, parsed, or
stamped.
"""

from __future__ import annotations

import json
import os
import secrets
import threading
import time

from . import locking
# the shared boolean vocabulary (envcheck.TRUTHY): KSS_TRACE honors
# every spelling startup validation accepts — a 'validated' tracing run
# must never silently record nothing
from .envcheck import TRUTHY as _TRUE

ENV_VAR = "KSS_TRACE"
CAP_VAR = "KSS_TRACE_RING_CAP"
PROPAGATE_VAR = "KSS_TRACE_PROPAGATE"
DEFAULT_RING_CAP = 65536

# the stat every profiler-mirrored span carries: ``cat=kss``, the
# category its ring events carry too
MIRROR_STAT = ("cat", "kss")

# the synthetic track for non-thread-shaped intervals (the async
# pipeline's in-flight device-execute windows). Python thread idents are
# CPython object addresses and never 0, so 0 is collision-free.
DEVICE_TID = 0

_PID = os.getpid()


def _now_us() -> float:
    return time.perf_counter() * 1e6


def ring_capacity_from_env() -> int:
    """Ring capacity from KSS_TRACE_RING_CAP; malformed or non-positive
    values fall back to the default — a typo must never disable the
    flight recorder or blow its bound."""
    raw = os.environ.get(CAP_VAR, "")
    try:
        cap = int(raw) if raw else DEFAULT_RING_CAP
    except ValueError:
        return DEFAULT_RING_CAP
    return cap if cap >= 1 else DEFAULT_RING_CAP


@locking.guard_inferred
class SpanRecorder:
    """A bounded ring buffer of Chrome-trace events + live subscribers.

    `emit` is the hot path: one short lock hold to place the event and
    advance the sequence (the bound holds under concurrent writers —
    test-pinned), then subscriber callbacks OUTSIDE the lock. Snapshots
    return the retained window oldest-first."""

    def __init__(self, capacity: "int | None" = None):
        cap = ring_capacity_from_env() if capacity is None else int(capacity)
        if cap < 1:
            raise ValueError(f"ring capacity must be >= 1, got {cap}")
        self.capacity = cap
        self._lock = locking.make_lock("telemetry.ring")
        self._ring: "list[dict | None]" = [None] * cap
        self._seq = 0  # monotonic count of events ever emitted
        self._subs: list = []

    # -- writing ------------------------------------------------------------

    def emit(self, ev: dict) -> None:
        with self._lock:
            self._ring[self._seq % self.capacity] = ev
            self._seq += 1
            subs = tuple(self._subs) if self._subs else ()
        for fn in subs:
            try:
                fn(ev)
            except Exception:  # noqa: BLE001 — a dead subscriber never breaks a pass
                pass

    # -- reading ------------------------------------------------------------

    @property
    def emitted(self) -> int:
        """Events ever emitted (>= len(self): the ring drops the oldest)."""
        with self._lock:
            return self._seq

    @property
    def dropped(self) -> int:
        with self._lock:
            return max(0, self._seq - self.capacity)

    def __len__(self) -> int:
        with self._lock:
            return min(self._seq, self.capacity)

    def snapshot(self) -> list[dict]:
        """The retained events, oldest first."""
        with self._lock:
            n = self._seq
            if n <= self.capacity:
                return list(self._ring[:n])
            i = n % self.capacity
            return self._ring[i:] + self._ring[:i]

    # -- live streaming (the SSE route's feed) ------------------------------

    def subscribe(self, fn) -> None:
        with self._lock:
            self._subs.append(fn)

    def unsubscribe(self, fn) -> None:
        with self._lock:
            try:
                self._subs.remove(fn)
            except ValueError:
                pass


# -- the process-global active recorder --------------------------------------

_lock = locking.make_lock("telemetry.config")
# (KSS_TRACE, KSS_TRACE_RING_CAP) raw strings -> recorder parsed from
# them; an explicit `activate` overrides the environment (tests, the
# lifecycle CLI's --perfetto-out) until `deactivate`. Both globals are
# read WITHOUT the lock on the hot path (single-reference loads are
# atomic under the GIL; each holds one immutable tuple swapped whole),
# so every span site across request threads doesn't serialize on one
# process-global mutex just to learn tracing is off.
_cached: "tuple[tuple[str, str], SpanRecorder | None] | None" = None
_override_state: "tuple[bool, SpanRecorder | None]" = (False, None)


def active() -> "SpanRecorder | None":
    """The active recorder, or None (the default: tracing off). Reads
    KSS_TRACE / KSS_TRACE_RING_CAP each call but re-builds the recorder
    only when they change — the disabled path is two dict probes and a
    tuple compare, no lock, cheap enough for every span site."""
    global _cached
    overridden, override = _override_state
    if overridden:
        return override
    key = (os.environ.get(ENV_VAR, ""), os.environ.get(CAP_VAR, ""))
    cached = _cached
    if cached is not None and cached[0] == key:
        return cached[1]
    with _lock:
        overridden, override = _override_state
        if overridden:
            return override
        cached = _cached
        if cached is not None and cached[0] == key:
            return cached[1]
        rec = (
            SpanRecorder(ring_capacity_from_env())
            if key[0].strip().lower() in _TRUE
            else None
        )
        _cached = (key, rec)
        return rec


def enabled() -> bool:
    return active() is not None


def activate(recorder: "SpanRecorder | None") -> None:
    """Install `recorder` as the active one regardless of the
    environment (None = tracing explicitly off). Until `deactivate`,
    the env vars are not consulted."""
    global _override_state
    with _lock:
        _override_state = (True, recorder)


def deactivate() -> None:
    """Drop any `activate` override; the environment rules again."""
    global _override_state
    with _lock:
        _override_state = (False, None)


# -- pass-id / session causality ----------------------------------------------

_ctx = threading.local()


def current_pass_id() -> "int | None":
    """The pass id of the innermost `pass_context` on this thread."""
    return getattr(_ctx, "pass_id", None)


def current_session_id() -> "str | None":
    """The session id of the innermost `session_context` on this thread
    (the multi-tenant session plane, docs/sessions.md)."""
    return getattr(_ctx, "session_id", None)


class session_context:
    """Thread-local session causality: spans/instants emitted inside
    carry ``args["session"] = session_id`` — the label the SSE route
    filters on and the Prometheus exposition keys by. Re-entered on
    broker worker threads for work a session's pass armed, exactly like
    `pass_context`."""

    __slots__ = ("_session_id", "_prev")

    def __init__(self, session_id: "str | None"):
        self._session_id = session_id
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_ctx, "session_id", None)
        _ctx.session_id = self._session_id
        return self

    def __exit__(self, *exc):
        _ctx.session_id = self._prev
        return False


class pass_context:
    """Thread-local causal context: spans/instants emitted inside carry
    `args["pass"] = pass_id`. Re-entered on worker threads for work a
    pass *armed* (speculative compiles), so background spans name their
    triggering pass. A plain class (not @contextmanager) keeps the
    disabled-tracing cost to two attribute writes."""

    __slots__ = ("_pass_id", "_prev")

    def __init__(self, pass_id: "int | None"):
        self._pass_id = pass_id
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_ctx, "pass_id", None)
        _ctx.pass_id = self._pass_id
        return self

    def __exit__(self, *exc):
        _ctx.pass_id = self._prev
        return False


# -- distributed trace context (docs/observability.md) ------------------------


def propagate_enabled() -> bool:
    """Whether trace-context propagation is armed: tracing must be on
    (no recorder = nothing to correlate) and KSS_TRACE_PROPAGATE not
    spelled falsy (default on — arming KSS_TRACE arms the fleet's
    causal joins too)."""
    if active() is None:
        return False
    raw = os.environ.get(PROPAGATE_VAR, "")
    if not raw:
        return True
    from .envcheck import FALSY

    return raw.strip().lower() not in FALSY


def new_trace_id() -> str:
    """A fresh 128-bit trace id, W3C trace-context shaped (32 lowercase
    hex chars, never all-zero)."""
    return secrets.token_hex(16)


def new_span_id() -> str:
    """A fresh 64-bit parent span id for a traceparent hop."""
    return secrets.token_hex(8)


def make_traceparent(trace_id: str, span_id: "str | None" = None) -> str:
    """The ``traceparent`` header value for one outbound hop:
    ``00-<32hex trace id>-<16hex parent span id>-01`` (version 00,
    sampled flag set — everything this plane propagates is recorded)."""
    return f"00-{trace_id}-{span_id or new_span_id()}-01"


def parse_traceparent(header: "str | None") -> "str | None":
    """The trace id carried by a ``traceparent`` header, or None for
    anything malformed — a bad header must degrade to an untraced
    request, never an error on the serving path."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, _flags = parts
    if version != "00" or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32:
        return None
    return trace_id


def current_trace_id() -> "str | None":
    """The trace id of the innermost `trace_context` on this thread."""
    return getattr(_ctx, "trace_id", None)


class trace_context:
    """Thread-local distributed-trace causality: spans/instants emitted
    inside carry ``args["trace"] = trace_id`` — the id the fleet router
    minted for the originating request (or None to run untraced). Same
    plain-class shape as `pass_context`; re-entered on broker worker
    threads and async-pass resolution for work a traced request armed."""

    __slots__ = ("_trace_id", "_prev")

    def __init__(self, trace_id: "str | None"):
        self._trace_id = trace_id
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_ctx, "trace_id", None)
        _ctx.trace_id = self._trace_id
        return self

    def __exit__(self, *exc):
        _ctx.trace_id = self._prev
        return False


# -- emission -----------------------------------------------------------------


def _args(pass_id, attrs: dict) -> dict:
    if pass_id is None:
        pass_id = current_pass_id()
    session_id = attrs.get("session", current_session_id())
    # explicit trace=None (an untraced async handle) must not leave a
    # null key behind — strip it and fall back to the thread-local id
    trace_id = attrs.get("trace") or current_trace_id()
    if (
        pass_id is not None
        or session_id is not None
        or trace_id is not None
        or "trace" in attrs
    ):
        attrs = dict(attrs)
        if pass_id is not None:
            attrs["pass"] = pass_id
        if session_id is not None:
            attrs["session"] = session_id
        if trace_id is not None:
            attrs["trace"] = trace_id
        else:
            attrs.pop("trace", None)
    return attrs


class _NullSpan:
    """The shared no-op span: what `span()` hands out when tracing is
    off — no allocation beyond the call itself."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()

def _mirror(name: str):
    """Enter and return a profiler annotation named `name`. JAX is
    imported here, on the live path only."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name, **{MIRROR_STAT[0]: MIRROR_STAT[1]})
    ann.__enter__()
    return ann


class _LiveSpan:
    __slots__ = ("_rec", "_name", "_a", "_b", "_ann")

    def __init__(self, rec: SpanRecorder, name: str, args: dict):
        self._rec = rec
        self._name = name
        self._a = args
        self._b = None
        self._ann = None

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (an encode's mode):
        the E event carries them, and so does its B partner in the ring.
        The B event gets a new args dict, never a mutated one, so a
        concurrent export never sees a dict change size."""
        self._a = {**self._a, **attrs}
        if self._b is not None:
            self._b["args"] = self._a

    def __enter__(self):
        self._b = {
            "ph": "B",
            "name": self._name,
            "cat": "kss",
            "ts": _now_us(),
            "pid": _PID,
            "tid": threading.get_ident(),
            "args": self._a,
        }
        self._rec.emit(self._b)
        self._ann = _mirror(self._name)
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(None, None, None)
        self._rec.emit(
            {
                "ph": "E",
                "name": self._name,
                "cat": "kss",
                "ts": _now_us(),
                "pid": _PID,
                "tid": threading.get_ident(),
                "args": self._a,
            }
        )
        return False


def span(name: str, pass_id: "int | None" = None, **attrs):
    """A context manager recording `name` as a B/E span on the calling
    thread's track, stamped with the current (or given) pass id, and
    mirrored into any running `jax.profiler` capture. When tracing is
    off this returns a shared no-op immediately. Both kinds have
    `set(**attrs)` for attributes known only before the span ends."""
    rec = active()
    if rec is None:
        return _NULL_SPAN
    return _LiveSpan(rec, name, _args(pass_id, attrs))


def complete(
    name: str,
    start_s: float,
    end_s: float,
    *,
    tid: "int | None" = None,
    pass_id: "int | None" = None,
    **attrs,
) -> None:
    """One `X` (complete) event over [start_s, end_s] perf_counter
    seconds, on `tid` (default: the calling thread; pass `DEVICE_TID`
    for the synthetic device track). The async pipeline emits its
    dispatch→resolve windows through this at resolve time — the one
    span shape that can OVERLAP host spans instead of nesting."""
    rec = active()
    if rec is None:
        return
    rec.emit(
        {
            "ph": "X",
            "name": name,
            "cat": "kss",
            "ts": start_s * 1e6,
            "dur": max(0.0, (end_s - start_s) * 1e6),
            "pid": _PID,
            "tid": threading.get_ident() if tid is None else tid,
            "args": _args(pass_id, attrs),
        }
    )


def counter(name: str, value: float, tid: "int | None" = None) -> None:
    """A Chrome counter-track sample (`C` event): Perfetto renders the
    series of `value`s under `name` as a stepped area alongside the
    span tracks — load (pending pods, cumulative stall/dispatch
    seconds) next to the work that caused it. One series per name (the
    args key is always ``value``), no-op when tracing is off."""
    rec = active()
    if rec is None:
        return
    rec.emit(
        {
            "ph": "C",
            "name": name,
            "cat": "kss",
            "ts": _now_us(),
            "pid": _PID,
            "tid": threading.get_ident() if tid is None else tid,
            "args": {"value": float(value)},
        }
    )


def instant(name: str, pass_id: "int | None" = None, **attrs) -> None:
    """A point event on the calling thread's track (injected faults,
    sim-time marks)."""
    rec = active()
    if rec is None:
        return
    rec.emit(
        {
            "ph": "i",
            "name": name,
            "cat": "kss",
            "s": "t",
            "ts": _now_us(),
            "pid": _PID,
            "tid": threading.get_ident(),
            "args": _args(pass_id, attrs),
        }
    )


# -- Chrome trace-event export -----------------------------------------------


def _thread_names() -> dict:
    return {t.ident: t.name for t in threading.enumerate() if t.ident}


def chrome_trace(events: list[dict], *, dropped: int = 0) -> dict:
    """The Chrome trace-event JSON document (the JSON Object Format:
    https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU)
    for `events`, with process/thread metadata so Perfetto names the
    tracks. Loadable as-is in https://ui.perfetto.dev."""
    names = _thread_names()
    meta: list[dict] = [
        {
            "ph": "M",
            "name": "process_name",
            "pid": _PID,
            "tid": DEVICE_TID,
            "args": {"name": "kube-scheduler-simulator-tpu"},
        }
    ]
    seen_tids: set = set()
    for ev in events:
        tid = ev.get("tid")
        if tid in seen_tids:
            continue
        seen_tids.add(tid)
        if tid == DEVICE_TID:
            label = "device (in-flight passes)"
        else:
            label = names.get(tid, f"thread-{tid}")
        meta.append(
            {
                "ph": "M",
                "name": "thread_name",
                "pid": _PID,
                "tid": tid,
                "args": {"name": label},
            }
        )
    return {
        "traceEvents": meta + list(events),
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "kube_scheduler_simulator_tpu.utils.telemetry",
            "droppedEvents": dropped,
        },
    }


def dump_chrome_trace(path: str, recorder: "SpanRecorder | None" = None) -> int:
    """Write the recorder's retained window as a Chrome trace JSON file
    (the lifecycle CLI's --perfetto-out); returns the event count
    written. With no active recorder, writes an empty (still loadable)
    document."""
    rec = recorder if recorder is not None else active()
    events = rec.snapshot() if rec is not None else []
    doc = chrome_trace(events, dropped=rec.dropped if rec is not None else 0)
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(events)


def clock_us() -> float:
    """This process's monotonic trace clock (perf_counter µs) — the
    value worker exports report in ``otherData.clockUs`` so the fleet
    router's merge handshake can estimate per-process clock offsets
    (`merged_chrome_trace`)."""
    return _now_us()


def merged_chrome_trace(tracks: "list[dict]", *, dropped: int = 0) -> dict:
    """Join per-process Chrome-trace exports into ONE Perfetto document:
    each `tracks` entry is ``{"pid": int, "name": str, "events": [...],
    "offset_us": float, "thread_names": {tid: label} | None}``. Every
    event's ``ts`` is shifted by its track's monotonic-clock offset
    (estimated NTP-style by the caller's probe handshake: the midpoint
    of the fetch window minus the export's ``otherData.clockUs``) and
    its ``pid`` remapped to the track's — one process lane per worker
    plus the router lane. A constant per-track shift preserves each
    process's B/E ordering, so merged intervals stay well-formed
    (`check_nesting` holds iff it held per export)."""
    meta: list[dict] = []
    out: list[dict] = []
    for track in tracks:
        pid = int(track["pid"])
        names = track.get("thread_names") or {}
        offset = float(track.get("offset_us") or 0.0)
        meta.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": DEVICE_TID,
                "args": {"name": str(track.get("name") or f"process {pid}")},
            }
        )
        seen_tids: set = set()
        for ev in track.get("events") or []:
            if ev.get("ph") == "M":
                # per-export metadata is rebuilt here with the merged
                # pids; carry the thread labels over instead
                if ev.get("name") == "thread_name":
                    names.setdefault(
                        ev.get("tid"), (ev.get("args") or {}).get("name")
                    )
                continue
            ev = dict(ev)
            ev["pid"] = pid
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + offset
            out.append(ev)
            tid = ev.get("tid")
            if tid not in seen_tids:
                seen_tids.add(tid)
                meta.append(
                    {
                        "ph": "M",
                        "name": "thread_name",
                        "pid": pid,
                        "tid": tid,
                        "args": {
                            "name": names.get(tid) or f"thread-{tid}"
                        },
                    }
                )
    return {
        "traceEvents": meta + out,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "kube_scheduler_simulator_tpu.utils.telemetry",
            "droppedEvents": dropped,
            "merged": True,
            "tracks": [
                {
                    "pid": int(t["pid"]),
                    "name": str(t.get("name") or ""),
                    "offsetUs": round(float(t.get("offset_us") or 0.0), 3),
                }
                for t in tracks
            ],
        },
    }


# -- span-interval utilities (tests, smoke tooling) ---------------------------


def span_intervals(events: list[dict]) -> list[dict]:
    """Reconstruct closed spans from a trace-event list: each `X` event
    directly, each per-thread balanced B/E pair as one interval. Returns
    dicts ``{"name", "pid", "tid", "start_us", "end_us", "args"}``;
    unmatched B/E (ring-evicted partners) are skipped. Stacks are keyed
    (pid, tid): in a MERGED document (`merged_chrome_trace`) thread ids
    can collide across process tracks — same-process exports carry one
    constant pid, so the grouping is unchanged there. Also the well-
    formedness checker's engine: `check_nesting` raises on interleaved
    pairs."""
    out: list[dict] = []
    stacks: "dict[tuple, list[dict]]" = {}
    for ev in events:
        ph = ev.get("ph")
        if ph == "X":
            out.append(
                {
                    "name": ev["name"],
                    "pid": ev.get("pid"),
                    "tid": ev.get("tid"),
                    "start_us": float(ev["ts"]),
                    "end_us": float(ev["ts"]) + float(ev.get("dur", 0.0)),
                    "args": ev.get("args", {}),
                }
            )
        elif ph == "B":
            stacks.setdefault(
                (ev.get("pid"), ev.get("tid")), []
            ).append(ev)
        elif ph == "E":
            stack = stacks.get((ev.get("pid"), ev.get("tid")))
            if stack and stack[-1]["name"] == ev["name"]:
                b = stack.pop()
                out.append(
                    {
                        "name": b["name"],
                        "pid": b.get("pid"),
                        "tid": b.get("tid"),
                        "start_us": float(b["ts"]),
                        "end_us": float(ev["ts"]),
                        "args": b.get("args", {}),
                    }
                )
    return out


def check_nesting(events: list[dict], *, dropped: int = 0) -> None:
    """Raise ValueError unless every thread's B/E events form balanced,
    properly-nested pairs (E matches the innermost open B by name).
    With `dropped` > 0 (a ring-wrapped window: pass the recorder's
    `dropped` count, or the export's `otherData.droppedEvents`), E
    events arriving on an empty stack are tolerated — their B partners
    were evicted; proper LIFO closing means such orphans always land on
    an empty stack, so interleaving detection is unaffected. Stacks are
    keyed (pid, tid), so merged multi-process documents check each
    process track independently."""
    stacks: "dict[tuple, list[str]]" = {}
    for ev in events:
        ph = ev.get("ph")
        tid = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks.setdefault(tid, []).append(ev["name"])
        elif ph == "E":
            stack = stacks.setdefault(tid, [])
            if not stack:
                if dropped > 0:
                    continue  # B partner evicted from the ring
                raise ValueError(
                    f"unmatched E {ev['name']!r} on tid {tid} (no open span)"
                )
            if stack[-1] != ev["name"]:
                raise ValueError(
                    f"interleaved spans on tid {tid}: E {ev['name']!r} "
                    f"closes innermost B {stack[-1]!r}"
                )
            stack.pop()
    open_spans = {t: s for t, s in stacks.items() if s}
    if open_spans:
        raise ValueError(f"unclosed spans at end of window: {open_spans}")
