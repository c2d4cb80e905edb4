"""The program performance observatory: per-program cost ledger +
cold-start phase accounting (docs/observability.md).

ROADMAP #1 names the frontier — compiles cost 80–162 s per engine shape,
MFU sits at ~1e-6, and the wished-for headline is *time-to-first-
scheduled-pod from cold* — but none of that was observable on the
serving path: XLA cost/memory analysis only ran inside bench.py, compile
walls aggregated into one ``stallSeconds`` counter, and nothing diffed
across runs. Two instruments fix that:

**Per-program ledger** (``KSS_PROGRAM_LEDGER=1``, hooked into
``utils/broker.jit`` next to the KSS7xx auditor): every broker-jitted
program records, keyed ``(site label, compile fingerprint)``:

  * compile wall with the **lowering vs backend-compile split** — the
    first call of each argument signature goes through the AOT path
    (``trace().lower()`` timed, then ``.compile()`` timed) and later
    calls dispatch through the compiled executable, so the split costs
    no second compile;
  * ``compiled.cost_analysis()`` FLOPs/bytes and ``memory_analysis()``
    temp/argument/output bytes — XLA's own cost model of the program;
  * call count and cumulative dispatch seconds (async-dispatch wall —
    the host-side cost of driving the program);
  * a **sampled warm device wall**: ``KSS_PROGRAM_TIMING_SAMPLE=N``
    blocks on the result every Nth call (first/compile call excluded)
    — off by default so the async hot path never synchronizes;
  * derived per-program **MFU** (``utils/metrics.PEAKS``)
    on known accelerators, from the cost-model FLOPs over the sampled
    warm wall;
  * per-session call attribution via the telemetry session labels.

The ledger persists as ``kss-program-ledger.json`` (format
``kss-program-ledger/v1``), a sibling of the KSS715 fingerprint
baseline, and ``diff_ledger`` flags compile-seconds drift (KSS731),
FLOPs drift (KSS732), and vanished/new programs (KSS733/KSS734) across
runs — the ``analysis ledger-diff`` CLI subcommand turns that into a
perf-regression gate (tools/perf_smoke.py runs it).

**Cold-start phase accounting** (`COLD_START`): process-global
first-occurrence marks — boot probe → first encode → first compile →
first pass — each emitted as a ``coldstart.*`` telemetry instant and
summarized as ``timeToFirstPassSeconds`` in the ``coldStart`` block of
``GET /api/v1/metrics`` (schema v3) and the ``bench.py --cold-start``
headline. The origin is this module's import (the first package import
of the process), so the numbers answer "how long from process start
until the first pod was scheduled" — the gate ROADMAP #1's AOT-bundle
work will be measured against.

Everything here is **off the hot path by default**: the ledger arms per
jit-wrap via the env switch (like ``KSS_JAXPR_AUDIT``), cold-start
marks are one dict probe under a leaf lock per site, and warm-timing
samples never happen unless ``KSS_PROGRAM_TIMING_SAMPLE`` asks.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from . import locking, telemetry
from .envcheck import env_truthy
from ..analysis.core import Finding

LEDGER_FORMAT = "kss-program-ledger/v1"
LEDGER_BASENAME = "kss-program-ledger.json"

ENV_VAR = "KSS_PROGRAM_LEDGER"
SAMPLE_VAR = "KSS_PROGRAM_TIMING_SAMPLE"

# the session key unattributed calls land under (sessionless services,
# bench, the lifecycle CLI) — matches the serving plane's implicit
# default session id (server/sessions.py)
DEFAULT_SESSION_KEY = "default"

# diff_ledger defaults: a compile-seconds regression must exceed BOTH
# the ratio and the absolute floor before it flags — compile walls are
# noisy run to run, and a 0.2 s jitter on a 0.3 s CPU compile is not
# the 80 s chip regression this gate exists to catch
DRIFT_RATIO = 1.5
DRIFT_FLOOR_S = 1.0


def ledger_enabled() -> bool:
    """The ledger switch (``KSS_PROGRAM_LEDGER``), read at jit-wrap
    time by ``utils/broker.jit`` — engine construction — exactly like
    the KSS7xx audit switch."""
    return env_truthy(os.environ.get(ENV_VAR))


def timing_sample_every() -> int:
    """Warm-timing sample cadence from ``KSS_PROGRAM_TIMING_SAMPLE``:
    0 (the default) never blocks — the async hot path stays async;
    N > 0 blocks on the result every Nth call of each program (the
    first, compile-bearing call is never sampled). Lenient parse: a
    malformed value must not start synchronizing the serving path."""
    raw = os.environ.get(SAMPLE_VAR, "")
    try:
        n = int(raw) if raw else 0
    except ValueError:
        return 0
    return n if n >= 0 else 0


@dataclass
class ProgramRecord:
    """One (site label, compile fingerprint) program in the ledger."""

    label: str
    fingerprint: str
    in_avals: "tuple[Any, ...]" = ()
    out_avals: "tuple[Any, ...]" = ()
    builds: int = 0  # how many times this program's compile was paid
    lowering_s: float = 0.0  # cumulative trace+lower wall
    backend_s: float = 0.0  # cumulative XLA backend-compile wall
    # AOT-bundle accounting (utils/bundles.py): executables served by
    # deserializing an on-disk bundle instead of compiling — the
    # deserialization wall is recorded DISTINCTLY from the compile wall
    # so a bundled boot's ledger shows zero compile seconds, not a
    # mislabeled fast compile
    bundle_loads: int = 0
    deserialize_s: float = 0.0  # cumulative bundle-deserialize wall
    flops: "float | None" = None  # cost_analysis of ONE execution
    bytes: "float | None" = None
    memory: "dict | None" = None  # memory_analysis byte breakdown
    calls: int = 0
    dispatch_s: float = 0.0  # cumulative async-dispatch wall
    warm_samples: int = 0  # sampled block_until_ready executions
    warm_s: float = 0.0  # cumulative sampled warm device wall
    sessions: "dict[str, int]" = field(default_factory=dict)
    degraded: bool = False  # AOT dispatch fell back to plain jit
    # monotonic stamp of this record's most recent call — how a batched
    # dispatch's session fan-out (attribute_sessions) finds the record
    # that actually dispatched when several fingerprints share a label
    last_call_seq: int = 0


@locking.guard_inferred
class ProgramLedger:
    """The process-global per-program cost ledger (module docstring).

    Writers are the broker's `AuditedJit` wrappers (one `open_program`
    per new (site, signature), one `record_call` per dispatch) and the
    bench probes (`observe` — the shared AOT cost path). Readers are
    ``GET /api/v1/debug/programs``, the Prometheus exposition, and
    `persist`/`diff_ledger`."""

    def __init__(self) -> None:
        self._lock = locking.make_lock("ledger.records")
        self._records: "dict[tuple[str, str], ProgramRecord]" = {}
        self._dispatch_total = 0.0
        self._call_seq = 0

    # -- writing -------------------------------------------------------------

    def open_program(
        self,
        label: str,
        fingerprint: str,
        *,
        in_avals: tuple = (),
        out_avals: tuple = (),
        lowering_s: float = 0.0,
        backend_s: float = 0.0,
        deserialize_s: float = 0.0,
        loaded: bool = False,
        cost: "dict | None" = None,
        memory: "dict | None" = None,
    ) -> ProgramRecord:
        """Record one compile of ``(label, fingerprint)``; a re-build of
        a known program (broker eviction, device-epoch bump) accumulates
        its compile wall instead of opening a duplicate row — recompile
        cost is exactly what the ledger must not hide. With
        ``loaded=True`` the program came from an AOT bundle
        (utils/bundles.py): the deserialize wall accumulates instead of
        a build — the two costs must never conflate."""
        key = (label, fingerprint)
        with self._lock:
            rec = self._records.get(key)
            if rec is None:
                rec = self._records[key] = ProgramRecord(
                    label, fingerprint, in_avals, out_avals
                )
            if loaded:
                rec.bundle_loads += 1
                rec.deserialize_s += float(deserialize_s)
            else:
                rec.builds += 1
            rec.lowering_s += float(lowering_s)
            rec.backend_s += float(backend_s)
            if cost:
                rec.flops = float(cost.get("flops", 0.0))
                rec.bytes = float(cost.get("bytes", 0.0))
            if memory:
                rec.memory = dict(memory)
            return rec

    def record_call(
        self,
        rec: ProgramRecord,
        dispatch_s: float,
        session: "str | None" = None,
        warm_s: "float | None" = None,
        degraded: bool = False,
    ) -> None:
        sid = session if session is not None else DEFAULT_SESSION_KEY
        with self._lock:
            self._call_seq += 1
            rec.last_call_seq = self._call_seq
            rec.calls += 1
            rec.dispatch_s += float(dispatch_s)
            rec.sessions[sid] = rec.sessions.get(sid, 0) + 1
            if warm_s is not None:
                rec.warm_samples += 1
                rec.warm_s += float(warm_s)
            if degraded:
                rec.degraded = True
            self._dispatch_total += float(dispatch_s)
            total = self._dispatch_total
        # the Perfetto counter track rides the flight recorder (no-op
        # when tracing is off); emitted OUTSIDE the ledger lock
        telemetry.counter("ledger.dispatchSeconds", total)

    def observe(self, label: str, jitted: Any, args: tuple) -> "dict | None":
        """The shared AOT cost probe (bench's ``cost_fields`` routes
        here, so bench and the serving ledger are ONE accounting): time
        ``trace().lower()`` and ``.compile()``, read the compiled cost
        and memory models, record the program under `label`, and return
        ``{"flops", "bytes", "lowering_s", "backend_s"}`` — or None
        when the backend exposes no cost model. Never raises: cost
        telemetry must not break a measurement run."""
        probe = aot_probe(jitted, args)
        if probe is None:
            return None
        _compiled, info, _traced = probe
        if info.get("flops") is None:
            return None
        fingerprint = _observe_fingerprint(label, args)
        self.open_program(
            label,
            fingerprint,
            lowering_s=info["lowering_s"],
            backend_s=info["backend_s"],
            cost={"flops": info["flops"], "bytes": info["bytes"]},
            memory=info.get("memory"),
        )
        return info

    # -- bookkeeping ---------------------------------------------------------

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._dispatch_total = 0.0

    def attribute_sessions(self, label: str, sids: "list[str | None]") -> None:
        """Fan one batched dispatch's attribution out to every enrolled
        tenant (server/batchplane.py): the window's single device
        dispatch was recorded under the LEADER's session context; the
        other enrolled sessions' passes were served by the same call.
        For ``batch.*`` programs the per-session counts are therefore
        PASSES SERVED and may exceed `calls` (device dispatches) — the
        gap IS the batching win, and `make batch-smoke` pins it.

        Several fingerprints can share a label (one per batch bucket /
        cluster shape): the fan-out lands on the record that MOST
        RECENTLY dispatched — the caller attributes immediately after
        its own call, so the freshest stamp is that dispatch (a
        concurrent other-key window can at worst swap two same-label
        attributions, never invent one)."""
        with self._lock:
            matching = [
                rec for rec in self._records.values() if rec.label == label
            ]
            if not matching:
                return
            rec = max(matching, key=lambda r: r.last_call_seq)
            for sid in sids:
                key = sid if sid is not None else DEFAULT_SESSION_KEY
                rec.sessions[key] = rec.sessions.get(key, 0) + 1

    def drop_session(self, sid: str) -> None:
        """Purge a deleted session's call attribution (the session-plane
        DELETE path, server/sessions.py) — a dead tenant's label must
        not linger in every later scrape. Programs themselves stay: the
        compiled executable (and its cost) outlives any one tenant."""
        with self._lock:
            for rec in self._records.values():
                rec.sessions.pop(sid, None)

    # -- reading -------------------------------------------------------------

    def memory_bytes_total(self) -> "int | None":
        """Summed `memory_analysis` bytes across every recorded program
        — the fleet observatory's estimate of what the broker's warm
        executables hold (utils/fleetstats.py buffer census). None when
        no program carries a memory model (the backend may expose
        none); never zero-as-unknown."""
        with self._lock:
            vals = [
                sum(rec.memory.values())
                for rec in self._records.values()
                if rec.memory
            ]
        return sum(vals) if vals else None

    def totals(self) -> dict:
        """The small summary block ``GET /api/v1/metrics`` embeds."""
        with self._lock:
            return {
                "enabled": ledger_enabled(),
                "count": len(self._records),
                "compileSeconds": round(
                    sum(
                        r.lowering_s + r.backend_s
                        for r in self._records.values()
                    ),
                    6,
                ),
                "dispatchSeconds": round(self._dispatch_total, 6),
                "deserializeSeconds": round(
                    sum(r.deserialize_s for r in self._records.values()), 6
                ),
                "bundleLoads": sum(
                    r.bundle_loads for r in self._records.values()
                ),
                "calls": sum(r.calls for r in self._records.values()),
            }

    def snapshot(self, session: "str | None" = None) -> dict:
        """The full ledger document (``GET /api/v1/debug/programs`` and
        the persisted file). `session` filters to programs that session's
        passes actually dispatched (the nested per-session route)."""
        device = _device()
        platform = device.platform if device is not None else ""
        from . import metrics as metrics_mod

        programs: list[dict] = []
        with self._lock:
            records = [
                rec
                for rec in self._records.values()
                if session is None or session in rec.sessions
            ]
            for rec in sorted(records, key=lambda r: (r.label, r.fingerprint)):
                warm_mean = (
                    rec.warm_s / rec.warm_samples if rec.warm_samples else None
                )
                entry = {
                    "label": rec.label,
                    "fingerprint": rec.fingerprint,
                    "builds": rec.builds,
                    "bundleLoads": rec.bundle_loads,
                    "deserializeSeconds": round(rec.deserialize_s, 6),
                    "compileSeconds": {
                        "lowering": round(rec.lowering_s, 6),
                        "backend": round(rec.backend_s, 6),
                        "total": round(rec.lowering_s + rec.backend_s, 6),
                    },
                    "flops": rec.flops,
                    "bytes": rec.bytes,
                    "memory": rec.memory,
                    "calls": rec.calls,
                    "dispatchSeconds": round(rec.dispatch_s, 6),
                    "warm": {
                        "samples": rec.warm_samples,
                        "seconds": round(rec.warm_s, 6),
                        "meanSeconds": round(warm_mean, 9)
                        if warm_mean is not None
                        else None,
                    },
                    "mfu": metrics_mod.mfu(rec.flops, warm_mean, device)
                    if warm_mean and device is not None
                    else None,
                    "sessions": dict(rec.sessions),
                    "degraded": rec.degraded,
                }
                programs.append(entry)
        return {
            "format": LEDGER_FORMAT,
            "platform": platform,
            "programs": programs,
        }

    def render_prometheus(self) -> str:
        """The ``kss_program_*`` exposition families, one sample per
        (program, fingerprint) series — appended to the session-labeled
        document by the metrics route. Empty string when the ledger has
        recorded nothing (an empty family block is just noise)."""
        doc = self.snapshot()
        if not doc["programs"]:
            return ""
        from .metrics import _fmt_value

        lines: list[str] = []

        def family(name: str, mtype: str, help_text: str, value_of) -> None:
            samples = [
                (p, value_of(p)) for p in doc["programs"]
            ]
            samples = [(p, v) for p, v in samples if v is not None]
            if not samples:
                return
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {mtype}")
            for p, v in samples:
                lines.append(
                    f'{name}{{program="{p["label"]}",'
                    f'fingerprint="{p["fingerprint"]}"}} {_fmt_value(v)}'
                )

        family(
            "kss_program_compile_seconds",
            "gauge",
            "Compile wall (lowering + backend) paid for this program.",
            lambda p: p["compileSeconds"]["total"],
        )
        family(
            "kss_program_flops",
            "gauge",
            "XLA cost-model FLOPs of one execution of this program.",
            lambda p: p["flops"],
        )
        family(
            "kss_program_calls_total",
            "counter",
            "Executions dispatched through this program.",
            lambda p: p["calls"],
        )
        family(
            "kss_program_warm_seconds",
            "gauge",
            "Mean sampled warm device wall of this program "
            "(KSS_PROGRAM_TIMING_SAMPLE).",
            lambda p: p["warm"]["meanSeconds"],
        )
        return "\n".join(lines) + "\n" if lines else ""

    # -- persistence ---------------------------------------------------------

    def persist(self, path: "str | None" = None) -> "list[Finding]":
        """Write the current ledger as the new baseline at `path`
        (default: next to the persistent compile cache), returning the
        drift findings against what was there (`diff_ledger`). Unlike
        the fingerprint baseline this OVERWRITES rather than merges:
        stale compile walls from dead programs would poison every later
        diff."""
        path = ledger_path() if path is None else path
        current = self.snapshot()
        previous = load_ledger(path)
        drift = (
            diff_ledger(previous, current) if previous is not None else []
        )
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(current, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return drift


def _device():
    """The default device, or None when no backend starts (a dead
    backend still has a ledger)."""
    try:
        import jax

        return jax.devices()[0]
    except Exception:  # noqa: BLE001
        return None


def _observe_fingerprint(label: str, args: tuple) -> str:
    """A bench-probe fingerprint from the argument avals alone (the
    full jaxpr fingerprint needs the jit kwargs the probe doesn't
    carry; aval identity is what the probe's compile is keyed by)."""
    sig = []
    for a in args:
        shape = tuple(int(d) for d in getattr(a, "shape", ()))
        sig.append((shape, str(getattr(a, "dtype", type(a).__name__))))
    doc = json.dumps({"label": label, "avals": sig}, sort_keys=True)
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


def aot_probe(jitted: Any, args: tuple, kwargs: "dict | None" = None):
    """Time the AOT path of one program: returns ``(compiled, info,
    traced)`` with ``info = {"lowering_s", "backend_s", "flops",
    "bytes", "memory"}`` (flops/bytes None when the backend exposes no
    cost model), or None when lowering/compiling itself failed. The one
    compile-splitting primitive the ledger wrapper and the bench cost
    path share; `traced` is handed back so the wrapper's fingerprint
    never pays a second trace."""
    try:
        t0 = time.perf_counter()
        traced = jitted.trace(*args, **(kwargs or {}))
        lowered = traced.lower()
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    except Exception:  # noqa: BLE001 — observability must not fail the program
        return None
    info: dict = {
        "lowering_s": t1 - t0,
        "backend_s": t2 - t1,
        "flops": None,
        "bytes": None,
        "memory": None,
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            info["flops"] = float(ca.get("flops", 0.0) or 0.0)
            info["bytes"] = float(ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:  # noqa: BLE001 — cost model is optional per backend
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                key: int(getattr(ma, attr))
                for key, attr in (
                    ("tempBytes", "temp_size_in_bytes"),
                    ("argumentBytes", "argument_size_in_bytes"),
                    ("outputBytes", "output_size_in_bytes"),
                    ("aliasBytes", "alias_size_in_bytes"),
                    ("generatedCodeBytes", "generated_code_size_in_bytes"),
                )
                if getattr(ma, attr, None) is not None
            }
            if mem:
                info["memory"] = mem
    except Exception:  # noqa: BLE001 — memory model is optional per backend
        pass
    return compiled, info, traced


# -- persistence / diff --------------------------------------------------------


def ledger_path(cache_dir: "str | None" = None) -> str:
    """The baseline file, next to the persistent compile cache and the
    KSS715 fingerprint baseline (`compilecache.cache_dir`)."""
    from . import compilecache

    if cache_dir is None:
        cache_dir = compilecache.cache_dir()
    return os.path.join(cache_dir, LEDGER_BASENAME)


def load_ledger(path: "str | None" = None) -> "dict | None":
    """A persisted ledger document, or None when absent/foreign/corrupt
    (callers distinguish "no baseline yet" from "unreadable baseline"
    only by existence — both mean: nothing to diff against)."""
    path = ledger_path() if path is None else path
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("format") != LEDGER_FORMAT:
        return None
    if not isinstance(doc.get("programs"), list):
        return None
    return doc


def _by_key(doc: dict) -> "dict[tuple[str, str], dict]":
    out: dict[tuple[str, str], dict] = {}
    for p in doc.get("programs", []):
        if isinstance(p, dict) and "label" in p and "fingerprint" in p:
            out[(str(p["label"]), str(p["fingerprint"]))] = p
    return out


def diff_ledger(
    previous: dict,
    current: dict,
    *,
    ratio: float = DRIFT_RATIO,
    floor_s: float = DRIFT_FLOOR_S,
) -> "list[Finding]":
    """Perf-regression diff of two ledger documents:

      KSS731  compile-seconds regression — a label's TOTAL compile wall
              (summed over its fingerprints, so a changed fingerprint
              cannot hide the cost under a 'different' key) grew past
              BOTH ``ratio`` × the baseline and the absolute ``floor_s``
              (compile walls jitter; only a real regression clears both
              bars — improvements never flag);
      KSS732  FLOPs drift — the cost model of an identically-
              fingerprinted program changed (the program is not the
              program the baseline measured);
      KSS733  a baseline program label the current run no longer
              builds (vanished work — or a silently renamed site);
      KSS734  a program label the baseline never saw (new compile
              cost the baseline didn't budget);
      KSS735  fingerprint churn under a surviving label — the site
              compiles DIFFERENT programs than the baseline (an
              avals/static-arg drift: exactly the recompile class the
              gate exists to catch, and invisible to per-fingerprint
              comparison alone).

    Two identically-seeded runs diff clean; the tier-1 gate pins it."""
    findings: list[Finding] = []
    prev, cur = _by_key(previous), _by_key(current)
    prev_labels = {label for label, _ in prev}
    cur_labels = {label for label, _ in cur}

    def label_compile_s(doc_keys: dict, label: str) -> float:
        return sum(
            float((p.get("compileSeconds") or {}).get("total", 0.0))
            for (lb, _fp), p in doc_keys.items()
            if lb == label
        )

    for label in sorted(prev_labels & cur_labels):
        site = f"<program:{label}>"
        p_fps = {fp for lb, fp in prev if lb == label}
        c_fps = {fp for lb, fp in cur if lb == label}
        if p_fps != c_fps:
            gained = sorted(c_fps - p_fps)
            lost = sorted(p_fps - c_fps)
            parts = []
            if gained:
                parts.append(f"gained {gained}")
            if lost:
                parts.append(f"lost {lost}")
            findings.append(
                Finding(
                    "KSS735",
                    site,
                    0,
                    f"compile-fingerprint churn at {label!r}: "
                    + "; ".join(parts)
                    + " — the site compiles different programs than "
                    "the baseline",
                    hint="an avals/static-arg change reached this site "
                    "(compare with the KSS715 fingerprint baseline); "
                    "re-baseline by persisting if intended",
                )
            )
        # compile regression at LABEL granularity: summed over
        # fingerprints, so a changed fingerprint cannot park the new
        # cost under a key the per-key comparison never visits
        p_compile = label_compile_s(prev, label)
        c_compile = label_compile_s(cur, label)
        if c_compile > p_compile * ratio and c_compile - p_compile > floor_s:
            findings.append(
                Finding(
                    "KSS731",
                    site,
                    0,
                    f"compile wall regressed {p_compile:.3f}s -> "
                    f"{c_compile:.3f}s (> {ratio}x and > +{floor_s}s)",
                    hint="a program this site compiles got expensive — "
                    "bisect the lowering change, or re-baseline by "
                    "persisting if intended",
                )
            )
    for key in sorted(set(prev) & set(cur)):
        label, fp = key
        site = f"<program:{label}@{fp}>"
        p, c = prev[key], cur[key]
        p_flops, c_flops = p.get("flops"), c.get("flops")
        if (
            p_flops is not None
            and c_flops is not None
            and float(p_flops) != float(c_flops)
        ):
            findings.append(
                Finding(
                    "KSS732",
                    site,
                    0,
                    f"cost-model FLOPs drifted {p_flops} -> {c_flops} "
                    f"for an identically-fingerprinted program",
                    hint="the compiled program changed under a stable "
                    "fingerprint — compare the two runs' jaxprs",
                )
            )
    for label in sorted(prev_labels - cur_labels):
        findings.append(
            Finding(
                "KSS733",
                f"<program:{label}>",
                0,
                f"baseline program {label!r} vanished from the current "
                f"run",
                hint="the site no longer compiles (dead code, a rename, "
                "or lost coverage) — re-baseline if intended",
            )
        )
    for label in sorted(cur_labels - prev_labels):
        findings.append(
            Finding(
                "KSS734",
                f"<program:{label}>",
                0,
                f"program {label!r} is new against the baseline",
                hint="new compile cost the baseline didn't budget — "
                "re-baseline by persisting if intended",
            )
        )
    return findings


# -- cold-start phase accounting ----------------------------------------------

# the canonical phase order (docs/performance.md): marks may land in
# any order at runtime (a lifecycle CLI has no boot probe), but the
# snapshot renders them in this sequence
COLD_START_PHASES = ("bootProbe", "firstEncode", "firstCompile", "firstPass")


@locking.guard_inferred
class ColdStartTracker:
    """Process-global first-occurrence marks from process start (this
    module's import) to the first scheduled pass. Each `mark` is
    latched — only the FIRST occurrence of a phase records — and emits
    a ``coldstart.<phase>`` telemetry instant so the Perfetto timeline
    shows where the cold start went."""

    def __init__(self) -> None:
        self._lock = locking.make_lock("ledger.coldstart")
        self._origin = time.perf_counter()
        self._marks: "dict[str, float]" = {}

    def mark(self, phase: str) -> None:
        now = time.perf_counter()
        with self._lock:
            if phase in self._marks:
                return
            self._marks[phase] = now - self._origin
            offset = self._marks[phase]
        telemetry.instant(
            f"coldstart.{phase}", secondsSinceStart=round(offset, 6)
        )

    def snapshot(self) -> dict:
        """The ``coldStart`` block of ``GET /api/v1/metrics``: seconds
        from process start per phase, the headline
        ``timeToFirstPassSeconds``, and whether the cold start is over
        (`complete`: the first pass happened)."""
        with self._lock:
            marks = dict(self._marks)
        phases = {
            phase: round(marks[phase], 6)
            for phase in COLD_START_PHASES
            if phase in marks
        }
        ttfp = marks.get("firstPass")
        return {
            "phases": phases,
            "timeToFirstPassSeconds": round(ttfp, 6)
            if ttfp is not None
            else None,
            "complete": ttfp is not None,
        }

    def reset(self) -> None:
        """Restart the clock (tests; a forked bench probe)."""
        with self._lock:
            self._origin = time.perf_counter()
            self._marks.clear()


LEDGER = ProgramLedger()
COLD_START = ColdStartTracker()
