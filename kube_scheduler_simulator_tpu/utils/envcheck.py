"""Strict startup validation of the KSS_* environment surface.

The serving-stack knobs are deliberately LENIENT at their point of use —
a malformed `KSS_ENCODING_CACHE_CAP` must not take a long-lived library
caller down, so the runtime parsers fall back to defaults (or, for the
fault plane, raise at the first fire point deep inside a request
handler). That leniency is exactly wrong at process startup: an operator
who typo'd a knob should learn it from a clear boot-time error, not from
a silently-defaulted cache size or a 500 mid-request. The entry points
(`python -m ...server`, `python -m ...lifecycle`) call `fail_fast()`
before doing anything else.

The registry below is the single catalogue of KSS_* variables
(docs/environment-variables.md mirrors it); unknown `KSS_`-prefixed
names are flagged too, catching the `KSS_ENCODNG_CACHE_CAP` class of
typo that otherwise configures nothing.
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Mapping

# a validator takes the raw env string and answers an error message, or
# None when the value parses (the registry's value type)
Validator = Callable[[str], "str | None"]

# The ONE boolean vocabulary: every spelling `check_env` accepts is a
# spelling the runtime parsers honor (broker speculation kill switch,
# telemetry KSS_TRACE). Validation blessing a value the runtime would
# silently ignore is exactly the misconfiguration class this module
# exists to catch.
TRUTHY: "tuple[str, ...]" = ("1", "true", "yes", "on", "t")
FALSY: "tuple[str, ...]" = ("", "0", "false", "no", "off", "f")
_BOOLISH = TRUTHY + FALSY


def env_truthy(raw: "str | None") -> bool:
    """Shared boolean env parse: True for any TRUTHY spelling (case- and
    whitespace-insensitive), False otherwise."""
    return (raw or "").strip().lower() in TRUTHY


def _int_validator(minimum: "int | None" = None) -> Validator:
    def check(raw: str) -> "str | None":
        try:
            v = int(raw)
        except ValueError:
            return f"expected an integer, got {raw!r}"
        if minimum is not None and v < minimum:
            return f"must be >= {minimum}, got {v}"
        return None

    return check


def _float_validator(minimum: "float | None" = None) -> Validator:
    def check(raw: str) -> "str | None":
        try:
            v = float(raw)
        except ValueError:
            return f"expected a number, got {raw!r}"
        if minimum is not None and v < minimum:
            return f"must be >= {minimum}, got {v}"
        return None

    return check


def _bool_validator(raw: str) -> "str | None":
    if raw.strip().lower() not in _BOOLISH:
        return f"expected a boolean (0/1/true/false/yes/no/on/off), got {raw!r}"
    return None


def _choice_validator(*choices: str) -> Validator:
    def check(raw: str) -> "str | None":
        if raw.strip().lower() not in choices:
            return f"expected one of {'/'.join(choices)}, got {raw!r}"
        return None

    return check


def _fault_spec_validator(raw: str) -> "str | None":
    from . import faultinject

    try:
        faultinject.FaultPlane.parse(raw)  # type: ignore[no-untyped-call]
    except ValueError as e:
        return str(e)
    return None


def _path_validator(raw: str) -> "str | None":
    return None  # any string is a path; existence is created on demand


def _label_value_validator(raw: str) -> "str | None":
    # the value lands verbatim inside Prometheus label bodies and file
    # paths (the fleet's per-worker session namespace): keep it to a
    # conservative identifier charset
    import re

    if not re.fullmatch(r"[A-Za-z0-9._-]{1,64}", raw.strip()):
        return (
            "expected a label-safe identifier "
            "([A-Za-z0-9._-], at most 64 chars), got " + repr(raw)
        )
    return None


def _slo_objectives_validator(raw: str) -> "str | None":
    from . import slo

    try:
        slo.parse_objectives(raw)
    except ValueError as e:
        return str(e)
    return None


# name -> validator(raw) returning an error string or None. The ONE
# catalogue of KSS_* configuration (docs/environment-variables.md).
KNOWN: "dict[str, Validator]" = {
    # serving stack
    "KSS_ENCODING_CACHE_CAP": _int_validator(1),
    # the encoded-cluster dtype policy (engine/encode.py policy_from_env,
    # docs/performance.md "Encoding widths"): "packed" stores the cluster
    # tensors bitpacked/narrowed with in-kernel unpack; placements stay
    # byte-identical to the default int32 plane. Empty = tpu32.
    "KSS_DTYPE_POLICY": _choice_validator("", "exact", "i32", "tpu32", "packed"),
    # the gang engine's serving-path evaluation chunk (server/service.py
    # gang_chunk): compact mode's skip-settled granularity on the fused
    # fixpoint AND the record path's replay evaluation; placements are
    # chunk-invariant, so this is a pure performance knob (default 64)
    "KSS_GANG_CHUNK": _int_validator(1),
    "KSS_NO_SPECULATIVE_COMPILE": _bool_validator,
    # the persistent AOT bundle store (utils/bundles.py): serialize
    # every broker-jitted program's compiled executable to disk and
    # deserialize it on the next boot instead of re-lowering; the
    # directory defaults to a sibling of kss-fingerprints.json in the
    # compile cache dir
    "KSS_AOT_BUNDLES": _bool_validator,
    "KSS_BUNDLE_DIR": _path_validator,
    # telemetry plane
    "KSS_TRACE": _bool_validator,
    "KSS_TRACE_RING_CAP": _int_validator(1),
    # cross-process trace-context propagation (defaults on whenever a
    # recorder is active; =0 keeps spans local to each process)
    "KSS_TRACE_PROPAGATE": _bool_validator,
    # the fleet & memory observatory (utils/fleetstats.py): per-pass
    # device-HBM + cluster-quality sampling into a bounded ring, served
    # by GET /api/v1/timeseries / Prometheus gauges / the dashboard;
    # SAMPLE records every Nth pass; HEADROOM_BYTES gates speculative
    # compiles on free device memory
    "KSS_FLEET_STATS": _bool_validator,
    "KSS_FLEET_RING_CAP": _int_validator(1),
    "KSS_FLEET_SAMPLE": _int_validator(1),
    "KSS_SPEC_MEM_HEADROOM_BYTES": _int_validator(0),
    # the SLO plane (utils/slo.py, docs/observability.md): per-tenant
    # objectives over already-recorded signals, multi-window burn-rate
    # alerts (pending -> firing -> resolved), and the alert history
    # ring served by GET /api/v1/alerts; OBJECTIVES is a strict grammar
    # over the default set (e.g. "passLatency:target=0.999,threshold=0.5")
    "KSS_SLO": _bool_validator,
    "KSS_SLO_OBJECTIVES": _slo_objectives_validator,
    "KSS_SLO_WINDOW_FAST_S": _float_validator(1.0),
    "KSS_SLO_WINDOW_SLOW_S": _float_validator(1.0),
    "KSS_SLO_BURN_FAST": _float_validator(0.0),
    "KSS_SLO_BURN_SLOW": _float_validator(0.0),
    "KSS_SLO_ALERT_FOR_S": _float_validator(0.0),
    "KSS_SLO_ALERT_RING_CAP": _int_validator(1),
    # histogram exemplar capture (utils/metrics.py): on by default —
    # any FALSY spelling disables attaching the causal pass id to
    # histogram buckets (the ?format=openmetrics exemplar source)
    "KSS_EXEMPLARS": _bool_validator,
    # run supervision
    "KSS_COMPILE_DEADLINE_S": _float_validator(0.0),
    "KSS_COMPILE_RETRIES": _int_validator(0),
    "KSS_COMPILE_BACKOFF_S": _float_validator(0.0),
    "KSS_COMPILE_COOLDOWN_PASSES": _int_validator(1),
    "KSS_COMPILE_COOLDOWN_TTL_S": _float_validator(0.0),
    # execution ladder + graceful drain (docs/resilience.md)
    "KSS_DISPATCH_DEADLINE_S": _float_validator(0.0),
    "KSS_DISPATCH_RETRIES": _int_validator(0),
    "KSS_DRAIN_DEADLINE_S": _float_validator(0.0),
    "KSS_FAULT_INJECT": _fault_spec_validator,
    "KSS_FAULT_INJECT_SEED": _int_validator(),
    # static analysis / debug tooling (docs/static-analysis.md): wrap
    # the serving stack's known locks in the runtime lock-order witness
    # (utils/locking.py) — raises on an acquisition-order inversion
    "KSS_LOCK_CHECK": _bool_validator,
    # the guarded-state witness (KSS6xx, utils/locking.py): wrap the
    # statically-inferred lock-claimed attributes in descriptors that
    # raise UnguardedAccess; SAMPLE checks every Nth access (default 1)
    "KSS_RACE_CHECK": _bool_validator,
    "KSS_RACE_CHECK_SAMPLE": _int_validator(1),
    # the jaxpr auditor (KSS7xx, analysis/jaxpr_audit.py): audit every
    # broker-jitted program's ClosedJaxpr on first trace
    "KSS_JAXPR_AUDIT": _bool_validator,
    # the program performance ledger (utils/ledger.py): record every
    # broker-jitted program's compile wall split, cost-model FLOPs/
    # bytes, memory bytes, calls, and dispatch seconds; SAMPLE blocks
    # on every Nth call for a warm device wall (0 = never block)
    "KSS_PROGRAM_LEDGER": _bool_validator,
    "KSS_PROGRAM_TIMING_SAMPLE": _int_validator(0),
    # `make lint` / the analysis CLI: missing ruff/mypy and a non-empty
    # allowlist become hard failures instead of notes (CI honesty)
    "KSS_LINT_STRICT": _bool_validator,
    # cross-tenant continuous batching (server/batchplane.py,
    # docs/sessions.md): stack bucket-compatible concurrent sessions'
    # passes onto ONE device dispatch; WINDOW_MS is the collection
    # window, MAX_WAIT_MS bounds any enrollee's added latency (default:
    # one window), MAX_SESSIONS caps the batch axis
    "KSS_BATCH": _bool_validator,
    "KSS_BATCH_WINDOW_MS": _float_validator(0.0),
    "KSS_BATCH_MAX_WAIT_MS": _float_validator(0.0),
    "KSS_BATCH_MAX_SESSIONS": _int_validator(1),
    # the horizontal serving fleet (kube_scheduler_simulator_tpu/fleet,
    # docs/fleet.md): WORKER_ID stamps every exposition sample with a
    # `worker` label (and the metrics JSON with `workerId`); the router
    # reads WORKERS (how many workers to spawn), DIR (the fleet state
    # root: per-worker session namespaces, logs), BASE_PORT (first
    # worker port; 0 = ephemeral), and PROBE_INTERVAL_S (the readyz
    # health-probe cadence)
    "KSS_WORKER_ID": _label_value_validator,
    "KSS_FLEET_WORKERS": _int_validator(1),
    "KSS_FLEET_DIR": _path_validator,
    "KSS_FLEET_BASE_PORT": _int_validator(0),
    "KSS_FLEET_PROBE_INTERVAL_S": _float_validator(0.05),
    # the fleet durability plane (server/durability.py,
    # server/replication.py, docs/fleet.md): JOURNAL arms per-session
    # write-ahead journaling of acknowledged store mutations;
    # JOURNAL_SYNC fsyncs each append and ships it inline to the ring
    # successors before the HTTP ack (zero-loss crash-kill); REPLICAS is
    # the successor count each session replicates to (0 = off);
    # REPLICATE_EVERY_S the full-unit ship cadence
    "KSS_FLEET_JOURNAL": _bool_validator,
    "KSS_FLEET_JOURNAL_SYNC": _bool_validator,
    "KSS_FLEET_REPLICAS": _int_validator(0),
    "KSS_FLEET_REPLICATE_EVERY_S": _float_validator(0.05),
    # router resilience (fleet/router.py, docs/resilience.md):
    # per-call deadline budgets, bounded idempotent retry with
    # exponential backoff, the per-worker circuit breaker, and the
    # re-home transport selector ("" / "auto" = file move when the
    # namespaces share a filesystem, "http" forces the cross-host
    # checkpoint transport)
    "KSS_FLEET_REQUEST_TIMEOUT_S": _float_validator(0.05),
    "KSS_FLEET_ADOPT_TIMEOUT_S": _float_validator(0.05),
    "KSS_FLEET_RETRIES": _int_validator(0),
    "KSS_FLEET_RETRY_BACKOFF_S": _float_validator(0.0),
    "KSS_FLEET_BREAKER_FAILURES": _int_validator(1),
    "KSS_FLEET_BREAKER_OPEN_S": _float_validator(0.0),
    "KSS_FLEET_TRANSPORT": _choice_validator("", "auto", "http"),
    # the router's bounded per-request ring (GET /api/v1/fleet/requests)
    "KSS_FLEET_REQUEST_RING_CAP": _int_validator(1),
    # session plane (docs/sessions.md)
    "KSS_MAX_SESSIONS": _int_validator(1),
    "KSS_MAX_PENDING_PODS_PER_SESSION": _int_validator(0),
    "KSS_MAX_CONCURRENT_PASSES": _int_validator(1),
    "KSS_SESSION_IDLE_EVICT_S": _float_validator(0.0),
    "KSS_SESSION_DIR": _path_validator,
    "KSS_SSE_MAX_SUBSCRIBERS": _int_validator(1),
}


def check_env(env: "Mapping[str, str] | None" = None) -> list[str]:
    """Validate every KSS_* variable in `env` (default: os.environ).
    Returns a list of human-readable problems — empty means the
    environment parses cleanly. Unset variables are never errors."""
    env = os.environ if env is None else env
    problems: list[str] = []
    for name, validator in KNOWN.items():
        raw = env.get(name)
        if raw is None or raw == "":
            continue
        err = validator(raw)
        if err:
            problems.append(f"{name}={raw!r}: {err}")
    for name in sorted(env):
        if name.startswith("KSS_") and name not in KNOWN:
            problems.append(
                f"{name}: unknown KSS_* variable (typo? see "
                f"docs/environment-variables.md)"
            )
    return problems


def fail_fast(env: "Mapping[str, str] | None" = None) -> None:
    """Entry-point gate: print every env problem and exit 2. A clear
    refusal at boot beats a silently-defaulted knob or a ValueError deep
    inside the first request handler."""
    problems = check_env(env)
    if not problems:
        return
    for p in problems:
        print(f"environment: {p}", file=sys.stderr)
    raise SystemExit(2)
