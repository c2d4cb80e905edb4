"""Runtime device-fault supervision — the EXECUTION half of the ladder.

PR 4's run supervision hardened *compile time* (watchdog, retry,
cooldown, eager fallback); this module supplies the shared pieces for
the *execution-time* ladder (docs/resilience.md) that
`server/service.py` walks when the device plane misbehaves AFTER a
successful compile:

  rung 0  dispatch as usual (warm engine, current device);
  rung 1  bounded retry — ``KSS_DISPATCH_RETRIES`` more attempts on a
          transient ``XlaRuntimeError`` / injected device fault /
          dispatch-watchdog timeout (``KSS_DISPATCH_DEADLINE_S``);
  rung 2  mesh shrink — drop the faulted device, rebuild the mesh over
          the survivors (`parallel/mesh.surviving_mesh`: the replicas
          axis absorbs the loss) and rebuild the engine through the
          CompileBroker under a bumped device epoch;
  rung 3  CPU failover — re-encode on the CPU backend and re-run the
          SAME pass there. Same placements, same trace bytes; only
          latency degrades, and every such pass is counted
          (``deviceFailovers``).

Classification lives here (`is_device_fault`) so the service's ladder
and the tests agree on exactly which exceptions escalate: real XLA
runtime errors (matched by type NAME — jaxlib's exception types are not
importable on every build), the fault plane's two device sites, and the
dispatch watchdog's timeout. Everything else propagates untouched —
a bug must never be retried into silence.
"""

from __future__ import annotations

import os

from . import broker as broker_mod
from . import faultinject

# device-fault sites of the fault-injection grammar (utils/faultinject.py)
DEVICE_FAULT_SITES = ("device_error", "device_lost")

# exception type NAMES treated as device-plane failures when they appear
# anywhere in the exception's MRO (jaxlib moves these between modules
# across versions; the name is the stable part)
_DEVICE_ERROR_TYPE_NAMES = ("XlaRuntimeError",)


class DispatchDeadlineExceeded(RuntimeError):
    """One device dispatch overran KSS_DISPATCH_DEADLINE_S (the probe
    thread is abandoned — a wedged dispatch cannot be interrupted from
    Python; its late result is discarded). Classified as a device fault:
    the execution ladder escalates instead of hanging the pass."""


def _env_number(name: str, default, convert, minimum):
    """A ladder knob from the environment — the env READ lives here so
    the KSS1xx env-registry analyzer ties the names to this module;
    coercion leniency is the broker's shared `_coerce_env_number` (a
    typo must never disarm the execution ladder)."""
    return broker_mod._coerce_env_number(
        os.environ.get(name, ""), default, convert, minimum
    )


def dispatch_deadline_s() -> float:
    """Per-attempt dispatch-probe deadline from KSS_DISPATCH_DEADLINE_S;
    0 (the default) disables the watchdog — no extra thread per pass.
    The window covers the fault plane's dispatch sites (the injected
    ``dispatch_hang`` wedged-dispatch stand-in); a hang deep inside a
    running XLA program is out of its reach — that cannot be abandoned
    without tearing the engine out from under a live pass."""
    return _env_number("KSS_DISPATCH_DEADLINE_S", 0.0, float, 0.0)


def dispatch_retries() -> int:
    """Extra dispatch attempts after the first device fault
    (KSS_DISPATCH_RETRIES, default 2) before the ladder escalates to
    the mesh-shrink rung."""
    return _env_number("KSS_DISPATCH_RETRIES", 2, int, 0)


def run_with_deadline(fn, deadline_s: float):
    """Run `fn()` under a dispatch watchdog: on timeout the runner
    thread is abandoned (its late result or exception is discarded) and
    `DispatchDeadlineExceeded` raises on the caller. With no deadline,
    `fn()` runs inline — zero thread cost on the healthy path. The
    watchdog machinery is the broker's (`_call_with_deadline`) with the
    dispatch exception swapped in — one implementation to fix."""

    def timed_out(_thread) -> DispatchDeadlineExceeded:
        return DispatchDeadlineExceeded(
            f"device dispatch exceeded KSS_DISPATCH_DEADLINE_S="
            f"{deadline_s}s"
        )

    return broker_mod._call_with_deadline(
        fn, deadline_s, make_exc=timed_out,
        thread_name="kss-dispatch-attempt",
    )


def is_device_fault(exc: BaseException) -> bool:
    """True when `exc` is a device-plane failure the execution ladder
    owns: a dispatch-watchdog timeout, an injected device site, or a
    real XLA runtime error. Anything else (encode bugs, value errors,
    the compile ladder's own terminal failures) must propagate —
    retrying it would hide a bug behind a mesh shrink."""
    if isinstance(exc, DispatchDeadlineExceeded):
        return True
    if isinstance(exc, faultinject.InjectedFault):
        return exc.site in DEVICE_FAULT_SITES
    return any(
        cls.__name__ in _DEVICE_ERROR_TYPE_NAMES
        for cls in type(exc).__mro__
    )


def cpu_devices() -> list:
    """The CPU backend's devices, or [] when that backend is unusable —
    the CPU-failover rung's precondition. Never raises: a process whose
    accelerator died AND whose CPU backend won't initialize reports
    EngineDegraded through the caller, not a secondary crash here."""
    try:
        import jax

        return list(jax.devices("cpu"))
    except Exception:  # noqa: BLE001 — absence of a backend, not a bug
        return []
