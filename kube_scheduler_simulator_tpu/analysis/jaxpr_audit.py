"""jaxpr auditor (KSS70x static / KSS71x runtime): program-level contracts.

The compile wall (ROADMAP item 1) is paid whenever an engine's COMPILED
program quietly drifts: a host callback sneaks into a traced body (a
device→host sync per step), a float64 creeps past the dtype policy
(every buffer doubles, TPUs emulate), an argument lands off the
``compilecache.shape_bucket`` grid (a recompile per exact count instead
of per bucket), a declared donation stops being consumed (peak memory
doubles), or a program's compile fingerprint changes between runs that
should be identical (recompile risk discovered in a bench postmortem).
None of that is visible to the source-level analyzers — it lives below
the AST, in the ClosedJaxpr. Two halves guard it:

**Static rules** (run with the other kss-lint analyzers):

  KSS701  a host-callback API call anywhere in the package —
          ``jax.pure_callback`` / ``io_callback`` / ``jax.debug.print``
          / ``jax.debug.callback``: nothing in this tree may emit a
          callback-bearing program (the engines are pure array code;
          the extender's HTTP hops run BETWEEN device segments, never
          inside one);
  KSS702  an explicit float64 dtype request (``jnp.float64`` /
          ``np.float64`` / ``"float64"``) outside the dtype-policy
          definition site (engine/encode.py) — f64 enters programs
          through the policy or not at all.

**Runtime witness** (``KSS_JAXPR_AUDIT=1``, hooked into
``utils/broker.jit``): every function jitted through the broker is
wrapped; on the first call of each argument signature the wrapper
traces the program to its ClosedJaxpr and audits it —

  KSS711  a host-callback primitive in the traced jaxpr (any depth:
          scan/cond/while bodies included);
  KSS712  a float64 aval anywhere in the program, unless the site was
          built under the EXACT policy (``allow_f64``);
  KSS713  an argument/result dimension off the shape_bucket grid: every
          dim must be <= 8, a power of two, or a declared static dim
          (the encoding's vocab axes — churn legitimately re-encodes
          them; the capacity axes N/P are deliberately NOT exempt);
  KSS714  a declared donation the lowering could not consume (caught
          from the "donated buffers were not usable" lowering warning);
  KSS715  compile-fingerprint drift: a site whose fingerprint set
          changed against the persisted baseline (`diff_fingerprints`).

Every audited program lands in the process-global `AUDITOR` registry:
``label -> [AuditRecord]`` with the avals signature and a **compile
fingerprint** — sha256 over (label, static jit kwargs, static-arg
values, input avals, output avals), the identity XLA's cache key is
built from. `persist()` writes the fingerprint sets next to the
persistent compile cache (``<cache dir>/kss-fingerprints.json``,
format ``kss-jaxpr-fingerprints/v1``) so two runs — or two commits —
diff in one call. The tier-1 gate (tests/test_jaxpr_audit.py) runs the
chaos engine under the audit and pins: zero findings, every engine
kind audited, and fingerprint sets identical across two identically
seeded runs.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import threading
import warnings
from dataclasses import dataclass, field
from typing import Any

from .core import Finding, RepoContext, SourceTree

FINGERPRINT_FORMAT = "kss-jaxpr-fingerprints/v1"
FINGERPRINT_BASENAME = "kss-fingerprints.json"

ENV_VAR = "KSS_JAXPR_AUDIT"

# host-effect primitives (KSS711) and the user-facing APIs that create
# them (KSS701). An eqn is a host effect when it carries one of the host
# effect types (matched by type NAME, the stable part across jax
# versions: jax 0.9 traces jax.debug.print to `debug_print` with a
# DebugEffect) or is a callback primitive by name (`pure_callback`
# carries no effect at all).
CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback")
CALLBACK_APIS = ("pure_callback", "io_callback", "debug_callback")
HOST_EFFECTS = ("DebugEffect", "IOEffect", "OrderedIOEffect")

# dims <= this are structural (plugin counts, taint slots, tuple
# widths) and never bucket-checked; larger dims must be powers of two
# or declared static (vocab axes)
SMALL_DIM_MAX = 8

# the one module allowed to spell float64: the dtype-policy definitions
F64_EXEMPT_REL = ("engine/encode.py",)

# functions implementing the EXACT policy's 64-bit arithmetic may spell
# f64 (e.g. kernels._exact_isqrt64 — a correctly-rounded integer sqrt
# THROUGH f64, reachable only under policy.name == "exact"); the
# runtime KSS712 still fires if one leaks into a 32-bit-policy program
F64_EXEMPT_FUNC_MARK = "exact"


# -- static rules (KSS701/KSS702) --------------------------------------------


def _call_name(node: ast.Call) -> "tuple[str, str]":
    """(root, attr) of a call like jax.pure_callback / jax.debug.print;
    bare names come back as ("", name)."""
    fn = node.func
    if isinstance(fn, ast.Attribute):
        parts: "list[str]" = [fn.attr]
        cur: ast.expr = fn.value
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if isinstance(cur, ast.Name):
            parts.append(cur.id)
        parts.reverse()
        return (parts[0], parts[-1])
    if isinstance(fn, ast.Name):
        return ("", fn.id)
    return ("", "")


def run(tree: SourceTree, repo: RepoContext) -> "list[Finding]":
    findings: "list[Finding]" = []
    for sf in tree.files:
        if sf.rel.startswith("analysis/"):
            continue  # the analyzers may NAME the banned APIs
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                root, attr = _call_name(node)
                is_debug_print = attr == "print" and root in ("jax", "debug")
                if attr in CALLBACK_APIS or is_debug_print:
                    api = f"jax.debug.{attr}" if is_debug_print else attr
                    findings.append(
                        Finding(
                            "KSS701",
                            sf.rel,
                            node.lineno,
                            f"host-callback API {api}() — a traced "
                            f"program carrying it pays a device→host "
                            f"sync per execution (and breaks AOT "
                            f"serialization)",
                            hint="compute host-side between device "
                            "segments instead (the extender-loop "
                            "pattern); for debugging, decode the "
                            "returned trace tensors",
                        )
                    )
        if sf.rel in F64_EXEMPT_REL:
            continue
        exempt_lines: "set[int]" = set()
        for node in ast.walk(sf.tree):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and F64_EXEMPT_FUNC_MARK in node.name
            ):
                exempt_lines.update(
                    range(node.lineno, (node.end_lineno or node.lineno) + 1)
                )
        for node in ast.walk(sf.tree):
            if getattr(node, "lineno", None) in exempt_lines:
                continue
            name: "str | None" = None
            if isinstance(node, ast.Attribute) and node.attr == "float64":
                root = node.value
                if isinstance(root, ast.Name) and root.id in (
                    "jnp", "np", "numpy", "jax",
                ):
                    name = f"{root.id}.float64"
            if name is not None:
                findings.append(
                    Finding(
                        "KSS702",
                        sf.rel,
                        node.lineno,
                        f"explicit {name} dtype request outside the "
                        f"dtype-policy definitions (engine/encode.py) — "
                        f"f64 enters programs through the policy or not "
                        f"at all",
                        hint="take the dtype from the encoding's "
                        "DTypePolicy (enc.policy) instead",
                    )
                )
        for value, lineno in sf.string_literals():
            if lineno in exempt_lines:
                continue
            if value == "float64":
                findings.append(
                    Finding(
                        "KSS702",
                        sf.rel,
                        lineno,
                        'explicit "float64" dtype literal outside the '
                        "dtype-policy definitions (engine/encode.py)",
                        hint="take the dtype from the encoding's "
                        "DTypePolicy (enc.policy) instead",
                    )
                )
    return findings


# -- runtime witness ----------------------------------------------------------


# The audit-spec dict each broker.jit site may pass (the `audit=`
# keyword; every key optional — `AuditedJit` normalizes via .get):
#
#   label       names the program in the registry + fingerprint file
#   enc         an EncodedCluster: derives the bucket-check exemptions
#               (every dim in the encoding's leaves EXCEPT the capacity
#               axes N/P, which must stay bucketed) and the EXACT-policy
#               f64 waiver
#   extra_dims  static dims the encoding cannot know (score-plugin
#               counts, eval windows)
#   exempt      overrides the bucket-exemption basis: "all" disables
#               the bucket check, "trailing" exempts every dim past
#               axis 0 of each argument (the delta-scatter shape), or a
#               callable (args, kwargs) -> dims
#   allow_f64   explicit f64 waiver (else derived from enc's policy)
#
# Without `enc` or `exempt` the bucket check is skipped — the universal
# rules (callbacks, f64, donation) still run. The enable switch is read
# by the broker at jit-wrap time (broker.jaxpr_audit_enabled).


def encoding_dims(enc: Any) -> "frozenset[int]":
    """Every dim in the encoding's array leaves except the bucketed
    capacity axes — the vocab/slot axes churn legitimately resizes."""
    import jax

    dims: "set[int]" = set()
    for leaf in jax.tree.leaves((enc.arrays, enc.state0)):
        dims.update(int(d) for d in getattr(leaf, "shape", ()))
    dims -= {int(enc.N), int(enc.P)}
    return frozenset(dims)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _aval_sig(x: Any) -> "tuple[Any, ...]":
    shape = tuple(int(d) for d in getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", type(x).__name__))
    return (shape, dtype)


@dataclass
class AuditRecord:
    """One audited (site, argument-signature) pair."""

    label: str
    avals: "tuple[tuple[Any, ...], ...]"
    out_avals: "tuple[tuple[Any, ...], ...]"
    fingerprint: str
    findings: "list[Finding]" = field(default_factory=list)


class JaxprAuditor:
    """The process-global audit registry (see module docstring)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.records: "list[AuditRecord]" = []
        self._seen: "set[tuple[str, tuple]]" = set()

    # -- bookkeeping ---------------------------------------------------------

    def reset(self) -> None:
        with self._lock:
            self.records.clear()
            self._seen.clear()

    def findings(self) -> "list[Finding]":
        with self._lock:
            return [f for r in self.records for f in r.findings]

    def labels(self) -> "set[str]":
        with self._lock:
            return {r.label for r in self.records}

    def fingerprints(self) -> "dict[str, list[str]]":
        """label -> sorted fingerprint digests (the persisted shape)."""
        out: "dict[str, set[str]]" = {}
        with self._lock:
            for r in self.records:
                out.setdefault(r.label, set()).add(r.fingerprint)
        return {k: sorted(v) for k, v in sorted(out.items())}

    # -- the audit -----------------------------------------------------------

    def audit_call(
        self,
        jitted: Any,
        jit_kw: "dict[str, Any]",
        sp: "dict[str, Any] | None",
        args: "tuple[Any, ...]",
        kwargs: "dict[str, Any]",
    ) -> "AuditRecord | None":
        """Audit one call's program if its signature is new; returns the
        new record (None when already seen). Never raises on the serving
        path — findings collect in the registry for the gate to assert."""
        label = (sp or {}).get("label") or getattr(
            getattr(jitted, "__wrapped__", None), "__qualname__", None
        ) or "<unlabeled>"
        sig = tuple(_aval_sig(a) for a in _flatten(args, kwargs))
        key = (label, sig)
        with self._lock:
            if key in self._seen:
                return None
            self._seen.add(key)
        try:
            record = self._audit(label, jitted, jit_kw, sp, args, kwargs)
        except Exception as e:  # noqa: BLE001 — the never-raise contract
            # an auditor-internal failure (a raising exempt callable, a
            # JAX-internals drift) must not crash the pass it observes:
            # it becomes a KSS719 finding the tier-1 gate surfaces
            record = AuditRecord(
                label,
                (),
                (),
                "<audit-error>",
                [
                    Finding(
                        "KSS719",
                        f"<jit:{label}>",
                        0,
                        f"the jaxpr auditor itself failed on this site: "
                        f"{type(e).__name__}: {e}",
                        hint="fix the site's audit spec (a raising "
                        "exempt callable?) or the auditor",
                    )
                ],
            )
        with self._lock:
            self.records.append(record)
        return record

    def _audit(
        self,
        label: str,
        jitted: Any,
        jit_kw: "dict[str, Any]",
        sp: "dict[str, Any] | None",
        args: "tuple[Any, ...]",
        kwargs: "dict[str, Any]",
    ) -> AuditRecord:
        sp = sp or {}
        site = f"<jit:{label}>"
        findings: "list[Finding]" = []
        donate = jit_kw.get("donate_argnums") or jit_kw.get("donate_argnames")
        caught: "list[warnings.WarningMessage]" = []
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            traced = jitted.trace(*args, **kwargs)
            if donate:
                # the donation verdict is a LOWERING product; tracing
                # alone never emits the warning
                traced.lower()
        closed = traced.jaxpr
        in_avals = tuple(_aval_sig(v.aval) for v in closed.jaxpr.invars)
        out_avals = tuple(_aval_sig(v.aval) for v in closed.jaxpr.outvars)

        # KSS711 — host-effect primitives, any depth
        for prim, depth in _walk_prims(closed.jaxpr):
            findings.append(
                Finding(
                    "KSS711",
                    site,
                    0,
                    f"host-effect primitive {prim!r} in the traced "
                    f"program (depth {depth}) — a device→host sync "
                    f"per execution",
                    hint="hoist the host work out of the traced "
                    "body (see KSS701)",
                )
            )

        # KSS712 — float64 avals anywhere in the program
        allow_f64 = sp.get("allow_f64")
        if allow_f64 is None:
            enc = sp.get("enc")
            allow_f64 = bool(
                enc is not None and getattr(enc.policy, "name", "") == "exact"
            )
        if not allow_f64:
            bad = sorted(
                {
                    str(aval)
                    for aval in _walk_avals(closed.jaxpr)
                    if str(getattr(aval, "dtype", "")) == "float64"
                }
            )
            if bad:
                findings.append(
                    Finding(
                        "KSS712",
                        site,
                        0,
                        f"float64 leaked into the program: "
                        f"{', '.join(bad[:4])}"
                        + ("…" if len(bad) > 4 else "")
                        + " (the site is not under the EXACT policy)",
                        hint="trace the f64 source: an unpolicied "
                        "np.float conversion, a python float under "
                        "jax_enable_x64, or a dtype-less jnp.asarray",
                    )
                )

        # KSS713 — bucket-aligned argument/result shapes
        exempt = self._exempt_dims(sp, args, kwargs)
        if exempt is not None:
            off = sorted(
                {
                    dim
                    for shape, _ in in_avals + out_avals
                    for dim in shape
                    if dim > SMALL_DIM_MAX
                    and not _is_pow2(dim)
                    and dim not in exempt
                }
            )
            if off:
                findings.append(
                    Finding(
                        "KSS713",
                        site,
                        0,
                        f"argument/result dims {off} are off the "
                        f"shape_bucket grid (not a power of two, not a "
                        f"declared static dim) — churn across them "
                        f"recompiles per exact count",
                        hint="pad the axis to utils/compilecache."
                        "shape_bucket, or declare it static in the "
                        "site's audit spec if it cannot churn",
                    )
                )

        # KSS714 — declared donations actually consumed
        if donate:
            dropped = [
                str(w.message)
                for w in caught
                if "donated buffers were not usable" in str(w.message)
            ]
            if dropped:
                findings.append(
                    Finding(
                        "KSS714",
                        site,
                        0,
                        f"declared donation dropped by lowering: "
                        f"{dropped[0]}",
                        hint="match the donated argument's shape/dtype "
                        "to an output, or stop declaring the donation "
                        "(the alias is silently not happening)",
                    )
                )

        fingerprint = self._fingerprint(
            label, jit_kw, args, in_avals, out_avals
        )
        return AuditRecord(label, in_avals, out_avals, fingerprint, findings)

    @staticmethod
    def _exempt_dims(
        sp: "dict[str, Any]",
        args: "tuple[Any, ...]",
        kwargs: "dict[str, Any]",
    ) -> "frozenset[int] | None":
        """The bucket-check exemption set, or None to skip the check
        (no basis declared — see the audit-spec key table above)."""
        exempt = sp.get("exempt")
        if exempt == "all":
            return None
        if exempt == "trailing":
            dims: "set[int]" = set()
            for a in _flatten(args, kwargs):
                shape = getattr(a, "shape", ())
                dims.update(int(d) for d in shape[1:])
            return frozenset(dims) | frozenset(sp.get("extra_dims", ()))
        if callable(exempt):
            return frozenset(
                int(d) for d in exempt(args, kwargs)
            ) | frozenset(sp.get("extra_dims", ()))
        enc = sp.get("enc")
        if enc is not None:
            return encoding_dims(enc) | frozenset(sp.get("extra_dims", ()))
        return None

    @staticmethod
    def _fingerprint(
        label: str,
        jit_kw: "dict[str, Any]",
        args: "tuple[Any, ...]",
        in_avals: tuple,
        out_avals: tuple,
    ) -> str:
        """sha256 over the program's compile identity: the site label,
        the static jit kwargs, the VALUES at static argnums, and the
        full input/output avals."""
        static_vals: "list[str]" = []
        static_argnums = jit_kw.get("static_argnums") or ()
        if isinstance(static_argnums, int):
            static_argnums = (static_argnums,)
        for i in static_argnums:
            if 0 <= i < len(args):
                static_vals.append(repr(args[i]))
        doc = json.dumps(
            {
                "label": label,
                "jit_kw": {k: repr(v) for k, v in sorted(jit_kw.items())},
                "static_args": static_vals,
                "in_avals": in_avals,
                "out_avals": out_avals,
            },
            sort_keys=True,
        )
        return hashlib.sha256(doc.encode()).hexdigest()[:16]

    # -- persistence ---------------------------------------------------------

    def persist(self, path: "str | None" = None) -> "list[Finding]":
        """Merge this process's fingerprint sets into the baseline file
        next to the persistent compile cache, returning KSS715 drift
        findings against what was there (`diff_fingerprints`). The file
        is written regardless — the new truth becomes the baseline the
        NEXT run diffs against."""
        path = fingerprint_path() if path is None else path
        current = self.fingerprints()
        previous = load_fingerprints(path)
        drift = diff_fingerprints(previous, current)
        merged = dict(previous)
        merged.update(current)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(
                {"format": FINGERPRINT_FORMAT, "fingerprints": merged},
                f,
                indent=1,
                sort_keys=True,
            )
        os.replace(tmp, path)
        return drift


def _flatten(args: tuple, kwargs: dict) -> "list[Any]":
    import jax

    return list(jax.tree_util.tree_leaves((args, kwargs)))


def _is_host_effect(eqn: Any) -> bool:
    prim = str(eqn.primitive)
    if prim in CALLBACK_PRIMS or prim.endswith("_callback"):
        return True
    return any(
        type(e).__name__ in HOST_EFFECTS for e in getattr(eqn, "effects", ())
    )


def _walk_prims(jaxpr: Any, depth: int = 0):
    """(primitive name, depth) for every host-effect eqn (see
    HOST_EFFECTS), recursing into sub-jaxprs (scan/while/cond bodies,
    closed or open)."""
    for eqn in jaxpr.eqns:
        if _is_host_effect(eqn):
            yield str(eqn.primitive), depth
        for sub in _sub_jaxprs(eqn):
            yield from _walk_prims(sub, depth + 1)


def _walk_avals(jaxpr: Any):
    seen: "set[int]" = set()

    def walk(j: Any):
        if id(j) in seen:
            return
        seen.add(id(j))
        for v in list(j.invars) + list(j.outvars) + list(j.constvars):
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield aval
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None:
                    yield aval
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub)

    yield from walk(jaxpr)


def _sub_jaxprs(eqn: Any):
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner  # ClosedJaxpr
            elif hasattr(v, "eqns"):
                yield v  # open Jaxpr


class AuditedJit:
    """The broker's observation wrapper around one ``jax.jit`` object —
    the shared hook of BOTH program-contract families:

      * **audit** (KSS71x, ``KSS_JAXPR_AUDIT=1``): calls pass straight
        through after a first-signature audit;
      * **ledger** (``KSS_PROGRAM_LEDGER=1``, utils/ledger.py): the
        first call of each signature goes through the timed AOT path
        (lowering vs backend-compile split, cost/memory analysis), and
        later calls dispatch through the compiled executable — so the
        split costs no second compile. ``KSS_PROGRAM_TIMING_SAMPLE=N``
        additionally blocks on every Nth result for a warm device wall.

    Everything else (``trace``/``lower``/attributes) delegates to the
    jitted object. Both observers share the never-raise contract: an
    observability failure degrades to plain jit dispatch, never a
    crashed pass."""

    def __init__(
        self,
        jitted: Any,
        jit_kw: "dict[str, Any]",
        sp: "dict[str, Any] | None",
        auditor: "JaxprAuditor | None" = None,
        *,
        audit_enabled: bool = True,
        ledger: Any = None,
    ):
        self._jitted = jitted
        self._jit_kw = dict(jit_kw)
        self._spec = sp
        self._auditor = AUDITOR if auditor is None else auditor
        self._audit_enabled = audit_enabled
        self._ledger = ledger
        if ledger is not None:
            from ..utils.ledger import timing_sample_every

            # per-signature (ProgramRecord, compiled-or-None): the
            # wrapper IS the AOT dispatch cache while the ledger is on
            self._programs: "dict[tuple, tuple[Any, Any]]" = {}
            self._sample_every = timing_sample_every()

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self._audit_enabled:
            self._auditor.audit_call(
                self._jitted, self._jit_kw, self._spec, args, kwargs
            )
        if self._ledger is None:
            return self._jitted(*args, **kwargs)
        return self._ledger_call(args, kwargs)

    # -- the ledger dispatch path (utils/ledger.py) --------------------------

    def _ledger_call(self, args: tuple, kwargs: dict) -> Any:
        import time

        from ..utils import telemetry

        sig = tuple(_aval_sig(a) for a in _flatten(args, kwargs))
        entry = self._programs.get(sig)
        if entry is None:
            entry = self._ledger_first_call(sig, args, kwargs)
        record, compiled = entry
        calls_before = record.calls
        degraded = False
        t0 = time.perf_counter()
        out = _SENTINEL
        if compiled is not None:
            try:
                out = compiled(*args, **kwargs)
            except Exception:  # noqa: BLE001 — degrade, never fail the pass
                # an aval/static mismatch the signature key missed (weak
                # types, committed devices): this signature falls back to
                # plain jit dispatch for good — correctness over split
                self._programs[sig] = (record, None)
                degraded = True
        if out is _SENTINEL:
            out = self._jitted(*args, **kwargs)
        dispatch_s = time.perf_counter() - t0
        warm_s = None
        if (
            self._sample_every
            and calls_before > 0
            and calls_before % self._sample_every == 0
        ):
            # the sampled warm device wall: block on THIS call's result
            # (the first, compile-bearing call is never sampled)
            try:
                import jax

                jax.block_until_ready(out)
                warm_s = time.perf_counter() - t0
            except Exception:  # noqa: BLE001 — sampling must not fail the pass
                pass
        self._ledger.record_call(
            record,
            dispatch_s,
            session=telemetry.current_session_id(),
            warm_s=warm_s,
            degraded=degraded,
        )
        return out

    def _ledger_first_call(self, sig: tuple, args: tuple, kwargs: dict):
        """Open this signature's ledger row: timed trace+lower, timed
        backend compile, cost/memory analysis, and the same compile
        fingerprint the KSS715 baseline uses. Failures leave a row with
        whatever was measured and fall back to plain jit dispatch."""
        from ..utils import ledger as ledger_mod

        label = (self._spec or {}).get("label") or getattr(
            getattr(self._jitted, "__wrapped__", None), "__qualname__", None
        ) or "<unlabeled>"
        compiled = None
        lowering_s = backend_s = 0.0
        cost = memory = None
        in_avals: tuple = ()
        out_avals: tuple = ()
        fingerprint = ""
        try:
            probe = ledger_mod.aot_probe(self._jitted, args, kwargs)
            if probe is not None:
                compiled, info, traced = probe
                lowering_s = info["lowering_s"]
                backend_s = info["backend_s"]
                if info["flops"] is not None:
                    cost = {"flops": info["flops"], "bytes": info["bytes"]}
                memory = info.get("memory")
                closed = traced.jaxpr
                in_avals = tuple(
                    _aval_sig(v.aval) for v in closed.jaxpr.invars
                )
                out_avals = tuple(
                    _aval_sig(v.aval) for v in closed.jaxpr.outvars
                )
                fingerprint = JaxprAuditor._fingerprint(
                    label, self._jit_kw, args, in_avals, out_avals
                )
        except Exception:  # noqa: BLE001 — the never-raise contract
            compiled = None
        if not fingerprint:
            import hashlib
            import json as json_mod

            fingerprint = hashlib.sha256(
                json_mod.dumps([label, sig], sort_keys=True, default=repr).encode()
            ).hexdigest()[:16]
        record = self._ledger.open_program(
            label,
            fingerprint,
            in_avals=in_avals,
            out_avals=out_avals,
            lowering_s=lowering_s,
            backend_s=backend_s,
            cost=cost,
            memory=memory,
        )
        entry = (record, compiled)
        self._programs[sig] = entry
        return entry

    def __getattr__(self, name: str) -> Any:
        return getattr(self._jitted, name)


# marks "no AOT result": None is a legal program output
_SENTINEL = object()


AUDITOR = JaxprAuditor()


def fingerprint_path(cache_dir: "str | None" = None) -> str:
    """The baseline file, next to the persistent compile cache
    (`compilecache.cache_dir`, same per-checkout isolation)."""
    from ..utils import compilecache

    if cache_dir is None:
        cache_dir = compilecache.cache_dir()
    return os.path.join(cache_dir, FINGERPRINT_BASENAME)


def load_fingerprints(path: "str | None" = None) -> "dict[str, list[str]]":
    path = fingerprint_path() if path is None else path
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if doc.get("format") != FINGERPRINT_FORMAT:
        return {}
    fp = doc.get("fingerprints")
    if not isinstance(fp, dict):
        return {}
    return {
        str(k): sorted(str(d) for d in v)
        for k, v in fp.items()
        if isinstance(v, list)
    }


def diff_fingerprints(
    previous: "dict[str, list[str]]", current: "dict[str, list[str]]"
) -> "list[Finding]":
    """KSS715: sites whose fingerprint set CHANGED between two runs —
    new digests mean new compilations a supposedly-identical run paid;
    vanished digests mean programs it no longer builds. New sites
    (labels absent before) are growth, not drift."""
    findings: "list[Finding]" = []
    for label in sorted(set(previous) & set(current)):
        old, new = set(previous[label]), set(current[label])
        if old == new:
            continue
        gained = sorted(new - old)
        lost = sorted(old - new)
        parts: "list[str]" = []
        if gained:
            parts.append(f"gained {gained}")
        if lost:
            parts.append(f"lost {lost}")
        findings.append(
            Finding(
                "KSS715",
                f"<jit:{label}>",
                0,
                f"compile fingerprint drift at {label!r}: "
                + "; ".join(parts),
                hint="an avals/static-arg change reached this site — "
                "if intended, re-baseline by persisting; if not, a "
                "bucket contract regressed (compare the avals in the "
                "two baselines)",
            )
        )
    return findings
