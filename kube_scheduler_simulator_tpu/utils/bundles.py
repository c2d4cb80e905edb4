"""Persistent AOT engine bundles: kill the compile wall at process boot.

BENCH_r05_chip put the frontier on compilation, not execution: warm
passes run in 0.9-15 s while every engine shape pays 80-162 s of XLA
compilation, and a cold process restart re-pays ALL of it before the
first pod schedules. The persistent XLA compile cache
(utils/compilecache.py) already amortizes the *backend* compile across
processes, but a cold boot still pays the full Python trace + MLIR
lowering per program — roughly half the CPU cold wall and all of the
request-thread latency a disk hit cannot remove.

With ``KSS_AOT_BUNDLES=1``, every program jitted through
``utils/broker.jit`` (seq.run / seq.segment / seq.attempt / seq.bind /
the gang programs / extender segments / delta scatters / sweeps) is
ahead-of-time compiled on its first call — ``jitted.trace(*args)
.lower().compile()`` — and the compiled executable is SERIALIZED
(``jax.experimental.serialize_executable``: the PJRT executable bytes
plus the pickled in/out treedefs) into an on-disk bundle under
``KSS_BUNDLE_DIR``. A later process (a cold restart, or a speculative
bucket-crossing warm-up on the broker's worker thread) finds the bundle
and **deserializes the executable instead of re-tracing, re-lowering,
or re-compiling** — the whole compile wall collapses to a file read
plus a PJRT deserialize. ``bench.py --cold-start`` with a warmed bundle
dir is the gate (docs/performance.md).

Bundle identity — the KEY (sha256 over a canonical JSON doc, every
component a mismatch-means-miss guard):

  * the site label (the KSS7xx audit label naming the program);
  * the BROKER scope: the serving layer's engine key
    ``(kind, compile signature, window)`` including the PR 8
    device-epoch suffix — captured thread-locally while
    ``CompileBroker`` runs a build, so a mesh change (epoch bump) can
    never resurrect a dead device's executable;
  * the jit kwargs (donations are baked into the executable);
  * the full argument-leaf signature (shape / dtype / weak-type);
  * jax + jaxlib versions, backend platform, device count and kind,
    the x64 switch;
  * a digest of the package's own source tree — any code change
    invalidates every bundle, the honest answer to "the avals didn't
    change but the program body did".

The HEADER (a JSON line prefixed to the payload) repeats the identity
fields plus the program's KSS715 compile fingerprint and a payload
checksum. Loads re-verify all of it: a truncated or corrupt file, a
foreign jax/jaxlib version, a platform mismatch, or a fingerprint that
the persisted KSS715 baseline (``kss-fingerprints.json``) does not
recognize for the site all count as a BYPASS — the caller falls back
silently to the normal compile path. A bundle can make a pass faster;
it can never make one wrong (placements byte-identical bundled vs
unbundled, parity-pinned in tests/test_aot_bundles.py).

Writes are ASYNC and ATOMIC: the serialized blob is enqueued to a
writer thread that writes ``<name>.tmp.<pid>`` and ``os.replace``s it
into place — the same discipline as the checkpoint writer — and
``CompileBroker.quiesce``/``drain`` flush the queue, so a SIGTERM
mid-save can never leave a torn bundle for the next boot to load (and
the loader's checksum catches one anyway).

Trust model: the bundle payload is a pickle (the PJRT executable bytes
ride inside one), so loading a bundle executes its pickle. The default
directory therefore lives next to the persistent compile cache —
per-checkout (or per-user) isolation, the same argument
utils/compilecache.py makes: a world-shared directory would let another
local user plant crafted entries that deserialize into in-process code.
Point ``KSS_BUNDLE_DIR`` only at directories you'd trust as code.

Accounting: ``bundleLoads`` / ``bundleSaves`` / ``bundleBypasses`` /
``aotDeserializeSeconds`` — store-global in ``STORE.stats()``, mirrored
into the building service's ``SchedulingMetrics`` (the broker arms the
sink around each build), and recorded DISTINCTLY from the compile wall
in the program ledger (``deserializeSeconds`` per program, never
conflated with lowering/backend seconds).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import threading
import time
from contextlib import contextmanager
from typing import Any

from . import locking, telemetry
from .envcheck import env_truthy

BUNDLE_FORMAT = "kss-aot-bundle/v1"
BUNDLE_SUFFIX = ".kssbundle"

ENV_VAR = "KSS_AOT_BUNDLES"
DIR_VAR = "KSS_BUNDLE_DIR"

_SAFE_LABEL_RE = re.compile(r"[^A-Za-z0-9_.-]+")


def bundles_enabled() -> bool:
    """The AOT-bundle switch (``KSS_AOT_BUNDLES``), read at jit-WRAP
    time by ``utils/broker.jit`` — engine construction — exactly like
    the KSS7xx audit and ledger switches."""
    return env_truthy(os.environ.get(ENV_VAR))


def bundle_dir() -> str:
    """The bundle directory: ``KSS_BUNDLE_DIR``, defaulting to a
    sibling of ``kss-fingerprints.json`` in the persistent compile
    cache dir (`compilecache.cache_dir`, same per-checkout isolation —
    see the trust model in the module docstring)."""
    override = os.environ.get(DIR_VAR)
    if override:
        return override
    from .compilecache import cache_dir

    return os.path.join(cache_dir(), "kss-bundles")


# -- the broker build scope ----------------------------------------------------

# While a CompileBroker runs a build, the engine key it is building —
# (kind, compile signature, window) + the device-epoch suffix — and the
# building service's metrics registry ride thread-locally, so every
# program jit-WRAPPED inside the build keys its bundle on the broker
# key (scope) and every bundle event attributes to the right tenant
# (sink). Builds outside a broker (direct engine construction in tests
# and bench probes) key without a scope — still valid, less qualified.
_ctx = threading.local()


@contextmanager
def build_scope(key: "tuple | None", metrics: "Any | None" = None):
    prev_scope = getattr(_ctx, "scope", None)
    prev_metrics = getattr(_ctx, "metrics", None)
    _ctx.scope = key
    _ctx.metrics = metrics
    try:
        yield
    finally:
        _ctx.scope = prev_scope
        _ctx.metrics = prev_metrics


def current_scope() -> "tuple | None":
    return getattr(_ctx, "scope", None)


def current_metrics() -> "Any | None":
    return getattr(_ctx, "metrics", None)


# -- bundle identity -----------------------------------------------------------

_env_digest_cache: "dict | None" = None


def _environment_identity() -> dict:
    """The environment half of every bundle key: serialized executables
    are only valid on the jax/jaxlib build, backend, and device
    topology that produced them — and only for the source tree whose
    programs they compiled. Computed once per process."""
    global _env_digest_cache
    if _env_digest_cache is not None:
        return _env_digest_cache
    import jax
    import jaxlib

    devs = jax.devices()
    _env_digest_cache = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devs[0].platform,
        "nDevices": len(devs),
        "deviceKind": getattr(devs[0], "device_kind", ""),
        "x64": bool(jax.config.jax_enable_x64),
        "source": _source_digest(),
    }
    return _env_digest_cache


_source_digest_cache: "str | None" = None


def _source_digest() -> str:
    """sha256 over the package's own .py sources (sorted relpaths +
    contents): any code change invalidates every bundle. Aval-based
    fingerprints cannot see a program-body change; the source tree
    can."""
    global _source_digest_cache
    if _source_digest_cache is not None:
        return _source_digest_cache
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in sorted(os.walk(pkg_root)):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fn), pkg_root)
            h.update(rel.encode())
            try:
                with open(os.path.join(dirpath, fn), "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(b"<unreadable>")
    _source_digest_cache = h.hexdigest()[:16]
    return _source_digest_cache


def _leaf_sig(x: Any) -> "tuple[Any, ...]":
    shape = tuple(int(d) for d in getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", type(x).__name__))
    weak = bool(getattr(x, "weak_type", False))
    return (shape, dtype, weak)


def bundle_key(
    label: str,
    scope: "tuple | None",
    jit_kw: "dict[str, Any]",
    args: tuple,
    kwargs: dict,
) -> "tuple[str, dict]":
    """(digest, identity doc) for one (site, scope, signature). The doc
    is what the header records and the loader re-verifies; the digest
    names the file."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    doc = {
        "format": BUNDLE_FORMAT,
        "label": label,
        "scope": repr(scope) if scope is not None else "",
        "jitKw": {k: repr(v) for k, v in sorted(jit_kw.items())},
        "argSig": [_leaf_sig(a) for a in leaves],
        "env": _environment_identity(),
    }
    # canonicalize through JSON so the in-memory doc compares equal to
    # a header that round-tripped through a file (tuples become lists)
    canonical = json.dumps(doc, sort_keys=True)
    digest = hashlib.sha256(canonical.encode()).hexdigest()[:24]
    return digest, json.loads(canonical)


def _bundle_basename(label: str, digest: str) -> str:
    safe = _SAFE_LABEL_RE.sub("_", label) or "program"
    return f"{safe}-{digest}{BUNDLE_SUFFIX}"


# -- the KSS715 fingerprint gate ----------------------------------------------

_baseline_cache: "dict[str, tuple[float, dict]]" = {}


def _fingerprint_baseline() -> "tuple[float, dict[str, list[str]]]":
    """(file mtime, fingerprint sets) of the persisted KSS715 baseline
    (``kss-fingerprints.json``, analysis/jaxpr_audit.py), mtime-cached.

    The drift gate is DIRECTIONAL: only a baseline persisted AFTER a
    bundle was written can invalidate it — "the auditor re-measured
    this site and no longer recognizes the bundled program" is drift;
    "an old baseline from a different config never saw this program"
    is not (labels legitimately carry many fingerprints across configs
    and shapes, and most serving runs never arm the auditor at all).
    Bundles newer than the baseline fall back to the source-digest
    component of the key, which already invalidates on any code
    change."""
    from ..analysis.jaxpr_audit import fingerprint_path, load_fingerprints

    path = fingerprint_path()
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return (0.0, {})
    cached = _baseline_cache.get(path)
    if cached is not None and cached[0] == mtime:
        return cached
    entry = (mtime, load_fingerprints(path))
    _baseline_cache[path] = entry
    return entry


# -- the store -----------------------------------------------------------------


class BundleBypass(Exception):
    """A bundle exists but must not be loaded (header mismatch, torn
    payload, fingerprint drift): the caller compiles fresh."""


@locking.guard_inferred
class BundleStore:
    """On-disk AOT bundle store: load on miss, save on build, async
    atomic writes (module docstring)."""

    def __init__(self, directory: "str | None" = None):
        self._dir = directory
        self._lock = locking.make_lock("bundles.lock")
        self._idle = threading.Condition(self._lock)
        self._queue: "list[tuple[str, bytes, Any]]" = []
        self._writer: "threading.Thread | None" = None
        self._busy = 0  # queued or mid-write
        self.loads = 0
        self.saves = 0
        self.bypasses = 0
        self.misses = 0
        self.deserialize_s = 0.0

    @property
    def directory(self) -> str:
        return self._dir if self._dir is not None else bundle_dir()

    # -- accounting ----------------------------------------------------------

    def _note(
        self,
        loads: int = 0,
        saves: int = 0,
        bypasses: int = 0,
        misses: int = 0,
        deserialize_s: float = 0.0,
        metrics: "Any | None" = None,
    ) -> None:
        with self._lock:
            self.loads += loads
            self.saves += saves
            self.bypasses += bypasses
            self.misses += misses
            self.deserialize_s += deserialize_s
        sink = metrics if metrics is not None else current_metrics()
        if sink is not None and (loads or saves or bypasses or deserialize_s):
            sink.record_bundles(
                loads=loads,
                saves=saves,
                bypasses=bypasses,
                deserialize_s=deserialize_s,
            )

    def stats(self) -> dict:
        with self._lock:
            return {
                "bundleLoads": self.loads,
                "bundleSaves": self.saves,
                "bundleBypasses": self.bypasses,
                "bundleMisses": self.misses,
                "aotDeserializeSeconds": round(self.deserialize_s, 6),
                "pendingWrites": self._busy,
            }

    def reset_stats(self) -> None:
        with self._lock:
            self.loads = 0
            self.saves = 0
            self.bypasses = 0
            self.misses = 0
            self.deserialize_s = 0.0

    # -- load ----------------------------------------------------------------

    def load(
        self,
        label: str,
        digest: str,
        doc: dict,
        metrics: "Any | None" = None,
    ):
        """``(Compiled, deserialize_seconds, fingerprint)`` for
        (label, digest) — the fingerprint is the KSS715 identity the
        header carries, so a bundled boot's ledger rows key exactly
        like a compiled boot's — or None: a plain MISS (no file) or a
        counted BYPASS (file present but unloadable/mismatched/
        drifted; the compile path takes over, never an error)."""
        path = os.path.join(self.directory, _bundle_basename(label, digest))
        try:
            # any read error (absent, unreadable) is a MISS, not a
            # bypass: there is nothing present to distrust
            with open(path, "rb") as f:
                blob = f.read()
            bundle_mtime = os.stat(path).st_mtime
        except OSError:
            self._note(misses=1, metrics=metrics)
            return None
        t0 = time.perf_counter()
        try:
            compiled, fingerprint = self._deserialize(
                blob, label, digest, doc, bundle_mtime
            )
        except Exception as e:  # noqa: BLE001 — bypass, never a crashed pass
            self._note(bypasses=1, metrics=metrics)
            telemetry.instant(
                "bundle.bypass",
                label=label,
                reason=f"{type(e).__name__}: {e}"[:200],
            )
            return None
        dt = time.perf_counter() - t0
        self._note(loads=1, deserialize_s=dt, metrics=metrics)
        telemetry.instant(
            "bundle.load", label=label, seconds=round(dt, 6)
        )
        return compiled, dt, fingerprint

    def _deserialize(
        self,
        blob: bytes,
        label: str,
        digest: str,
        doc: dict,
        bundle_mtime: float = 0.0,
    ):
        """Verify header + checksum + fingerprint baseline, then load
        the executable; returns ``(Compiled, header fingerprint)``.
        Raises (BundleBypass or anything the unpickler throws) —
        ``load`` converts every raise into a counted bypass."""
        nl = blob.find(b"\n")
        if nl < 0:
            raise BundleBypass("no header line (truncated?)")
        try:
            header = json.loads(blob[:nl].decode("utf-8"))
        except (ValueError, UnicodeDecodeError) as e:
            raise BundleBypass(f"unparseable header: {e}") from e
        if not isinstance(header, dict):
            raise BundleBypass("header is not an object")
        if header.get("format") != BUNDLE_FORMAT:
            raise BundleBypass(f"foreign format {header.get('format')!r}")
        if header.get("key") != digest:
            raise BundleBypass("key digest mismatch")
        # the environment identity must match EXACTLY — a bundle from
        # another jax/jaxlib build, backend, topology, or source tree
        # is a bypass even under a colliding digest
        if header.get("identity") != doc:
            raise BundleBypass("identity mismatch (jax version / "
                               "platform / source drift)")
        payload = blob[nl + 1:]
        want = header.get("payloadSha256")
        if hashlib.sha256(payload).hexdigest() != want:
            raise BundleBypass("payload checksum mismatch (torn write?)")
        # KSS715 gate (directional — see _fingerprint_baseline): a
        # baseline persisted AFTER this bundle that knows the site but
        # not this fingerprint means the site's program set drifted
        # since the bundle was written — compile fresh and let the
        # auditor flag it
        baseline_mtime, baseline = _fingerprint_baseline()
        site_fps = baseline.get(label)
        fp = header.get("fingerprint")
        if (
            baseline_mtime > bundle_mtime
            and site_fps
            and fp
            and fp not in site_fps
        ):
            raise BundleBypass(
                f"fingerprint {fp} drifted from the KSS715 baseline"
            )
        from jax.experimental import serialize_executable as se

        se_payload, in_tree, out_tree = pickle.loads(payload)
        compiled = se.deserialize_and_load(se_payload, in_tree, out_tree)
        return compiled, str(fp or "")

    # -- save ----------------------------------------------------------------

    def save(
        self,
        label: str,
        digest: str,
        doc: dict,
        compiled: Any,
        fingerprint: str,
        metrics: "Any | None" = None,
    ) -> bool:
        """Serialize `compiled`, VERIFY the payload deserializes, and
        enqueue the atomic write. False when the executable does not
        produce a loadable payload (the compile still served the pass;
        only persistence is skipped) — notably, an executable that XLA
        served from its own persistent disk cache re-serializes into a
        blob that cannot load ('Symbols not found' on XLA:CPU), so the
        verification here is what keeps the store free of bundles that
        would bypass on every future boot. `BundledJit` reacts to a
        False by MINTING: one re-compile with the disk cache disarmed."""
        from jax.experimental import serialize_executable as se

        try:
            se_payload, in_tree, out_tree = se.serialize(compiled)
            payload = pickle.dumps((se_payload, in_tree, out_tree))
            # the round-trip proof: a payload that cannot load must
            # never be persisted (the deserialized probe is dropped)
            se.deserialize_and_load(se_payload, in_tree, out_tree)
        except Exception as e:  # noqa: BLE001 — persistence is optional
            telemetry.instant(
                "bundle.save_skipped",
                label=label,
                reason=f"{type(e).__name__}: {e}"[:200],
            )
            return False
        header = {
            "format": BUNDLE_FORMAT,
            "key": digest,
            "identity": doc,
            "fingerprint": fingerprint,
            "payloadSha256": hashlib.sha256(payload).hexdigest(),
        }
        blob = (
            json.dumps(header, sort_keys=True).encode("utf-8")
            + b"\n"
            + payload
        )
        path = os.path.join(self.directory, _bundle_basename(label, digest))
        sink = metrics if metrics is not None else current_metrics()
        with self._lock:
            self._queue.append((path, blob, sink))
            self._busy += 1
            if self._writer is None:
                self._writer = threading.Thread(
                    target=self._write_loop, name="kss-bundle-writer",
                    daemon=True,
                )
                self._writer.start()
        return True

    def _write_loop(self) -> None:
        while True:
            with self._lock:
                if not self._queue:
                    self._writer = None
                    return
                path, blob, sink = self._queue.pop(0)
            try:
                self._write_atomic(path, blob)
            except OSError:
                # an unwritable bundle dir costs persistence, never a pass
                pass
            else:
                self._note(saves=1, metrics=sink)
                telemetry.instant(
                    "bundle.save", path=os.path.basename(path)
                )
            with self._lock:
                self._busy -= 1
                self._idle.notify_all()

    @staticmethod
    def _write_atomic(path: str, blob: bytes) -> None:
        """tmp-file + rename, the checkpoint writer's discipline: a
        reader can see the old file or the new file, never a torn one."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)

    def flush(self, timeout: "float | None" = None) -> bool:
        """Block until every queued bundle write has landed; True on
        success, False on timeout. ``CompileBroker.quiesce``/``drain``
        call this so a graceful exit never abandons an in-flight save
        (and a SIGTERM mid-save tears only the tmp file, which no
        loader ever opens)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._busy:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._idle.wait(remaining)
        return True


STORE = BundleStore()


def flush(timeout: "float | None" = None) -> bool:
    """Flush the process-global store's pending writes (the broker's
    quiesce/drain hook)."""
    return STORE.flush(timeout=timeout)


# -- the dispatch wrapper ------------------------------------------------------

# marks "no AOT result": None is a legal program output
_SENTINEL = object()


class BundledJit:
    """The broker's AOT-bundle wrapper around one ``jax.jit`` object.

    The first call of each argument signature resolves the program:

      * bundle HIT — the executable deserializes from disk (no trace,
        no lowering, no backend compile) and serves every later call;
      * bundle MISS — the program is AOT-compiled
        (``trace().lower().compile()``) on this thread, serves the
        call, and is serialized to the store for the next process;
      * anything else (static argnums the flat signature cannot key,
        serialization unsupported, a loaded executable rejecting the
        call) degrades to plain jit dispatch — correctness over reuse.

    With the program ledger armed, loads record ``deserializeSeconds``
    and misses record the lowering/backend split — the two walls stay
    distinct (utils/ledger.py). Everything else (``trace``/``lower``/
    attributes) delegates to the jitted object, so the KSS7xx auditor
    wrapping THIS wrapper still traces the raw program."""

    def __init__(
        self,
        jitted: Any,
        jit_kw: "dict[str, Any]",
        sp: "dict[str, Any] | None",
        *,
        store: "BundleStore | None" = None,
        ledger: Any = None,
    ):
        self._jitted = jitted
        self._jit_kw = dict(jit_kw)
        self._label = (sp or {}).get("label") or getattr(
            getattr(jitted, "__wrapped__", None), "__qualname__", None
        ) or "<unlabeled>"
        # wrap time IS engine-construction time, inside the broker's
        # build: the engine key rides the thread-local scope, and the
        # building service's metrics registry is captured as the sink —
        # first-call load/save events fire later on whatever thread
        # dispatches first (often a request thread outside any build
        # scope), and must still mirror into the tenant's counters
        self._scope = current_scope()
        self._metrics = current_metrics()
        self._store = STORE if store is None else store
        self._ledger = ledger
        self._programs: "dict[tuple, tuple[Any, Any]]" = {}
        # first-call resolution pays the full AOT wall: serialize it,
        # as jax.jit itself does, so two sessions sharing one warm-map
        # engine can never duplicate a compile (or double-save a bundle)
        self._resolve_lock = threading.Lock()
        if ledger is not None:
            from .ledger import timing_sample_every

            self._sample_every = timing_sample_every()
        else:
            self._sample_every = 0
        # static argnums/argnames change the calling convention of the
        # compiled object; no broker site uses them today — bail to
        # plain dispatch if one ever does rather than mis-key
        self._unbundleable = bool(
            jit_kw.get("static_argnums") or jit_kw.get("static_argnames")
        )

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self._unbundleable:
            return self._jitted(*args, **kwargs)
        import jax

        sig = tuple(
            _leaf_sig(a)
            for a in jax.tree_util.tree_leaves((args, kwargs))
        )
        entry = self._programs.get(sig)
        if entry is None:
            with self._resolve_lock:
                entry = self._programs.get(sig)
                if entry is None:
                    entry = self._first_call(sig, args, kwargs)
        compiled, record = entry
        calls_before = record.calls if record is not None else 0
        degraded = False
        t0 = time.perf_counter()
        out = _SENTINEL
        if compiled is not None:
            try:
                out = compiled(*args, **kwargs)
            except Exception:  # noqa: BLE001 — degrade, never fail the pass
                # an aval/static mismatch the flat signature missed:
                # this signature falls back to plain jit for good
                self._programs[sig] = (None, record)
                degraded = True
        if out is _SENTINEL:
            out = self._jitted(*args, **kwargs)
        if record is not None:
            dispatch_s = time.perf_counter() - t0
            warm_s = None
            if (
                self._sample_every
                and calls_before > 0
                and calls_before % self._sample_every == 0
            ):
                # the sampled warm device wall: block on THIS call's
                # result (the first, resolve-bearing call never samples)
                try:
                    jax.block_until_ready(out)
                    warm_s = time.perf_counter() - t0
                except Exception:  # noqa: BLE001 — sampling never fails a pass
                    pass
            self._record_ledger_call(record, dispatch_s, warm_s, degraded)
        return out

    # -- first-call resolution ----------------------------------------------

    def _first_call(self, sig: tuple, args: tuple, kwargs: dict):
        digest, doc = bundle_key(
            self._label, self._scope, self._jit_kw, args, kwargs
        )
        loaded = self._store.load(
            self._label, digest, doc, metrics=self._metrics
        )
        if loaded is not None:
            compiled, deserialize_s, fingerprint = loaded
            record = self._open_ledger_row(
                args,
                kwargs,
                deserialize_s=deserialize_s,
                loaded=True,
                fingerprint=fingerprint,
            )
            entry = (compiled, record)
            self._programs[sig] = entry
            return entry
        # miss: AOT-compile here (the same wall jit's first call would
        # pay — the shared timed probe splits lowering vs backend and
        # reads the cost model), serve the pass from the compiled
        # object, and persist it for the next process. A failed probe
        # pins the signature to plain jit dispatch, whose own first
        # call surfaces the compile error to the broker's retry ladder.
        from . import ledger as ledger_mod

        probe = ledger_mod.aot_probe(self._jitted, args, kwargs)
        if probe is None:
            entry = (None, None)
            self._programs[sig] = entry
            return entry
        compiled, info, traced = probe
        fingerprint = self._fingerprint(traced, args)
        if not self._store.save(
            self._label, digest, doc, compiled, fingerprint,
            metrics=self._metrics,
        ):
            # the executable came out unserializable — almost always an
            # XLA persistent-disk-cache HIT (those re-serialize into
            # blobs that cannot load). MINT a persistable one: a single
            # re-compile with the disk cache disarmed; identical
            # program, and the verified executable serves the dispatch.
            minted = self._mint_fresh(args, kwargs)
            if minted is not None and self._store.save(
                self._label, digest, doc, minted, fingerprint,
                metrics=self._metrics,
            ):
                compiled = minted
        record = self._open_ledger_row(
            args,
            kwargs,
            lowering_s=info["lowering_s"],
            backend_s=info["backend_s"],
            cost=(
                {"flops": info["flops"], "bytes": info["bytes"]}
                if info.get("flops") is not None
                else None
            ),
            memory=info.get("memory"),
            traced=traced,
            fingerprint=fingerprint,
        )
        entry = (compiled, record)
        self._programs[sig] = entry
        return entry

    def _mint_fresh(self, args: tuple, kwargs: dict):
        """One re-compile with the XLA persistent compile cache
        disarmed, to mint a serializable executable (see `save`'s
        verification). Two caches must be sidestepped, or the
        "recompile" silently hands back the same poisoned executable:

          * the persistent disk cache — and flipping
            ``jax_compilation_cache_dir`` alone is NOT enough, because
            jax memoizes "is the cache used" after the first compile
            (``compilation_cache._cache_checked``): the
            ``jax_enable_compilation_cache`` flag must be lowered AND
            the memo reset, then both restored;
          * jax's in-memory compilation LRU, which would return the
            disk-loaded executable in ~1 ms without ever reaching the
            backend — busted by passing an explicitly-default
            ``compiler_options`` (part of the LRU key, no effect on
            the program).

        The toggle is global config, restored in finally; a concurrent
        compile on another thread may skip the disk cache for its one
        build — a slower compile, never a wrong one. None when the
        fresh compile fails (the pass keeps the original executable;
        only persistence is skipped)."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        prev = jax.config.jax_enable_compilation_cache
        try:
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
            compiled = (
                self._jitted.trace(*args, **kwargs)
                .lower()
                .compile(
                    compiler_options={"xla_embed_ir_in_executable": False}
                )
            )
        except Exception:  # noqa: BLE001 — minting is optional
            return None
        finally:
            try:
                jax.config.update("jax_enable_compilation_cache", prev)
                # re-evaluate with the restored flag
                compilation_cache.reset_cache()
            except Exception:  # noqa: BLE001 — never leave config torn
                pass
        telemetry.instant("bundle.mint_recompile", label=self._label)
        return compiled

    def _fingerprint(self, traced: Any, args: tuple) -> str:
        """The program's KSS715 compile fingerprint — the same function
        the auditor and ledger use, so the bundle header, the
        fingerprint baseline, and the ledger all name one identity."""
        try:
            from ..analysis.jaxpr_audit import JaxprAuditor, _aval_sig

            closed = traced.jaxpr
            in_avals = tuple(_aval_sig(v.aval) for v in closed.jaxpr.invars)
            out_avals = tuple(_aval_sig(v.aval) for v in closed.jaxpr.outvars)
            return JaxprAuditor._fingerprint(
                self._label, self._jit_kw, args, in_avals, out_avals
            )
        except Exception:  # noqa: BLE001 — identity beats precision here
            return ""

    # -- ledger integration (KSS_PROGRAM_LEDGER) -----------------------------

    def _open_ledger_row(
        self,
        args: tuple,
        kwargs: dict,
        *,
        lowering_s: float = 0.0,
        backend_s: float = 0.0,
        deserialize_s: float = 0.0,
        loaded: bool = False,
        cost: "dict | None" = None,
        memory: "dict | None" = None,
        traced: Any = None,
        fingerprint: str = "",
    ):
        if self._ledger is None:
            return None
        in_avals: tuple = ()
        out_avals: tuple = ()
        try:
            if traced is not None:
                from ..analysis.jaxpr_audit import _aval_sig

                closed = traced.jaxpr
                in_avals = tuple(
                    _aval_sig(v.aval) for v in closed.jaxpr.invars
                )
                out_avals = tuple(
                    _aval_sig(v.aval) for v in closed.jaxpr.outvars
                )
        except Exception:  # noqa: BLE001 — observability never fails a pass
            pass
        if not fingerprint:
            import jax

            sig = tuple(
                _leaf_sig(a)
                for a in jax.tree_util.tree_leaves((args, kwargs))
            )
            fingerprint = hashlib.sha256(
                json.dumps([self._label, sig], sort_keys=True).encode()
            ).hexdigest()[:16]
        try:
            return self._ledger.open_program(
                self._label,
                fingerprint,
                in_avals=in_avals,
                out_avals=out_avals,
                lowering_s=lowering_s,
                backend_s=backend_s,
                deserialize_s=deserialize_s,
                loaded=loaded,
                cost=cost,
                memory=memory,
            )
        except Exception:  # noqa: BLE001 — the never-raise contract
            return None

    def _record_ledger_call(self, record, dispatch_s, warm_s, degraded) -> None:
        try:
            self._ledger.record_call(
                record,
                dispatch_s,
                session=telemetry.current_session_id(),
                warm_s=warm_s,
                degraded=degraded,
            )
        except Exception:  # noqa: BLE001 — the never-raise contract
            pass

    def __getattr__(self, name: str) -> Any:
        return getattr(self._jitted, name)
