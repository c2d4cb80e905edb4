"""The persistent AOT bundle store (utils/bundles.py) + the fused
mega-pass dispatch (docs/performance.md).

The contract under test:

* a warm bundle dir serves a fresh engine's programs by DESERIALIZING
  executables (bundleLoads, zero compile), with placements byte-
  identical to the unbundled run — the parity pin;
* every invalidation rung falls back SILENTLY to a fresh compile with
  identical placements: KSS715 fingerprint drift, a device-epoch bump
  in the broker key, a jax-version mismatch (key-level and
  header-level), and a truncated/corrupt bundle file;
* `CompileBroker.quiesce`/`drain` flush in-flight bundle writes
  (atomic tmp-file + rename — no torn bundle for the next boot);
* the fused programs cut per-pass broker dispatch counts (asserted
  from program-ledger call counts) with records/trace unchanged:
  `seq.step` halves the extender loop's per-pod dispatches for pods no
  extender touches, and `gang.replay_round` folds the record replay's
  eval+bind pair into one dispatch per round chunk.
"""

import json
import os

import numpy as np
import pytest

from kube_scheduler_simulator_tpu.engine import TPU32, encode_cluster
from kube_scheduler_simulator_tpu.engine.engine import BatchedScheduler
from kube_scheduler_simulator_tpu.engine.gang import GangScheduler
from kube_scheduler_simulator_tpu.sched.config import SchedulerConfiguration
from kube_scheduler_simulator_tpu.utils import broker as broker_mod
from kube_scheduler_simulator_tpu.utils import bundles as bundles_mod
from kube_scheduler_simulator_tpu.utils import ledger as ledger_mod

from helpers import node, pod

# a deliberately small compile class: one filter, one score — the
# bundle machinery is what's under test, not the kernels
TINY_CFG = SchedulerConfiguration.from_dict(
    {
        "profiles": [
            {
                "schedulerName": "default-scheduler",
                "plugins": {
                    "preFilter": {"disabled": [{"name": "*"}]},
                    "filter": {
                        "disabled": [{"name": "*"}],
                        "enabled": [{"name": "NodeResourcesFit"}],
                    },
                    "postFilter": {"disabled": [{"name": "*"}]},
                    "preScore": {"disabled": [{"name": "*"}]},
                    "score": {
                        "disabled": [{"name": "*"}],
                        "enabled": [{"name": "NodeResourcesFit"}],
                    },
                },
            }
        ]
    }
)


def _tiny_enc(n_nodes=2, n_pods=6):
    nodes = [node(f"n{i}", cpu="8", mem="16Gi") for i in range(n_nodes)]
    pods = [pod(f"p{i}", cpu="500m") for i in range(n_pods)]
    return encode_cluster(nodes, pods, TINY_CFG, policy=TPU32)


@pytest.fixture
def store(monkeypatch, tmp_path):
    """A fresh, isolated bundle store armed via the real env switch
    (read at jit-WRAP time), swapped in for the process global so
    engine builds inside the test hit it."""
    monkeypatch.setenv(bundles_mod.ENV_VAR, "1")
    monkeypatch.setenv(bundles_mod.DIR_VAR, str(tmp_path / "bundles"))
    fresh = bundles_mod.BundleStore()
    monkeypatch.setattr(bundles_mod, "STORE", fresh)
    yield fresh
    fresh.flush(30.0)


def _run_placements(enc):
    s = BatchedScheduler(enc, record=False)
    s.run()
    return s.placements()


def _bundle_files(store):
    d = store.directory
    try:
        return sorted(
            f for f in os.listdir(d) if f.endswith(bundles_mod.BUNDLE_SUFFIX)
        )
    except OSError:
        return []


# -- round trip + parity -------------------------------------------------------


def test_roundtrip_loads_and_placements_identical(monkeypatch, store):
    enc = _tiny_enc()
    # the unbundled truth, computed with the switch OFF
    monkeypatch.setenv(bundles_mod.ENV_VAR, "0")
    baseline = _run_placements(enc)
    monkeypatch.setenv(bundles_mod.ENV_VAR, "1")

    first = _run_placements(enc)  # compiles + saves
    assert first == baseline
    assert store.flush(30.0)
    st = store.stats()
    assert st["bundleSaves"] >= 1 and st["bundleLoads"] == 0
    assert _bundle_files(store)

    store.reset_stats()
    second = _run_placements(enc)  # a fresh engine: must deserialize
    assert second == baseline
    st = store.stats()
    assert st["bundleLoads"] >= 1
    assert st["bundleMisses"] == 0 and st["bundleBypasses"] == 0


def test_scope_keys_bundles_per_broker_key(store):
    """The broker key (incl. the PR 8 device-epoch suffix) is part of
    bundle identity: an epoch-bumped key can never resurrect the old
    epoch's executable — it misses cleanly and compiles fresh."""
    enc = _tiny_enc()
    key0 = ("seq", ("sig",))
    with bundles_mod.build_scope(key0):
        p0 = _run_placements(enc)
    assert store.flush(30.0)
    n_files = len(_bundle_files(store))
    assert n_files >= 1

    store.reset_stats()
    with bundles_mod.build_scope(key0):
        p_same = _run_placements(enc)  # same scope: loads
    assert store.stats()["bundleLoads"] >= 1
    assert p_same == p0

    store.reset_stats()
    key1 = key0 + (("devepoch", 1),)
    with bundles_mod.build_scope(key1):
        p_bumped = _run_placements(enc)  # bumped epoch: clean miss
    st = store.stats()
    assert st["bundleLoads"] == 0 and st["bundleMisses"] >= 1
    assert st["bundleBypasses"] == 0
    assert p_bumped == p0
    assert store.flush(30.0)
    assert len(_bundle_files(store)) > n_files  # its own bundle saved


# -- the invalidation matrix ---------------------------------------------------


def _warm_store(store, enc):
    p = _run_placements(enc)
    assert store.flush(30.0)
    files = _bundle_files(store)
    assert files
    store.reset_stats()
    return p, [os.path.join(store.directory, f) for f in files]


def test_fingerprint_drift_bypasses_to_fresh_compile(
    monkeypatch, tmp_path, store
):
    """A persisted KSS715 baseline that knows the site but NOT the
    bundle's fingerprint means the site's program set drifted: the
    bundle is bypassed and the pass compiles fresh — same placements."""
    from kube_scheduler_simulator_tpu.analysis import jaxpr_audit

    enc = _tiny_enc()
    baseline_placements, files = _warm_store(store, enc)
    # doctor a baseline next to the (isolated) compile cache claiming
    # every bundled site compiles a different program
    cache_dir = tmp_path / "cache"
    cache_dir.mkdir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    labels = set()
    for path in files:
        with open(path, "rb") as f:
            header = json.loads(f.read().split(b"\n", 1)[0])
        labels.add(header["identity"]["label"])
    with open(jaxpr_audit.fingerprint_path(), "w") as f:
        json.dump(
            {
                "format": jaxpr_audit.FINGERPRINT_FORMAT,
                "fingerprints": {lb: ["0123456789abcdef"] for lb in labels},
            },
            f,
        )
    placements = _run_placements(enc)
    st = store.stats()
    assert st["bundleBypasses"] >= 1 and st["bundleLoads"] == 0
    assert placements == baseline_placements


def test_jax_version_mismatch_keys_and_bypasses(monkeypatch, store):
    """Version drift is caught twice: a DIFFERENT running version keys
    to different filenames (clean miss), and a doctored header claiming
    another version under the same key is bypassed by verification."""
    enc = _tiny_enc()
    baseline_placements, files = _warm_store(store, enc)

    # header-level: rewrite one bundle's identity to a foreign jax
    for path in files:
        with open(path, "rb") as f:
            head, payload = f.read().split(b"\n", 1)
        header = json.loads(head)
        header["identity"]["env"]["jax"] = "0.0.0-foreign"
        with open(path, "wb") as f:
            f.write(json.dumps(header).encode() + b"\n" + payload)
    placements = _run_placements(enc)
    st = store.stats()
    assert st["bundleBypasses"] >= 1 and st["bundleLoads"] == 0
    assert placements == baseline_placements

    # key-level: a process running a foreign jax computes different
    # digests and never even opens the old files
    assert store.flush(30.0)
    store.reset_stats()
    foreign = dict(bundles_mod._environment_identity(), jax="0.0.0-foreign")
    monkeypatch.setattr(bundles_mod, "_env_digest_cache", foreign)
    placements = _run_placements(enc)
    st = store.stats()
    assert st["bundleMisses"] >= 1 and st["bundleLoads"] == 0
    assert placements == baseline_placements


def test_truncated_and_corrupt_bundles_bypass(store):
    enc = _tiny_enc()
    baseline_placements, files = _warm_store(store, enc)
    # truncate to half: the payload checksum (or the unpickler) rejects
    for path in files:
        with open(path, "rb") as f:
            blob = f.read()
        with open(path, "wb") as f:
            f.write(blob[: len(blob) // 2])
    placements = _run_placements(enc)
    st = store.stats()
    assert st["bundleBypasses"] >= 1 and st["bundleLoads"] == 0
    assert placements == baseline_placements

    # pure garbage: no parseable header
    assert store.flush(30.0)
    store.reset_stats()
    for path in _bundle_files(store):
        with open(os.path.join(store.directory, path), "wb") as f:
            f.write(b"\x00\xff garbage not a bundle")
    placements = _run_placements(enc)
    st = store.stats()
    assert st["bundleBypasses"] >= 1 and st["bundleLoads"] == 0
    assert placements == baseline_placements


# -- quiesce/drain flushes writes ---------------------------------------------


def test_broker_drain_flushes_inflight_bundle_writes(monkeypatch, store):
    """A quiescing broker must out-wait the bundle writer: after
    `quiesce()` returns True there are zero pending writes and every
    bundle landed via tmp-file + rename (no torn siblings)."""
    import threading
    import time as time_mod

    import jax

    gate = threading.Event()
    real_write = bundles_mod.BundleStore._write_atomic

    def slow_write(path, blob):
        gate.wait(5.0)
        real_write(path, blob)

    monkeypatch.setattr(
        bundles_mod.BundleStore, "_write_atomic", staticmethod(slow_write)
    )
    jitted = jax.jit(lambda x: x + 1)
    args = (np.arange(4, dtype=np.int32),)
    compiled = jitted.trace(*args).lower().compile()
    digest, doc = bundles_mod.bundle_key("t.prog", None, {}, args, {})
    assert store.save("t.prog", digest, doc, compiled, "fp")
    assert store.stats()["pendingWrites"] == 1

    broker = broker_mod.CompileBroker(speculative=False)
    done = {}

    def drain():
        done["ok"] = broker.quiesce(timeout=10.0)

    t = threading.Thread(target=drain)
    t.start()
    time_mod.sleep(0.05)
    assert not done  # drain is genuinely blocked on the bundle write
    gate.set()
    t.join(10.0)
    assert done.get("ok") is True
    st = store.stats()
    assert st["pendingWrites"] == 0 and st["bundleSaves"] == 1
    files = os.listdir(store.directory)
    assert any(f.endswith(bundles_mod.BUNDLE_SUFFIX) for f in files)
    assert not any(".tmp." in f for f in files)  # rename, not in-place


# -- fused mega-pass dispatch counts ------------------------------------------


@pytest.fixture
def ledger(monkeypatch):
    monkeypatch.setenv(ledger_mod.ENV_VAR, "1")
    ledger_mod.LEDGER.reset()
    yield ledger_mod.LEDGER
    ledger_mod.LEDGER.reset()


def _ledger_calls(ledger):
    return {
        p["label"]: p["calls"] for p in ledger.snapshot()["programs"]
    }


def test_fused_step_halves_extender_loop_dispatches(ledger):
    """Pods no extender touches ride the fused seq.step program: ONE
    dispatch per pod instead of attempt+bind — asserted from the
    ledger's per-program call counts — with records identical to the
    split path."""
    from kube_scheduler_simulator_tpu.engine.extender_loop import (
        ExtenderScheduler,
    )
    from kube_scheduler_simulator_tpu.sched.extender import ExtenderService

    # an extender managing a resource no pod requests: configured, but
    # interested in nothing — every pod takes the fused fast path
    service = ExtenderService(
        [
            {
                "urlPrefix": "http://127.0.0.1:1",  # never called
                "filterVerb": "filter",
                "managedResources": [{"name": "example.com/phantom"}],
            }
        ]
    )
    enc = _tiny_enc(n_nodes=2, n_pods=5)

    es = ExtenderScheduler(enc, service, strict=False)
    fused_results = es.run()
    fused_placements = es.placements()
    fused_calls = _ledger_calls(ledger)
    assert fused_calls.get("seq.step") == 5
    assert fused_calls.get("seq.attempt", 0) == 0
    assert fused_calls.get("seq.bind", 0) == 0

    # force the split path on a fresh engine: same records, 2x the
    # per-pod dispatches for the pods that placed
    ledger_mod.LEDGER.reset()
    es2 = ExtenderScheduler(enc, service, strict=False)
    es2._extender_touches = lambda pod: True
    split_results = es2.run()
    split_calls = _ledger_calls(ledger)
    assert split_calls.get("seq.step", 0) == 0
    assert split_calls.get("seq.attempt") == 5
    placed = sum(1 for r in split_results if r.status == "Scheduled")
    assert split_calls.get("seq.bind") == placed
    fused_total = sum(fused_calls.values())
    split_total = sum(split_calls.values())
    assert fused_total < split_total

    assert es2.placements() == fused_placements
    assert [r.to_annotations() if hasattr(r, "to_annotations") else vars(r)
            for r in split_results] == [
        r.to_annotations() if hasattr(r, "to_annotations") else vars(r)
        for r in fused_results
    ]


def test_gang_replay_round_fuses_eval_and_bind(ledger):
    """The record replay dispatches ONE fused program per round chunk
    (gang.replay_round) — the old separate bind_all dispatch per round
    is gone — and recorded placements still match the plain run."""
    enc = _tiny_enc(n_nodes=2, n_pods=6)
    g = GangScheduler(enc, strict=False, chunk=8)
    g.run_recorded()
    results = g.results()
    assert results
    recorded_placements = g.placements()
    calls = _ledger_calls(ledger)
    assert calls.get("gang.replay_round", 0) >= 1
    assert "gang.bind_all" not in calls

    g2 = GangScheduler(enc, strict=False, chunk=8)
    g2.run()
    assert g2.placements() == recorded_placements
