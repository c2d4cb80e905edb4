"""bench.py's one-process-per-chip contract.

  * every probe that touches the device runs in the parent process and
    returns its JSON dict — no child process is started for it;
  * the only children are CPU-pinned probes, or device probes started
    before the parent imported JAX (the cold-start probes); a device
    child after that is refused, since the parent holds the chip;
  * a child that fails or overruns its window costs that measurement
    only (None), and a timed-out child is killed.

bench.py is loaded from the repo root by path (it is a script, not a
package module).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location("_bench_under_test", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_run(monkeypatch, *, stdout="", returncode=0, raises=None):
    seen: dict = {}

    def run(argv, **kw):
        seen.update(argv=argv, **kw)
        if raises is not None:
            raise raises
        return subprocess.CompletedProcess(argv, returncode, stdout=stdout)

    monkeypatch.setattr(subprocess, "run", run)
    return seen


class TestChildProbe:
    def test_cpu_child_is_pinned_to_cpu(self, bench, monkeypatch):
        seen = _fake_run(
            monkeypatch,
            stdout="noise\n" + json.dumps({"k": 1}) + "\n" + json.dumps({"x": 2}),
        )
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert bench._child_probe(["--lifecycle-probe"], 5.0, "k") == {"k": 1}
        assert seen["env"]["JAX_PLATFORMS"] == "cpu"
        assert seen["argv"][-1] == "--lifecycle-probe"

    def test_device_child_refused_once_jax_is_imported(self, bench, monkeypatch):
        """conftest imported jax: this process would hold the chip."""
        seen = _fake_run(monkeypatch, stdout=json.dumps({"k": 1}))
        with pytest.raises(RuntimeError, match="after this process imported"):
            bench._child_probe(["--cold-start"], 5.0, "k", device=True)
        assert seen == {}

    def test_timeout_costs_the_measurement_only(self, bench, monkeypatch):
        _fake_run(
            monkeypatch, raises=subprocess.TimeoutExpired(["x"], 1.0)
        )
        assert bench._child_probe(["--fleet-probe"], 1.0, "k") is None

    def test_failed_child_costs_the_measurement_only(self, bench, monkeypatch):
        _fake_run(monkeypatch, stdout=json.dumps({"k": 1}), returncode=3)
        assert bench._child_probe(["--fleet-probe"], 5.0, "k") is None

    def test_missing_key_is_no_result(self, bench, monkeypatch):
        _fake_run(monkeypatch, stdout=json.dumps({"other": 1}))
        assert bench._child_probe(["--fleet-probe"], 5.0, "k") is None


def test_no_removed_fallback_surface(bench):
    for name in ("CPU_FALLBACK", "TUNNEL_MARKER", "_probe_json_subprocess"):
        assert not hasattr(bench, name), name


@pytest.mark.parametrize(
    "probe, key",
    [
        (lambda b: b._gang_probe("dynamic"), "gang_dps"),
        (lambda b: b._gang_sweep_probe(), "gang_sweep_dps"),
        (lambda b: b._sweep_preempt_probe(), "sweep_pre_dps"),
    ],
    ids=["gang", "gang-sweep", "sweep-preempt"],
)
def test_device_probes_run_in_process(bench, monkeypatch, probe, key):
    """The device probes return their dicts from this process: any
    attempt to start a child fails the test."""

    def no_child(*a, **k):
        raise AssertionError("a device probe started a child process")

    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(subprocess, "Popen", no_child)
    # the probes arm the program ledger; restore the env afterwards
    monkeypatch.setenv("KSS_PROGRAM_LEDGER", "1")
    monkeypatch.setattr(bench, "N_NODES", 16)
    monkeypatch.setattr(bench, "N_PODS", 32)
    monkeypatch.setattr(bench, "N_VARIANTS", 2)
    out = probe(bench)
    assert out[key] > 0
    json.dumps(out)
