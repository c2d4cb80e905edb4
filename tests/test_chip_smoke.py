"""chip_smoke.py off the chip: it refuses any platform but the TPU, its
last line carries only `ok` and `device`, and its phases — called
directly at a tiny size on the CPU — pass and catch what they check."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


def test_exits_nonzero_on_cpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize(
    "devices, n, match",
    [
        ([], 1, "empty"),
        ([SimpleNamespace(platform="cpu", device_kind="cpu")], 1, "'cpu'"),
        ([SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")], 4,
         "1 TPU device"),
    ],
    ids=["no-devices", "cpu", "too-few-chips"],
)
def test_require_tpu_refuses(monkeypatch, devices, n, match):
    monkeypatch.setattr(jax, "devices", lambda: devices)
    with pytest.raises(SystemExit, match=match):
        chip_smoke.require_tpu(n)


def test_result_line_has_only_ok_and_device():
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = chip_smoke.result_line([dev])
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert "\n" not in line


@pytest.fixture()
def client(monkeypatch):
    monkeypatch.setenv("KSS_NO_SPECULATIVE_COMPILE", "1")
    server, client = chip_smoke.start_server()
    yield client
    server.shutdown()


def test_served_and_parity_phases_pass_at_a_tiny_size(client):
    metrics = chip_smoke.served_phase(client, n_nodes=16, n_pods=96, n_delta=8)
    assert metrics["phases"]["deltaEncodes"] >= 1
    chip_smoke.parity_phase(client, n_nodes=8, n_pods=32)
    chip_smoke.check_device_counters(client.call("GET", "/metrics"))


def test_mesh_phase_passes_on_virtual_devices():
    chip_smoke.mesh_phase(
        jax.devices(), n_nodes=32, n_pods=64, seq_variants=4, gang_variants=2
    )


def test_capacity_check_catches_an_overcommitted_node():
    node = {
        "metadata": {"name": "n0"},
        "status": {"allocatable": {"cpu": "1", "memory": "1Gi", "pods": "10"}},
    }
    pod = {
        "metadata": {"name": "p", "namespace": "default"},
        "spec": {
            "nodeName": "n0",
            "containers": [
                {"name": "c", "resources": {"requests": {"cpu": "2"}}}
            ],
        },
    }
    with pytest.raises(AssertionError, match="cpu"):
        chip_smoke.check_capacity([node], [pod])


def test_device_counter_check_catches_a_hidden_pass():
    phases = {k: 0 for k in chip_smoke.HIDDEN_DEVICE_COUNTERS}
    chip_smoke.check_device_counters({"phases": phases})
    with pytest.raises(AssertionError, match="deviceFailovers"):
        chip_smoke.check_device_counters(
            {"phases": dict(phases, deviceFailovers=1)}
        )
