"""The server and lifecycle CLIs start on the backend JAX gives them and
never re-exec: a device that cannot start fails the boot, and there is
no flag or marker that skips device init."""

from __future__ import annotations

import json
import os

import jax
import pytest


def _spec_file(tmp_path) -> str:
    from helpers import node, pod

    spec = {
        "name": "boot",
        "seed": 1,
        "horizon": 1.0,
        "snapshot": {"nodes": [node("n0")], "pods": [pod("p0")]},
        "faults": [{"at": 0.5, "action": "cordon", "node": "n0"}],
    }
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    return str(path)


@pytest.fixture()
def no_reexec(monkeypatch):
    def execve(*a, **k):  # pragma: no cover - the assertion
        raise AssertionError("the CLI tried to re-exec itself")

    monkeypatch.setattr(os, "execve", execve)


def _dead_backend(monkeypatch):
    def devices(*a, **k):
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", devices)


def test_lifecycle_runs_in_process(tmp_path, no_reexec):
    from kube_scheduler_simulator_tpu.lifecycle.__main__ import main

    assert main(["--spec", _spec_file(tmp_path)]) == 0


def test_lifecycle_fails_when_the_device_cannot_start(
    tmp_path, no_reexec, monkeypatch
):
    from kube_scheduler_simulator_tpu.lifecycle.__main__ import main

    _dead_backend(monkeypatch)
    with pytest.raises(RuntimeError, match="no backend"):
        main(["--spec", _spec_file(tmp_path)])


def test_server_fails_when_the_device_cannot_start(no_reexec, monkeypatch):
    from kube_scheduler_simulator_tpu.server.__main__ import main

    _dead_backend(monkeypatch)
    with pytest.raises(RuntimeError, match="no backend"):
        main(["--port", "0"])


@pytest.mark.parametrize(
    "module", ["server", "lifecycle"],
)
def test_no_device_probe_flag_is_gone(module, tmp_path):
    import importlib

    main = importlib.import_module(
        f"kube_scheduler_simulator_tpu.{module}.__main__"
    ).main
    argv = ["--no-device-probe"]
    if module == "lifecycle":
        argv += ["--spec", _spec_file(tmp_path)]
    with pytest.raises(SystemExit) as e:
        main(argv)
    assert e.value.code == 2  # argparse: unrecognized argument
