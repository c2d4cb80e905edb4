"""A whole run, on the CPU at a test size, with the chip's look skipped:
sound, it is correct; with the timed path broken underneath, or with the
configuration's control in the program's place, it is not.

The faults are planted where the gang pass writes its answer back
(server/service.py `SchedulerService._gang_finish_inner`): the pass
leaves the store unchanged, writes back only half of its placements, or
writes its second placement onto the node of its first."""

from __future__ import annotations

import os

import pytest



@pytest.fixture(scope="module")
def cpu_devices(tmp_path_factory):
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jaxcache")))
    import jax

    return jax.devices()


def _run(spec, devices, seed=3000000007, control=False):
    import run as R

    os.environ.update(spec["config"]["server_env"])
    result = R.result_line(R.run_cell(spec, seed, 1.0, False, devices, control), devices, False)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(result)[-1] == "checks"
    return result


def _plant(monkeypatch, fault):
    from kube_scheduler_simulator_tpu.server.service import SchedulerService

    real = SchedulerService._gang_finish_inner

    def broken(self, disp, record):
        if fault == "unchanged":
            return {}, 0, None
        store, apply, n, first = self.store, self.store.apply, [0], []

        def planted(kind, obj, *a, **k):
            n[0] += 1
            if fault == "half" and n[0] % 2 == 0:
                return obj
            if fault == "altered" and n[0] == 2:
                obj["spec"]["nodeName"] = first[0]
            first.append(obj["spec"]["nodeName"])
            return apply(kind, obj, *a, **k)

        store.apply = planted
        try:
            return real(self, disp, record)
        finally:
            del store.apply

    monkeypatch.setattr(SchedulerService, "_gang_finish_inner", broken)


def test_sound_run_is_correct(cpu_devices, small):
    out = _run(small, cpu_devices)
    assert out["correct"] and out["checks"]["gap"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"pods_per_s", "pass_mean_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_broken_timed_path_is_not_correct(cpu_devices, monkeypatch, small, fault):
    _plant(monkeypatch, fault)
    out = _run(small, cpu_devices)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct(cpu_devices, small):
    out = _run(small, cpu_devices, seed=1, control=True)
    assert not out["correct"], out["checks"]
