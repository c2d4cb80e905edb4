"""utils/metrics.py's peak table: keyed by `device_kind`, a CPU has no
peak, and an accelerator kind with no published peak is an error."""

from types import SimpleNamespace

import pytest

from kube_scheduler_simulator_tpu.utils import metrics


def _dev(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_known_kind_uses_its_published_peak():
    v5e = _dev("tpu", "TPU v5 lite")
    assert metrics.device_peak(v5e) == {
        "flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9,
    }
    assert metrics.mfu(197.0e12, 2.0, v5e) == pytest.approx(0.5)


def test_unknown_accelerator_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        metrics.mfu(1.0e12, 1.0, _dev("tpu", "TPU v9 imaginary"))


def test_cpu_has_no_peak():
    cpu = _dev("cpu", "cpu")
    assert metrics.device_peak(cpu) is None
    assert metrics.mfu(1.0e9, 1.0, cpu) is None
