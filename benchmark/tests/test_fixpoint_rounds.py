"""The reader of the gang program's commit rounds, on synthetic run dicts."""

from __future__ import annotations

import pytest

from harness import load_module


def _run(n_passes):
    passes = [{"phase": "window", "count": 2, "queued": 2, "decided": 2, "failed": 0,
               "t0": 2.6 * i, "t_send": 2.6 * i + 0.5, "t_recv": 2.6 * i + 2.5,
               "t_end": 2.6 * i + 2.6} for i in range(n_passes)]
    return {
        "window": passes, "spans": [], "trace": None,
        "metrics_open": {"gangFixpointRounds": 10}, "metrics_close": {"gangFixpointRounds": 16},
    }


def _read(run):
    return load_module("metrics", "fixpoint_rounds").read(run)


@pytest.mark.parametrize("n_passes,want", [(2, 3.0), (3, 2.0), (6, 1.0)])
def test_rounds_per_pass_of_the_window(n_passes, want):
    assert _read(_run(n_passes)) == pytest.approx(want)


def test_a_window_without_passes_reads_nothing():
    assert _read(_run(0)) is None
