"""The reader of the gang matching's iterations, on the synthetic run dicts
the other counter readers are tested with."""

from __future__ import annotations

import pytest

from harness import load_module


def _run(n_passes, open_, close):
    passes = [{"phase": "window", "count": 2, "queued": 2, "decided": 2, "failed": 0,
               "t0": 2.6 * i, "t_send": 2.6 * i + 0.5, "t_recv": 2.6 * i + 2.5,
               "t_end": 2.6 * i + 2.6} for i in range(n_passes)]
    return {"window": passes, "spans": [], "trace": None,
            "metrics_open": {"gangFixpointRounds": 10, **open_},
            "metrics_close": {"gangFixpointRounds": 24, **close}}


def _read(run):
    return load_module("metrics", "match_iters").read(run)


@pytest.mark.parametrize("n_passes,want", [(3, 676 / 3), (1, 676.0)])
def test_iterations_per_pass_of_the_window(n_passes, want):
    run = _run(n_passes, {"gangMatchIters": 1000}, {"gangMatchIters": 1676})
    assert _read(run) == pytest.approx(want)


def test_full_width_reads_zero():
    assert _read(_run(4, {"gangMatchIters": 0}, {"gangMatchIters": 0})) == 0


def test_a_program_without_the_counter_reads_nothing():
    assert _read(_run(3, {}, {})) is None


def test_a_window_without_passes_reads_nothing():
    assert _read(_run(0, {"gangMatchIters": 5}, {"gangMatchIters": 5})) is None
