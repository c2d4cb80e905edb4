"""The reduction from a profiler capture to device_s, device_idle_share
and the breakdown, on hand-made records and on a small record taken from
a traced chip run (tests/data/recorded_trace.json)."""

from __future__ import annotations

import json
import os

import pytest

import tracereduce

DATA = os.path.join(os.path.dirname(__file__), "data", "recorded_trace.json")


def _record(ops, host=()):
    return {"device": {"/device:TPU:0": [list(o) for o in ops]}, "host": [list(h) for h in host], "lines": {}}


def test_nested_ops_count_once_and_self_time_excludes_children():
    ops = [("%while.1 = (s32[8]) while(...)", 0.1, 0.4), ("%fusion.2 = s32[8] fusion(...)", 0.15, 0.1),
           ("%fusion.2 = s32[8] fusion(...)", 0.3, 0.1), ("%copy.3 = s32[8] copy(...)", 0.7, 0.1)]
    r = tracereduce.reduce(_record(ops), 2, 1.0)
    assert r["busy_s"] == pytest.approx(0.5)
    assert r["device_op_s"] == pytest.approx(0.5)
    assert r["device_s"] == pytest.approx(0.25)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["breakdown"]["device_ops"] == [
        ["%while.1", pytest.approx(0.2)], ["%fusion.2", pytest.approx(0.2)], ["%copy.3", pytest.approx(0.1)]
    ]


def test_gaps_are_named_by_the_host_event_that_overlaps_most():
    host = [("encode", 0.45, 0.1), ("json", 0.7, 0.3)]
    r = tracereduce.reduce(_record([("a", 0.0, 0.4), ("b", 0.5, 0.2)], host), 1, 1.0)
    assert r["device_op_s"] == pytest.approx(0.6)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["host: json", pytest.approx(0.3)]
    assert gaps[1] == ["host: encode", pytest.approx(0.1)]


def test_ops_outside_the_window_are_clipped_and_no_device_means_none():
    r = tracereduce.reduce(_record([("a", -0.5, 1.0), ("b", 0.9, 0.5)]), 1, 1.0)
    assert r["busy_s"] == pytest.approx(0.6)
    assert tracereduce.reduce(_record([]), 1, 1.0) is None
    assert tracereduce.reduce({"device": {}, "host": [], "lines": {}}, 1, 1.0) is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded chip trace")
def test_recorded_chip_trace():
    with open(DATA) as f:
        rec = json.load(f)
    window_s = rec.pop("window_s")
    n_passes = rec.pop("n_passes")
    rec.pop("source")
    r = tracereduce.reduce(rec, n_passes, window_s)
    (ops,) = rec["device"].values()
    # busy by brute force on a 10 us grid
    step = 1e-5
    covered = set()
    for _, s, d in ops:
        covered.update(range(max(0, round(s / step)), min(round(window_s / step), round((s + d) / step))))
    assert r["busy_s"] == pytest.approx(len(covered) * step, abs=len(ops) * step)
    assert 0 < r["busy_s"] <= window_s
    assert 0 <= r["idle_share"] < 1
    assert r["device_op_s"] == pytest.approx(r["busy_s"], rel=1e-3)  # children may end a few ns past their parent
    assert len(r["breakdown"]["device_ops"]) <= 10 and len(r["breakdown"]["idle_gaps"]) <= 10
