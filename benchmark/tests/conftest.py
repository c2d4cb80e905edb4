"""The benchmark's own tests, on the CPU: `python -m pytest benchmark/tests`.
They import the benchmark's files the way run.py does (its directory on
sys.path) and build small cells from the real files with the sizes cut."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))

import pytest  # noqa: E402


def small_spec(cell: str, nodes: int, init: int, measure: int, batches: list) -> dict:
    from harness import load_spec

    spec = load_spec(cell)
    spec["config"].update(initNodes=nodes, initPods=init, measurePods=measure)
    spec["traffic"]["batches"] = batches
    return spec


# the sizes a test run holds: both cells, cut in scale only
SMALL = {
    "basic-5000.churn": (128, 40, 40, [4, 16, 48]),
    "antiaffinity-500.churn": (40, 8, 32, [2, 4, 8]),
}


@pytest.fixture(params=sorted(SMALL))
def small(request):
    cell = request.param
    return small_spec(cell, *SMALL[cell])
