"""The check against the plain reference: sound placements read 0, and
each broken guarantee or worse choice is caught; each configuration's
control is caught."""

from __future__ import annotations

import pytest

from conftest import SMALL, small_spec
from harness import load_module
from traffic import Traffic

ref = load_module("references", "default_plugins")


def _pass(spec, seed):
    """(cluster, specs, before) of the mix's largest cycle after the bound
    residents."""
    t = Traffic(spec, seed)
    nodes, pods = t.cluster()
    live = {p["metadata"]["name"]: p for p in pods}
    _, deletes, creates = t.cycle(live, len(t.batches) - 1)
    for p in deletes:
        del live[p["metadata"]["name"]]
    for p in creates:
        live[p["metadata"]["name"]] = p
    specs = {n: ref.pod_spec(p) for n, p in live.items()}
    before = {n: p["spec"].get("nodeName") for n, p in live.items()}
    return ref.Cluster(nodes, spec["config"]), specs, before


def _after(cluster, specs, before, control="exact"):
    placed = ref.schedule(cluster, specs, before, control)
    return {n: placed.get(n, v) if v is None else v for n, v in before.items()}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_reference_in_the_programs_place_reads_zero(small, seed):
    cluster, specs, before = _pass(small, seed)
    c = ref.check_pass(cluster, specs, before, _after(cluster, specs, before))
    assert (c.broken, c.gap) == (0, 0) and c.checked > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_configurations_control_is_caught(small, seed):
    cluster, specs, before = _pass(small, seed)
    after = _after(cluster, specs, before, small["config"]["control"])
    assert ref.check_pass(cluster, specs, before, after).reading() >= 1


def _sound(spec, seed=5):
    cluster, specs, before = _pass(spec, seed)
    return cluster, specs, before, _after(cluster, specs, before)


def test_over_capacity_is_broken():
    cluster, specs, before, after = _sound(small_spec("basic-5000.churn", *SMALL["basic-5000.churn"]))
    for n in specs:  # 48 pods of 100m onto one node of 4 cpu
        if before[n] is None:
            after[n] = cluster.names[0]
    c = ref.check_pass(cluster, specs, before, after)
    assert c.reading() == ref.BROKEN and "more than it can allocate" in " ".join(c.reasons)


def test_anti_affinity_break_is_caught():
    cluster, specs, before, after = _sound(small_spec("antiaffinity-500.churn", *SMALL["antiaffinity-500.churn"]))
    a, b = [n for n, v in before.items() if v is None][:2]
    after[b] = after[a]
    c = ref.check_pass(cluster, specs, before, after)
    assert c.reading() == ref.BROKEN and "breaks a filter" in " ".join(c.reasons)


def test_pending_pod_that_fits_is_caught(small):
    cluster, specs, before, after = _sound(small)
    name = next(n for n, v in before.items() if v is None)
    after[name] = None
    assert ref.check_pass(cluster, specs, before, after).reading() == ref.BROKEN


def test_moved_or_lost_bound_pod_is_caught(small):
    cluster, specs, before, after = _sound(small)
    bound = next(n for n, v in before.items() if v is not None)
    moved = dict(after, **{bound: next(x for x in cluster.names if x != before[bound])})
    assert ref.check_pass(cluster, specs, before, moved).reading() == ref.BROKEN
    lost = {n: v for n, v in after.items() if n != bound}
    assert ref.check_pass(cluster, specs, before, lost).reading() == ref.BROKEN


def test_a_worse_node_reads_its_gap():
    spec = small_spec("basic-5000.churn", 128, 40, 40, [4])
    cluster, specs, before, after = _sound(spec)
    name = next(n for n, v in before.items() if v is None)
    st = ref.State(cluster, specs, {n: v for n, v in before.items() if v is not None})
    s = specs[name]
    feas = st.feasible(s)
    scores = ref.node_scores(st, s)
    touched = {cluster.index[v] for n, v in after.items() if before[n] is None and v}
    worst = min((j for j in range(cluster.n) if feas[j] and j not in touched), key=lambda j: scores[j])
    after[name] = cluster.names[worst]
    c = ref.check_pass(cluster, specs, before, after)
    assert c.broken == 0 and c.gap >= 1


def test_the_profiles_weights_are_read():
    cluster, _, _ = _pass(small_spec("basic-5000.churn", *SMALL["basic-5000.churn"]), 1)
    assert cluster.weights == (1, 1)
    s = ref.pod_spec(small_spec("basic-5000.churn", *SMALL["basic-5000.churn"])["config"]["podTemplate"])
    empty = ref.resource_scores(cluster, 4000, 32768, 0, 0, s)
    assert empty == (97 + 98) // 2 + 99  # LeastAllocated 97/98, BalancedAllocation 99
