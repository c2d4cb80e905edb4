"""Both traffic mixes are drawn from the seed alone, and every seed gets
the same counts and the same pods, in the same order."""

from __future__ import annotations

from traffic import Traffic


def _drive(spec, seed, cycles=6):
    t = Traffic(spec, seed)
    nodes, pods = t.cluster()
    live = {p["metadata"]["name"]: p for p in pods}
    out = [(nodes, pods)]
    for serial in range(cycles):
        k, deletes, creates = t.cycle(live, serial)
        for p in deletes:
            del live[p["metadata"]["name"]]
        for p in creates:
            live[p["metadata"]["name"]] = p
        out.append((k, deletes, creates))
    return out


def test_same_seed_same_inputs(small):
    seed = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    assert _drive(small, seed) == _drive(small, seed)


def test_other_seed_other_inputs_same_counts(small):
    a, b = _drive(small, 7), _drive(small, 8)
    assert a != b
    assert [c[0] for c in a[1:]] == [c[0] for c in b[1:]]
    assert [len(c[2]) for c in a[1:]] == [len(c[2]) for c in b[1:]]


def test_every_seed_gets_the_same_pods(small):
    a, b = _drive(small, 7), _drive(small, 8)
    assert [c[2] for c in a[1:]] == [c[2] for c in b[1:]]
    strip = lambda pods: [{**p, "spec": {k: v for k, v in p["spec"].items() if k != "nodeName"}} for p in pods]  # noqa: E731
    assert strip(a[0][1]) == strip(b[0][1])


def test_residents_one_to_a_node_on_the_full_anti_affinity_cluster():
    from conftest import SMALL, small_spec

    nodes, pods = Traffic(small_spec("antiaffinity-500.churn", *SMALL["antiaffinity-500.churn"]), 5).cluster()
    assert sorted(p["spec"]["nodeName"] for p in pods) == sorted(n["metadata"]["name"] for n in nodes)
