"""The PreemptionBasic cell's generator and reference: a preempt wave of k
high pods evicts 3k low pods and its refill fills the nodes back; the
reference in the program's place reads 0, and its control, a pass that
left a preemptor pending, and a victim of its preemptor's priority read
broken. A whole run on the CPU at a test size is correct, its control
is not."""

from __future__ import annotations

import os

import pytest

from conftest import small_spec
from harness import load_module
from traffic import Traffic

ref = load_module("references", "default_preemption")
gen = load_module("generators", "scheduler_perf_preemption")

CELL = "preemption-500.preempt"
SMALL = (12, 48, 0, [4, 4, 12, 12])  # nodes, low pods, (unused), the mix's counts


def _spec():
    return small_spec(CELL, *SMALL)


def _play(cluster, specs, before):
    """The reference in the program's place, preemption included: the
    pass's queue in PrioritySort order against the state it leaves."""
    st = ref.State(cluster, specs, {n: v for n, v in before.items() if v is not None})
    after = dict(before)
    for name in ref._queue(specs, [n for n, v in before.items() if v is None]):
        fits = st.fits(specs[name])
        if fits.any():
            j = int(fits.argmax())
        else:
            pick = st.dry_run(specs[name])
            if pick is None:
                continue
            j, victims = pick
            for v in victims:
                st.remove(v, j)
                del after[v]
        st.add(name, j)
        after[name] = cluster.names[j]
    return after


def _cycles(spec, seed):
    """(kind, count, cluster, specs, before, after) of each cycle of one
    round of the mix, the reference in the program's place."""
    t = Traffic(spec, seed)
    nodes, pods = t.cluster()
    cluster = ref.Cluster(nodes, spec["config"])
    live = {p["metadata"]["name"]: p for p in pods}
    node_of = {n: p["spec"]["nodeName"] for n, p in live.items()}
    specs = {n: ref.pod_spec(p) for n, p in live.items()}
    out = []
    for serial in range(len(t.batches)):
        k, deletes, creates = t.cycle(live, serial)
        for p in deletes:
            del live[p["metadata"]["name"]], node_of[p["metadata"]["name"]]
        for p in creates:
            live[p["metadata"]["name"]] = p
            node_of[p["metadata"]["name"]] = None
            specs[p["metadata"]["name"]] = ref.pod_spec(p)
        before = dict(node_of)
        after = _play(cluster, specs, {n: v for n, v in before.items() if n not in cluster.gone})
        out.append((spec["traffic"]["phases"][serial], k, cluster, specs, before, after))
        assert ref.check_pass(cluster, specs, before, after).reading() == 0
        node_of = {n: after.get(n) for n in live}
    return out


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_phases_evict_three_per_preemptor_and_refill_the_nodes(seed):
    spec = _spec()
    per = gen.victims_per_preemptor(spec["config"])
    assert per == 3
    for kind, k, cluster, specs, before, after in _cycles(spec, seed):
        evicted = [n for n, v in before.items() if v is not None and n not in after]
        placed = [n for n, v in before.items() if v is None and after.get(n)]
        if kind == "preempt":
            assert (len(placed), len(evicted)) == (k, per * k)
            assert all(specs[n].priority == 10 for n in placed)
        else:
            assert (len(placed), len(evicted)) == (per * k, 0)
            load = {}
            for v in after.values():
                if v is not None:
                    load[v] = load.get(v, 0) + 1
            assert sorted(load.values()) == [gen.residents_per_node(spec["config"])] * SMALL[0]


@pytest.mark.parametrize("slow_cycle, stops", [(None, False), (1, True), (5, True)])
def test_a_window_cycle_past_the_limit_stops_the_run(monkeypatch, slow_cycle, stops):
    """Set-up's cycle may take as long as it takes; any later cycle that
    outlasts `cycle_limit_s` stops the run when the next one starts."""
    spec = _spec()
    limit = spec["config"]["cycle_limit_s"]
    clock = [0.0]
    monkeypatch.setattr(gen, "_clock", lambda: clock[0])
    t = Traffic(spec, 11)
    live = {p["metadata"]["name"]: p for p in t.cluster()[1]}

    def run_cycles():
        for serial in range(7):
            _, deletes, creates = t.cycle(live, serial)
            for p in deletes:
                del live[p["metadata"]["name"]]
            live.update((p["metadata"]["name"], p) for p in creates)
            clock[0] += 10 * limit if serial in (0, slow_cycle) else 1.0

    if stops:
        with pytest.raises(SystemExit, match="cycle_limit_s"):
            run_cycles()
    else:
        run_cycles()


def _first_wave(seed=5):
    spec = _spec()
    _, _, _, specs, before, after = _cycles(spec, seed)[0]
    nodes, _ = Traffic(spec, seed).cluster()
    return lambda: ref.Cluster(nodes, spec["config"]), specs, before, after


def test_control_is_caught():
    fresh, specs, before, after = _first_wave()
    control = ref.schedule(fresh(), specs, before, "no_preemption")
    after_c = {n: (control.get(n) if v is None else v) for n, v in before.items()}
    c = ref.check_pass(fresh(), specs, before, after_c)
    assert c.broken > 0 and "left pending" in " ".join(c.reasons)


def test_preemptor_on_another_node_is_caught():
    fresh, specs, before, after = _first_wave()
    a, b = [n for n, v in before.items() if v is None][:2]
    after = dict(after, **{a: after[b], b: after[a]})
    c = ref.check_pass(fresh(), specs, before, after)
    assert c.broken > 0 and "the reference's node" in " ".join(c.reasons)


def test_victim_of_equal_priority_is_caught():
    """One of the first preemptor's victims is given the preemptor's own
    priority: the reference then reprieves it, and the server's eviction
    of it reads broken."""
    fresh, specs, before, after = _first_wave()
    first = next(n for n, v in before.items() if v is None)
    j = after[first]
    v = next(n for n, x in before.items() if x == j and n not in after)
    s = specs[v]
    specs = dict(specs, **{v: ref.PodSpec(s.cpu, s.mem, specs[first].priority, s.namespace)})
    c = ref.check_pass(fresh(), specs, before, after)
    assert c.broken > 0 and "not below its preemptor's priority" in " ".join(c.reasons)


def test_more_victims_than_the_reference_is_caught():
    fresh, specs, before, after = _first_wave()
    j = after[next(n for n, v in before.items() if v is None)]
    reprieved = next(n for n, v in before.items() if v == j and n in after)
    after = {n: v for n, v in after.items() if n != reprieved}
    c = ref.check_pass(fresh(), specs, before, after)
    assert c.broken > 0 and "the reference evicts" in " ".join(c.reasons)


def test_earlier_victims_are_skipped_in_later_passes():
    """The client keeps deleted victims as pending: a later pass's before
    holds them, its after does not, and the check reads 0."""
    fresh, specs, before, after = _first_wave()
    cluster = fresh()
    assert ref.check_pass(cluster, specs, before, after).reading() == 0
    later = {n: after.get(n) for n in before}  # victims read pending
    assert ref.check_pass(cluster, specs, later, after).reading() == 0


@pytest.fixture(scope="module")
def cpu_devices(tmp_path_factory):
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jaxcache")))
    import jax

    return jax.devices()


def _run(devices, seed, control=False):
    import run as R

    spec = _spec()
    os.environ.update(spec["config"]["server_env"])
    run = R.run_cell(spec, seed, 1.0, False, devices, control)
    return run, R.result_line(run, devices, False)


def test_whole_run_is_correct_and_reads_its_counters(cpu_devices):
    run, out = _run(cpu_devices, 3000000019)
    assert out["correct"], out["checks"]
    assert out["checks"]["broken"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"pods_per_s", "pass_mean_s", "setup_s"}
    # per pass: (4 + 12) preemptors over the mix's 4 passes, three victims each
    assert load_module("metrics", "preempt_steps").read(run) == 4.0
    assert load_module("metrics", "preempt_victims").read(run) == 12.0


def test_whole_run_control_is_not_correct(cpu_devices):
    _, out = _run(cpu_devices, 7, control=True)
    assert not out["correct"] and out["checks"]["broken"]["value"] > 0


def test_counter_readers_read_nothing_where_the_program_keeps_no_counter():
    run = {"window": [{}] * 4, "metrics_open": {"gangFixpointRounds": 0},
           "metrics_close": {"gangFixpointRounds": 8}}
    for name in ("preempt_steps", "preempt_victims"):
        assert load_module("metrics", name).read(run) is None
    run["metrics_open"].update(gangPreemptSteps=10, gangPreemptVictims=30)
    run["metrics_close"].update(gangPreemptSteps=26, gangPreemptVictims=78)
    assert load_module("metrics", "preempt_steps").read(run) == 4.0
    assert load_module("metrics", "preempt_victims").read(run) == 12.0
