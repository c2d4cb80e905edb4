"""A cluster of Kubernetes `scheduler_perf` workload PreemptionBasic
(test/integration/scheduler_perf): `initNodes` copies of the node template
and `initPods` copies of the low-priority pod template, bound at import;
the high-priority pods of `measurePods` arrive through the traffic.

The low pods are placed as the default scheduler spreads identical pods
over identical nodes: one to each node before any node gets a second, the
order of the nodes drawn from the seed. Four low pods (900m) fill a 4-cpu
node to 3 600m, so a high pod (3 000m) fits nowhere and has to evict the
low pods it cannot sit beside.

The traffic's `phases` name each cycle's kind, round robin with its
`batches`:

  preempt  k   creates k high pods in the measured namespace and deletes
               nothing: the pass evicts what they need
  refill   k   deletes the previous cycle's high pods and creates as many
               low pods as those evicted, which fill the freed nodes back
               to `initPods` / `initNodes` low pods each

A run has to end within the benchmark's time per run. A program whose
preempt phase steps through the whole pod bucket takes over a minute a
preempt pass here, and a run of it would be killed with nothing to show.
So each cycle after the first (set-up, which compiles) has to end within
the configuration's `cycle_limit_s`. When one does not, the next cycle
stops the run with an error instead of starting, so no pass is in flight
when the run exits.
"""

from __future__ import annotations

import copy
import random
import time

UNIT = "pod"

_clock = time.perf_counter
# the `live` map of the run being paced, the clock at its last cycle, and
# how many cycles it has had
_paced: dict = {"live": None, "t": 0.0, "cycles": 0}


def _named(template: dict, name: str, namespace: "str | None" = None) -> dict:
    obj = copy.deepcopy(template)
    meta = obj.setdefault("metadata", {})
    meta.pop("generateName", None)
    meta["name"] = name
    if namespace is not None:
        meta["namespace"] = namespace
    return obj


def _cpu_milli(q: str) -> int:
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def _pod_cpu(template: dict) -> int:
    return sum(_cpu_milli(c["resources"]["requests"]["cpu"]) for c in template["spec"]["containers"])


def residents_per_node(config: dict) -> int:
    nodes, low = int(config["initNodes"]), int(config["initPods"])
    if low % nodes:
        raise ValueError("the low pods do not fill the nodes evenly")
    return low // nodes


def victims_per_preemptor(config: dict) -> int:
    """The low pods a high pod evicts from a node of `residents_per_node`:
    all but those that still fit beside it."""
    cap = _cpu_milli(config["nodeTemplate"]["status"]["capacity"]["cpu"])
    low, high = _pod_cpu(config["lowPodTemplate"]), _pod_cpu(config["highPodTemplate"])
    r = residents_per_node(config)
    if r * low + high <= cap:
        raise ValueError("a high pod fits beside the residents: nothing to preempt")
    return r - (cap - high) // low


def nodes(config: dict) -> list:
    return [_named(config["nodeTemplate"], f"scheduler-perf-{i}") for i in range(int(config["initNodes"]))]


def make_cluster(config: dict, rng: random.Random) -> tuple[list, list]:
    ns_low = config["namespaces"][0]
    cluster = nodes(config)
    order = [n["metadata"]["name"] for n in cluster]
    rng.shuffle(order)
    residents_per_node(config)
    pods = []
    for i in range(int(config["initPods"])):
        p = _named(config["lowPodTemplate"], f"low-{i}", ns_low)
        p["spec"]["nodeName"] = order[i % len(order)]
        pods.append(p)
    return cluster, pods


def _pace(live: dict, config: dict) -> None:
    """Stop the run if the cycle before this one, set-up's aside, took
    longer than `cycle_limit_s`."""
    t = _clock()
    if _paced["live"] is not live:
        _paced.update(live=live, cycles=0)
    elif _paced["cycles"] >= 2 and t - _paced["t"] > config["cycle_limit_s"]:
        raise SystemExit(
            f"scheduler_perf_preemption: a cycle took {t - _paced['t']:.1f}s, more than"
            f" cycle_limit_s {config['cycle_limit_s']}s: the program cannot end a run of"
            " this cell within its time"
        )
    _paced["t"] = t
    _paced["cycles"] += 1


def churn(live: dict, count: int, rng: random.Random, serial: int, config: dict, params: dict):
    """(pods to delete, pods to create) for cycle `serial` of `count`."""
    _pace(live, config)
    if params.get("unit", UNIT) != UNIT:
        raise ValueError(f"this cluster churns by {UNIT!r}, not {params['unit']!r}")
    phases, batches = params["phases"], params["batches"]
    phase = phases[serial % len(phases)]
    ns_low, ns_high = config["namespaces"]
    if phase == "preempt":
        return [], [_named(config["highPodTemplate"], f"high-{serial}-{i}", ns_high) for i in range(count)]
    if phase != "refill":
        raise ValueError(f"unknown phase {phase!r}")
    prev = serial - 1
    if prev < 0 or phases[prev % len(phases)] != "preempt":
        raise ValueError("a refill cycle follows a preempt cycle")
    names = [f"high-{prev}-{i}" for i in range(int(batches[prev % len(batches)]))]
    deletes = [live[n] for n in names if n in live]
    k = victims_per_preemptor(config) * len(deletes)
    creates = [_named(config["lowPodTemplate"], f"low-{serial}-{i}", ns_low) for i in range(k)]
    return deletes, creates
