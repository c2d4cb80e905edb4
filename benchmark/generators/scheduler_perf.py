"""A cluster of Kubernetes `scheduler_perf` (test/integration/scheduler_perf),
as one of its workloads leaves it: `initNodes` copies of the node template,
then `initPods` and `measurePods` copies of the pod template, each
`createPods` step in a namespace of its own. The configuration file holds
the templates and counts as upstream states them.

The pods arrive bound, placed as the default scheduler spreads identical
pods over identical nodes: one pod to a node before any node gets a
second, the order of the nodes drawn from the seed. Churn deletes pods
drawn uniformly from the live set and creates fresh pods from the same
template in the measured namespace."""

from __future__ import annotations

import copy
import random

UNIT = "pod"


def _named(template: dict, name: str, namespace: "str | None" = None) -> dict:
    obj = copy.deepcopy(template)
    meta = obj.setdefault("metadata", {})
    meta.pop("generateName", None)
    meta["name"] = name
    if namespace is not None:
        meta["namespace"] = namespace
    return obj


def nodes(config: dict) -> list:
    out = []
    key = config.get("uniqueNodeLabelKey")
    for i in range(int(config["initNodes"])):
        n = _named(config["nodeTemplate"], f"scheduler-perf-{i}")
        if key:
            n["metadata"].setdefault("labels", {})[key] = n["metadata"]["name"]
        out.append(n)
    return out


def pod(config: dict, name: str, namespace: str) -> dict:
    return _named(config["podTemplate"], name, namespace)


def make_cluster(config: dict, rng: random.Random) -> tuple[list, list]:
    ns_init, ns_measure = config["namespaces"]
    ns = [ns_init] * int(config["initPods"]) + [ns_measure] * int(config["measurePods"])
    cluster = nodes(config)
    order = [n["metadata"]["name"] for n in cluster]
    rng.shuffle(order)
    if len(ns) > len(order) * int(config["nodeTemplate"]["status"]["capacity"]["pods"]):
        raise ValueError("more resident pods than the nodes hold")
    pods = []
    for i, namespace in enumerate(ns):
        p = pod(config, f"pod-{i}", namespace)
        p["spec"]["nodeName"] = order[i % len(order)]
        pods.append(p)
    return cluster, pods


def churn(live: dict, count: int, rng: random.Random, serial: int, config: dict, params: dict):
    """(pods to delete, pods to create) for one cycle of `count` pods."""
    if params.get("unit", UNIT) != UNIT:
        raise ValueError(f"this cluster churns by {UNIT!r}, not {params['unit']!r}")
    deletes = rng.sample(sorted(live), count) if params.get("delete", True) else []
    ns = config["namespaces"][1]
    creates = [pod(config, f"c{serial}-{i}", ns) for i in range(count)]
    return [live[n] for n in deletes], creates
