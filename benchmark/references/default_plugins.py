"""Plain reference of the default scheduler profile, as far as the
benchmark's clusters use it, and the check of a served gang pass.

It imports nothing of the program. It reads the manifests the benchmark
sent, the configuration's score weights and the placements the server's
store holds, and it re-derives, with numpy integers, what the upstream
plugins say:

  filters   NodeResourcesFit (cpu, memory, pod count, against
            status.allocatable or, where that is unset, status.capacity);
            InterPodAffinity's required anti-affinity terms, both
            directions, matchLabels selectors over the term's namespaces,
            any node-label topology
  scores    NodeResourcesFit LeastAllocated over cpu and memory;
            NodeResourcesBalancedAllocation over cpu and memory, with
            fractions floored to 1/2^16 as the program states
            (kernels.build_balanced_score); each times its weight in the
            configuration's profile

Every other plugin of the default profile gives every node the same score
on these clusters (no taints, node affinity, images on nodes, host ports,
volumes, preferred or required affinity terms, and no owning controller
for the default spread constraints), so it cannot move a choice and is
left out. A manifest using anything else is refused.

The check of one pass (`check_pass`) holds the server to the guarantees
of a gang pass (engine/gang.py): bound pods stay where they are, every
queued pod is decided, every node stays within what it can allocate, no
placement breaks a required term, a pod left pending fits no node of the
final state (the fixpoint), and each placed pod took a node that scored
at least as high as every node that the pass left untouched. That last
one is the reading `gap`: the most by which a node the pod could have
taken outscored the node it got, at the state the pod was decided
against. For an untouched node that state is the state before the pass;
for the node it got, it is the state before the pass plus some of the
other pods the pass put there, and the pod gets the best of those. A
broken guarantee reads `BROKEN`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

MAX_NODE_SCORE = 100
BALANCED_SCALE = 1 << 16
BROKEN = 10**6
MAX_SHARERS = 12  # enumerate at most 2^12 subsets of a node's other pass pods


def cpu_milli(q: str) -> int:
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


_MEM = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40}


def mem_mi(q: str) -> int:
    """Memory in Mi; the benchmark's quantities are Mi-granular."""
    for suffix, mult in _MEM.items():
        if q.endswith(suffix):
            b = int(q[:-2]) * mult
            break
    else:
        b = int(q)
    if b % (1 << 20):
        raise ValueError(f"memory {q!r} is not Mi-granular")
    return b >> 20


def score_weights(config: dict) -> tuple[int, int]:
    """(NodeResourcesFit, NodeResourcesBalancedAllocation) weights of the
    configuration's profile."""
    enabled = config["schedulerConfiguration"]["profiles"][0]["plugins"]["score"]["enabled"]
    w = {p["name"]: int(p["weight"]) for p in enabled}
    return w["NodeResourcesFit"], w["NodeResourcesBalancedAllocation"]


class Cluster:
    """Node arrays: allocatable cpu (m), memory (Mi), pods, a domain id
    per node for each topology key the pods use (-1: no such label), and
    the profile's score weights."""

    def __init__(self, nodes: list, config: dict):
        self.names = [n["metadata"]["name"] for n in nodes]
        self.index = {n: i for i, n in enumerate(self.names)}
        alloc = [n["status"].get("allocatable") or n["status"]["capacity"] for n in nodes]
        self.cpu = np.array([cpu_milli(a["cpu"]) for a in alloc], np.int64)
        self.mem = np.array([mem_mi(a["memory"]) for a in alloc], np.int64)
        self.pods = np.array([int(a["pods"]) for a in alloc], np.int64)
        self.labels = [n["metadata"].get("labels") or {} for n in nodes]
        self.weights = score_weights(config)
        self._domains: dict[str, np.ndarray] = {}

    @property
    def n(self) -> int:
        return len(self.names)

    def domain(self, key: str) -> np.ndarray:
        if key not in self._domains:
            ids: dict[str, int] = {}
            self._domains[key] = np.array(
                [ids.setdefault(lab[key], len(ids)) if key in lab else -1 for lab in self.labels],
                np.int64,
            )
        return self._domains[key]

    def spread(self, counts: np.ndarray, key: str) -> np.ndarray:
        """Per node, the sum of `counts` over the nodes of its domain."""
        dom = self.domain(key)
        has = dom >= 0
        sums = np.bincount(dom[has], weights=counts[has], minlength=int(dom.max()) + 1)
        return np.where(has, sums[np.maximum(dom, 0)], 0).astype(np.int64)


@dataclass(frozen=True)
class Term:
    """A required anti-affinity term: pods labelled `key: value` in one of
    `namespaces`, counted over `topology`."""

    key: str
    value: str
    topology: str
    namespaces: frozenset

    def selects(self, s: "PodSpec") -> bool:
        return s.namespace in self.namespaces and (self.key, self.value) in s.labels


@dataclass(frozen=True)
class PodSpec:
    cpu: int
    mem: int
    namespace: str
    labels: frozenset  # {(key, value)}
    anti: tuple = ()  # required anti-affinity Terms


def _anti_terms(aff: dict, namespace: str) -> tuple:
    if set(aff) - {"podAntiAffinity"}:
        raise ValueError("only required pod anti-affinity is modelled")
    block = aff.get("podAntiAffinity") or {}
    if set(block) - {"requiredDuringSchedulingIgnoredDuringExecution"}:
        raise ValueError("only required anti-affinity terms are modelled")
    out = []
    for t in block.get("requiredDuringSchedulingIgnoredDuringExecution") or []:
        sel = t.get("labelSelector") or {}
        if set(sel) != {"matchLabels"} or len(sel["matchLabels"]) != 1:
            raise ValueError("only a one-label matchLabels selector is modelled")
        if set(t) - {"labelSelector", "topologyKey", "namespaces"}:
            raise ValueError(f"term keys {sorted(t)} are not modelled")
        ((key, value),) = sel["matchLabels"].items()
        nss = frozenset(t.get("namespaces") or [namespace])
        out.append(Term(key, value, t["topologyKey"], nss))
    return tuple(out)


def pod_spec(p: dict) -> PodSpec:
    spec = p["spec"]
    if set(spec) - {"containers", "affinity", "nodeName"}:
        raise ValueError(f"pod spec keys {sorted(spec)} are not modelled")
    cpu = mem = 0
    for c in spec["containers"]:
        if any("hostPort" in port for port in c.get("ports") or []):
            raise ValueError("host ports are not modelled")
        req = c["resources"]["requests"]
        cpu += cpu_milli(req["cpu"])
        mem += mem_mi(req["memory"])
    ns = p["metadata"].get("namespace") or "default"
    labels = frozenset((p["metadata"].get("labels") or {}).items())
    return PodSpec(cpu, mem, ns, labels, _anti_terms(spec.get("affinity") or {}, ns))


class State:
    """The bound pods of one moment, as per-node sums."""

    def __init__(self, cluster: Cluster, specs: dict, bound: dict):
        n = cluster.n
        self.cluster = cluster
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.count = np.zeros(n, np.int64)
        self.labelled = {}  # (namespace, key, value) -> pods per node
        self.anti = {}  # Term -> pods per node carrying it
        for name, node in bound.items():
            self.add(specs[name], cluster.index[node])

    def _bump(self, s: PodSpec, j: int, by: int) -> None:
        n = self.cluster.n
        self.cpu[j] += by * s.cpu
        self.mem[j] += by * s.mem
        self.count[j] += by
        for k, v in s.labels:
            self.labelled.setdefault((s.namespace, k, v), np.zeros(n, np.int64))[j] += by
        for t in s.anti:
            self.anti.setdefault(t, np.zeros(n, np.int64))[j] += by

    def add(self, s: PodSpec, j: int) -> None:
        self._bump(s, j, 1)

    def remove(self, s: PodSpec, j: int) -> None:
        self._bump(s, j, -1)

    def feasible(self, s: PodSpec, anti_affinity: bool = True) -> np.ndarray:
        c = self.cluster
        ok = (self.cpu + s.cpu <= c.cpu) & (self.mem + s.mem <= c.mem) & (self.count + 1 <= c.pods)
        if not anti_affinity:
            return ok
        zero = np.zeros(c.n, np.int64)
        for t in s.anti:
            matching = sum((self.labelled.get((ns, t.key, t.value), zero) for ns in t.namespaces), zero)
            ok &= c.spread(matching, t.topology) == 0
        for t, per_node in self.anti.items():
            if t.selects(s):
                ok &= c.spread(per_node, t.topology) == 0
        return ok


def resource_scores(cluster: Cluster, cap_c, cap_m, used_c, used_m, s: PodSpec):
    """Weighted LeastAllocated + BalancedAllocation over cpu and memory."""
    w_fit, w_bal = cluster.weights
    req_c, req_m = used_c + s.cpu, used_m + s.mem
    least = lambda cap, req: np.where(  # noqa: E731
        req > cap, 0, (cap - req) * MAX_NODE_SCORE // np.maximum(cap, 1)
    )
    fit = (least(cap_c, req_c) + least(cap_m, req_m)) // 2
    q_c = np.minimum(req_c * BALANCED_SCALE // cap_c, BALANCED_SCALE)
    q_m = np.minimum(req_m * BALANCED_SCALE // cap_m, BALANCED_SCALE)
    d = np.abs(q_c - q_m)
    bal = (200 * BALANCED_SCALE - 100 * d) // (2 * BALANCED_SCALE)
    return w_fit * fit + w_bal * bal


def node_scores(st: State, s: PodSpec) -> np.ndarray:
    c = st.cluster
    return resource_scores(c, c.cpu, c.mem, st.cpu, st.mem, s)


@dataclass
class PassCheck:
    gap: int = 0
    broken: int = 0
    checked: int = 0  # placed pods whose gap was read
    unverified: int = 0  # placed pods sharing a node with too many pass pods
    reasons: list = field(default_factory=list)

    def fail(self, why: str) -> None:
        self.broken += 1
        if len(self.reasons) < 5:
            self.reasons.append(why)

    def reading(self) -> int:
        return BROKEN if self.broken else self.gap


def check_pass(cluster: Cluster, specs: dict, before: dict, after: dict) -> PassCheck:
    """`before`: every pod in the store when the pass started, name ->
    node (None: pending, the pass's queue). `after`: the same read back
    when the pass had returned."""
    out = PassCheck()
    for name in sorted(set(before) ^ set(after)):
        out.fail(f"pod {name} {'lost' if name in before else 'appeared'} in the pass")
    queue = [n for n, v in before.items() if v is None and n in after]
    bound0 = {n: v for n, v in before.items() if v is not None}
    for name, node in bound0.items():
        if name in after and after[name] != node:
            out.fail(f"bound pod {name} moved from {node} to {after[name]}")
    s0 = State(cluster, specs, bound0)
    final_bound = {n: v for n, v in after.items() if v is not None}
    final = State(cluster, specs, final_bound)
    over = (final.cpu > cluster.cpu) | (final.mem > cluster.mem) | (final.count > cluster.pods)
    for j in np.nonzero(over)[0]:
        out.fail(f"node {cluster.names[j]} holds more than it can allocate")
    on_node: dict[int, list] = {}
    for name in queue:
        if after[name] is not None:
            on_node.setdefault(cluster.index[after[name]], []).append(name)
    touched = np.zeros(cluster.n, bool)
    touched[list(on_node)] = True
    best_free: dict = {}  # PodSpec -> best score of the untouched nodes it fits before the pass
    for name in queue:
        s = specs[name]
        if after[name] is None:
            # the pass ends at its fixpoint: a pod it left pending fits nowhere
            if final.feasible(s).any():
                out.fail(f"pod {name} left pending though a node fits it")
            continue
        j = cluster.index[after[name]]
        final.remove(s, j)
        fits = final.feasible(s)[j]
        final.add(s, j)
        if not fits:
            out.fail(f"pod {name} on {after[name]} breaks a filter")
        if s not in best_free:
            free = node_scores(s0, s)[s0.feasible(s) & ~touched]
            best_free[s] = int(free.max()) if free.size else None
    for j, names in on_node.items():
        if len(names) > MAX_SHARERS:
            out.unverified += len(names)
            continue
        out.gap = max(out.gap, _node_gap(cluster, s0, j, [specs[n] for n in names], best_free))
        out.checked += len(names)
    return out


def _node_gap(cluster: Cluster, s0: State, j: int, pods: list, best_free: dict) -> int:
    """The gap of the pass pods on node j, in the order the pass most
    likely put them there: the least, over the orders, of the most by
    which a pod's best untouched node outscored node j holding the pods
    before it. A gang round gives each node to one pod, so a pod that
    shares node j came in a later round and saw the ones before it."""

    @lru_cache(maxsize=None)
    def gap(mask: int) -> int:
        if mask == 0:
            return -BROKEN
        best = BROKEN
        for i, s in enumerate(pods):
            if not mask >> i & 1:
                continue
            rest = mask & ~(1 << i)
            before = [pods[k] for k in range(len(pods)) if rest >> k & 1]
            got = int(resource_scores(
                cluster, cluster.cpu[j], cluster.mem[j],
                s0.cpu[j] + sum(o.cpu for o in before), s0.mem[j] + sum(o.mem for o in before), s,
            ))
            mine = -BROKEN if best_free[s] is None else best_free[s] - got
            best = min(best, max(mine, gap(rest)))
        return best

    return gap((1 << len(pods)) - 1)


# The reference put in the program's place. "exact" is the reference
# itself; the others each break one guarantee that a configuration
# states, and serve as its control (configs/<name>.json "control").
CONTROLS = {
    "exact": dict(score=True, anti_affinity=True),
    # no scoring: the first node in the cluster's order that passes every
    # filter (breaks "a node scoring at least as high as every node the
    # pass left untouched")
    "first_fit": dict(score=False, anti_affinity=True),
    # no scoring and no anti-affinity filter (breaks "no placement breaks
    # a required anti-affinity term"; with scores on, the emptiest nodes
    # would win and the term would never bind on these clusters)
    "first_fit_no_anti_affinity": dict(score=False, anti_affinity=False),
}


def schedule(cluster: Cluster, specs: dict, before: dict, control: str = "exact") -> dict:
    """The queue (the pending pods of `before`, in order) decided one pod
    at a time against the state its predecessors left. Returns name ->
    node name (None: no node fits)."""
    how = CONTROLS[control]
    st = State(cluster, specs, {n: v for n, v in before.items() if v is not None})
    out = {}
    for name, node in before.items():
        if node is not None:
            continue
        s = specs[name]
        feas = st.feasible(s, how["anti_affinity"])
        if not feas.any():
            out[name] = None
            continue
        sc = np.where(feas, node_scores(st, s), -1) if how["score"] else feas.astype(np.int64)
        j = int(np.argmax(sc))
        st.add(s, j)
        out[name] = cluster.names[j]
    return out
