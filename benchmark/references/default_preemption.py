"""Plain reference of DefaultPreemption on the benchmark's preemption
clusters, and the check of a served gang pass that preempts.

It imports nothing of the program. With numpy integers it re-derives what
the upstream scheduler decides for a queue of prioritised pods, with the
semantics of `sched/oracle_plugins.py` `default_preemption`:

  queue     PrioritySort: priority descending, then arrival order
  filters   NodeResourcesFit (cpu, memory, pod count, against
            status.allocatable or, where that is unset, status.capacity)
  dry run   per node, remove every pod of lower priority than the
            preemptor; if it then fits, add them back highest priority
            first, then in bind order, keeping each that leaves the
            preemptor fitting; the rest are the node's victims
  choice    the candidate with the lowest highest-victim priority, then
            the lowest priority sum, the fewest victims, the lowest node
            index; its victims are evicted and the preemptor is bound in
            the same pass

Where it departs from upstream (pkg/scheduler/framework/plugins/
defaultpreemption, v1.30):

  * no candidate sampling: every node is a candidate
    (`minCandidateNodesPercentage` / `minCandidateNodesAbsolute` and the
    random offset are not modelled);
  * no PodDisruptionBudgets;
  * no tiebreak on the victims' start time;
  * the preemptor is bound in the same pass, where upstream nominates it
    and binds it once the victims have terminated.

A pass (`check_pass`) is simulated in queue order, as the served gang
pass runs it (engine/gang.py): the pods the server placed on nodes it
evicted nothing from are the rounds' placements, taken as the server
made them and checked for their score `gap` by `default_plugins`; every
other queued pod goes through the preempt phase in queue order, where
the reference places it plainly if a node fits it, else preempts. The
check is `broken` for a preemptor on another node than the reference's
or left pending; a victim whose priority is not below its preemptor's or
that sat on another node; more victims on a node than the reference
evicts there; a node over cpu, memory or pods; a bound pod moved; a pod
left pending that fits a node. Among victims of equal priority only the
count and the node are checked: the client cannot see the bind order
inside a pass, which decides which of them is reprieved.

The client keeps the pods the server deleted as pending in its later
passes' `before` (they are missing from `after`): the cluster keeps the
victims it has accounted for (`Cluster.gone`) and skips them. The passes
are checked in order, each once.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from harness import load_module

plain = load_module("references", "default_plugins")
BROKEN = plain.BROKEN
PassCheck = plain.PassCheck
cpu_milli, mem_mi = plain.cpu_milli, plain.mem_mi


class Cluster:
    """Node arrays (allocatable cpu in m, memory in Mi, pods), the plain
    reference's cluster for the score check, and the victims already
    accounted for."""

    def __init__(self, nodes: list, config: dict):
        self.plain = plain.Cluster(nodes, config)
        self.names = self.plain.names
        self.index = self.plain.index
        self.cpu, self.mem, self.pods = self.plain.cpu, self.plain.mem, self.plain.pods
        self.gone: set = set()

    @property
    def n(self) -> int:
        return len(self.names)


@dataclass(frozen=True)
class PodSpec:
    cpu: int
    mem: int
    priority: int
    namespace: str

    def plain(self):
        return plain.PodSpec(self.cpu, self.mem, self.namespace, frozenset())


def pod_spec(p: dict) -> PodSpec:
    spec = p["spec"]
    if set(spec) - {"containers", "nodeName", "priority", "terminationGracePeriodSeconds"}:
        raise ValueError(f"pod spec keys {sorted(spec)} are not modelled")
    cpu = mem = 0
    for c in spec["containers"]:
        if any("hostPort" in port for port in c.get("ports") or []):
            raise ValueError("host ports are not modelled")
        req = c["resources"]["requests"]
        cpu += cpu_milli(req["cpu"])
        mem += mem_mi(req["memory"])
    return PodSpec(cpu, mem, int(spec.get("priority", 0)), p["metadata"].get("namespace") or "default")


class State:
    """The bound pods of one moment: per-node sums and each node's
    residents in reprieve order (priority descending, then bind order)."""

    def __init__(self, cluster: Cluster, specs: dict, bound: dict):
        self.cluster, self.specs = cluster, specs
        self.cpu = np.zeros(cluster.n, np.int64)
        self.mem = np.zeros(cluster.n, np.int64)
        self.count = np.zeros(cluster.n, np.int64)
        self.on: list[list] = [[] for _ in range(cluster.n)]  # (-priority, seq, name)
        self.seq = 0
        self._decided: dict = {}  # PodSpec -> what `decide` said, until the state changes
        for name, node in bound.items():
            self.add(name, cluster.index[node])

    def add(self, name: str, j: int) -> None:
        s = self.specs[name]
        self.cpu[j] += s.cpu
        self.mem[j] += s.mem
        self.count[j] += 1
        self.on[j].append((-s.priority, self.seq, name))
        self.on[j].sort()
        self.seq += 1
        self._decided.clear()

    def remove(self, name: str, j: int) -> None:
        s = self.specs[name]
        self.cpu[j] -= s.cpu
        self.mem[j] -= s.mem
        self.count[j] -= 1
        self.on[j] = [r for r in self.on[j] if r[2] != name]
        self._decided.clear()

    def decide(self, s: PodSpec) -> tuple:
        """("fits", nodes that fit `s`), or ("preempt", the dry run's
        (node, victims)), or ("none", None). Pods of one spec that fit
        nowhere leave the state as it was, so the answer is kept until
        the state changes: a long queue of them costs one dry run."""
        if s not in self._decided:
            fits = self.fits(s)
            if fits.any():
                self._decided[s] = ("fits", fits)
            else:
                pick = self.dry_run(s)
                self._decided[s] = ("none", None) if pick is None else ("preempt", pick)
        return self._decided[s]

    def fits(self, s: PodSpec, cpu=None, mem=None, count=None) -> np.ndarray:
        c = self.cluster
        cpu = self.cpu if cpu is None else cpu
        mem = self.mem if mem is None else mem
        count = self.count if count is None else count
        return (cpu + s.cpu <= c.cpu) & (mem + s.mem <= c.mem) & (count + 1 <= c.pods)

    def dry_run(self, s: PodSpec) -> "tuple[int, list] | None":
        """(node index, victim names) DefaultPreemption picks for `s`, or
        None where no node is a candidate. Vectorised over the nodes, one
        reprieve slot at a time."""
        n = self.cluster.n
        if not any(-r[-1][0] < s.priority for r in self.on if r):
            return None  # no resident of lower priority anywhere
        width = max((len(r) for r in self.on), default=0)
        names = np.full((n, width), "", object)
        lower = np.zeros((n, width), bool)
        cpu = np.zeros((n, width), np.int64)
        mem = np.zeros((n, width), np.int64)
        prio = np.zeros((n, width), np.int64)
        for j, rows in enumerate(self.on):
            for k, (_, _, name) in enumerate(rows):
                v = self.specs[name]
                names[j, k] = name
                lower[j, k] = v.priority < s.priority
                cpu[j, k], mem[j, k], prio[j, k] = v.cpu, v.mem, v.priority
        used_c = self.cpu - (cpu * lower).sum(axis=1)
        used_m = self.mem - (mem * lower).sum(axis=1)
        used_n = self.count - lower.sum(axis=1)
        fits = self.fits(s, used_c, used_m, used_n)
        victim = np.zeros((n, width), bool)
        for k in range(width):
            c2, m2, n2 = used_c + cpu[:, k], used_m + mem[:, k], used_n + 1
            ok = self.fits(s, c2, m2, n2)
            keep = lower[:, k] & ok
            used_c, used_m, used_n = np.where(keep, c2, used_c), np.where(keep, m2, used_m), np.where(keep, n2, used_n)
            victim[:, k] = lower[:, k] & ~ok
        cand = lower.any(axis=1) & fits & victim.any(axis=1)
        if not cand.any():
            return None
        big = np.iinfo(np.int64).max
        maxp = np.where(victim, prio, -big).max(axis=1, initial=-big)
        sump = np.where(victim, prio, 0).sum(axis=1)
        cnt = victim.sum(axis=1)
        js = np.nonzero(cand)[0]
        j = int(js[np.lexsort((js, cnt[js], sump[js], maxp[js]))[0]])
        return j, [str(x) for x in names[j][victim[j]]]


def _queue(specs: dict, names: list) -> list:
    """PrioritySort: priority descending, then arrival order (`names`'
    order)."""
    return [n for _, _, n in sorted((-specs[n].priority, i, n) for i, n in enumerate(names))]


def check_pass(cluster: Cluster, specs: dict, before: dict, after: dict) -> PassCheck:
    """`before`: every pod in the store when the pass started, as the
    client saw it, name -> node (None: pending). `after`: the store read
    back when the pass had returned."""
    out = PassCheck()
    before = {n: v for n, v in before.items() if n not in cluster.gone}
    evicted = {n for n, v in before.items() if v is not None and n not in after}
    cluster.gone |= evicted
    for name in sorted(n for n in after if n not in before):
        out.fail(f"pod {name} appeared in the pass")
    for name, node in before.items():
        if node is None and name not in after:
            out.fail(f"pod {name} lost in the pass")
        elif node is not None and name in after and after[name] != node:
            out.fail(f"bound pod {name} moved from {node} to {after[name]}")
    final = State(cluster, specs, {n: v for n, v in after.items() if v is not None and n in specs})
    over = (final.cpu > cluster.cpu) | (final.mem > cluster.mem) | (final.count > cluster.pods)
    for j in np.nonzero(over)[0]:
        out.fail(f"node {cluster.names[j]} holds more than it can allocate")

    queue = _queue(specs, [n for n, v in before.items() if v is None and n in after])
    hit = {cluster.index[before[v]] for v in evicted}  # nodes the server evicted from
    rounds = [n for n in queue if after[n] is not None and cluster.index[after[n]] not in hit]
    _plain_gap(out, cluster, specs, before, evicted, rounds, after)

    st = State(cluster, specs, {n: v for n, v in before.items() if v is not None})
    for name in rounds:
        st.add(name, cluster.index[after[name]])
    ref_victims: dict = {}  # node index -> [victims the reference evicted there]
    ref_prio: dict = {}  # node index -> highest priority of its preemptors
    in_rounds = set(rounds)
    for name in (n for n in queue if n not in in_rounds):
        s, got = specs[name], after[name]
        kind, what = st.decide(s)
        if kind == "fits":
            if got is None:
                out.fail(f"pod {name} left pending though a node fits it")
            elif not what[cluster.index[got]]:
                out.fail(f"pod {name} on {got} breaks a filter")
            else:
                st.add(name, cluster.index[got])
            continue
        if kind == "none":
            if got is not None:
                out.fail(f"pod {name} placed on {got}, where the reference finds no node")
            continue
        j, victims = what
        if got is None:
            out.fail(f"preemptor {name} left pending; the reference preempts on {cluster.names[j]}")
        elif got != cluster.names[j]:
            out.fail(f"preemptor {name} on {got}; the reference's node is {cluster.names[j]}")
        for v in victims:
            st.remove(v, j)
        st.add(name, j)
        ref_victims.setdefault(j, []).extend(victims)
        ref_prio[j] = max(ref_prio.get(j, s.priority), s.priority)

    by_node: dict = {}
    for v in evicted:
        by_node.setdefault(cluster.index[before[v]], []).append(v)
    for j, victims in sorted(by_node.items()):
        where = cluster.names[j]
        if j not in ref_victims:
            out.fail(f"{len(victims)} pod(s) evicted from {where}, where the reference preempts nothing")
            continue
        if len(victims) > len(ref_victims[j]):
            out.fail(f"{len(victims)} victims on {where}; the reference evicts {len(ref_victims[j])}")
        for v in victims:
            if specs[v].priority >= ref_prio[j]:
                out.fail(f"victim {v} on {where} is not below its preemptor's priority")
    return out


def _plain_gap(out: PassCheck, cluster: Cluster, specs: dict, before: dict, evicted: set,
               rounds: list, after: dict) -> None:
    """The rounds' placements, on nodes the pass evicted nothing from,
    read by the plain reference's check: its `gap`, and its guarantees
    over the pods it sees. The rounds ran before the preempt phase, so
    the view holds the victims where they sat and leaves the phase's
    pods out."""
    bound = [n for n, v in before.items() if v is not None]
    names = bound + rounds
    view_before = {n: before[n] for n in names}
    view_after = {n: before[n] if n in evicted else after[n] for n in names}
    c = plain.check_pass(cluster.plain, {n: specs[n].plain() for n in names}, view_before, view_after)
    out.gap = max(out.gap, c.gap)
    out.checked += c.checked
    out.unverified += c.unverified
    out.broken += c.broken
    out.reasons += c.reasons[: max(0, 5 - len(out.reasons))]


# The reference put in the program's place, with one guarantee broken:
# the configuration's control (configs/<name>.json "control").
CONTROLS = {
    # no PostFilter: a pod that fits no node stays pending (breaks "a
    # preemptor takes the node the DefaultPreemption dry run ranks first")
    "no_preemption": dict(preempt=False),
}


def schedule(cluster: Cluster, specs: dict, before: dict, control: str = "no_preemption") -> dict:
    """The queue (the pending pods of `before`) decided one pod at a time
    against the state its predecessors left: the best-scoring node that
    fits, else (with preemption) the dry run's node. Returns name -> node
    name (None: left pending)."""
    how = CONTROLS[control]
    if how["preempt"]:
        raise ValueError("the client binds, it cannot evict: only a control without preemption runs")
    before = {n: v for n, v in before.items() if n not in cluster.gone}
    st = State(cluster, specs, {n: v for n, v in before.items() if v is not None})
    out = {}
    for name in _queue(specs, [n for n, v in before.items() if v is None]):
        s = specs[name]
        kind, fits = st.decide(s)
        if kind != "fits":
            out[name] = None
            continue
        score = plain.resource_scores(cluster.plain, cluster.cpu, cluster.mem, st.cpu, st.mem, s.plain())
        j = int(np.argmax(np.where(fits, score, -1)))
        st.add(name, j)
        out[name] = cluster.names[j]
    return out
