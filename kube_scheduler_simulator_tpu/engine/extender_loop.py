"""Extender-aware scheduling: device segments with HTTP callbacks between.

With extenders configured, control must leave the device after Filter
(extender filter verb) and after Score (extender prioritize verb) — the
reference's upstream scheduler does the same HTTP round-trips one pod at
a time (SURVEY.md §3.4). The loop here runs the compiled single-pod
segments (`BatchedScheduler.attempt_fn` / `bind_fn`) and interleaves the
extender calls host-side:

    per pod (PrioritySort order):
      attempt_fn  (device)  → per-node filter codes + framework scores
      extender.filter       → feasible set shrinks (FailedNodes recorded)
      extender.prioritize   → weight-rescaled scores add to the totals
      argmax + tie-break    (host; same lowest-index rule as the engine)
      [extender.bind]       → delegated bind when a bind-verb extender
                              manages the pod (upstream binder delegation)
      bind_fn     (device)  → state update

Preemption (DefaultPreemption enabled): when a pod fails all filters —
framework or extender — the dry-run kernel nominates candidates, then
every preempt-verb extender gets the candidate victim map and may trim
or veto it (upstream processPreemptionWithExtenders; wire shapes
ExtenderPreemptionArgs / ExtenderPreemptionResult with meta-victim UID
mapping). The best surviving candidate is re-ranked host-side with the
same rule as the kernel (min highest-victim priority, min priority sum,
fewest victims, lowest index), its victims evicted on device, and the
pod retried through the full framework+extender cycle — two records
(Nominated + retry), exactly like the batch engine's trace.
"""

from __future__ import annotations

import numpy as np

from ..sched.results import (
    PASSED_FILTER_MESSAGE,
    SUCCESS_MESSAGE,
    PodSchedulingResult,
    record_bind_points,
)
from ..sched.extender import ExtenderError, ExtenderService
from ..utils import broker as broker_mod
from . import kernels as K
from .engine import BatchedScheduler, victims_by_node_index
from .encode import EncodedCluster


class ExtenderScheduler:
    """Sequential scheduler with extender callbacks (one compiled segment
    pair, reused across all pods)."""

    def __init__(
        self,
        enc: EncodedCluster,
        service: ExtenderService,
        *,
        strict: bool = True,
    ):
        import jax

        self.enc = enc
        self.service = service
        self.sched = BatchedScheduler(enc, record=True, strict=strict)
        # preemption segments (DefaultPreemption enabled): the dry-run
        # kernel and the batched eviction, jitted once like
        # attempt_fn/bind_fn
        if self.sched._preempt is not None:
            aud = self.sched.audit_spec()
            self.preempt_fn = broker_mod.jit(
                lambda arrays, state, p: self.sched._preempt(arrays, state, p),
                audit={**aud, "label": "ext.preempt"},
            )
            self.evict_fn = broker_mod.jit(
                lambda arrays, state, mask: self.sched._evict_all(
                    state, arrays, mask
                ),
                audit={**aud, "label": "ext.evict"},
            )
        else:
            self.preempt_fn = None
            self.evict_fn = None
        self._results: "list[PodSchedulingResult] | None" = None
        self.final_state = None

    def retarget(self, enc: EncodedCluster, service: ExtenderService):
        """Reuse the compiled segments for a compile-compatible encoding
        (see BatchedScheduler.retarget); the extender service is swapped
        too — a config restart replaces it even at equal fingerprint."""
        self.sched.retarget(enc)
        self.enc = enc
        self.service = service
        self._results = None
        self.final_state = None
        return self

    # -- extender interplay -------------------------------------------------

    def _extender_touches(self, pod: dict) -> bool:
        """True when any configured extender would see this pod on the
        schedule path (filter/prioritize/bind verbs; the preempt verb
        only runs on the preemption path, which is split regardless).
        Pods NO extender touches take the fused single-dispatch step
        (`attempt_bind_fn`) — control never needs to return to the host
        between select and bind, so the split segment pair would be
        pure dispatch overhead."""
        return any(
            (ext.filter_verb or ext.prioritize_verb or ext.bind_verb)
            and ext.is_interested(pod)
            for ext in self.service.extenders
        )

    def _extender_args(self, pod: dict, ext, node_names: list[str]) -> dict:
        if ext.node_cache_capable:
            return {"Pod": pod, "NodeNames": node_names}
        nodes = {
            (n.get("metadata", {}) or {}).get("name"): n
            for n in self.enc.objects.get("nodes", [])
        }
        return {
            "Pod": pod,
            "Nodes": {"items": [nodes[n] for n in node_names if n in nodes]},
        }

    def _apply_extenders(self, pod: dict, feasible: list[int], totals):
        """Filter then prioritize through every interested extender;
        results (incl. FailedNodes) are recorded by `service.handle` into
        the 4 extender annotations — the reference keeps extender verdicts
        out of the 13 framework annotations too. Returns the surviving
        node indices and updated totals."""
        enc = self.enc
        name_to_idx = {enc.node_names[n]: n for n in feasible}
        for i, ext in enumerate(self.service.extenders):
            if not ext.is_interested(pod):
                continue
            surviving = [enc.node_names[n] for n in feasible]
            if ext.filter_verb:
                try:
                    out = self.service.handle(
                        "filter", i, self._extender_args(pod, ext, surviving)
                    )
                except ExtenderError:
                    if ext.ignorable:
                        continue
                    raise
                if out.get("Error"):
                    if ext.ignorable:
                        continue
                    raise ExtenderError(out["Error"])
                if ext.node_cache_capable:
                    kept = out.get("NodeNames")
                    kept = surviving if kept is None else list(kept)
                else:
                    items = (out.get("Nodes") or {}).get("items")
                    kept = (
                        surviving
                        if items is None
                        else [
                            (n.get("metadata", {}) or {}).get("name")
                            for n in items
                        ]
                    )
                feasible = [name_to_idx[n] for n in kept if n in name_to_idx]
            if ext.prioritize_verb and feasible:
                surviving = [enc.node_names[n] for n in feasible]
                try:
                    hosts = self.service.handle(
                        "prioritize", i, self._extender_args(pod, ext, surviving)
                    )
                except ExtenderError:
                    if ext.ignorable:
                        continue
                    raise
                for h in hosts:
                    n = name_to_idx.get(h.get("Host"))
                    if n is not None:
                        totals[n] += int(h.get("Score", 0))
        return feasible, totals

    def _delegated_bind(self, pod: dict, node_name: str) -> bool:
        """Call the first interested bind-verb extender; False = no
        delegation (local bind), raise on extender-reported error."""
        for i, ext in enumerate(self.service.extenders):
            if ext.bind_verb and ext.is_interested(pod):
                meta = pod.get("metadata", {}) or {}
                out = self.service.handle(
                    "bind",
                    i,
                    {
                        "PodName": meta.get("name", ""),
                        "PodNamespace": meta.get("namespace", "default"),
                        "PodUID": meta.get("uid", ""),
                        "Node": node_name,
                    },
                )
                if out and out.get("Error"):
                    raise ExtenderError(out["Error"])
                return True
        return False

    # -- preemption interplay ----------------------------------------------

    def _dry_run(self, res, state, p):
        """Run the dry-run kernel for pod p, record the per-node
        DefaultPreemption messages into `res` via the engine's shared
        trace-decode helpers (one definition of message format and
        reprieve order), and return (nominated node idx,
        {candidate node idx: ordered victim pod indices}, per-node
        codes)."""
        import jax.numpy as jnp

        pcode, slots, nominated = self.preempt_fn(
            self.enc.arrays, state, jnp.int32(p)
        )
        pcode = np.asarray(pcode)
        slots = np.asarray(slots)
        self.sched._fill_postfilter(res, pcode, slots)
        victims = victims_by_node_index(slots, self.enc.n_nodes)
        return int(np.asarray(nominated)), victims, pcode

    def _victim_uid(self, v: int) -> str:
        """The meta-victim identifier: the pod's UID, or ns/name for
        manifests without one (mapped back symmetrically)."""
        meta = (self.enc.pods[v].get("metadata", {}) or {})
        return meta.get("uid") or f"{self.enc.pod_keys[v][0]}/{self.enc.pod_keys[v][1]}"

    def _process_preemption_with_extenders(
        self, pod: dict, candidates: "dict[int, list[int]]"
    ) -> "dict[int, list[int]] | None":
        """upstream processPreemptionWithExtenders: every preempt-verb,
        interested extender sees the candidate victim map
        (ExtenderPreemptionArgs) and returns the trimmed surviving map
        (ExtenderPreemptionResult.NodeNameToMetaVictims, victims keyed by
        UID). Extenders chain — each sees the previous one's survivors.
        Returns None when an ignorable extender failed (skip) collapses
        to nothing or a veto empties the map."""
        enc = self.enc
        surviving = dict(candidates)
        uid_to_idx = {
            self._victim_uid(v): v for vs in candidates.values() for v in vs
        }
        for i, ext in enumerate(self.service.extenders):
            if not ext.preempt_verb or not ext.is_interested(pod):
                continue
            if ext.node_cache_capable:
                wire = {
                    "Pod": pod,
                    "NodeNameToMetaVictims": {
                        enc.node_names[n]: {
                            "Pods": [{"UID": self._victim_uid(v)} for v in vs],
                            "NumPDBViolations": 0,
                        }
                        for n, vs in surviving.items()
                    },
                }
            else:
                wire = {
                    "Pod": pod,
                    "NodeNameToVictims": {
                        enc.node_names[n]: {
                            "Pods": [enc.pods[v] for v in vs],
                            "NumPDBViolations": 0,
                        }
                        for n, vs in surviving.items()
                    },
                }
            try:
                out = self.service.handle("preempt", i, wire)
            except ExtenderError:
                if ext.ignorable:
                    continue
                raise
            name_to_idx = {enc.node_names[n]: n for n in surviving}
            meta = (out or {}).get("NodeNameToMetaVictims")
            if meta is None:
                continue  # extender expressed no opinion
            trimmed: dict[int, list[int]] = {}
            for node_name, vict in meta.items():
                n = name_to_idx.get(node_name)
                if n is None:
                    continue
                vs = [
                    uid_to_idx[m.get("UID")]
                    for m in (vict or {}).get("Pods") or []
                    if m.get("UID") in uid_to_idx
                ]
                if vs:
                    trimmed[n] = vs
            surviving = trimmed
            if not surviving:
                return None
        return surviving

    def _try_preemption(self, pod, p, qi, res, state, results):
        """PostFilter for one unschedulable pod. Appends the Nominated and
        retry records on success and returns the post-bind state; returns
        None when preemption cannot help (res carries the dry-run
        messages; caller records Unschedulable)."""
        import jax.numpy as jnp

        enc = self.enc
        nom, victims_by_node, pcode = self._dry_run(res, state, p)
        if nom < 0:
            return None
        candidates = {
            n: victims_by_node[n]
            for n in range(enc.n_nodes)
            if int(pcode[n]) in (K.PREEMPT_CANDIDATE, K.PREEMPT_SELECTED)
            and victims_by_node[n]
        }
        try:
            surviving = self._process_preemption_with_extenders(pod, candidates)
        except ExtenderError:
            return None  # non-ignorable extender failure aborts preemption
        if not surviving:
            return None
        prio = np.asarray(enc.arrays.pod_priority)

        def rank(n):
            ps = [int(prio[v]) for v in surviving[n]]
            return (max(ps), sum(ps), len(ps), n)

        best = min(surviving, key=rank)
        victims = surviving[best]
        res.status = "Nominated"
        res.nominated_node = enc.node_names[best]
        res.preemption_victims = [
            f"{enc.pod_keys[v][0]}/{enc.pod_keys[v][1]}" for v in victims
        ]
        results.append(res)
        mask = np.zeros(enc.P, bool)
        mask[victims] = True
        state = self.evict_fn(enc.arrays, state, jnp.asarray(mask))
        # the retry cycle (oracle re-queues at the head; a second failure
        # is terminally Unschedulable, with its own dry-run messages)
        res2 = PodSchedulingResult(
            pod_namespace=res.pod_namespace, pod_name=res.pod_name
        )
        res2.pre_filter_status = dict(res.pre_filter_status)
        state, placed = self._attempt_once(pod, p, qi, res2, state)
        if not placed:
            nom2, _, _ = self._dry_run(res2, state, p)
            if nom2 >= 0:
                res2.nominated_node = enc.node_names[nom2]
            res2.status = "Unschedulable"
        results.append(res2)
        return state

    # -- the loop -----------------------------------------------------------

    def _decode_filters_scores(self, res, codes, raw, final):
        """Decode one attempt's per-node filter codes and (raw, final)
        score tables into `res` — the ONE definition of the filter/
        score record format shared by the split segment path and the
        fused single-dispatch path. Returns (feasible node indices,
        final scores as ndarray)."""
        enc = self.enc
        sched = self.sched
        codes = np.asarray(codes)
        raw = np.asarray(raw)
        final = np.asarray(final)
        feasible = []
        for n in range(enc.n_nodes):
            ok = True
            for j, fname in enumerate(sched._filter_names):
                c = int(codes[n, j])
                if c:
                    res.add_filter(
                        enc.node_names[n], fname,
                        K.FILTER_KERNELS[fname][1](c, enc, n),
                    )
                    ok = False
                    break
                res.add_filter(enc.node_names[n], fname, PASSED_FILTER_MESSAGE)
            if ok:
                feasible.append(n)
        if feasible:
            for pname in sched._prescore_names:
                res.pre_score[pname] = SUCCESS_MESSAGE
            for j, sname in enumerate(sched._score_specs_names):
                for n in feasible:
                    res.add_score(enc.node_names[n], sname, int(raw[n, j]))
                    res.add_final_score(
                        enc.node_names[n], sname, int(final[n, j])
                    )
        return feasible, final

    def _finish_fused(self, p, res, state, fused_out):
        """Decode the fused step's outputs (the no-extender-interest
        fast path; `run()` already dispatched `attempt_bind_fn` and
        handled the prefilter decode). The program's argmax select is
        the host rule exactly (highest weighted total, lowest node
        index on ties), and an unschedulable pod's bind is the
        engine's exact no-op — so the records and the state trajectory
        are byte-identical to the split attempt_fn/bind_fn path, at
        half the dispatches. Returns (state, placed): not placed hands
        the pod to the caller's preemption / Unschedulable path with
        the pre-step state untouched."""
        enc = self.enc
        sched = self.sched
        _, codes, raw, final, sel, _, new_state = fused_out
        self._decode_filters_scores(res, codes, raw, final)
        s = int(np.asarray(sel))
        if s < 0:
            return state, False
        res.selected_node = enc.node_names[s]
        res.status = "Scheduled"
        permit = (
            {n_: h(p, s) for n_, h in sched._permit_handlers.items()}
            if sched._permit_handlers
            else None
        )
        record_bind_points(enc.config, res, permit=permit)
        return new_state, True

    def _attempt_once(self, pod, p, qi, res, state, attempt_out=None):
        """One full framework+extender cycle for pod p against `state`:
        attempt segment → decode filters/scores into `res` → extender
        filter/prioritize → select → permit/bind records → (delegated)
        bind. Returns (state, placed). `attempt_out`: the caller's
        already-computed `attempt_fn` output for (state, p) — the main
        loop runs the segment once for the prefilter decode and hands it
        down; the preemption retry recomputes against the evicted state."""
        import jax.numpy as jnp

        enc = self.enc
        sched = self.sched
        arrays = enc.arrays
        weights = sched.weights
        if attempt_out is None:
            attempt_out = sched.attempt_fn(arrays, state, weights, jnp.int32(p))
        _, codes, raw, final, sel, _ = attempt_out
        feasible, final = self._decode_filters_scores(res, codes, raw, final)
        totals = {n: int(final[n].sum()) for n in feasible}
        feasible, totals = self._apply_extenders(pod, feasible, totals)
        if not feasible:
            return state, False
        best = min(feasible, key=lambda n: (-totals[n], n))
        res.selected_node = enc.node_names[best]
        res.status = "Scheduled"
        # custom permit kernels record the same wait/timeout verdicts
        # here as on the batch path (engine._fill_attempt)
        permit = (
            {
                n_: h(p, best)
                for n_, h in self.sched._permit_handlers.items()
            }
            if self.sched._permit_handlers
            else None
        )
        record_bind_points(enc.config, res, permit=permit)
        try:
            delegated = self._delegated_bind(pod, enc.node_names[best])
        except ExtenderError as e:
            res.status = "Unschedulable"
            res.bind["ExtenderBinder"] = str(e)
            return state, False
        if delegated:
            res.bind["ExtenderBinder"] = SUCCESS_MESSAGE
        state = sched.bind_fn(
            arrays, state, jnp.int32(p), jnp.int32(best), jnp.int32(qi)
        )
        return state, True

    def run(self) -> list[PodSchedulingResult]:
        enc = self.enc
        sched = self.sched
        import jax.numpy as jnp

        state = enc.state0
        arrays = enc.arrays
        weights = sched.weights
        results = []
        for qi, p in enumerate(np.asarray(enc.queue)):  # PrioritySort order
            p = int(p)
            pod = enc.pods[p]
            ns, name = enc.pod_keys[p]
            res = PodSchedulingResult(pod_namespace=ns, pod_name=name)
            # pods no extender touches take the FUSED single-dispatch
            # step (attempt+select+bind in one program); pods with
            # extender interplay keep the split segments, because
            # control must return to the host between Filter/Score and
            # the bind (the HTTP verbs run in between)
            fused = not self._extender_touches(pod)
            if fused:
                fused_out = sched.attempt_bind_fn(
                    arrays, state, weights, jnp.int32(p), jnp.int32(qi)
                )
                pf_codes = fused_out[0]
            else:
                attempt_out = sched.attempt_fn(
                    arrays, state, weights, jnp.int32(p)
                )
                pf_codes = attempt_out[0]
            pf_failed = False
            for pname in sched._prefilter_names:
                if pname in K.PREFILTER_KERNELS:
                    k = sched._prefilter_kernel_names.index(pname)
                    c = int(np.asarray(pf_codes)[k])
                else:
                    c = 0
                res.pre_filter_status[pname] = (
                    K.PREFILTER_KERNELS[pname][1](c, enc) if c else SUCCESS_MESSAGE
                )
                pf_failed = pf_failed or bool(c)
            if pf_failed:
                # the fused step's bind was an exact no-op (a prefilter
                # failure empties the feasible set, so sel == -1):
                # `state` stays the pre-step value on both paths
                res.status = "Unschedulable"
                results.append(res)
                continue
            if fused:
                state, placed = self._finish_fused(p, res, state, fused_out)
            else:
                state, placed = self._attempt_once(
                    pod, p, qi, res, state, attempt_out=attempt_out
                )
            if placed or res.bind.get("ExtenderBinder"):
                # scheduled, or a delegated bind failed terminally (the
                # bind error is this pod's record; no preemption retry)
                results.append(res)
                continue
            if self.preempt_fn is not None:
                new_state = self._try_preemption(pod, p, qi, res, state, results)
                if new_state is not None:
                    state = new_state
                    continue
            res.status = "Unschedulable"
            results.append(res)
        self.final_state = state
        self._results = results
        return results

    def placements(self) -> dict[tuple[str, str], str]:
        if self._results is None:
            self.run()
        assign = np.asarray(self.final_state.assignment)
        out = {}
        for qi in self.enc.queue:
            sel = int(assign[qi])
            out[self.enc.pod_keys[qi]] = (
                self.enc.node_names[sel] if sel >= 0 else ""
            )
        return out
