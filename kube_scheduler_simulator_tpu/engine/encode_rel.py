"""Pod-relational encodings: label-selector clause tensors + topology pairs.

PodTopologySpread and InterPodAffinity aggregate over the set of *currently
bound* pods, which changes at every scan step. The reference recomputes
these aggregations in PreFilter/PreScore per pod over object graphs
(oracle: spread_pre_filter / interpod_pre_filter); the TPU engine instead
compiles every label selector into fixed clause tensors at encode time and
evaluates them per step against static pod-label bitsets, reducing the
counts by scatter-adds keyed on `state.assignment` — no P×P matrix is ever
materialized.

Selector → clauses (oracle match_label_selector semantics):
  * matchLabels k=v and In(k, vs)  → PAIR_ANY over the (k,v) pair ids
  * NotIn(k, vs)                   → no pair hit (an absent key MATCHES,
                                     upstream labels.Requirement)
  * Exists(k) / DoesNotExist(k)    → key-presence bit
  * nil selector                   → NEVER (matches nothing)
  * empty selector                 → zero clauses (matches everything)
"""

from __future__ import annotations

import chex
import jax.numpy as jnp
import numpy as np

from ..models.vocab import Vocab
from ..sched.oracle_plugins import spread_log_weight
from ..utils.compilecache import shape_bucket
from .packing import put_field

PAIR_ANY, NOTIN, EXISTS, DNE, NEVER = 0, 1, 2, 3, 4
CL_PAD = -1


@chex.dataclass
class PodRelArrays:
    """Pod-relational device arrays (nested in ClusterArrays.rel)."""

    # pod label bitsets
    pair_present: jnp.ndarray  # [P, LP] bool — pod has (key,value) pair
    key_present: jnp.ndarray  # [P, KK] bool — pod has label key
    ns_id: jnp.ndarray  # [P] int32 namespace id
    deleted: jnp.ndarray  # [P] bool — metadata.deletionTimestamp set
    # node topology pairs: id+1 into the node-pair vocab (0 = key absent)
    node_pair: jnp.ndarray  # [N, K] int32
    # PodTopologySpread hard (DoNotSchedule) constraints
    sph_key: jnp.ndarray  # [P, HC] int32 node-label key col | -1 pad
    sph_skew: jnp.ndarray  # [P, HC] int32 maxSkew
    sph_self: jnp.ndarray  # [P, HC] bool — selector matches the pod itself
    sph_ctype: jnp.ndarray  # [P, HC, C] int32 clause type | CL_PAD
    sph_ckey: jnp.ndarray  # [P, HC, C] int32 pod-label key id | -1
    sph_cpairs: jnp.ndarray  # [P, HC, C, VP] int32 pod-label pair id | -1
    # PodTopologySpread soft (ScheduleAnyway) constraints
    sps_key: jnp.ndarray  # [P, SC]
    sps_skew: jnp.ndarray  # [P, SC]
    sps_host: jnp.ndarray  # [P, SC] bool — topologyKey == kubernetes.io/hostname
    sps_ctype: jnp.ndarray  # [P, SC, C]
    sps_ckey: jnp.ndarray  # [P, SC, C]
    sps_cpairs: jnp.ndarray  # [P, SC, C, VP]
    req_all: jnp.ndarray  # [P] bool — pod has explicit constraints
    spread_lut: jnp.ndarray  # [N+2] int32 fixed-point log weights
    # InterPodAffinity term domains. Each domain d has: d_key [P, T] (node
    # label key col | -1), d_ctype/d_ckey [P, T, C], d_cpairs [P, T, C, VP],
    # d_nsall [P, T] bool, d_ns [P, T, NSV] bool. The same tensors serve
    # both directions (incoming pod's terms vs all pods, and all pods'
    # terms vs the incoming pod).
    ia_key: jnp.ndarray  # required affinity
    ia_ctype: jnp.ndarray
    ia_ckey: jnp.ndarray
    ia_cpairs: jnp.ndarray
    ia_nsall: jnp.ndarray
    ia_ns: jnp.ndarray
    ia_self: jnp.ndarray  # [P, T] bool — term matches its own pod
    ian_key: jnp.ndarray  # required anti-affinity
    ian_ctype: jnp.ndarray
    ian_ckey: jnp.ndarray
    ian_cpairs: jnp.ndarray
    ian_nsall: jnp.ndarray
    ian_ns: jnp.ndarray
    ipa_key: jnp.ndarray  # preferred affinity
    ipa_ctype: jnp.ndarray
    ipa_ckey: jnp.ndarray
    ipa_cpairs: jnp.ndarray
    ipa_nsall: jnp.ndarray
    ipa_ns: jnp.ndarray
    ipa_weight: jnp.ndarray  # [P, T] int32
    ipan_key: jnp.ndarray  # preferred anti-affinity
    ipan_ctype: jnp.ndarray
    ipan_ckey: jnp.ndarray
    ipan_cpairs: jnp.ndarray
    ipan_nsall: jnp.ndarray
    ipan_ns: jnp.ndarray
    ipan_weight: jnp.ndarray  # [P, T] int32


# Width class per PodRelArrays field (kss-lint KSS716; classes as in
# engine/encode.py WIDTH_CLASSES — field names are unique across the two
# dataclasses, so the delta encoder and unpacker use one flat namespace).
REL_WIDTH_CLASSES: "dict[str, str]" = {
    "pair_present": "mask",
    "key_present": "mask",
    "ns_id": "id",
    "deleted": "mask",
    "node_pair": "id",
    "sph_key": "id",
    "sph_skew": "count",
    "sph_self": "mask",
    "sph_ctype": "id",
    "sph_ckey": "id",
    "sph_cpairs": "id",
    "sps_key": "id",
    "sps_skew": "count",
    "sps_host": "mask",
    "sps_ctype": "id",
    "sps_ckey": "id",
    "sps_cpairs": "id",
    "req_all": "mask",
    "spread_lut": "exact",  # fixed-point log weights, full int32 range
    "ia_key": "id",
    "ia_ctype": "id",
    "ia_ckey": "id",
    "ia_cpairs": "id",
    "ia_nsall": "mask",
    "ia_ns": "mask",
    "ia_self": "mask",
    "ian_key": "id",
    "ian_ctype": "id",
    "ian_ckey": "id",
    "ian_cpairs": "id",
    "ian_nsall": "mask",
    "ian_ns": "mask",
    "ipa_key": "id",
    "ipa_ctype": "id",
    "ipa_ckey": "id",
    "ipa_cpairs": "id",
    "ipa_nsall": "mask",
    "ipa_ns": "mask",
    "ipa_weight": "count",
    "ipan_key": "id",
    "ipan_ctype": "id",
    "ipan_ckey": "id",
    "ipan_cpairs": "id",
    "ipan_nsall": "mask",
    "ipan_ns": "mask",
    "ipan_weight": "count",
}

# clause-type ids are the tiny closed enum above (PAIR_ANY..NEVER, CL_PAD)
REL_ENUM8 = frozenset(
    {"sph_ctype", "sps_ctype", "ia_ctype", "ian_ctype", "ipa_ctype", "ipan_ctype"}
)


class _ClauseBuilder:
    """Compiles label selectors against shared pod-label vocabularies."""

    def __init__(self):
        self.pair_vocab = Vocab()  # "key\x00value"
        self.key_vocab = Vocab()

    def pair_id(self, k: str, v: str) -> int:
        return self.pair_vocab.intern(f"{k}\x00{v}")

    def compile(self, selector: "dict | None") -> "list[tuple[int, int, list[int]]]":
        """selector -> [(ctype, key_id, pair_ids)]"""
        if selector is None:
            return [(NEVER, -1, [])]
        clauses = []
        for k, v in (selector.get("matchLabels") or {}).items():
            clauses.append((PAIR_ANY, self.key_vocab.intern(k), [self.pair_id(k, str(v))]))
        for req in selector.get("matchExpressions") or []:
            k = req.get("key") or ""
            op = req.get("operator") or ""
            vals = [str(x) for x in (req.get("values") or [])]
            kid = self.key_vocab.intern(k)
            if op == "In":
                clauses.append((PAIR_ANY, kid, [self.pair_id(k, v) for v in vals]))
            elif op == "NotIn":
                clauses.append((NOTIN, kid, [self.pair_id(k, v) for v in vals]))
            elif op == "Exists":
                clauses.append((EXISTS, kid, []))
            elif op == "DoesNotExist":
                clauses.append((DNE, kid, []))
            else:
                # Gt/Lt or unknown in a metav1.LabelSelector: matches nothing
                # (oracle _match_expression with allow_numeric=False)
                clauses.append((NEVER, -1, []))
        return clauses


def _fill_clauses(slots, builder_dims, P):
    """Pack per-(pod, term) clause lists into dense arrays."""
    TC, C, VP = builder_dims
    ctype = np.full((P, TC, C), CL_PAD, np.int32)
    ckey = np.full((P, TC, C), -1, np.int32)
    cpairs = np.full((P, TC, C, VP), -1, np.int32)
    for p, terms in enumerate(slots):
        for t, clauses in enumerate(terms):
            for c, (ct, k, pairs) in enumerate(clauses):
                ctype[p, t, c] = ct
                ckey[p, t, c] = k
                for vi, pid in enumerate(pairs):
                    cpairs[p, t, c, vi] = pid
    return ctype, ckey, cpairs


def parse_pod_spread(pv, constraint_triple, label_keys, cb):
    """ONE pod's resolved spread constraints → the (hard_terms,
    soft_terms, explicit) triple `_pack_spread` packs. `label_keys` and
    `cb` are anything vocab-shaped (.intern / .pair_id+.key_vocab) — the
    full encode passes the live vocabularies, the delta encoder passes
    no-grow guards. Shared so the two fills can never drift."""
    from ..models.objects import match_label_selector

    hard, soft, explicit = constraint_triple
    hard_terms = [
        (
            label_keys.intern(c["topologyKey"]),
            int(c.get("maxSkew", 1)),
            match_label_selector(c.get("labelSelector"), pv.labels),
            _ClauseBuilder.compile(cb, c.get("labelSelector")),
            False,
        )
        for c in hard
    ]
    soft_terms = [
        (
            label_keys.intern(c["topologyKey"]),
            int(c.get("maxSkew", 1)),
            False,
            _ClauseBuilder.compile(cb, c.get("labelSelector")),
            c["topologyKey"] == "kubernetes.io/hostname",
        )
        for c in soft
    ]
    return hard_terms, soft_terms, explicit


def _pack_spread(all_terms, n, TC, C, VP):
    """Dense spread-constraint rows for `n` pods at FIXED dims (the full
    encode computes the dims as content maxima; the delta path reuses the
    retained arrays' shapes)."""
    key = np.full((n, TC), -1, np.int32)
    skew = np.ones((n, TC), np.int32)
    selfm = np.zeros((n, TC), bool)
    host = np.zeros((n, TC), bool)
    for p, terms in enumerate(all_terms):
        for t, (k, ms, sm, _cl, hh) in enumerate(terms):
            key[p, t] = k
            skew[p, t] = ms
            selfm[p, t] = sm
            host[p, t] = hh
    ctype, ckey, cpairs = _fill_clauses(
        [[cl for (_, _, _, cl, _) in t] for t in all_terms], (TC, C, VP), n
    )
    return key, skew, selfm, host, ctype, ckey, cpairs


def _pack_ia(parsed, n, T, C, VP, NSV):
    """Dense InterPodAffinity term rows for `n` pods at FIXED dims."""
    key = np.full((n, T), -1, np.int32)
    nsall = np.zeros((n, T), bool)
    nsmh = np.zeros((n, T, NSV), bool)
    weight = np.zeros((n, T), np.int32)
    selfm = np.zeros((n, T), bool)
    for p, terms in enumerate(parsed):
        for t, term in enumerate(terms):
            key[p, t] = term["kcol"]
            nsall[p, t] = term["nsall"]
            for nid in term["nsids"]:
                nsmh[p, t, nid] = True
            weight[p, t] = term.get("weight", 0)
            selfm[p, t] = term.get("selfm", False)
    ctype, ckey, cpairs = _fill_clauses(
        [[t["clauses"] for t in x] for x in parsed], (T, C, VP), n
    )
    return key, ctype, ckey, cpairs, nsall, nsmh, weight, selfm


def encode_pod_relations(
    node_views,
    pod_views,
    N: int,
    P: int,
    *,
    label_keys: Vocab,
    constraints,
    namespaces: "list[dict] | None" = None,
    policy=None,
) -> tuple[PodRelArrays, dict]:
    """Build PodRelArrays.

    `label_keys` is the node-label key vocab from the affinity encoder
    (topology keys are pre-interned there, so they index the same
    label_val columns). `constraints[i] = (hard, soft, explicit)` is each
    pod's resolved spread-constraint split (oracle _spread_constraints
    semantics).
    """
    from types import SimpleNamespace

    from ..models.objects import match_label_selector
    from ..sched.oracle_plugins import (
        _namespaces_for_term,
        _preferred_terms,
        _required_terms,
        _term_matches_pod,
    )

    cb = _ClauseBuilder()
    ns_vocab = Vocab()
    ns_objs = {
        (ns.get("metadata", {}) or {}).get("name", ""): ns for ns in namespaces or []
    }
    # the shape _namespaces_for_term expects (oracle ClusterSnapshot)
    fake_snapshot = SimpleNamespace(namespaces=ns_objs)

    # -- per-pod spread constraints, compiled --------------------------------
    hard_all, soft_all = [], []
    req_all = np.zeros(P, bool)
    for i, pv in enumerate(pod_views):
        hard_terms, soft_terms, explicit = parse_pod_spread(
            pv, constraints[i], label_keys, cb
        )
        req_all[i] = explicit
        hard_all.append(hard_terms)
        soft_all.append(soft_terms)

    # -- InterPodAffinity terms, parsed (oracle interpod_pre_filter /
    # interpod_pre_score term handling; _term_matches_pod semantics) --------
    def parse_term(term, owner_ns):
        key = term.get("topologyKey", "")
        kcol = label_keys.get(key)  # pre-interned via encode.py topo_keys
        ns_set = _namespaces_for_term(term, owner_ns, fake_snapshot)
        return {
            "kcol": kcol,
            "clauses": cb.compile(term.get("labelSelector")),
            "nsall": ns_set is None,
            "nsids": [ns_vocab.intern(n) for n in (ns_set or [])],
        }

    ia_parsed, ian_parsed, ipa_parsed, ipan_parsed = [], [], [], []
    for pv in pod_views:
        ia_parsed.append(
            [
                dict(
                    parse_term(t, pv.namespace),
                    selfm=_term_matches_pod(t, pv.namespace, pv, fake_snapshot),
                )
                for t in _required_terms(pv.pod_affinity)
            ]
        )
        ian_parsed.append(
            [parse_term(t, pv.namespace) for t in _required_terms(pv.pod_anti_affinity)]
        )
        ipa_parsed.append(
            [
                dict(
                    parse_term(pr.get("podAffinityTerm") or {}, pv.namespace),
                    weight=int(pr.get("weight", 0)),
                )
                for pr in _preferred_terms(pv.pod_affinity)
            ]
        )
        ipan_parsed.append(
            [
                dict(
                    parse_term(pr.get("podAffinityTerm") or {}, pv.namespace),
                    weight=int(pr.get("weight", 0)),
                )
                for pr in _preferred_terms(pv.pod_anti_affinity)
            ]
        )

    # -- pod label bitsets (vocabs now final for pods' own labels too) -------
    for pv in pod_views:
        for k, v in pv.labels.items():
            cb.key_vocab.intern(k)
            cb.pair_id(k, str(v))
        ns_vocab.intern(pv.namespace)
    LP = max(1, len(cb.pair_vocab))
    KK = max(1, len(cb.key_vocab))
    pair_present = np.zeros((P, LP), bool)
    key_present = np.zeros((P, KK), bool)
    ns_id = np.zeros(P, np.int32)
    deleted = np.zeros(P, bool)
    for i, pv in enumerate(pod_views):
        for k, v in pv.labels.items():
            key_present[i, cb.key_vocab.get(k)] = True
            pair_present[i, cb.pair_id(k, str(v))] = True
        ns_id[i] = ns_vocab.get(pv.namespace)
        deleted[i] = bool((pv.obj.get("metadata", {}) or {}).get("deletionTimestamp"))

    # -- node topology pairs -------------------------------------------------
    K = len(label_keys)
    node_pair_vocab = Vocab()
    node_pair = np.zeros((N, K), np.int32)  # 0 = absent
    for n, nv in enumerate(node_views):
        for k, v in nv.labels.items():
            col = label_keys.get(k)
            if col >= 0:
                node_pair[n, col] = node_pair_vocab.intern(f"{k}\x00{v}") + 1

    # -- pack constraint tensors ---------------------------------------------
    def spread_dims(all_terms):
        TC = max(1, max((len(t) for t in all_terms), default=0))
        C = max(
            1, max((len(cl) for t in all_terms for (_, _, _, cl, _) in t), default=0)
        )
        VP = max(
            1,
            max(
                (len(pr) for t in all_terms for (_, _, _, cl, _) in t for (_, _, pr) in cl),
                default=0,
            ),
        )
        return TC, C, VP

    def pack(all_terms):
        return _pack_spread(all_terms, P, *spread_dims(all_terms))

    hk, hs, hself, _, hct, hck, hcp = pack(hard_all)
    sk, ss_, _, shost, sct, sck, scp = pack(soft_all)

    # the namespace axis is bucketed like the pod and node axes: a store
    # whose pods leave and re-enter a namespace keeps one program
    NSV = max(1, shape_bucket(len(ns_vocab), lo=8))

    def pack_terms(parsed):
        T = max(1, max((len(x) for x in parsed), default=0))
        C = max(
            1, max((len(t["clauses"]) for x in parsed for t in x), default=0)
        )
        VP = max(
            1,
            max(
                (len(pr) for x in parsed for t in x for (_, _, pr) in t["clauses"]),
                default=0,
            ),
        )
        return _pack_ia(parsed, P, T, C, VP, NSV)

    iak, iact, iack, iacp, iana, ians_, _, iaself = pack_terms(ia_parsed)
    nk, nct, nck, ncp, nna, nns, _, _ = pack_terms(ian_parsed)
    pak, pact, pack_, pacp, pana, pans, paw, _ = pack_terms(ipa_parsed)
    qk, qct, qck, qcp, qna, qns, qw, _ = pack_terms(ipan_parsed)

    lut = np.asarray([spread_log_weight(m) for m in range(N + 2)], np.int32)

    rel_host = dict(
        pair_present=pair_present,
        key_present=key_present,
        ns_id=ns_id,
        deleted=deleted,
        node_pair=node_pair,
        sph_key=hk,
        sph_skew=hs,
        sph_self=hself,
        sph_ctype=hct,
        sph_ckey=hck,
        sph_cpairs=hcp,
        sps_key=sk,
        sps_skew=ss_,
        sps_host=shost,
        sps_ctype=sct,
        sps_ckey=sck,
        sps_cpairs=scp,
        req_all=req_all,
        spread_lut=lut,
        ia_key=iak,
        ia_ctype=iact,
        ia_ckey=iack,
        ia_cpairs=iacp,
        ia_nsall=iana,
        ia_ns=ians_,
        ia_self=iaself,
        ian_key=nk,
        ian_ctype=nct,
        ian_ckey=nck,
        ian_cpairs=ncp,
        ian_nsall=nna,
        ian_ns=nns,
        ipa_key=pak,
        ipa_ctype=pact,
        ipa_ckey=pack_,
        ipa_cpairs=pacp,
        ipa_nsall=pana,
        ipa_ns=pans,
        ipa_weight=paw,
        ipan_key=qk,
        ipan_ctype=qct,
        ipan_ckey=qck,
        ipan_cpairs=qcp,
        ipan_nsall=qna,
        ipan_ns=qns,
        ipan_weight=qw,
    )
    packed_dims: "dict[str, int]" = {}
    rel = PodRelArrays(
        **{
            k: put_field(
                k,
                v,
                REL_WIDTH_CLASSES[k],
                policy=policy,
                enum8=REL_ENUM8,
                packed_dims=packed_dims,
            )
            for k, v in rel_host.items()
        }
    )
    aux = {
        "n_node_pairs": len(node_pair_vocab),
        "clause_builder": cb,
        "ns_vocab": ns_vocab,
        "packed_dims": packed_dims,
    }
    return rel, aux


def _eval_clauses(t, pair_hit, key_hit) -> jnp.ndarray:
    """The selector-semantics decision table, shared by both matching
    directions. t/pair_hit/key_hit broadcast together; CL_PAD clauses are
    neutral for the enclosing AND."""
    m = jnp.where(
        t == PAIR_ANY, pair_hit,
        # upstream labels.Requirement: NotIn matches when the key is
        # absent too (no key bit -> no pair bit -> ~pair_hit is exact)
        jnp.where(t == NOTIN, ~pair_hit,
        jnp.where(t == EXISTS, key_hit,
        jnp.where(t == DNE, ~key_hit, False))))
    return m | (t == CL_PAD)


def match_clauses(rel: PodRelArrays, ctype, ckey, cpairs) -> jnp.ndarray:
    """Evaluate clause tensors for ONE pod's terms against EVERY pod.

    ctype/ckey: [T, C]; cpairs: [T, C, VP]. Returns match[T, P] (label part
    only — callers add namespace / mask / liveness conditions).
    """
    pp = rel.pair_present  # [P, LP]
    kp = rel.key_present  # [P, KK]
    pair_hit = (
        pp.T[jnp.maximum(cpairs, 0)] & (cpairs >= 0)[..., None]
    ).any(axis=-2)  # [T, C, P]
    key_hit = kp.T[jnp.maximum(ckey, 0)] & (ckey >= 0)[..., None]  # [T, C, P]
    return _eval_clauses(ctype[..., None], pair_hit, key_hit).all(axis=-2)  # [T, P]


def match_clauses_rev(rel: PodRelArrays, ctype, ckey, cpairs, b) -> jnp.ndarray:
    """Evaluate EVERY pod's term clauses against ONE pod `b` (the reverse
    direction: existing pods' affinity/anti-affinity terms vs the incoming
    pod). ctype/ckey: [P, T, C]; cpairs: [P, T, C, VP]. Returns [P, T]."""
    pp = rel.pair_present[b]  # [LP]
    kp = rel.key_present[b]  # [KK]
    pair_hit = (pp[jnp.maximum(cpairs, 0)] & (cpairs >= 0)).any(axis=-1)  # [P, T, C]
    key_hit = kp[jnp.maximum(ckey, 0)] & (ckey >= 0)
    return _eval_clauses(ctype, pair_hit, key_hit).all(axis=-1)  # [P, T]