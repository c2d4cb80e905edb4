"""The gang round's top-k matching (engine/gang.py `match_candidates`)
carries a per-row candidate cursor instead of re-reading `taken` at every
[K, k] candidate each iteration. It must place exactly as the gather
formulation did: checked here against a plain numpy transliteration of
that matching on seeded random candidate lists, in both inner-loop modes
and under `jax.vmap`, and on a served pass in top-k mode, where the
gather step (kept below) is the reference and `gangMatchIters` counts
the iterations."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kube_scheduler_simulator_tpu.engine import encode_cluster
from kube_scheduler_simulator_tpu.engine import gang as gang_mod
from kube_scheduler_simulator_tpu.engine.gang import GangScheduler, match_candidates
from kube_scheduler_simulator_tpu.server.service import SimulatorService

from helpers import node, pod

NEG = int(jnp.iinfo(jnp.int32).min // 2)
NO_ORDER = int(jnp.iinfo(jnp.int32).max)


def reference_match(vals, idx, order, claims, c_min, n_nodes, inner_iters):
    """The truncated proposal matching, one iteration at a time: each row
    proposes its best untaken candidate, the earliest queue position wins
    each node, then each claim, and nothing commits after an iteration
    that committed nothing or after `inner_iters` iterations."""
    K, k = vals.shape
    rows = np.arange(K)
    taken = np.zeros(n_nodes, bool)
    claim_taken = np.zeros(claims.shape[1], bool)
    sel = np.full(K, -1, np.int64)
    iters = 0
    while iters < inner_iters:
        iters += 1
        m = np.where(taken[idx], NEG, vals)
        m[sel >= 0] = NEG
        m[(claims & claim_taken[None, :]).any(axis=1)] = NEG
        if c_min is not None:
            m[order >= c_min] = NEG
        col = m.argmax(axis=1)
        has = m[rows, col] > NEG
        cand = idx[rows, col]
        winner = np.full(n_nodes + 1, NO_ORDER, np.int64)
        np.minimum.at(winner, np.where(has, cand, n_nodes), order)
        commit = has & (winner[cand] == order)
        claim_min = np.where(commit[:, None] & claims, order[:, None], NO_ORDER).min(axis=0)
        commit &= np.where(claims, claim_min[None, :] == order[:, None], True).all(axis=1)
        sel[commit] = cand[commit]
        taken[cand[commit]] = True
        claim_taken |= (claims & commit[:, None]).any(axis=0)
        if not commit.any():
            break
    return sel, iters


def gather_match_candidates(
    vals, idx, order_v, pod_claim_v, c_min, *, n_nodes, inner_iters, inner_static, neg,
):
    """The matching before the cursor: every iteration reads `taken` at all
    [K, k] candidates and argmaxes each row (dynamic inner loop)."""
    assert not inner_static
    N = n_nodes
    K = vals.shape[0]

    def step(c):
        taken, claim_taken, sel_acc, _, it = c
        m = jnp.where(taken[idx], neg, vals)
        m = jnp.where((sel_acc >= 0)[:, None], neg, m)
        m = jnp.where((pod_claim_v & claim_taken[None, :]).any(axis=1)[:, None], neg, m)
        if c_min is not None:
            m = jnp.where((order_v >= c_min)[:, None], neg, m)
        col = jnp.argmax(m, axis=1).astype(jnp.int32)
        has = jnp.take_along_axis(m, col[:, None], axis=1)[:, 0] > neg
        cand = jnp.take_along_axis(idx, col[:, None], axis=1)[:, 0]
        winner = jnp.full((N + 1,), NO_ORDER, jnp.int32).at[jnp.where(has, cand, N)].min(order_v)
        commit = has & (winner[jnp.maximum(cand, 0)] == order_v)
        claim_min = jnp.where(commit[:, None] & pod_claim_v, order_v[:, None], NO_ORDER).min(axis=0)
        commit = commit & jnp.where(
            pod_claim_v, claim_min[None, :] == order_v[:, None], True
        ).all(axis=1)
        sel_acc = jnp.where(commit, cand, sel_acc)
        taken = taken | jnp.zeros((N + 1,), bool).at[jnp.where(commit, cand, N)].set(True)[:N]
        claim_taken = claim_taken | (pod_claim_v & commit[:, None]).any(axis=0)
        return taken, claim_taken, sel_acc, commit.any(), it + 1

    out = jax.lax.while_loop(
        lambda c: c[3] & (c[4] < inner_iters),
        step,
        (jnp.zeros((N,), bool), jnp.zeros(pod_claim_v.shape[1:], bool),
         jnp.full((K,), -1, jnp.int32), jnp.bool_(True), jnp.int32(0)),
    )
    return out[2], out[4]


def _candidates(scores, k):
    """`lax.top_k`'s rows in numpy: scores descending, ties to the lower
    node index."""
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, idx, axis=1), idx


def _inputs(case, seed):
    """(vals, idx, order, claims, c_min, n_nodes, inner_iters) for one case."""
    rng = np.random.default_rng(seed)
    K, N, k, C, iters = 24, 40, 8, 3, 64
    scores = rng.integers(0, 4, size=(K, N)).astype(np.int64)  # many ties
    scores[rng.random((K, N)) < 0.3] = NEG  # infeasible nodes: NEG tails
    order = rng.permutation(3 * K)[:K]  # global queue positions, with gaps
    idle = rng.random(K) < 0.15  # rows not pending: all NEG
    claims = np.zeros((K, C), bool)
    c_min = None
    if case == "identical":
        # every row the same list: one commit per iteration
        scores[:] = 7
    elif case == "narrow":
        # fewer candidates than contenders: lists run out mid-round
        K, k = 24, 3
        scores[:, 6:] = NEG
    elif case == "claims":
        claims = rng.random((K, C)) < 0.25
    elif case == "carrier":
        c_min = int(np.sort(order)[K // 2])
    elif case == "truncated":
        # the iteration cap is reached mid-list
        scores[:] = rng.integers(5, 7, size=(K, N))
        iters = 5
    scores[idle] = NEG
    vals, idx = _candidates(scores, k)
    return vals, idx, order, claims, c_min, N, iters


CASES = ["random", "identical", "narrow", "claims", "carrier", "truncated"]


def _matcher(carrier, n_nodes, iters, static):
    def fn(v, i, o, c, cm):
        return match_candidates(
            v, i, o, c, cm if carrier else None,
            n_nodes=n_nodes, inner_iters=iters, inner_static=static, neg=NEG,
        )

    return fn


@pytest.mark.parametrize("mode", ["dynamic", "static", "vmap"])
@pytest.mark.parametrize("case", CASES)
def test_cursor_matching_equals_the_reference(case, mode):
    seeds = [11, 12, 13] if mode == "vmap" else [11]
    inputs = [_inputs(case, s) for s in seeds]
    _, _, _, _, c_min, n_nodes, iters = inputs[0]
    fn = _matcher(c_min is not None, n_nodes, iters, mode == "static")
    args = [
        jnp.asarray(np.stack([x[j] for x in inputs]), jnp.int32 if j < 3 else bool)
        for j in range(4)
    ] + [jnp.asarray([-1 if x[4] is None else x[4] for x in inputs], jnp.int32)]
    if mode == "vmap":
        got_sel, got_iters = jax.jit(jax.vmap(fn))(*args)
    else:
        got_sel, got_iters = jax.jit(fn)(*[a[0] for a in args])
        got_sel, got_iters = got_sel[None], got_iters[None]
    for b, (vals, idx, order, claims, c_min, n_nodes, iters) in enumerate(inputs):
        want_sel, want_iters = reference_match(vals, idx, order, claims, c_min, n_nodes, iters)
        np.testing.assert_array_equal(np.asarray(got_sel[b]), want_sel, err_msg=f"{case} {b}")
        assert int(got_iters[b]) == (iters if mode == "static" else want_iters)
    # the cases reach what they name
    if case == "identical" and mode == "dynamic":
        assert int((want_sel >= 0).sum()) == want_iters - 1
    if case == "truncated":
        assert want_iters == iters and (want_sel < 0).any()


def test_full_width_counts_no_iterations():
    vals, idx, order, claims, _, n_nodes, iters = _inputs("random", 5)
    scores = np.full((vals.shape[0], n_nodes), NEG, np.int64)
    np.put_along_axis(scores, idx, vals, axis=1)
    sel, n = jax.jit(
        lambda s, o, c: match_candidates(
            s, None, o, c, None, n_nodes=n_nodes, inner_iters=iters,
            inner_static=False, neg=NEG,
        )
    )(jnp.asarray(scores, jnp.int32), jnp.asarray(order, jnp.int32), jnp.asarray(claims))
    want, _ = reference_match(
        scores, np.tile(np.arange(n_nodes), (len(order), 1)), order, claims, None, n_nodes, iters
    )
    np.testing.assert_array_equal(np.asarray(sel), want)
    assert int(n) == 0


def _served(n_nodes, n_pods):
    svc = SimulatorService()
    for i in range(n_nodes):
        svc.store.apply("nodes", node(f"n{i:04d}"))
    for i in range(n_pods):
        svc.store.apply("pods", pod(f"p{i:04d}"))
    return svc


def _match_iters(svc):
    return svc.scheduler.metrics.snapshot()["phases"]["gangMatchIters"]


def test_served_topk_pass_places_as_the_gather_step(monkeypatch):
    # 600 identical nodes (N bucket 1024: top-k mode, k = 128) and 100
    # identical pods: every row holds the same list, so each iteration
    # commits one pod. Round 1 commits 64 (the inner_iters cap), round 2
    # the other 36 and runs one empty iteration, the closing round one
    # more: 102
    svc = _served(600, 100)
    svc.scheduler.schedule_gang(record=False)
    placed = {p["metadata"]["name"]: p["spec"].get("nodeName") for p in svc.store.list("pods")}
    assert all(placed.values()) and len(set(placed.values())) == 100
    assert _match_iters(svc) == 102
    # the same cluster at the served buckets, the gather step in the
    # matching's place
    enc = encode_cluster(
        [node(f"n{i:04d}") for i in range(600)], [pod(f"p{i:04d}") for i in range(100)],
        svc.scheduler.config, node_capacity=1024, pod_capacity=128,
    )
    new = GangScheduler(enc)
    assert new.match_width == 128
    new.run()
    monkeypatch.setattr(gang_mod, "match_candidates", gather_match_candidates)
    old = GangScheduler(enc)
    old.run()
    assert int(new._match_iters) == int(old._match_iters) == 102
    assert new.placements() == old.placements()
    assert {n: v for (_, n), v in new.placements().items()} == placed


def test_served_full_width_pass_counts_no_iterations():
    svc = _served(40, 30)
    svc.scheduler.schedule_gang(record=False)
    assert all(p["spec"].get("nodeName") for p in svc.store.list("pods"))
    assert _match_iters(svc) == 0
