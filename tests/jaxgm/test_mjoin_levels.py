"""The frontier MJoin runs each level's constraint loop over the edges that
bind that level only.  Counts and overflow equal host GM, every vmapped
lane equals its single-query run, materialized rows come in the search
order's lexicographic order, and the trip counters count what ran."""

from functools import partial
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import match
from repro.core.bruteforce import brute_force_answers
from repro.core.query import CHILD, DESC, PatternQuery, QueryEdge
from repro.data.graphs import random_labeled_graph
from repro.data.queries import random_query_from_graph
from repro.jaxgm import (JaxGM, decode_tuples, double_simulation,
                         encode_query, from_host, jo_order, mjoin_count)
from repro.jaxgm.simulation import fb_sizes
from repro.obs.metrics import MetricsRegistry

MAX_Q, MAX_E = 8, 16
BATCH = 8           # one compiled shape for every parametrised batch
CAPACITY = 4096


@pytest.fixture(scope="module")
def graph():
    return random_labeled_graph(48, avg_degree=2.4, n_labels=4, seed=21)


@pytest.fixture(scope="module")
def dg(graph):
    return from_host(graph, block=128)


def _raw_edges(q: PatternQuery, rng) -> list:
    """``q``'s edges plus duplicates of a pair under the other kind and
    reversed copies, in a shuffled order, at most ``MAX_E``."""
    edges = list(q.edges)
    for e in q.edges:
        r = rng.random()
        if r < 0.35:
            edges.append(QueryEdge(e.src, e.dst, DESC if e.kind == CHILD
                                   else CHILD))
        elif r < 0.5:
            edges.append(QueryEdge(e.dst, e.src, DESC))
    edges = edges[:MAX_E]
    rng.shuffle(edges)
    return edges


def _batch(graph, seed: int):
    """``BATCH`` queries of 2–8 nodes, C, H and D kinds, as raw edge lists
    (with duplicate pairs) and the pattern host GM sees for each."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(BATCH):
        n = int(rng.integers(2, MAX_Q + 1))
        q = random_query_from_graph(graph, n, qtype="CHD"[b % 3],
                                    extra_edge_prob=0.5,
                                    seed=seed * 100 + b)
        edges = _raw_edges(q, rng)
        # PatternQuery keeps one edge per pair (child over descendant):
        # the same constraint as ANDing both
        out.append((edges, PatternQuery(labels=list(q.labels), edges=edges)))
    return out


def _encode(labels, edges):
    raw = SimpleNamespace(n=len(labels), m=len(edges), labels=list(labels),
                          edges=edges)
    return encode_query(raw, MAX_Q, MAX_E)


def _stack(qts):
    return jax.tree.map(lambda *xs: np.stack(xs), *qts)


def _pipeline(dg, qt, *, capacity, materialize):
    fb = double_simulation(dg, qt, exact=True, impl="reference")
    order = jo_order(qt, fb_sizes(fb))
    res = mjoin_count(dg, qt, fb, order, capacity=capacity,
                      materialize=materialize)
    return res, order


@partial(jax.jit, static_argnames=("capacity", "materialize"))
def _single(dg, qt, capacity, materialize):
    return _pipeline(dg, qt, capacity=capacity, materialize=materialize)


@partial(jax.jit, static_argnames=("capacity", "materialize"))
def _vmapped(dg, qts, capacity, materialize):
    return jax.vmap(partial(_pipeline, capacity=capacity,
                            materialize=materialize),
                    in_axes=(None, 0))(dg, qts)


def _host_level_edges(edges, order) -> np.ndarray:
    """Per level, the edges that bind it: the later endpoint's position;
    an edge whose endpoints share a node binds none."""
    inv = {int(v): i for i, v in enumerate(order) if v >= 0}
    k = np.zeros(MAX_Q, np.int64)
    for e in edges:
        if e.src != e.dst:
            k[max(inv[e.src], inv[e.dst])] += 1
    return k


def _projections(answers: np.ndarray, order, n: int) -> int:
    """Distinct partial assignments of the search order's first n-1 nodes
    that some answer extends."""
    cols = [int(v) for v in order[:n - 1]]
    return len({tuple(r) for r in answers[:, cols]})


# batches whose every lane fits CAPACITY (seeds 3 and 5 hold a 7- and an
# 8-node query with ~78,000 answers, which overflow it)
@pytest.mark.parametrize("seed", [1, 2, 4, 6])
def test_vmapped_levels_match_host_gm_and_single_runs(graph, dg, seed):
    batch = _batch(graph, seed)
    qts = [_encode(pq.labels, edges) for edges, pq in batch]
    res, order = _vmapped(dg, _stack(qts), CAPACITY, True)
    res = jax.tree.map(np.asarray, res)
    order = np.asarray(order)
    for b, (edges, pq) in enumerate(batch):
        n = pq.n
        assert not res.overflowed[b]
        assert res.count[b] == match(graph, pq, limit=None).count
        np.testing.assert_array_equal(res.level_edges[b],
                                      _host_level_edges(edges, order[b]))
        one, one_order = _single(dg, qts[b], CAPACITY, True)
        np.testing.assert_array_equal(np.asarray(one_order), order[b])
        for field in ("count", "overflowed", "frontier", "alive",
                      "level_edges"):
            np.testing.assert_array_equal(np.asarray(getattr(one, field)),
                                          getattr(res, field)[b], field)
        # the first `capacity` brute-force answers in the search order's
        # lexicographic order (the last level keeps that many rows)
        ans = brute_force_answers(graph, pq)
        got = decode_tuples(jax.tree.map(lambda x: x[b], res), order[b], n)
        keys = [ans[:, int(v)] for v in order[b][:n]][::-1]
        want = ans[np.lexsort(keys)] if len(ans) else ans.reshape(0, n)
        np.testing.assert_array_equal(got, want[:CAPACITY])


@pytest.mark.parametrize("seed", [1, 2, 3, 5])
def test_tiny_capacity_still_overflows(graph, dg, seed):
    cap = 4
    batch = _batch(graph, seed)
    qts = [_encode(pq.labels, edges) for edges, pq in batch]
    res, order = _vmapped(dg, _stack(qts), cap, False)
    over, count = np.asarray(res.overflowed), np.asarray(res.count)
    order = np.asarray(order)
    certain = 0
    for b, (_, pq) in enumerate(batch):
        ans = brute_force_answers(graph, pq)
        if len(ans) and _projections(ans, order[b], pq.n) > cap:
            certain += 1
            assert over[b]
        if not over[b]:
            assert count[b] == len(ans)
        one, _ = _single(dg, qts[b], cap, False)
        assert bool(one.overflowed) == bool(over[b])
        assert int(one.count) == int(count[b])
    assert certain, "no query of the batch outgrows the tiny capacity"


def test_same_position_and_padding_edges_bind_no_level(graph, dg):
    q = random_query_from_graph(graph, 4, qtype="H", seed=5)
    plain = _encode(q.labels, list(q.edges))
    loops = _encode(q.labels, list(q.edges) + [QueryEdge(1, 1, CHILD),
                                               QueryEdge(2, 2, DESC)])
    # the same candidate sets and order: only the enumeration sees the
    # same-position edges
    fb = double_simulation(dg, plain, exact=True, impl="reference")
    order = jo_order(plain, fb_sizes(fb))
    a, b = (mjoin_count(dg, qt, fb, order, capacity=CAPACITY,
                        materialize=True) for qt in (plain, loops))
    for field in ("count", "overflowed", "frontier", "alive",
                  "level_edges"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)
    assert int(a.count) == match(graph, q, limit=None).count
    k = np.asarray(a.level_edges)
    assert k.sum() == q.m and k[0] == 0 and not k[q.n:].any()
    np.testing.assert_array_equal(
        k, _host_level_edges(q.edges, np.asarray(order)))


# ------------------------------------------------------------- counters
def _order(jgm: JaxGM, q: PatternQuery) -> np.ndarray:
    qt = encode_query(q, jgm.max_q, jgm.max_e)
    fb = double_simulation(jgm.dg, qt, n_passes=jgm.n_passes,
                           impl=jgm.impl, exact=jgm.exact_sim)
    return np.asarray(jo_order(qt, fb_sizes(fb)))


def _counters(reg):
    snap = reg.snapshot()
    return snap["jaxgm_mjoin_edge_trips"], snap["jaxgm_mjoin_edge_slots"]


@pytest.fixture(scope="module")
def counted(graph):
    reg = MetricsRegistry()
    jgm = JaxGM(graph, block=128, capacity=CAPACITY, exact_sim=True,
                impl="reference", use_transitive_reduction=False,
                metrics=reg)
    return jgm, reg


def test_batch_counts_the_longest_lane_per_level(graph, counted):
    jgm, reg = counted
    queries = [pq for _, pq in _batch(graph, 4)]
    before, slots_before = _counters(reg)
    calls = jgm.calls
    jgm.match_batch(queries)
    jgm.match_batch(queries)
    ks = np.stack([_host_level_edges(q.edges, _order(jgm, q))
                   for q in queries])
    trips, slots = _counters(reg)
    assert trips - before == 2 * ks.max(axis=0).sum()
    assert ks.max(axis=0).sum() < ks.sum()     # lanes share their trips
    assert slots - slots_before == (jgm.calls - calls) * MAX_Q * MAX_E
    assert jgm.calls - calls == 2


@pytest.mark.parametrize("path", ["batch", "single"])
def test_one_query_counts_its_edges(graph, counted, path):
    jgm, reg = counted
    q = random_query_from_graph(graph, 5, qtype="H", extra_edge_prob=0.6,
                                seed=9)
    before, slots_before = _counters(reg)
    if path == "batch":
        jgm.match_batch([q])
    else:
        jgm.match(q, materialize=True)
    trips, slots = _counters(reg)
    assert trips - before == q.m
    assert slots - slots_before == MAX_Q * MAX_E
