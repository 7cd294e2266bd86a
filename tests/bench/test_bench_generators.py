"""The benchmark's own graph and query generators."""

import json

import numpy as np
import pytest

import graphgen
import querygen
import run


@pytest.mark.parametrize("config", ["hprd-small", "epinions-small"])
def test_scaled_profile_meets_published_counts(small_cell, config):
    cfg = small_cell(config, "c8-mixed-small")["config"]
    n, e = cfg["nodes"], cfg["edges"]
    edges, labels = graphgen.from_config(cfg, 2**31 + 5)
    assert labels.shape == (n,)
    assert len(np.unique(labels)) == cfg["labels"]
    assert edges.shape == (e, 2)
    assert len(np.unique(edges[:, 0] * n + edges[:, 1])) == e
    assert not np.any(edges[:, 0] == edges[:, 1])
    assert edges.min() >= 0 and edges.max() < n


def test_cell_config_is_the_published_profile():
    cfg = json.loads((run.ROOT / "bench/configs/hprd.json").read_text())
    assert (cfg["nodes"], cfg["edges"], cfg["labels"]) == (9460, 34998, 307)
    assert cfg["reduced"] == []


def _connected(q):
    seen, todo = {0}, [0]
    while todo:
        v = todo.pop()
        for s, d, _ in q.edges:
            for a, b in ((s, d), (d, s)):
                if a == v and b not in seen:
                    seen.add(b)
                    todo.append(b)
    return len(seen) == q.n


@pytest.mark.parametrize("config,mix", [("epinions-small", "c8-mixed-small"),
                                        ("hprd-small", "c16-child-small")])
def test_queries_connected_within_caps_and_seeded(small_cell, config, mix):
    c = small_cell(config, mix)
    cfg, mix = c["config"], c["mix"]
    n = cfg["nodes"]
    edges, labels = graphgen.from_config(cfg, 7)

    def draw(seed):
        t = querygen.Traffic(mix, graphgen.Csr(n, edges), labels, seed)
        return (t.requests(mix["clients"], querygen.WARMUP)
                + t.requests(3 * len(mix["nodes"]) * len(mix["classes"])))

    qs = draw(2**32 + 11)
    assert qs == draw(2**32 + 11)
    assert qs != draw(2**32 + 12)
    sigs = [tuple(sorted(q.labels)) for q in qs]
    assert len(set(sigs)) == len(sigs)
    cycle = [(c_, k) for k in mix["nodes"] for c_ in mix["classes"]]
    for j, q in enumerate(qs[mix["clients"]:]):
        assert q.qclass == cycle[j % len(cycle)][0]
        assert 2 <= q.n <= mix["max_nodes"]
        assert len(q.edges) <= mix["max_edges"]
        assert _connected(q)
        for s, d, k in q.edges:
            assert s != d and k in (querygen.CHILD, querygen.DESC)
            if q.qclass == "C":
                assert k == querygen.CHILD
            if q.qclass == "D":
                assert k == querygen.DESC


def test_a_request_is_drawn_once(small_cell):
    c = small_cell("hprd-small", "c16-child-small")
    edges, labels = graphgen.from_config(c["config"], 9)
    t = querygen.Traffic(c["mix"], graphgen.Csr(c["config"]["nodes"], edges),
                         labels, 9)
    first = t.requests(10)
    assert t.requests(10) == first
    assert t.request(3) is first[3]
