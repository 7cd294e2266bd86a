"""The plain reference against a brute-force count and against the
engine's served counts."""

import pytest

import graphgen
import querygen
import run
from reference import Reference


def _queries(n, edges, labels, seed, count, classes=("C", "H", "D")):
    mix = {"classes": list(classes), "nodes": [3, 4], "max_nodes": 8,
           "max_edges": 16}
    return querygen.Traffic(mix, graphgen.Csr(n, edges), labels,
                            seed).requests(count)


@pytest.mark.parametrize("seed,kind", [(1, "uniform"), (2, "powerlaw")])
def test_reference_matches_bruteforce(seed, kind):
    from repro.core.bruteforce import brute_force_answers
    from repro.core.graph import graph_from_edge_list
    edges, labels = graphgen.generate(200, 480, 5, kind, 1.2, seed)
    ref = Reference(200, edges, labels)
    g = graph_from_edge_list(edges, labels, num_labels=5)
    for q in _queries(200, edges, labels, seed, 12):
        answers = brute_force_answers(g, run.to_pattern(q))
        assert ref.count(q.labels, q.edges) == len(answers), q
        assert ref.count(q.labels, q.edges, stop=3) >= min(len(answers), 3)
        injective = sum(len(set(a)) == len(a) for a in answers)
        assert ref.count(q.labels, q.edges, injective=True) == injective, q


@pytest.mark.parametrize("config,mix", [("epinions-small", "c8-mixed-small"),
                                        ("hprd-small", "c8-mixed-small")])
def test_reference_agrees_with_engine_execute_many(small_cell, config, mix):
    """C, H and D queries through ``Engine.execute_many`` with the
    server's options, one of them capped by a lowered result limit."""
    from repro.core.graph import graph_from_edge_list
    from repro.engine import Engine, EngineOptions
    c = small_cell(config, mix)
    cfg, mix = c["config"], c["mix"]
    n = cfg["nodes"]
    edges, labels = graphgen.from_config(cfg, 3)
    ref = Reference(n, edges, labels)
    qs = querygen.Traffic(mix, graphgen.Csr(n, edges), labels, 3).requests(12)
    truth = [ref.count(q.labels, q.edges) for q in qs]
    cap = sorted(truth)[-2]            # caps at least the largest answer
    assert cap > 0 and max(truth) > cap
    g = graph_from_edge_list(edges, labels, num_labels=cfg["labels"])
    eng = Engine(g, options=EngineOptions(
        device_min_nodes=0, exact_sim=True, materialize=False, limit=cap))
    res = eng.execute_many([run.to_pattern(q) for q in qs])
    assert {q.qclass for q in qs} == {"C", "H", "D"}
    served = []
    for j, (q, r) in enumerate(zip(qs, res)):
        s = run.Served(j=j, query=q, submitted=0.0, answered=0.0,
                       status="done", count=r.count)
        served.append(s)
        assert r.count == min(truth[j], cap) or r.count == truth[j]
    assert any(r.count == cap for r in res)
    checks = run.check(served, served, ref, cap)
    assert checks["mismatched"]["value"] == 0
    assert checks["failed"]["value"] == 0
    served[0].count += 1
    assert run.check(served, served, ref, cap)["mismatched"]["value"] == 1
    served[1].status = "failed"
    assert run.check(served, served[2:], ref, cap)["failed"]["value"] == 1


def test_sample_is_drawn_from_the_seed(monkeypatch):
    monkeypatch.setattr(run, "CHECK_SAMPLE", 5)
    reqs = [run.Served(j=j, query=None, submitted=0.0, status="done")
            for j in range(20)] + [run.Served(j=20, query=None,
                                              submitted=0.0, status="failed")]
    a = run.sample(reqs, 2**40 + 1)
    assert len(a) == 5 and all(r.status == "done" for r in a)
    assert [r.j for r in a] == [r.j for r in run.sample(reqs, 2**40 + 1)]
    assert [r.j for r in a] != [r.j for r in run.sample(reqs, 2**40 + 2)]
    monkeypatch.setattr(run, "CHECK_SAMPLE", 50)
    assert len(run.sample(reqs, 1)) == 20
