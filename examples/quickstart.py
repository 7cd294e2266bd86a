"""Quickstart: evaluate hybrid graph pattern queries through the engine.

  PYTHONPATH=src python examples/quickstart.py
"""

from repro.core import GM
from repro.core.graph import paper_example_graph
from repro.core.query import paper_example_query
from repro.data.graphs import random_labeled_graph
from repro.data.queries import random_query_from_graph
from repro.engine import Engine, EngineOptions


def main():
    # --- the paper's Fig. 1 example, written in the query language --------
    g = paper_example_graph()
    eng = Engine(g, label_names=["A", "B", "C", "D", "E"])
    text = ("(a:A)-/->(b:B), (c:C)-/->(b), (a)-//->(c), "
            "(b)-//->(d:D)-//->(e:E), (c)-//->(e)")
    res = eng.execute(text)
    print(f"[fig1] {text}")
    print(f"[fig1] occurrences={res.count}  RIG nodes={res.stats.rig_nodes} "
          f"edges={res.stats.rig_edges}  plan: {res.plan.explain()}")
    print(f"[fig1] first tuples (A,B,C,D,E):\n{res.tuples[:5]}")

    # textual and programmatic queries are the same thing
    assert res.count == GM(g).match(paper_example_query()).count
    print("[fig1] text query == hand-built PatternQuery ✓")

    # --- a larger random graph: the planner picks the backend -------------
    g2 = random_labeled_graph(800, avg_degree=3.0, n_labels=8, seed=1)
    eng2 = Engine(g2, options=EngineOptions(materialize=False))
    q2 = random_query_from_graph(g2, n_nodes=5, qtype="H", seed=2)
    print(f"\n[random] query: {eng2.format(q2)}")
    print(f"[random] plan:  {eng2.explain(q2)}")
    r1 = eng2.execute(q2)
    print(f"[random] cold:  count={r1.count} backend={r1.stats.backend} "
          f"({r1.stats.total_s * 1e3:.1f} ms, label cache "
          f"{'hit' if r1.stats.label_cache_hit else 'miss'})")
    r2 = eng2.execute(q2)
    print(f"[random] warm:  count={r2.count} "
          f"({r2.stats.total_s * 1e3:.1f} ms, plan cache "
          f"{'hit' if r2.stats.plan_cache_hit else 'miss'}, label cache "
          f"{'hit' if r2.stats.label_cache_hit else 'miss'})")
    assert r1.count == r2.count == GM(g2).match(q2).count
    print(f"[random] engine == host GM ✓   caches: {eng2.cache_info()}")


if __name__ == "__main__":
    main()
