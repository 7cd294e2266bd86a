"""End-to-end behaviour tests for the paper's system."""

import numpy as np
import pytest

from repro.core import GM, GMOptions
from repro.core.bruteforce import answer_set, brute_force_answers
from repro.core.graph import paper_example_graph
from repro.core.query import paper_example_query
from repro.data.graphs import random_labeled_graph
from repro.data.queries import random_query_from_graph


def test_paper_running_example_end_to_end():
    """Fig. 1: build the graph, run the full GM pipeline, check the answer
    against brute force and the occurrence-set definition."""
    g = paper_example_graph()
    q = paper_example_query()
    res = GM(g).match(q)
    want = answer_set(brute_force_answers(g, q))
    assert answer_set(res.tuples) == want
    assert res.count == len(want) > 0
    assert res.rig_nodes > 0 and res.rig_edges > 0
    # os(q) ⊆ cos(q): every answer node survives in the RIG candidate sets
    for i in range(q.n):
        occ = set(np.unique(res.tuples[:, i]).tolist())
        cos = set(res.rig.cos_indices(i).tolist())
        assert occ <= cos


def test_query_server_survives_worker_failure():
    """Serving loop: journal + re-dispatch; all requests answered and
    counts equal the host matcher's."""
    from repro.launch.serve import QueryServer

    graph = random_labeled_graph(300, avg_degree=3.0, n_labels=6, seed=0)
    server = QueryServer(graph, batch_size=4, capacity=8192)
    queries = {}
    for i in range(8):
        q = random_query_from_graph(graph, 3 + i % 2,
                                    qtype=["C", "H", "D"][i % 3], seed=i)
        queries[i] = q
        assert server.submit(i, q)
    server.step(fail=True)          # a worker dies mid-batch
    server.drain()
    gm = GM(graph, GMOptions(materialize=False))
    for i, q in queries.items():
        r = server.journal[i]
        assert r.done, f"request {i} not served"
        assert r.count == gm.match(q).count
    assert server.stats["redispatched"] > 0


def test_training_end_to_end_with_crash_resume(tmp_path):
    """Tiny LM trained through a simulated crash: loss decreases and the
    resumed run is bit-identical to an uninterrupted one."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.tokens import TokenPipeline, TokenPipelineConfig
    from repro.models import transformer as tf
    from repro.train import (AdamWConfig, ElasticConfig, ElasticTrainer,
                             SimulatedFailure)
    from repro.train import optimizer as opt

    cfg = get_config("qwen2-7b").smoke_config()
    ocfg = AdamWConfig(lr=3e-3, warmup_steps=4, total_steps=40,
                       weight_decay=0.0)
    pipe = TokenPipeline(TokenPipelineConfig(vocab=cfg.vocab, batch=8,
                                             seq_len=32, seed=0))

    def init_state():
        params = tf.init_params(cfg, jax.random.key(0))
        return {"params": params, "opt": opt.init_state(params)}

    @jax.jit
    def step(state, batch):
        loss, grads = jax.value_and_grad(tf.loss_fn)(state["params"], batch,
                                                     cfg)
        params, ostate, m = opt.apply_updates(state["params"], grads,
                                              state["opt"], ocfg)
        m["loss"] = loss
        return {"params": params, "opt": ostate}, m

    def make(d):
        return ElasticTrainer(
            step_fn=step,
            make_batch=lambda i: jax.tree.map(jnp.asarray, pipe.batch_at(i)),
            init_state=init_state,
            cfg=ElasticConfig(checkpoint_dir=str(d), checkpoint_every=10,
                              async_save=False),
            get_step=lambda s: int(s["opt"]["step"]))

    t = make(tmp_path / "a")
    t.start_or_resume()
    out = t.run(30)
    losses = [m["loss"] for m in out["metrics"]]
    assert losses[-1] < losses[0]
    w_straight = np.asarray(t.state["params"]["embed"], np.float32)

    t2 = make(tmp_path / "b")
    t2.start_or_resume()
    with pytest.raises(SimulatedFailure):
        t2.run(30, fail_at=10)
    t3 = make(tmp_path / "b")
    info = t3.start_or_resume()
    assert info["resumed"]
    t3.run(30)
    w_resumed = np.asarray(t3.state["params"]["embed"], np.float32)
    np.testing.assert_allclose(w_resumed, w_straight, rtol=1e-5, atol=1e-6)


def test_chip_smoke_refuses_without_tpu():
    """The chip smoke never reports a result from the CPU: without a TPU
    it exits non-zero before building the graph or running a query."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(root / "chip_smoke.py")],
                         cwd=root, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout and "graph" not in out.stdout
