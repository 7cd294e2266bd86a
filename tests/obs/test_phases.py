"""Served-path phases: profiler annotations timed into
``serve_phase_seconds``, per-step host time, and the process-wide stall
evidence (GC pauses, programs built, slow steps)."""

from __future__ import annotations

import gc
import os
import subprocess
import sys
import time
import tracemalloc
from pathlib import Path

import pytest

from repro.data.graphs import random_labeled_graph
from repro.data.queries import random_query_from_graph
from repro.engine import Engine, EngineOptions
from repro.engine.engine import ENGINE_PHASES
from repro.launch.serve import QueryServer
from repro.obs import NULL_TRACER, MetricsRegistry
from repro.obs.process import PROCESS
from repro.obs.trace import PHASE_METRIC, phase

jax = pytest.importorskip("jax")

from repro.jaxgm.matcher import JAXGM_PHASES  # noqa: E402

SERVE_PHASES = ("serve.step", "serve.pending", "serve.collect")
DIRECT = ("serve.pending", "engine.prepare", "engine.labels",
          "engine.device_batch", "engine.finish", "serve.collect")
ROOT = Path(__file__).resolve().parents[2]


def _totals(eng):
    return {dict(m.labels)["phase"]: (m.count, m.total)
            for m in eng.metrics if m.name == PHASE_METRIC}


def _delta(a, b):
    return {k: (v[0] - a.get(k, (0, 0.0))[0], v[1] - a.get(k, (0, 0.0))[1])
            for k, v in b.items()}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two device-batch steps of four queries each, under the JAX
    profiler: the first compiles the batch executor, the second holds a
    query whose frontier overflows (capacity 64) and is recomputed."""
    g = random_labeled_graph(200, avg_degree=3.0, n_labels=4, seed=0)
    srv = QueryServer(g, batch_size=4, max_q=4, max_e=4, capacity=64)
    for i in range(8):
        srv.submit(i, random_query_from_graph(g, 3, qtype="C", seed=i))
    trace_dir = tmp_path_factory.mktemp("profile")
    steps = []
    jax.profiler.start_trace(str(trace_dir))
    try:
        for _ in range(2):
            before = _totals(srv.engine)
            srv.step()
            steps.append(_delta(before, _totals(srv.engine)))
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    overflowed = [sum(srv.journal[i].overflowed for i in range(k, k + 4))
                  for k in (0, 4)]
    return {"server": srv, "steps": steps, "events": events,
            "overflowed": overflowed}


def test_every_phase_once_per_step(served):
    assert served["overflowed"][0] == 0 and served["overflowed"][1] >= 1
    for n, step in enumerate(served["steps"]):
        for name in SERVE_PHASES + ("host",) + ENGINE_PHASES + JAXGM_PHASES:
            want = {"jaxgm.compile": 1 - n,
                    "engine.overflow_recompute": served["overflowed"][n]
                    }.get(name, 1)
            assert step.get(name, (0, 0.0))[0] == want, (n, name)


def test_annotations_nest_in_the_profiler_trace(served):
    events = served["events"]
    names = {e[0] for e in events}
    for name in SERVE_PHASES + ENGINE_PHASES + JAXGM_PHASES:
        assert name in names, name

    def within(child, parent):
        outer = [e for e in events if e[0] == parent]
        for _, a, b in (e for e in events if e[0] == child):
            assert any(p0 <= a and b <= p1 for _, p0, p1 in outer), \
                (child, parent)

    for name in ("serve.pending", "serve.collect") + ENGINE_PHASES:
        within(name, "serve.step")
    for name in JAXGM_PHASES:
        within(name, "engine.device_batch")
    within("engine.overflow_recompute", "engine.finish")


def test_direct_phases_cover_the_step(served):
    for step in served["steps"]:
        direct = sum(step[name][1] for name in DIRECT)
        assert direct <= step["serve.step"][1]
        assert direct >= 0.9 * step["serve.step"][1]


def test_host_is_step_wall_minus_dispatch(served):
    for step in served["steps"]:
        host = step["serve.step"][1] - step["jaxgm.dispatch"][1]
        assert step["host"][1] == pytest.approx(host, abs=1e-9)
        assert step["jaxgm.dispatch"][1] > 0


def test_profiled_spans_reach_the_profiler(tmp_path):
    g = random_labeled_graph(60, avg_degree=2.0, n_labels=3, seed=1)
    eng = Engine(g)
    q = random_query_from_graph(g, 3, qtype="C", seed=1)
    eng.execute(q)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.execute(q, profile=True)
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    names = {ev.name for plane in pd.planes for line in plane.lines
             for ev in line.events}
    assert {s.name for s in res.trace.iter()} <= names


# ------------------------------------------------------------ slow steps
def _host_server(n_steps):
    g = random_labeled_graph(80, avg_degree=2.0, n_labels=3, seed=2)
    eng = Engine(g, options=EngineOptions(force_backend="host",
                                          materialize=False))
    srv = QueryServer(g, engine=eng, batch_size=1)
    for i in range(n_steps):
        srv.submit(i, random_query_from_graph(g, 3, qtype="C", seed=i))
    return srv


def _plant(monkeypatch, eng, on_call, base_s=0.05):
    """Every ``engine._prepare`` call sleeps ``base_s``; call number
    ``k`` also runs ``on_call[k]``."""
    real = eng._prepare
    calls = iter(range(10 ** 6))

    def prepare(*a, **kw):
        time.sleep(base_s)
        on_call.get(next(calls), lambda: None)()
        return real(*a, **kw)
    monkeypatch.setattr(eng, "_prepare", prepare)


def test_planted_stall_is_one_slow_step_named_by_its_phase(monkeypatch):
    srv = _host_server(12)
    _plant(monkeypatch, srv.engine, {10: lambda: time.sleep(1.0)})
    for _ in range(12):
        srv.step()
    assert srv.stats["slow_steps"] == 1
    assert srv.engine.metrics_snapshot()["server_slow_steps"] == 1
    ev = [e for e in srv.flight.events() if e.get("action") == "slow_step"]
    assert len(ev) == 1
    data = ev[0]["data"]
    assert data["longest_phase"] == "engine.prepare"
    assert "engine.prepare" in ev[0]["detail"]
    assert data["phases"]["engine.prepare"] >= 1.0
    assert data["wall_s"] > 4 * data["median_s"]
    assert {"gc_s", "compiles", "overflow_recomputes"} <= set(data)


def test_no_slow_step_before_eight_steps(monkeypatch):
    srv = _host_server(8)
    _plant(monkeypatch, srv.engine, {7: lambda: time.sleep(1.0)}, 0.0)
    for _ in range(8):
        srv.step()
    assert srv.stats["slow_steps"] == 0


def test_forced_collection_shows_in_gc_seconds(monkeypatch):
    srv = _host_server(3)
    _plant(monkeypatch, srv.engine, {1: gc.collect}, 0.0)
    key = 'process_gc_seconds{generation="2"}'
    before = srv.engine.metrics_snapshot()[key]
    for _ in range(3):
        srv.step()
    after = srv.engine.metrics_snapshot()[key]
    assert after["count"] >= before["count"] + 1
    assert after["sum"] > before["sum"]


def test_engines_share_one_gc_hook_and_one_compile_listener():
    from jax._src import monitoring
    g = random_labeled_graph(40, avg_degree=2.0, n_labels=3, seed=3)
    Engine(g).metrics_snapshot()
    n_gc = len(gc.callbacks)
    n_mon = len(monitoring.get_event_duration_listeners())
    engines = [Engine(g) for _ in range(50)]
    for eng in engines[:3]:
        eng._resident(None).jgm()
        eng.metrics_snapshot()
    assert len(gc.callbacks) == n_gc
    assert len(monitoring.get_event_duration_listeners()) == n_mon
    assert gc.callbacks.count(PROCESS._on_gc) == 1


# -------------------------------------------------------------- primitive
def test_phase_times_into_its_histogram():
    h = MetricsRegistry().histogram(PHASE_METRIC, phase="x")
    with phase("x", h) as ph:
        time.sleep(0.01)
    assert h.count == 1 and h.total == ph.duration_s >= 0.01


def test_null_tracer_allocates_nothing_with_jax_loaded():
    def loop():
        for _ in range(1000):
            with NULL_TRACER.span("phase") as s:
                s.set()

    with phase("load", MetricsRegistry().histogram("h")):
        pass                                  # annotation class resolved
    loop()
    tracemalloc.start()
    loop()
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 4096, f"no-op tracer allocated {peak} bytes"


def test_host_only_import_does_not_load_jax():
    code = ("import sys\n"
            "from repro.obs import MetricsRegistry\n"
            "from repro.obs.trace import phase, Tracer\n"
            "h = MetricsRegistry().histogram('h')\n"
            "with phase('p', h):\n"
            "    with Tracer('q').span('s'):\n"
            "        pass\n"
            "assert h.count == 1\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
