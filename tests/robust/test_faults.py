"""Chaos suite for resource-governed execution (repro.robust).

Covers the three layers of the governance stack:

* the fault harness itself (deterministic triggers, scoped install),
* the circuit breaker / budget state machines (injected clock + sleep, so
  no test ever really waits),
* end-to-end chaos: for every injection site, a faulted run must either
  surface the typed error or — when a degradation path exists — return
  counts identical to the fault-free run.  The RIG is runtime state, so
  every recovery is recompute; equality of counts is the proof.
"""

import pytest

from repro.data.graphs import random_labeled_graph
from repro.engine import (Budget, CircuitBreaker, DeadlineExceeded, Engine,
                          EngineOptions, ResourceExhausted)
from repro.launch.serve import QueryServer
from repro.robust import faults
from repro.robust.breaker import CLOSED, HALF_OPEN, OPEN
from repro.robust.errors import (BreakerOpen, DeviceFailure, InjectedFault,
                                 QueryError, TransientError)
from repro.testing import HAVE_HYPOTHESIS, given, settings, st


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with no fault plan installed."""
    faults.uninstall()
    yield
    faults.uninstall()


def _graph(seed=0, n=300):
    return random_labeled_graph(n, avg_degree=3.0, n_labels=4, seed=seed)


def _host_engine(g, **kw):
    return Engine(g, options=EngineOptions(device_min_nodes=10**9,
                                           materialize=False, **kw))


QUERY = "(a:L0)-/->(b:L1)-//->(c:L2)"


# ===================================================== fault harness itself
class TestFaultHarness:
    def test_no_plan_is_free_noop(self):
        faults.maybe_fail("rig_expand")            # must not raise
        assert faults.call_count("rig_expand") == 0

    def test_nth_fires_on_exact_call_numbers(self):
        with faults.inject(faults.nth("rig_expand", 2, 4)) as plan:
            fired = []
            for i in range(1, 6):
                try:
                    faults.maybe_fail("rig_expand")
                except InjectedFault as e:
                    fired.append(i)
                    assert e.site == "rig_expand" and e.call_no == i
            assert fired == [2, 4]
            assert plan.calls["rig_expand"] == 5

    def test_every_k(self):
        with faults.inject(faults.every("label_build", 3)):
            fired = [i for i in range(1, 10)
                     if _fires("label_build")]
            assert fired == [3, 6, 9]

    def test_times_bounds_total_fires(self):
        with faults.inject(faults.every("label_build", 1, times=2)):
            fired = [i for i in range(1, 6) if _fires("label_build")]
            assert fired == [1, 2]

    def test_probability_is_deterministic_per_seed(self):
        def draw(seed):
            with faults.inject(faults.probability("rig_expand", 0.5,
                                                  seed=seed)):
                return [i for i in range(1, 33) if _fires("rig_expand")]
        a, b = draw(7), draw(7)
        assert a == b and 0 < len(a) < 32
        assert draw(8) != a

    def test_inject_scopes_the_plan(self):
        with faults.inject(faults.every("rig_expand", 1)):
            with pytest.raises(InjectedFault):
                faults.maybe_fail("rig_expand")
        faults.maybe_fail("rig_expand")            # plan gone: no raise

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError):
            faults.nth("not_a_site", 1)

    def test_sites_do_not_interfere(self):
        with faults.inject(faults.every("label_build", 1)):
            faults.maybe_fail("device_dispatch")   # other site: no raise
            with pytest.raises(InjectedFault):
                faults.maybe_fail("label_build")

    def test_injected_fault_is_transient(self):
        assert issubclass(InjectedFault, TransientError)
        assert not issubclass(DeadlineExceeded, TransientError)
        assert not issubclass(BreakerOpen, TransientError)


def _fires(site):
    try:
        faults.maybe_fail(site)
        return False
    except InjectedFault:
        return True


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10**6),
           p=st.floats(0.05, 0.95))
    def test_probability_replays_exactly(seed, p):
        """Property: the seeded probability trigger is a pure function of
        (seed, p, call number) — two fresh specs fire identically."""
        def draw():
            spec = faults.probability("rig_expand", p, seed=seed)
            return [n for n in range(1, 65) if spec.should_fire(n)]
        assert draw() == draw()


# ============================================== breaker state machine (unit)
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _breaker(**kw):
    clk = FakeClock()
    sleeps = []
    br = CircuitBreaker(clock=clk, sleep=sleeps.append, **kw)
    return br, clk, sleeps


class TestCircuitBreaker:
    def test_retry_then_success(self):
        br, _, sleeps = _breaker(max_retries=2)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return "ok"

        assert br.call(flaky) == "ok"
        assert calls["n"] == 2 and br.retries == 1 and len(sleeps) == 1
        assert br.state == CLOSED and br.consecutive_failures == 0

    def test_consecutive_failures_open_the_breaker(self):
        br, _, _ = _breaker(failure_threshold=3, max_retries=0)
        for _ in range(3):
            with pytest.raises(DeviceFailure):
                br.call(_always_boom)
        assert br.state == OPEN and br.opened == 1

    def test_open_refuses_without_touching_device(self):
        br, _, _ = _breaker(failure_threshold=1, max_retries=0)
        with pytest.raises(DeviceFailure):
            br.call(_always_boom)
        assert br.state == OPEN
        touched = {"n": 0}

        def fn():
            touched["n"] += 1
            return "ok"

        with pytest.raises(BreakerOpen):
            br.call(fn)
        assert touched["n"] == 0

    def test_half_open_probe_success_recloses(self):
        br, clk, _ = _breaker(failure_threshold=1, max_retries=0,
                              reset_after_s=30.0)
        with pytest.raises(DeviceFailure):
            br.call(_always_boom)
        assert br.state == OPEN
        clk.t += 31.0                       # reset window passes
        assert br.call(lambda: "probe-ok") == "probe-ok"
        assert br.state == CLOSED
        assert br.call(lambda: "ok") == "ok"   # traffic flows again

    def test_half_open_probe_failure_reopens(self):
        br, clk, _ = _breaker(failure_threshold=1, max_retries=2,
                              reset_after_s=30.0)
        with pytest.raises(DeviceFailure):
            br.call(_always_boom)
        clk.t += 31.0
        assert br.allow() and br.state == HALF_OPEN
        with pytest.raises(DeviceFailure):
            br.call(_always_boom)           # probe gets exactly ONE attempt
        assert br.state == OPEN and br.opened == 2
        with pytest.raises(BreakerOpen):
            br.call(lambda: "nope")         # window restarted

    def test_backoff_never_sleeps_past_deadline(self):
        br, clk, sleeps = _breaker(max_retries=3, backoff_base_s=10.0)
        b = Budget(deadline_s=0.5).start(clock=clk)
        with pytest.raises(DeviceFailure):
            br.call(_always_boom, budget=b)
        assert sleeps and all(s <= 0.5 for s in sleeps)

    def test_fault_site_fires_per_attempt(self):
        br, _, _ = _breaker(max_retries=2)
        with faults.inject(faults.every("device_dispatch", 1)) as plan:
            with pytest.raises(DeviceFailure):
                br.call(lambda: "never-reached")
            assert plan.calls["device_dispatch"] == 3   # 1 + 2 retries


def _always_boom():
    raise RuntimeError("boom")


# ======================================================== budget semantics
class TestBudget:
    def test_start_arms_a_copy_not_the_template(self):
        template = Budget(deadline_s=5.0)
        armed = template.start()
        assert armed.armed and not template.armed
        assert armed is not template

    def test_deadline_with_injected_clock(self):
        clk = FakeClock()
        b = Budget(deadline_s=2.0).start(clock=clk)
        assert not b.expired() and b.remaining_s() == pytest.approx(2.0)
        clk.t += 2.5
        assert b.expired()
        with pytest.raises(DeadlineExceeded):
            b.check_deadline("rig_expand[0]")

    def test_charge_rig_raises_over_cap(self):
        b = Budget(max_rig_bytes=100).start()
        b.charge_rig(60)
        with pytest.raises(ResourceExhausted):
            b.charge_rig(60)

    def test_caps(self):
        b = Budget(max_frontier_rows=10, max_slab_bytes=1024).start()
        assert b.frontier_cap(1 << 20) == 10
        assert b.frontier_cap(4) == 4              # tightens only
        assert b.slab_cap_rows(256) == 4
        assert Budget().start().slab_cap_rows(256) is None


# ========================================== end-to-end chaos, per fault site
class TestEngineChaos:
    def test_rig_expand_fault_recomputes_to_identical_count(self):
        g = _graph()
        want = _host_engine(g).execute(QUERY).count
        eng = _host_engine(g)
        with faults.inject(faults.nth("rig_expand", 1)) as plan:
            res = eng.execute(QUERY, budget=Budget(max_attempts=2))
            assert plan.calls["rig_expand"] >= 1
        assert res.count == want and res.stats.status == "ok"
        assert res.stats.attempts == 2

    def test_rig_expand_fault_without_retries_is_typed(self):
        eng = _host_engine(_graph())
        with faults.inject(faults.every("rig_expand", 1)):
            res = eng.execute(QUERY, budget=Budget(max_attempts=2))
            assert res.stats.status == "injected_fault"
            assert res.stats.partial and res.count == 0
            with pytest.raises(QueryError):
                eng.execute(QUERY, budget=Budget(max_attempts=2,
                                                 raise_on_error=True))

    def test_label_build_fault_rebuilds_transactionally(self):
        g = _graph(seed=1)
        want = _host_engine(g).execute(QUERY).count
        eng = _host_engine(g)
        with faults.inject(faults.nth("label_build", 1)):
            res = eng.execute(QUERY, budget=Budget(max_attempts=2))
        assert res.count == want
        # the failed attempt left nothing half-built: exactly one committed
        # build, and the warm path reuses it
        assert eng.context().label_builds == 1
        eng.execute(QUERY)
        assert eng.context().label_builds == 1

    def test_device_dispatch_fault_falls_back_to_host(self):
        g = _graph(seed=2)
        want = _host_engine(g).execute(QUERY).count
        br = CircuitBreaker(sleep=lambda s: None, failure_threshold=3)
        eng = Engine(g, options=EngineOptions(
            device_min_nodes=0, materialize=False,
            force_backend="device", breaker=br))
        with faults.inject(faults.every("device_dispatch", 1)) as plan:
            res = eng.execute(QUERY)
            # the injected fault fires before fn(), so the device was never
            # touched; the engine recomputed on the host
            assert plan.calls["device_dispatch"] >= 1
            assert res.count == want
            assert res.stats.status == "ok" and res.stats.backend == "host"
            assert "host" in res.stats.degradations
            # 3 failed attempts in one call tripped the threshold
            assert br.state == OPEN
            # while open, dispatches are refused outright — still correct
            res2 = eng.execute(QUERY)
            assert res2.count == want and "host" in res2.stats.degradations
        assert br.retries >= 1

    def test_breaker_recloses_after_faults_stop(self):
        g = _graph(seed=2)
        clk = FakeClock()
        br = CircuitBreaker(sleep=lambda s: None, failure_threshold=1,
                            max_retries=0, reset_after_s=30.0, clock=clk)
        eng = Engine(g, options=EngineOptions(
            device_min_nodes=0, materialize=False,
            force_backend="device", breaker=br))
        want = _host_engine(g).execute(QUERY).count
        with faults.inject(faults.every("device_dispatch", 1)):
            assert eng.execute(QUERY).count == want
            assert br.state == OPEN
        clk.t += 31.0                       # faults gone, window passed:
        res = eng.execute(QUERY)            # the probe dispatch succeeds
        assert br.state == CLOSED
        assert res.count == want and res.stats.backend == "device"
        assert "host" not in res.stats.degradations

    def test_journal_dispatch_fault_redispatches_to_same_counts(self):
        g = _graph(seed=3)
        queries = [QUERY, "(a:L1)-//->(b:L2)", "(a:L0)-/->(b:L3)"]
        ref = QueryServer(g, engine=_host_engine(g))
        for i, q in enumerate(queries):
            ref.submit(i, q)
        ref.drain()
        want = [ref.journal[i].count for i in range(len(queries))]

        srv = QueryServer(g, engine=_host_engine(g), max_attempts=3)
        for i, q in enumerate(queries):
            srv.submit(i, q)
        with faults.inject(faults.nth("journal_dispatch", 1)):
            srv.drain()
        got = [srv.journal[i].count for i in range(len(queries))]
        assert got == want
        assert all(srv.journal[i].status == "done"
                   for i in range(len(queries)))
        assert srv.stats["redispatched"] >= 1

    def test_unrelenting_worker_death_goes_terminal_failed(self):
        g = _graph(seed=3)
        srv = QueryServer(g, engine=_host_engine(g), max_attempts=2)
        srv.submit(0, QUERY)
        with faults.inject(faults.every("journal_dispatch", 1)):
            srv.drain()
        r = srv.journal[0]
        assert r.status == "failed" and not r.done
        assert srv.stats["failed"] == 1 and srv.stats["served"] == 0


# =========================================================== budget, engine
class TestEngineBudgets:
    def test_deadline_partial_status(self):
        g = random_labeled_graph(1500, avg_degree=8.0, n_labels=1, seed=1)
        eng = _host_engine(g, force_enum="backtrack", limit=None)
        q = "(a:L0)-//->(b:L0)-//->(c:L0)"
        eng.execute("(a:L0)-/->(b:L0)")      # warm labels
        res = eng.execute(q, budget=Budget(deadline_s=0.05))
        assert res.stats.status == "deadline_exceeded"
        assert res.stats.partial and res.stats.deadline_exceeded

    def test_deadline_raises_in_strict_mode(self):
        g = random_labeled_graph(1500, avg_degree=8.0, n_labels=1, seed=1)
        eng = _host_engine(g, force_enum="backtrack", limit=None)
        eng.execute("(a:L0)-/->(b:L0)")
        with pytest.raises(DeadlineExceeded):
            eng.execute("(a:L0)-//->(b:L0)-//->(c:L0)",
                        budget=Budget(deadline_s=0.05, raise_on_error=True))

    def test_rig_memory_cap_is_typed(self):
        eng = _host_engine(_graph())
        res = eng.execute(QUERY, budget=Budget(max_rig_bytes=16))
        assert res.stats.status == "resource_exhausted" and res.count == 0
        with pytest.raises(ResourceExhausted):
            eng.execute(QUERY, budget=Budget(max_rig_bytes=16,
                                             raise_on_error=True))

    def test_ungoverned_execution_unchanged(self):
        g = _graph(seed=4)
        eng = _host_engine(g)
        res = eng.execute(QUERY)
        assert res.stats.status == "ok" and not res.stats.partial
        assert res.stats.degradations == []
        # a generous budget changes nothing about the answer
        res2 = _host_engine(g).execute(QUERY, budget=Budget(deadline_s=60.0))
        assert res2.count == res.count and res2.stats.status == "ok"


# ==================== no hiding: compile errors surface, faults degrade
# One case per device path the engine can take: the vmapped device
# matcher, the frontier slab intersector, and the resident enumerator.
_DEVICE_PATHS = {
    "device": dict(force_backend="device"),
    "frontier-device": dict(force_backend="host",
                            force_enum="frontier-device"),
    "frontier-device-resident": dict(force_backend="host",
                                     force_enum="frontier-device-resident"),
}


def _dense_graph():
    # uniform topology: frontiers past the padded-dispatch floor, so the
    # frontier paths really dispatch
    return random_labeled_graph(1500, avg_degree=3.0, n_labels=4,
                                kind="uniform", seed=3)


def _path_engine(g, path, **kw):
    return Engine(g, options=EngineOptions(
        device_min_nodes=0, materialize=False, **_DEVICE_PATHS[path], **kw))


@pytest.fixture
def fresh_slab_executor(monkeypatch):
    """The slab intersector is a process-wide singleton that caches its
    compiled executors; start these tests from a cold one."""
    import importlib
    monkeypatch.setattr(importlib.import_module("repro.core.mjoin"),
                        "_DEVICE", None)


class TestNoHiding:
    """A kernel that does not compile is not a transient device failure:
    it reaches the caller instead of being retried and recomputed on the
    host.  Faults in the dispatch itself keep the degradation ladder."""

    @pytest.mark.parametrize("path", list(_DEVICE_PATHS))
    def test_executor_compile_error_reaches_caller(
            self, path, monkeypatch, fresh_slab_executor):
        import jax

        def refuse(self, *a, **kw):
            raise RuntimeError("kernel refused by the compiler")

        g = _dense_graph()
        eng = _path_engine(g, path)
        monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
        with pytest.raises(RuntimeError, match="refused by the compiler"):
            eng.execute(QUERY)
        assert eng.breaker.retries == 0 and eng.breaker.state == CLOSED
        assert eng.counters["budget_degradations"] == 0

    @pytest.mark.parametrize("path", list(_DEVICE_PATHS))
    def test_injected_dispatch_fault_degrades_with_exact_count(
            self, path, fresh_slab_executor):
        g = _dense_graph()
        want = _host_engine(g).execute(QUERY).count
        eng = _path_engine(g, path, breaker=CircuitBreaker(
            sleep=lambda s: None))
        with faults.inject(faults.every("device_dispatch", 1)) as plan:
            res = eng.execute(QUERY)
            assert plan.calls["device_dispatch"] >= 1
        assert res.count == want and res.stats.status == "ok"
        assert res.stats.degradations in (["host"], ["host-intersect"])
