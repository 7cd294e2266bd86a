"""The device matcher's ``mjoin_trip_share`` reader: the frontier MJoin's
constraint-loop trips as a share of its padded slots, from the program's
two counters; nothing when the program has no such counters."""

import pytest

import run

METRIC = "mjoin_trip_share"
TRIPS, SLOTS = "jaxgm_mjoin_edge_trips", "jaxgm_mjoin_edge_slots"


def _window(before, after, seconds=10.0):
    return run.Window(cell={}, seconds=seconds, before=before, after=after)


@pytest.mark.parametrize("before,after,want", [
    ({TRIPS: 0, SLOTS: 0}, {TRIPS: 40, SLOTS: 2560}, 1.5625),
    ({TRIPS: 7, SLOTS: 256}, {TRIPS: 7 + 3 * 19, SLOTS: 256 + 128 * 19},
     100.0 * 3 / 128),
    ({}, {TRIPS: 128, SLOTS: 128}, 100.0),
])
def test_share_of_the_window_deltas(before, after, want):
    assert run.reader(METRIC)(_window(before, after)) == pytest.approx(want)


def test_none_without_the_counters():
    # the parent program has no such counters
    w = _window({"server_served": 0}, {"server_served": 304})
    assert run.reader(METRIC)(w) is None


def test_none_when_no_dispatch_ran():
    c = {TRIPS: 30, SLOTS: 1280}
    assert run.reader(METRIC)(_window(c, dict(c))) is None


def test_reads_the_matchers_registry():
    from repro.core.query import CHILD, PatternQuery, QueryEdge
    from repro.data.graphs import random_labeled_graph
    from repro.jaxgm import JaxGM
    from repro.obs.metrics import MetricsRegistry
    g = random_labeled_graph(40, avg_degree=2.0, n_labels=3, seed=3)
    reg = MetricsRegistry()
    jgm = JaxGM(g, block=128, capacity=256, exact_sim=True,
                impl="reference", metrics=reg)
    path = PatternQuery(labels=[0, 1, 2],
                        edges=[QueryEdge(0, 1, CHILD), QueryEdge(1, 2, CHILD)])
    before = reg.snapshot()
    jgm.match_batch([path, path])
    share = run.reader(METRIC)(_window(before, reg.snapshot()))
    # a 3-node path binds one edge at each of levels 1 and 2
    assert share == pytest.approx(100.0 * 2 / (jgm.max_q * jgm.max_e))


def test_resolves_in_the_cell():
    cell = run.load_cell("hprd-c16-child")
    assert METRIC in [m["name"] for m in cell["per_layer"]]
    assert callable(run.reader(METRIC))
