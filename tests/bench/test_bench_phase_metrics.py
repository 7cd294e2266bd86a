"""The served path's phase and stall readers on made windows: a value when
their series moved, nothing when the program has no such series."""

import pytest

import run

NEW = ("encode_ms", "finish_ms", "step_host_share", "gc_share",
       "slow_steps")


def _hist(total, count):
    return {"sum": total, "count": count}


def _phase(name):
    return f'serve_phase_seconds{{phase="{name}"}}'


def _gc(g):
    return f'process_gc_seconds{{generation="{g}"}}'


def _window(before, after, seconds=10.0):
    return run.Window(cell={}, seconds=seconds, before=before, after=after)


MOVED = {
    "encode_ms": ({_phase("jaxgm.encode"): _hist(1.0, 10)},
                  {_phase("jaxgm.encode"): _hist(1.5, 20)}, 50.0),
    "finish_ms": ({_phase("engine.finish"): _hist(0.2, 4)},
                  {_phase("engine.finish"): _hist(0.23, 10)}, 5.0),
    "step_host_share": ({_phase("host"): _hist(3.0, 100)},
                        {_phase("host"): _hist(4.2, 118)}, 12.0),
    "gc_share": ({_gc(0): _hist(0.01, 5), _gc(2): _hist(0.2, 1)},
                 {_gc(0): _hist(0.03, 9), _gc(1): _hist(0.01, 1),
                  _gc(2): _hist(0.25, 2)}, 0.8),
    "slow_steps": ({"server_slow_steps": 1}, {"server_slow_steps": 3}, 2.0),
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_value_when_the_series_moved(metric):
    before, after, want = MOVED[metric]
    assert run.reader(metric)(_window(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_reader_none_without_the_series(metric):
    # the parent program has none of these series
    w = _window({"server_served": 0}, {"server_served": 304})
    assert run.reader(metric)(w) is None


@pytest.mark.parametrize("metric", ["encode_ms", "finish_ms",
                                    "step_host_share"])
def test_phase_reader_none_when_no_phase_ran(metric):
    series = {"encode_ms": "jaxgm.encode", "finish_ms": "engine.finish",
              "step_host_share": "host"}[metric]
    h = {_phase(series): _hist(2.0, 7)}
    assert run.reader(metric)(_window(h, dict(h))) is None


def test_quiet_counters_read_zero():
    gc = {_gc(g): _hist(0.1, 3) for g in range(3)}
    assert run.reader("gc_share")(_window(gc, dict(gc))) == 0.0
    slow = {"server_slow_steps": 2}
    assert run.reader("slow_steps")(_window(slow, dict(slow))) == 0.0


def test_new_readers_resolve_in_the_cell():
    cell = run.load_cell("hprd-c16-child")
    names = [m["name"] for m in cell["per_layer"]]
    for metric in NEW:
        assert metric in names
        assert callable(run.reader(metric))
