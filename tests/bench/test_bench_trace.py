"""Trace reduction on hand-made events shaped like a v5e profile."""

from types import SimpleNamespace as NS

import pytest

import devtrace


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _profile(ops, host):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
    ])


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        devtrace.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        devtrace.reduce_profile(_profile([], []), "cpu")


def test_busy_idle_gaps_and_top_ops_on_made_events():
    ops = [_ev("fusion.1", 100, 200),
           _ev("fusion.2", 250, 100),          # overlaps fusion.1
           _ev("custom-call.7", 1000, 4000)]
    host = [_ev("bench.step", 0, 10_000_000), _ev("np.asarray", 400, 500)]
    r = devtrace.reduce_profile(_profile(ops, host), "TPU v5 lite")
    assert r["window_s"] == pytest.approx(10_000_000 / 1e9)
    assert r["busy_s"] == pytest.approx((250 + 4000) / 1e9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "bench.step"
    assert ["np.asarray", 650 / 1e9] in gaps
    assert r["breakdown"]["device_ops"][0] == ["custom-call.7", 4000 / 1e9]


def test_no_bench_marks_reads_nothing():
    ops = [_ev("fusion.1", 100, 200)]
    assert devtrace.reduce_profile(_profile(ops, []), "TPU v5 lite") is None
