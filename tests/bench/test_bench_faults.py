"""A run whose timed path is broken underneath reads ``correct`` false,
and the control (injective counts in place of homomorphic ones) fails the
same check.  CPU, a small copy of the cell's configuration and mix; the
look for a chip is skipped."""

import pytest

import control
import run

CELL = ("hprd-small", "c16-child-small")


def _run(small_cell, cpu_device, seed):
    return run.run(small_cell(*CELL), seed, 2.0, False, require=cpu_device)


def test_sound_run_is_correct(small_cell, cpu_device):
    line = _run(small_cell, cpu_device, 2**31 + 101)
    assert line["correct"] and line["attempted"] > 0
    assert line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"mismatched", "failed"}


def test_answer_altered_where_produced(monkeypatch, small_cell, cpu_device):
    from repro.jaxgm.matcher import JaxGM
    prepare_batch = JaxGM.prepare_batch

    def altered(self, queries):
        dispatch = prepare_batch(self, queries)

        def run_():
            out = dispatch()
            for r in out:
                r.count += 1
            return out
        return run_

    monkeypatch.setattr(JaxGM, "prepare_batch", altered)
    line = _run(small_cell, cpu_device, 2**31 + 102)
    assert not line["correct"]
    assert line["checks"]["mismatched"]["value"] > 0


def test_half_the_batch_left_out(monkeypatch, small_cell, cpu_device):
    from repro.engine import Engine
    execute_many = Engine.execute_many

    def half(self, queries, **kw):
        k = max(1, len(queries) // 2)
        out = execute_many(self, queries[:k], **kw)
        return out + [out[i % k] for i in range(len(queries) - k)]

    monkeypatch.setattr(Engine, "execute_many", half)
    line = _run(small_cell, cpu_device, 2**31 + 103)
    assert not line["correct"]
    assert line["checks"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("seed", [2**31 + 201, 2**31 + 202, 2**31 + 203])
def test_control_is_not_correct(small_cell, seed):
    checks = control.control(small_cell(*CELL), seed, 200)
    assert checks["mismatched"]["value"] > checks["mismatched"]["limit"]
    assert not run.passed(checks)
