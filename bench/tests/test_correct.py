"""``correct`` holds for the program, and its control and planted faults
fail it: at a small size on the CPU, through the harness's own run with
the look for a chip skipped."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import CHIP


def _run(cell, seed=2**33 + 17):
    return harness.run_cell(cell, seed=seed, seconds=0.3, trace=False,
                            device=CHIP, t0=time.perf_counter())


def test_program_is_correct(tiny_cell):
    r = _run(tiny_cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"] == {"wrong_elements": {"value": 0, "limit": 0}}
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"step_ms", "setup_s"}


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**40 + 1])
def test_control_reads_above_the_limit(tiny_cell, seed):
    from bench import control

    [row] = control.readings(tiny_cell, [seed], 0.1)
    assert row["program"] == 0
    assert row["control"] > 0


def _zeros(out):
    return {op: np.zeros_like(y) for op, y in out.items()}


def _half_batch(out):
    # rows of the second half left out, the first half kept
    bad = {}
    for op, y in out.items():
        y = y.copy()
        y[y.shape[0] // 2:] = 0
        bad[op] = y
    return bad


def _one_answer(out):
    bad = dict(out)
    op = sorted(out)[-1]
    y = out[op].copy()
    y.flat[0] += 1
    bad[op] = y
    return bad


@pytest.mark.parametrize("fault", [_zeros, _half_batch, _one_answer],
                         ids=["nothing_computed", "half_batch", "one_answer"])
def test_fault_in_the_timed_path_is_caught(tiny_cell, monkeypatch, fault):
    from repro.plan.pallas_exec import ScheduleExecutable

    real = ScheduleExecutable.run
    monkeypatch.setattr(ScheduleExecutable, "run",
                        lambda self: fault(real(self)))
    r = _run(tiny_cell)
    assert not r["correct"]
    assert r["checks"]["wrong_elements"]["value"] > 0
    assert r["failed"] >= 1
