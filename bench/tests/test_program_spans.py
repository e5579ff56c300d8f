"""The readers of the program's spans and counters, on a synthetic run
and a synthetic recorder: each reads the window's calls or the newest
set-up record, and a program without the recorder reports nothing."""
import importlib
import types

import pytest

from bench import program_spans
from repro.spans import Record

RUN = ("run_place_ms", "run_dispatch_ms", "run_wait_ms", "run_fetch_ms")
SETUP = ("setup_plan_s", "setup_pack_s", "setup_compile_s", "setup_warm_s")
NEW = RUN + ("host_transfer_bytes",) + SETUP


def _rec(dur_ns, compile_ns=0):
    return Record(0, dur_ns, None, int(compile_ns > 0), compile_ns)


def _fake_recorder(records: dict):
    def recent(name, n):
        got = records[name]
        if len(got) < n:
            raise LookupError(name)
        return got[-n:]

    return types.SimpleNamespace(recent=recent,
                                 last=lambda name: recent(name, 1)[0])


@pytest.fixture
def recorded(monkeypatch):
    records = {
        # a warm-up call (9 ms each) and then the window's two calls
        "schedule.place": [_rec(9e6), _rec(1e6), _rec(3e6)],
        "schedule.dispatch": [_rec(9e6), _rec(2e6), _rec(2e6)],
        "schedule.wait": [_rec(9e6), _rec(10e6), _rec(30e6)],
        "schedule.fetch": [_rec(9e6), _rec(4e6), _rec(6e6)],
        "schedule.place_bytes": [7, 100, 100],
        "schedule.fetch_bytes": [7, 40, 40],
        "workload.trace": [_rec(2e9)],
        "plan.compile": [_rec(1e9)],
        "plan.lower": [_rec(0.5e9)],
        "schedule.pack": [_rec(9e9), _rec(4e9)],
        "schedule.first_run": [_rec(9e9), _rec(10e9, compile_ns=7.5e9)],
    }
    monkeypatch.setattr(program_spans, "recorder",
                        lambda: _fake_recorder(records))


def _read(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


def test_every_new_reader_on_a_synthetic_run(recorded):
    run = types.SimpleNamespace(step_s=[0.02, 0.04], window_s=0.06)
    got = {name: _read(name, run) for name in NEW}
    assert got == {
        "run_place_ms": 2.0, "run_dispatch_ms": 2.0, "run_wait_ms": 20.0,
        "run_fetch_ms": 5.0, "host_transfer_bytes": 140.0,
        "setup_plan_s": 3.5, "setup_pack_s": 4.0, "setup_compile_s": 7.5,
        "setup_warm_s": 2.5}
    # the four run spans account for the window's calls here
    assert sum(got[n] for n in RUN) == pytest.approx(
        run.window_s / len(run.step_s) * 1e3 - 1.0)


def test_run_readers_raise_when_the_window_outnumbers_the_records(recorded):
    run = types.SimpleNamespace(step_s=[0.01] * 4, window_s=0.04)
    with pytest.raises(LookupError):
        _read("run_wait_ms", run)


def test_a_program_without_the_recorder_reports_none(monkeypatch):
    monkeypatch.setattr(program_spans, "recorder", lambda: None)
    run = types.SimpleNamespace(step_s=[0.02], window_s=0.02)
    assert {name: _read(name, run) for name in NEW} == dict.fromkeys(NEW)
