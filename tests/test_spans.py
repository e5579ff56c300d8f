"""The program's span recorder (``repro.spans``): nesting, the bounded
record, counters, JAX compile events charged to the open spans, and the
spans and counters one ``ScheduleExecutable.run()`` records."""
import itertools
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.plan import compile_schedule, synth_inputs

from test_pallas_exec import _hybrid_schedule

_fresh = itertools.count()


def _name(tag: str) -> str:
    """A span name no other test has recorded."""
    return f"test.{tag}.{next(_fresh)}"


def test_nested_spans_record_their_parent_and_enclose_children():
    outer, inner = _name("outer"), _name("inner")
    with spans.span(outer, call=3):
        with spans.span(inner):
            pass
    o, i = spans.last(outer), spans.last(inner)
    assert o.parent is None and i.parent == outer
    assert o.start_ns <= i.start_ns
    assert i.start_ns + i.dur_ns <= o.start_ns + o.dur_ns


def test_span_records_even_when_the_body_raises():
    name = _name("raises")
    with pytest.raises(ValueError):
        with spans.span(name):
            raise ValueError("boom")
    assert spans.last(name).dur_ns >= 0
    after = _name("after")
    with spans.span(after):
        pass
    assert spans.last(after).parent is None   # the stack was unwound


def test_span_decorates_a_function():
    name = _name("decorated")

    @spans.span(name)
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert len(spans.recent(name, 2)) == 2


def test_record_is_bounded_and_recent_raises_on_too_few():
    name = _name("bounded")
    for v in range(spans.MAXLEN + 10):
        spans.count(name, v)
    got = spans.recent(name, spans.MAXLEN)
    assert got[0] == 10 and got[-1] == spans.MAXLEN + 9
    with pytest.raises(LookupError):
        spans.recent(name, spans.MAXLEN + 1)
    with pytest.raises(LookupError):
        spans.last(_name("never"))
    with pytest.raises(LookupError):
        spans.recent(name, 0)


def test_counters_keep_values_in_order():
    name = _name("counter")
    for v in (5, 7, 11):
        spans.count(name, v)
    assert spans.recent(name, 2) == [7, 11]
    assert spans.last(name) == 11


def test_jax_compile_events_are_charged_to_the_open_spans():
    outer, inner = _name("compile_outer"), _name("compile_inner")
    # a shape and a function no other test compiles
    f = jax.jit(lambda x: jnp.sin(x) * 3 + 1)
    x = jnp.ones((7, 13), jnp.float32)
    with spans.span(outer):
        with spans.span(inner):
            f(x).block_until_ready()
    o, i = spans.last(outer), spans.last(inner)
    assert i.compiles >= 1 and o.compiles == i.compiles
    assert 0 < i.compile_ns <= i.dur_ns
    warm = _name("warm")
    with spans.span(warm):
        f(x).block_until_ready()
    assert spans.last(warm).compiles == 0
    assert spans.last(warm).compile_ns == 0


def test_nested_compile_events_count_once():
    assert spans._union_ns([(0, 10), (2, 5), (8, 12), (20, 25)]) == 17
    assert spans._union_ns([]) == 0


def test_threads_keep_their_own_parents_and_lose_no_record():
    """Each thread has its own open-span stack; records from many threads
    share one bounded record and none is lost."""
    n_threads, per_thread = 16, 100
    outer, counter = _name("thread_outer"), _name("thread_count")
    inners = [_name(f"thread_inner{i}") for i in range(n_threads)]
    wrong_parent = []

    def work(i):
        for _ in range(per_thread):
            with spans.span(outer):
                with spans.span(inners[i]):
                    spans.count(counter, i)
            if spans.last(inners[i]).parent != outer:
                wrong_parent.append(i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not wrong_parent
    total = n_threads * per_thread
    assert sorted(spans.recent(counter, total)) == sorted(
        i for i in range(n_threads) for _ in range(per_thread))
    assert len(spans.recent(outer, total)) == total
    for name in inners:
        assert len(spans.recent(name, per_thread)) == per_thread


RUN_SPANS = ("place", "dispatch", "wait", "fetch")


@pytest.fixture(scope="module")
def exe():
    _, sched = _hybrid_schedule("vgg13")
    return compile_schedule(sched, synth_inputs(sched, seed=3), seed=3)


def test_each_run_records_one_of_each_run_span(exe):
    exe.run()   # warm
    names = ("schedule.run",) + tuple(f"schedule.{p}" for p in RUN_SPANS)
    before = {n: spans.last(n).start_ns for n in names}
    exe.run()
    run = spans.last("schedule.run")
    for p in RUN_SPANS:
        rec = spans.last(f"schedule.{p}")
        assert rec.start_ns > before[f"schedule.{p}"]
        assert rec.parent == "schedule.run"
        assert run.start_ns <= rec.start_ns
        assert rec.start_ns + rec.dur_ns <= run.start_ns + run.dur_ns
    # exactly one each: the second newest belongs to the warm-up call
    for n in names:
        assert spans.recent(n, 2)[0].start_ns == before[n]
    assert sum(spans.last(f"schedule.{p}").dur_ns for p in RUN_SPANS) \
        <= run.dur_ns


def test_transfer_counters_are_the_entry_and_result_bytes(exe):
    got = exe.run()
    # the entries are resident on the device: no bytes are placed
    assert exe.entry_bytes > 0
    assert spans.last("schedule.place_bytes") == 0
    assert spans.last("schedule.fetch_bytes") == sum(
        np.asarray(v).nbytes for v in got.values())
    assert spans.last("schedule.fetch_bytes") > 0


def test_a_small_schedule_fetches_one_array(exe):
    # vgg13's three classifier results fit one FETCH_CHUNK_BYTES buffer
    exe.run()
    got = exe.run()
    assert spans.last("schedule.fetch_arrays") == 1
    assert spans.recent("schedule.fetch_arrays", 2) == [1, 1]
    assert spans.last("schedule.fetch_bytes") == sum(
        v.nbytes for v in got.values())


def test_warm_run_compiles_nothing(exe):
    exe.run()
    exe.run()
    for n in ("schedule.run",) + tuple(f"schedule.{p}" for p in RUN_SPANS):
        assert spans.last(n).compiles == 0, n
