"""Chained schedule execution (DESIGN.md Sec. 15): one compiled program
per schedule, bit-exact against the per-step differential reference and
the plain-integer reference on hybrid BP<->BS plans of real Table-6
apps; re-runs on resident entry operands; content-addressed executable
caching."""
import dataclasses

import numpy as np
import pytest

from repro import spans
from repro.core.cost_model import Layout
from repro.plan import (
    ExecutableCache,
    compile_plan,
    compile_schedule,
    lower_plan_pallas,
    reference_results,
    run_schedule,
    schedule_key,
    synth_inputs,
)
from repro.workloads import get_workload
from repro.workloads.ir import Op, Workload

#: Table-6 multi-step apps: 3 measured classifier FCs each (the convs
#: exceed any honest interpret-mode budget and stay modelled)
APPS = ("vgg13", "vgg16", "vgg19")


def _hybrid_schedule(app):
    """Force the middle classifier FC to BS: a BP->BS boundary into fc1
    (bp2bs, fused) and a BS->BP boundary into fc2 (bs2bp).  The cost
    model never picks BS at these widths, so the hybrid is constructed
    by hand -- lowering consumes any LayoutPlan."""
    w = get_workload(app)
    p = compile_plan(w)
    p = dataclasses.replace(p, steps=tuple(
        dataclasses.replace(s, layout=Layout.BS) if s.op == "fc1" else s
        for s in p.steps))
    sched = lower_plan_pallas(p, w)
    by_op = {s.op: s for s in sched.steps}
    assert by_op["fc1"].repack == "bp2bs"
    assert by_op["fc1"].kernel == "fused_bitserial_matmul"
    assert by_op["fc2"].repack == "bs2bp"
    return w, sched


@pytest.mark.parametrize("app", APPS)
def test_chained_matches_per_step_and_reference_on_hybrid(app):
    """The ISSUE-10 acceptance: the ONE-program executable of a hybrid
    plan returns bit-identical results to per-step run_schedule AND the
    plain-integer reference, repacks folded in-program."""
    _, sched = _hybrid_schedule(app)
    inputs = synth_inputs(sched, seed=5)
    exe = compile_schedule(sched, inputs, seed=5)
    got = exe.run()
    per = run_schedule(sched, inputs)
    ref = reference_results(sched, inputs)
    assert set(got) == {"fc0", "fc1", "fc2"}
    for op in got:
        np.testing.assert_array_equal(got[op], per[op], err_msg=op)
        np.testing.assert_array_equal(got[op], ref[op], err_msg=op)
    # outputs thread through the deps DAG, not synthetic operands:
    # perturbing fc0's weights must change fc2's threaded result
    x0, w0 = inputs["fc0"]
    inputs2 = dict(inputs)
    inputs2["fc0"] = (x0, (w0 + 1).astype(w0.dtype))
    got2 = compile_schedule(sched, inputs2, seed=5).run()
    assert not np.array_equal(got2["fc2"], got["fc2"])


def test_resident_entry_rerun_is_identical():
    """The entry operands stay resident and are read in place: running
    the same executable twice returns bit-identical outputs."""
    _, sched = _hybrid_schedule("vgg16")
    exe = compile_schedule(sched, synth_inputs(sched, seed=2), seed=2)
    a, b = exe.run(), exe.run()
    assert set(a) == set(b) == {"fc0", "fc1", "fc2"}
    for op in a:
        np.testing.assert_array_equal(a[op], b[op], err_msg=op)
    assert exe.runs >= 2


def test_warm_runs_place_no_bytes_and_record_one_place_span():
    _, sched = _hybrid_schedule("vgg13")
    exe = compile_schedule(sched, synth_inputs(sched, seed=3), seed=3)
    exe.run()   # warm
    for _ in range(3):
        before = spans.last("schedule.run")
        exe.run()
        run = spans.last("schedule.run")
        assert run is not before
        assert spans.recent("schedule.place_bytes", 2) == [0, 0]
        # exactly one place span per call, inside this call's run span
        place = spans.recent("schedule.place", 2)
        assert run.start_ns <= place[1].start_ns
        assert place[0].start_ns < run.start_ns
        assert place[1].parent == "schedule.run"


def test_caller_device_inputs_stay_valid():
    """Nothing is donated: the caller's device arrays outlive compile
    and run, and still hold their values."""
    import jax.numpy as jnp

    _, sched = _hybrid_schedule("vgg13")
    inputs = {op: (jnp.asarray(x), jnp.asarray(w))
              for op, (x, w) in synth_inputs(sched, seed=4).items()}
    copies = {op: (np.asarray(x), np.asarray(w))
              for op, (x, w) in inputs.items()}
    exe = compile_schedule(sched, inputs, seed=4)
    # a device entry becomes resident as it is, without a copy
    assert exe.entry_ops
    for op in exe.entry_ops:
        assert exe._entry[op] is inputs[op][0], op
    exe.run()
    exe.run()
    for op, (x, w) in inputs.items():
        assert not x.is_deleted() and not w.is_deleted(), op
        np.testing.assert_array_equal(np.asarray(x), copies[op][0])
        np.testing.assert_array_equal(np.asarray(w), copies[op][1])


def test_each_run_returns_fresh_result_arrays():
    _, sched = _hybrid_schedule("vgg13")
    exe = compile_schedule(sched, synth_inputs(sched, seed=6), seed=6)
    a, b = exe.run(), exe.run()
    for op in a:
        assert a[op] is not b[op], op
        assert not np.shares_memory(a[op], b[op]), op


def _chain_schedule():
    """A linear chain of three matmuls of different ``[m, n]``: every
    step after the first is threaded from its producer's result."""
    w = Workload(name="chain", ops=(
        Op(name="a", kind="matmul", m=4, k=64, n=48, width=8),
        Op(name="b", kind="matmul", m=8, k=40, n=16, width=16),
        Op(name="c", kind="matmul", m=2, k=96, n=24, width=4)))
    sched = lower_plan_pallas(compile_plan(w), w)
    assert sched.threaded_producers() == {"b": "a", "c": "b"}
    return sched


def test_chained_matches_per_step_and_reference_on_threaded_chain():
    sched = _chain_schedule()
    inputs = synth_inputs(sched, seed=8)
    got = compile_schedule(sched, inputs, seed=8).run()
    per = run_schedule(sched, inputs)
    ref = reference_results(sched, inputs)
    assert {op: v.shape for op, v in got.items()} == {
        "a": (4, 48), "b": (8, 16), "c": (2, 24)}
    for op in got:
        assert got[op].dtype == np.int32, op
        np.testing.assert_array_equal(got[op], per[op], err_msg=op)
        np.testing.assert_array_equal(got[op], ref[op], err_msg=op)


def _build(build):
    return (_hybrid_schedule("vgg13")[1] if build == "hybrid"
            else _chain_schedule())


#: (schedule, FETCH_CHUNK_BYTES) -> the elements of each flat output:
#: the chain's results are 192, 128 and 48 elements, vgg13's 512, 512
#: and 10; a new buffer starts where the next result would pass the
#: chunk, and a result larger than the chunk sits alone
BUFFERS = [("hybrid", None, [1034]), ("chain", None, [368]),
           ("chain", 1024, [192, 176]), ("chain", 4, [192, 128, 48]),
           ("hybrid", 2048, [512, 512, 10]), ("hybrid", 2088, [512, 522])]


@pytest.mark.parametrize("build,chunk,sizes", BUFFERS)
def test_program_returns_flat_int32_buffers(monkeypatch, build, chunk,
                                            sizes):
    """The program's outputs are flat int32 buffers that hold every
    step's result, in step order and without gaps."""
    from repro.plan import pallas_exec

    if chunk is not None:
        monkeypatch.setattr(pallas_exec, "FETCH_CHUNK_BYTES", chunk)
    sched = _build(build)
    exe = compile_schedule(sched, synth_inputs(sched, seed=9), seed=9)
    bufs = exe._fn(exe._entry, exe._params)
    assert [b.shape for b in bufs] == [(n,) for n in sizes]
    assert all(b.dtype == np.int32 for b in bufs)
    got = exe.run()
    assert spans.last("schedule.fetch_arrays") == len(sizes)
    assert spans.last("schedule.fetch_bytes") == 4 * sum(sizes) == sum(
        v.nbytes for v in got.values())
    np.testing.assert_array_equal(
        np.concatenate([got[s.op].reshape(-1)
                        for s in sched.measured_steps]),
        np.concatenate([np.asarray(b) for b in bufs]))
    ref = reference_results(sched, synth_inputs(sched, seed=9))
    for op in got:
        np.testing.assert_array_equal(got[op], ref[op], err_msg=op)


@pytest.mark.parametrize("build", ["hybrid", "chain"])
def test_one_call_results_are_views_of_one_buffer(build):
    """A small schedule's results fit one buffer: one call's results
    are views of it, back to back, and the next call's are not."""
    sched = _build(build)
    exe = compile_schedule(sched, synth_inputs(sched, seed=10), seed=10)
    a, b = exe.run(), exe.run()
    ops = [s.op for s in sched.measured_steps]
    base = a[ops[0]].base
    assert base is not None
    for op in ops:
        assert a[op].base is base, op
        assert np.shares_memory(a[op], base), op
        assert not np.shares_memory(a[op], b[op].base), op
    # back to back, in step order
    addr = {op: a[op].__array_interface__["data"][0] for op in ops}
    for p, q in zip(ops, ops[1:]):
        assert addr[p] + a[p].nbytes == addr[q], (p, q)


def test_summary_reports_resident_entry_bytes():
    _, sched = _hybrid_schedule("vgg13")
    inputs = synth_inputs(sched, seed=7)
    exe = compile_schedule(sched, inputs, seed=7)
    want = sum(inputs[op][0].nbytes for op in exe.entry_ops)
    assert exe.entry_ops and want > 0
    summ = exe.summary()
    assert summ["entry_bytes"] == exe.entry_bytes == want
    assert "donate" not in summ


def test_executable_cache_hits_on_recompile():
    cache = ExecutableCache()
    _, sched = _hybrid_schedule("vgg13")
    exe1, key1, hit1 = cache.get_or_compile(sched, seed=0)
    exe2, key2, hit2 = cache.get_or_compile(sched, seed=0)
    assert (hit1, hit2) == (False, True)
    assert key1 == key2 and exe1 is exe2
    s = cache.stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["entries"] == 1
    assert s["hit_rate"] == 0.5
    # a different seed is a different executable (different operands)
    _, _, hit3 = cache.get_or_compile(sched, seed=1)
    assert hit3 is False


def test_schedule_key_is_content_addressed():
    _, s13 = _hybrid_schedule("vgg13")
    _, s16 = _hybrid_schedule("vgg16")
    assert schedule_key(s13) == schedule_key(s13)
    assert schedule_key(s13) != schedule_key(s16)
    assert schedule_key(s13) != schedule_key(s13, seed=1)
    assert schedule_key(s13) != schedule_key(s13, fingerprint="other")


def test_compile_cost_charged_separately_from_run():
    _, sched = _hybrid_schedule("vgg13")
    exe = compile_schedule(sched, synth_inputs(sched))
    pack, first = spans.last("schedule.pack"), spans.last("schedule.first_run")
    assert exe.compile_us == (pack.dur_ns + first.dur_ns) / 1e3 > 0
    assert first.compiles >= 1           # the program is built here
    assert exe.params_bytes > 0          # weights are device-resident
    assert exe.n_measured == 3
    exe.run()
    warm = spans.last("schedule.run")
    assert warm.compiles == 0
    assert 0 < warm.dur_ns / 1e3 < exe.compile_us  # steady state beats compile
    summ = exe.summary()
    assert summ["key"] == exe.key and summ["n_measured"] == 3


def test_synth_inputs_cover_the_top_bit_at_width_32():
    """Width-32 weights must exercise the sign bit: the old
    ``1 << min(width, 31)`` bound silently halved the sampled range."""
    w = Workload(name="w32", ops=(
        Op(name="mm", kind="matmul", m=4, k=64, n=64, width=32),))
    sched = lower_plan_pallas(compile_plan(w), w)
    (step,) = sched.measured_steps
    assert step.width == 32
    inputs = synth_inputs(sched, seed=0)
    _, wm = inputs["mm"]
    assert (wm < 0).any(), "top bit never set: width-32 range is halved"
    got = compile_schedule(sched, inputs).run()
    ref = reference_results(sched, inputs)
    np.testing.assert_array_equal(got["mm"], ref["mm"])
