"""Plan -> Pallas lowering (DESIGN.md Sec. 14): a LayoutPlan executes as
a measured kernel sequence whose numbers match every other path bit-exactly
-- the plain-integer reference AND the pim micro-op executor's MAC
decomposition (the ISSUE-9 acceptance criterion)."""
import numpy as np
import pytest

from repro.core.cost_model import Layout
from repro.plan import (
    PallasSchedule,
    compile_plan,
    compile_schedule,
    lower_plan_pallas,
    reference_results,
    run_schedule,
    synth_inputs,
    time_schedule,
)
from repro.plan.pallas import apply, hold, step_for
from repro.workloads.ir import Op, Workload


def _hybrid_workload():
    """Two matmuls the planner splits: a 1-bit high-DoP op (BS wins) and
    a full-width INT16 op (BP wins)."""
    return Workload(name="hybrid_mm", ops=(
        Op(name="mm_lo", kind="matmul", m=1, k=32, n=512, width=1,
           bit_level_fraction=1.0),
        Op(name="mm_hi", kind="matmul", m=1, k=64, n=64, width=16),
    ))


@pytest.fixture(scope="module")
def hybrid_plan():
    w = _hybrid_workload()
    p = compile_plan(w, initial_layout=Layout.BS)
    assert p.is_hybrid, "fixture must compile to a genuine hybrid plan"
    return w, p


def test_hybrid_plan_lowers_to_kernel_sequence(hybrid_plan):
    w, p = hybrid_plan
    sched = lower_plan_pallas(p, w)
    assert sched.workload == "hybrid_mm"
    by_op = {s.op: s for s in sched.steps}
    lo, hi = by_op["mm_lo"], by_op["mm_hi"]
    assert lo.layout is Layout.BS and lo.kernel == "bitserial_matmul"
    assert hi.layout is Layout.BP and hi.kernel == "bitparallel_matmul"
    # the BS->BP boundary is an explicit repack, never an implicit cast
    assert hi.repack == "bs2bp"
    assert sched.n_repacks == 1
    # both true and padded dims are recorded (honest measurement contract)
    assert lo.dims == (1, 32, 512)
    assert lo.padded_dims is not None
    d = sched.to_dict()
    assert [s["measured"] for s in d["steps"]] == [True, True]


def test_hybrid_schedule_matches_reference(hybrid_plan):
    w, p = hybrid_plan
    sched = lower_plan_pallas(p, w)
    inputs = synth_inputs(sched, seed=3)
    got = run_schedule(sched, inputs)
    want = reference_results(sched, inputs)
    assert set(got) == {"mm_lo", "mm_hi"}
    for op in got:
        np.testing.assert_array_equal(got[op], want[op])


def test_hybrid_schedule_matches_executor_bit_exact(hybrid_plan):
    """ISSUE-9 acceptance: the Pallas kernel sequence of a hybrid plan
    returns bit-identical numbers to the pim micro-op executor's
    multu + vector_add MAC decomposition of the same ops."""
    from repro.pim import executor as ex
    from repro.pim import programs as pr
    from repro.pim.bitserial import unpack

    w, p = hybrid_plan
    sched = lower_plan_pallas(p, w)

    # operands valid on BOTH paths: multu is an unsigned w-bit multiply,
    # so draw values < 4 (fits the 1-bit op's plane count times nothing
    # -- weights stay < 2^width -- and keeps every MAC accumulator far
    # from its 32-bit limit)
    rng = np.random.default_rng(17)
    inputs = {}
    for s in sched.measured_steps:
        m, k, n = s.dims
        inputs[s.op] = (
            rng.integers(0, 2, (m, k)).astype(np.int8) if s.width == 1
            else rng.integers(0, 4, (m, k)).astype(np.int8),
            rng.integers(0, 1 << min(s.width, 2), (k, n)).astype(np.int32),
        )

    # thread=False: the executor replays each op on ITS synthetic
    # operands; threading would overwrite mm_hi's x with mm_lo's output
    pallas_out = run_schedule(sched, inputs, thread=False)

    def run_prog(prog, inp, n):
        cells = ex.init_cells(prog, n)
        for key, vals in inp.items():
            cells = ex.set_input(cells, prog, key, vals)
        return ex.execute(prog, cells)

    def mult_out(prog, res, n):
        if prog.layout is Layout.BS:
            return unpack(ex.get_output(res.array.cells, prog, "prod", n))
        # BP multu returns the product as a lo/hi word-row pair
        lo = np.asarray(ex.get_output(res.array.cells, prog, "prod_lo",
                                      n)).astype(np.uint64)
        hi = np.asarray(ex.get_output(res.array.cells, prog, "prod_hi",
                                      n)).astype(np.uint64)
        return lo | (hi << np.uint64(prog.width))

    for s in sched.measured_steps:
        x, wm = inputs[s.op]
        m, k, n = s.dims
        # the executor computes in the *assigned* layout's micro-ops;
        # a 32-bit vector_add accumulator keeps the chain exact
        mult = pr.build("multu", s.layout, width=max(s.width, 2))
        add = pr.build("vector_add", Layout.BS, width=32)
        executed = np.zeros((m, n), np.int64)
        for i in range(m):
            acc = np.zeros(n, np.uint64)
            for kk in range(k):
                res = run_prog(
                    mult, {"a": np.full(n, x[i, kk], np.uint64),
                           "b": wm[kk].astype(np.uint64)}, n)
                prod = mult_out(mult, res, n)
                acc = unpack(ex.get_output(
                    run_prog(add, {"a": acc, "b": prod}, n).array.cells,
                    add, "sum", n))
            executed[i] = acc.astype(np.int64)
        np.testing.assert_array_equal(
            pallas_out[s.op].astype(np.int64), executed,
            err_msg=f"{s.op}: Pallas kernel sequence != micro-op executor")


def test_fused_repack_on_bp2bs_boundary():
    """A BP->BS boundary folds the repack into the fused kernel by
    default; fuse_pack=False keeps the explicit pack->matmul pipeline.
    Both paths return identical numbers.

    The cost model never *chooses* BP->BS at sizes this small (a 1-wide
    matmul's BS saving is below the transpose price), so the hybrid
    assignment is constructed by hand -- lowering consumes any
    LayoutPlan, planner-compiled or not."""
    import dataclasses

    w = Workload(name="bp_then_bs", ops=(
        Op(name="mm_hi", kind="matmul", m=1, k=64, n=64, width=16),
        Op(name="mm_lo", kind="matmul", m=1, k=32, n=512, width=1,
           bit_level_fraction=1.0),
    ))
    p = compile_plan(w, initial_layout=Layout.BP)
    p = dataclasses.replace(p, steps=tuple(
        dataclasses.replace(s, layout=Layout.BS) if s.op == "mm_lo" else s
        for s in p.steps))
    assert p.is_hybrid
    fused = lower_plan_pallas(p, w)
    lo = {s.op: s for s in fused.steps}["mm_lo"]
    assert lo.repack == "bp2bs"
    assert lo.kernel == "fused_bitserial_matmul"
    unfused = lower_plan_pallas(p, w, fuse_pack=False)
    lo_u = {s.op: s for s in unfused.steps}["mm_lo"]
    assert lo_u.kernel == "bitserial_matmul"
    assert lo_u.repack == "bp2bs"
    inputs = synth_inputs(fused, seed=9)
    np.testing.assert_array_equal(
        run_schedule(fused, inputs)["mm_lo"],
        run_schedule(unfused, inputs)["mm_lo"])


def test_unsupported_and_over_budget_rows_are_honest():
    """Ops the kernels cannot measure lower to modelled-only rows with a
    reason -- never to a silently clamped launch."""
    w = Workload(name="mixed", ops=(
        Op(name="wide", kind="matmul", m=1, k=32, n=512, width=48,
           bit_level_fraction=1.0),
        Op(name="huge", kind="matmul", m=4096, k=4096, n=4096, width=8),
        Op(name="ker", kind="kernel", kernel="vector_add", n=4096,
           width=16),
    ))
    p = compile_plan(w)
    sched = lower_plan_pallas(p, w, max_macs=2 ** 20)
    by_op = {s.op: s for s in sched.steps}
    assert not by_op["ker"].measured
    assert "no Pallas lowering" in by_op["ker"].note
    assert not by_op["huge"].measured
    assert "over budget" in by_op["huge"].note
    assert by_op["huge"].padded_dims is not None  # reports what it priced
    wide = by_op["wide"]
    if wide.layout is Layout.BS:
        assert not wide.measured and "unsupported: width" in wide.note
    assert sched.measured_steps == ()


def test_conv_lowers_to_im2col_gemv():
    """Conv dims follow the ExecutorBackend lowering: op.n output
    elements x op.k-deep MACs (a GEMV), not an n x n square."""
    w = Workload(name="c", ops=(
        Op(name="cv", kind="conv", k=9, n=64, width=8),))
    p = compile_plan(w)
    sched = lower_plan_pallas(p, w)
    (step,) = sched.measured_steps
    assert step.dims == (64, 9, 1)
    inputs = synth_inputs(sched, seed=1)
    got = run_schedule(sched, inputs)
    want = reference_results(sched, inputs)
    np.testing.assert_array_equal(got["cv"], want["cv"])


def test_time_schedule_reports_every_step(hybrid_plan):
    w, p = hybrid_plan
    sched = lower_plan_pallas(p, w)
    rows = time_schedule(sched, synth_inputs(sched), reps=1)
    assert [r["op"] for r in rows] == [s.op for s in sched.steps]
    for r in rows:
        assert r["us"] is not None and r["us"] > 0
        assert r["dims"] is not None and r["padded_dims"] is not None


#: every form a lowered step takes: (layout, repack, fuse_pack, kernel)
STEP_FORMS = {
    "bp": (Layout.BP, None, True, "bitparallel_matmul"),
    "bp_bs2bp": (Layout.BP, "bs2bp", True, "bitparallel_matmul"),
    "bs": (Layout.BS, None, True, "bitserial_matmul"),
    "bs_bp2bs_unfused": (Layout.BS, "bp2bs", False, "bitserial_matmul"),
    "bs_bp2bs_fused": (Layout.BS, "bp2bs", True, "fused_bitserial_matmul"),
}


@pytest.mark.parametrize("form", sorted(STEP_FORMS))
def test_step_function_matches_reference_and_program(form):
    """``apply(s, x, hold(s, w))`` -- the step function every path runs
    -- equals the plain-integer reference and the same step compiled as
    a one-step schedule program, in each step form (ragged K and N, and
    a width of two BP limbs)."""
    import jax.numpy as jnp

    layout, repack, fuse, kernel = STEP_FORMS[form]
    op = Op(name="mm", kind="matmul", m=3, k=40, n=130, width=12)
    s = step_for(op, layout, repack, fuse_pack=fuse)
    assert s.kernel == kernel and s.repack == repack
    sched = PallasSchedule(workload="one_step", steps=(s,), fuse_pack=fuse)
    inputs = synth_inputs(sched, seed=21)
    x, w = inputs["mm"]
    got = np.asarray(apply(s, jnp.asarray(x), hold(s, w)))
    np.testing.assert_array_equal(
        got, reference_results(sched, inputs)["mm"])
    np.testing.assert_array_equal(
        got, compile_schedule(sched, inputs, seed=21).run()["mm"])
