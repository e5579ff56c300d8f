"""Workload-IR tests (ISSUE 3): golden equivalence of the IR route
against the legacy surfaces, the Backend protocol, the deprecation
shims, and the `python -m repro` CLI."""
from __future__ import annotations

import json
import subprocess
import sys
import warnings
from pathlib import Path

import pytest

from repro.core import apps
from repro.core.cost_model import Layout
from repro.core.microkernels import MICROKERNELS, kernel_cost
from repro.core.planner import plan
from repro.workloads import (
    AnalyticBackend,
    Backend,
    BACKENDS,
    ExecutorBackend,
    Op,
    PlannerBackend,
    Report,
    characterize,
    get_workload,
    microkernel_workload,
    op_phases,
    workload_names,
)

SRC = str(Path(__file__).parent.parent / "src")


# ------------------------------------------------- golden equivalence ------

@pytest.mark.parametrize("name", sorted(MICROKERNELS))
@pytest.mark.parametrize("width", [8, 16, 32])
def test_analytic_backend_matches_kernel_cost(name, width):
    """AnalyticBackend on a Table-5 IR workload reproduces the legacy
    `kernel_cost` load/compute/readout bit-for-bit, at every width and
    in both layouts."""
    n = 8192 if name == "relu" else 1024
    rep = AnalyticBackend().estimate(microkernel_workload(name, n=n,
                                                          width=width))
    assert isinstance(rep, Report)
    (op_rep,) = rep.ops
    for layout in (Layout.BP, Layout.BS):
        c = kernel_cost(name, layout, n=n, width=width)
        assert op_rep.breakdown[layout.value] == \
            (c.load, c.compute, c.readout), (name, layout, width)
    assert rep.summary["bp_cycles"] == kernel_cost(name, Layout.BP,
                                                   n=n, width=width).total


@pytest.mark.parametrize("app", apps.workload_names("table6"))
def test_planner_backend_matches_legacy_evaluate(app):
    """Planner/Analytic backends on the IR reproduce the legacy
    `evaluate_app` numbers exactly (the golden snapshot pins the values
    themselves; see tests/golden/paper_tables.txt [table6])."""
    legacy = apps.evaluate_app(app)
    reports = characterize(app, backends=("analytic", "planner"))
    a, p = reports["analytic"].summary, reports["planner"].summary
    assert a["bp_cycles"] == legacy["bp_cycles"] == p["bp_cycles"]
    assert a["bs_cycles"] == legacy["bs_cycles"] == p["bs_cycles"]
    assert p["hybrid_cycles"] == legacy["hybrid_cycles"]
    assert p["n_transposes"] == legacy["n_transposes"]
    assert p["is_hybrid"] == legacy["is_hybrid"]


def test_pinned_headline_numbers():
    """Hard pins (captured from the pre-IR builders) so equivalence does
    not become tautological after the legacy path delegates to the IR."""
    pins = {  # app: (bp, bs, hybrid)
        "aes": (18624, 24702, 6961),
        "vgg16": (3704282, 4794817, 3686062),
        "hdc": (134417, 108688, 101793),
        "keccak": (22896, 42072, 11582),
    }
    for app, (bp, bs, hybrid) in pins.items():
        s = characterize(app, backends=("planner",))["planner"].summary
        assert (s["bp_cycles"], s["bs_cycles"], s["hybrid_cycles"]) == \
            (bp, bs, hybrid), app
    aes = characterize("aes", backends=("planner",))["planner"].summary
    assert aes["hybrid_speedup"] >= 2.66  # DP >= published hand schedule


def test_vgg_alias_resolves():
    assert get_workload("vgg").name == "vgg16"


# ------------------------------------------------- backend protocol --------

def test_all_backends_satisfy_protocol():
    vgg = get_workload("vgg16")
    for name, cls in BACKENDS.items():
        b = cls()
        assert isinstance(b, Backend), name
        assert b.name == name
        assert isinstance(b.supports(vgg), bool)


def test_executor_backend_matches_executed_programs():
    """ExecutorBackend on Table-5 IR workloads reports exactly the
    micro-op program cycle counts (single batch at N=1024)."""
    from repro.pim import programs as pr

    for name in ("vector_add", "multu", "bitcount", "gt_0"):
        rep = ExecutorBackend().estimate(microkernel_workload(name))
        (row,) = rep.ops
        assert row.supported
        assert row.bp_cycles == pr.build(name, Layout.BP, width=16).cycles
        assert row.bs_cycles == pr.build(name, Layout.BS, width=16).cycles
    # documented calibration deltas surface in the report notes
    rep = ExecutorBackend().estimate(microkernel_workload("gt_0"))
    assert any("delta" in n for n in rep.notes)


def test_executor_backend_unsupported_kernels_are_flagged():
    rep = ExecutorBackend().estimate(microkernel_workload("divu"))
    (row,) = rep.ops
    assert not row.supported and "no micro-op program" in row.note
    assert rep.summary["coverage"] == 0.0


def test_executor_backend_lowers_vgg_macs():
    """The acceptance workload: executor coverage on VGG is total (every
    conv/matmul op lowers to multu + vector_add programs)."""
    rep = ExecutorBackend().estimate(get_workload("vgg"))
    assert rep.summary["coverage"] == 1.0
    assert rep.summary["bp_cycles"] > 0 and rep.summary["bs_cycles"] > 0


def test_planner_backend_schedule_maps_back_to_ops():
    rep = PlannerBackend().estimate(get_workload("aes"))
    assert all(r.note.startswith("sched=") for r in rep.ops)
    assert rep.summary["is_hybrid"]


def test_characterize_entry_point_accepts_instances_and_names():
    import repro

    w = get_workload("mk/vector_add")
    out = repro.characterize(w, backends=("analytic", AnalyticBackend()))
    assert set(out) == {"analytic"}
    out = characterize("mk/vector_add", backends=("analytic", "executor"))
    assert set(out) == {"analytic", "executor"}


def test_unknown_workload_and_backend_raise():
    with pytest.raises(KeyError, match="unknown workload"):
        get_workload("nope")
    with pytest.raises(KeyError, match="unknown backend"):
        characterize("aes", backends=("nope",))


def test_pallas_backend_measures_matmul_tiles():
    from repro.workloads import PallasBackend

    rep = PallasBackend().estimate(get_workload("gemv"))
    (row,) = rep.ops
    assert row.supported and row.bp_us > 0 and row.bs_us > 0
    assert rep.summary["measured_ops"] == 1
    # un-clamped: true and padded dims are both on the report (gemv is
    # 1 x 4096 x 512; padding only lifts m to the sublane minimum)
    assert row.dims == (1, 4096, 512)
    assert row.padded_dims[1:] == (4096, 512) and row.padded_dims[0] >= 1


def test_pallas_backend_conv_dims_match_executor_lowering():
    """PR-9 regression: conv lowers to the im2col GEMV ExecutorBackend
    prices -- (op.n, op.k, 1) -- not the (op.n, op.k, op.n) square the
    old `m, k, n = op.n, op.k, op.n` bug measured."""
    from repro.core.cost_model import Layout
    from repro.plan.pallas import step_for
    from repro.workloads import PallasBackend

    be = PallasBackend()
    vgg_convs = [op for op in get_workload("vgg").ops if op.kind == "conv"]
    assert vgg_convs
    for op in vgg_convs:
        assert step_for(op, Layout.BP, None).dims == (op.n, op.k, 1)
    # and the full estimate records those dims on every conv row, even
    # ones too large to measure (over budget -> honest modelled row)
    rep = be.estimate(get_workload("vgg13"))
    conv_rows = [r for r in rep.ops if r.kind == "conv"]
    by_name = {op.name: op for op in get_workload("vgg13").ops}
    for r in conv_rows:
        op = by_name[r.op]
        assert r.dims == (op.n, op.k, 1)
        if not r.supported:
            assert "over budget" in r.note


def test_pallas_backend_runs_true_width_and_rejects_over_32():
    """PR-9 regression: no `min(width, 8)` clamp. A 16-bit op really
    runs 16 plane passes (the note says so); width > 32 is an honest
    unsupported row, never a silently narrowed launch."""
    from repro.workloads import PallasBackend, Workload as W

    w16 = W(name="w16", ops=(
        Op(name="mm", kind="matmul", m=4, k=64, n=64, width=16),))
    rep = PallasBackend(reps=1).estimate(w16)
    (row,) = rep.ops
    assert row.supported and "@16b" in row.note

    w48 = W(name="w48", ops=(
        Op(name="mm", kind="matmul", m=4, k=64, n=64, width=48),))
    rep = PallasBackend(reps=1).estimate(w48)
    (row,) = rep.ops
    assert not row.supported and "unsupported: width 48" in row.note
    assert row.dims == (4, 64, 64)


def test_pallas_backend_over_budget_row_reports_padded_work():
    from repro.workloads import PallasBackend, Workload as W

    w = W(name="big", ops=(
        Op(name="mm", kind="matmul", m=512, k=512, n=512, width=8),))
    rep = PallasBackend(max_macs=2 ** 20).estimate(w)
    (row,) = rep.ops
    assert not row.supported and "over budget" in row.note
    assert row.dims == (512, 512, 512)
    assert row.padded_dims is not None
    assert rep.summary["measured_ops"] == 0


# ------------------------------------------------- arch (advisor) route ----

def test_arch_workload_and_advisor_shim():
    """`advisor.arch_op_trace` emits a single DeprecationWarning and
    returns rows identical to the IR route; `advise_op` classifies IR
    ops and legacy OpTraces identically."""
    from repro.configs import get_config
    from repro.core.advisor import OpTrace, advise_op, arch_op_trace
    from repro.workloads import arch_workload

    cfg = get_config("tinyllama_1_1b")
    w = arch_workload(cfg)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        legacy = arch_op_trace(cfg)
    assert len([x for x in rec
                if issubclass(x.category, DeprecationWarning)]) == 1
    assert [(t.name, t.m, t.k, t.n, t.weight_bits, t.control_intensity)
            for t in legacy] == \
        [(o.name, o.m, o.k, o.n, o.width, o.control_intensity)
         for o in w.ops]
    for t, o in zip(legacy, w.ops):
        assert advise_op(t) == advise_op(o)
    assert isinstance(legacy[0], OpTrace)


def test_arch_workloads_registered():
    names = workload_names("arch")
    assert "arch/tinyllama_1_1b" in names and len(names) == 11
    w = get_workload("arch/tinyllama_1_1b")
    assert all(op.kind == "matmul" for op in w.ops)


# ------------------------------------------------- deprecation shims -------

@pytest.mark.parametrize("app", sorted(apps.APP_TRACES))
def test_apps_shims_warn_once_and_match_ir(app):
    """Every old `core.apps` constructor emits exactly one
    DeprecationWarning and returns the IR lowering verbatim."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = apps.APP_TRACES[app]()
    assert len([x for x in rec
                if issubclass(x.category, DeprecationWarning)]) == 1
    assert old == get_workload(app).to_phases()


def test_vgg_trace_shim_honours_which():
    with pytest.warns(DeprecationWarning):
        assert apps.vgg_trace("vgg19") == get_workload("vgg19").to_phases()


def test_evaluate_all_does_not_warn():
    """The supported APIs route through the IR without deprecation."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        res = apps.evaluate_all()
    assert len(res) == 22


# ------------------------------------------------- IR lowering details -----

def test_workload_cost_equals_sum_of_phases():
    w = get_workload("fir")
    for layout in (Layout.BP, Layout.BS):
        total = w.cost(layout).total
        phases = w.to_phases()
        key = "bp_cycles" if layout is Layout.BP else "bs_cycles"
        assert total == sum(getattr(p, key) for p in phases)


def test_op_validation():
    with pytest.raises(ValueError, match="unknown op kind"):
        Op(name="x", kind="bogus")
    with pytest.raises(ValueError, match="microkernel name"):
        Op(name="x", kind="kernel")
    with pytest.raises(ValueError, match="positive dims"):
        Op(name="x", kind="matmul", m=1, n=8)  # forgot k
    with pytest.raises(ValueError, match="positive dims"):
        Op(name="x", kind="conv", n=8)  # forgot taps
    with pytest.raises(ValueError, match="no ops"):
        from repro.workloads import Workload
        Workload(name="empty", ops=())


def test_matmul_streamed_vs_chunked_phase_shapes():
    chunked = Op(name="mm", kind="matmul", m=1, k=512, n=512, chunk=64)
    streamed = Op(name="mm", kind="matmul", m=64, k=64, n=64, chunk=0)
    assert len(op_phases(chunked)) == 3
    assert len(op_phases(streamed)) == 1


def test_planner_dp_still_beats_or_ties_statics():
    """Sanity over the whole registry: the DP never loses to a static."""
    for app in workload_names("table6"):
        p = plan(get_workload(app).to_phases())
        assert p.total_cycles <= min(p.static_bp, p.static_bs)


# ------------------------------------------------- CLI --------------------

def test_cli_list(capsys):
    from repro.__main__ import main

    assert main(["list", "--source", "table6"]) == 0
    out = capsys.readouterr().out
    assert "vgg16" in out and "aes" in out and "backends" in out


def test_cli_characterize_acceptance(capsys):
    """The ISSUE-3 acceptance command, in-process."""
    from repro.__main__ import main

    rc = main(["characterize", "vgg",
               "--backends", "analytic,planner,executor"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "[analytic]" in out and "[planner]" in out \
        and "[executor]" in out
    assert "hybrid_cycles" in out and "bs_cycles" in out


def test_cli_characterize_quick_writes_artifact(tmp_path, monkeypatch,
                                                capsys):
    from repro.__main__ import main

    monkeypatch.setenv("REPRO_BENCH_ARTIFACT_DIR", str(tmp_path))
    assert main(["characterize", "--quick", "--backends", "analytic"]) == 0
    env = json.loads((tmp_path / "characterize.json").read_text())
    assert env["artifact"] == "characterize"
    assert env["schema_version"] == 1
    data = env["payload"]
    assert len(data) == len(workload_names("table5")) \
        + len(workload_names("table6"))
    assert data["aes"]["analytic"]["bp_cycles"] == 18624
    capsys.readouterr()


def test_cli_tables_matches_golden(capsys):
    from repro.__main__ import main

    assert main(["tables"]) == 0
    out = capsys.readouterr().out
    golden = (Path(__file__).parent / "golden" / "paper_tables.txt")
    assert out == golden.read_text()


def test_cli_module_entrypoint_subprocess():
    """`python -m repro` works as shipped (the CI smoke invocation)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro", "characterize", "mk/vector_add",
         "--backends", "analytic"],
        capture_output=True, text=True, timeout=120,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
    )
    assert r.returncode == 0, r.stderr
    assert "mk/vector_add" in r.stdout


# ------------------------------------------------- choose_layout fix -------

def test_choose_layout_flips_for_deep_contractions():
    """Regression for the ISSUE-3 satellite: `working_set_bits` used to
    be hardcoded to `weight_bits * 4`, ignoring the dims -- every 4-bit
    matmul classified BS regardless of contraction depth.  The footprint
    is now the real weight-stationary operand set (k*width + double-width
    accumulator), so deep-k matmuls overflow the 128-row BS column and
    flip to BP (Challenge 2)."""
    from repro.kernels.ops import choose_layout
    from repro.workloads import matmul_working_set_bits

    shallow = choose_layout(weight_bits=4, m=128, n=128, k=16)
    deep = choose_layout(weight_bits=4, m=128, n=128, k=2048)
    assert shallow.value == "BS"
    assert deep.value == "BP"
    assert shallow != deep  # the flip the old hardcoding could not produce
    # footprint actually tracks k
    assert matmul_working_set_bits(2048, 4) > \
        matmul_working_set_bits(16, 4) > 4 * 4
    # the existing dispatch operating points keep their recommendations
    assert choose_layout(weight_bits=2, m=128, n=128, k=64).value == "BS"
    assert choose_layout(weight_bits=8, m=128, n=128, k=64).value == "BP"
