"""mellum2_12b_a2_5b: the layer table, the traced step it is checked
against, the harness's acceptance of the cell, and its per-layer metric."""
from pathlib import Path

import pytest

from bench import harness
from bench.spec import load_cell

ROOT = Path(__file__).resolve().parents[2]
CELL = "mellum2_12b_a2_5b.decode256_kv4096_int8"


@pytest.fixture(scope="module")
def built():
    """(cell, workload, steps, schedule) at the published widths; tracing
    is abstract and the schedule is lowered, never run."""
    cell = load_cell(ROOT, CELL)
    return (cell, *harness.build(cell))


def _expert(name: str) -> bool:
    return name.split("#")[0] in ("w_gate", "w_up", "w_down")


def test_layer_table_macs(built):
    cell = built[0]
    layers = cell.builder.layers(cell.config, cell.traffic)
    assert sum(l.macs for l in layers) == 107_592_286_208
    assert sum(l.macs for l in layers if _expert(l.name)) == 12_683_575_296


def test_traced_macs_are_the_table_with_experts_at_capacity(built):
    """Attention, projections, router and LM head trace to the table's
    MACs exactly; the experts to the table's x 40/32: 40 capacity slots
    per held expert against the 32 tokens each sees on average."""
    cell, wl, steps, _sched = built
    layers = cell.builder.layers(cell.config, cell.traffic)
    table = sum(l.macs for l in layers if _expert(l.name))
    traced = {op.name: op.expert for op in wl.ops}
    ex = sum(s.m * s.k * s.n for s in steps if traced[s.op])
    assert ex * 32 == table * 40
    assert sum(s.m * s.k * s.n for s in steps if not traced[s.op]) == \
        sum(l.macs for l in layers if not _expert(l.name))


def test_harness_accepts_the_cell(built):
    _cell, _wl, steps, sched = built
    assert len(steps) == 233 and sched.threaded_producers() == {}
    ex = [s for s in sched.measured_steps if s.expert]
    assert len(ex) == 192 and {s.dims[0] for s in ex} == {40}
    # score chunks: 2 per sliding layer (1024 slots), 8 on the full one
    scores = [s for s in steps if s.op.split("#")[0] == "k"]
    assert len(scores) == 3 * 2 + 8
    assert {(s.m, s.k, s.n) for s in scores} == {(256 * 4 * 512, 128, 8)}


def test_expert_padded_work_ratio_reads_the_lowering_counters(built):
    from bench.metrics import expert_padded_work_ratio
    from repro import spans
    from repro.plan.pallas import mxu_passes

    sched = built[3]
    harness.build(built[0])  # the newest lowering is this cell's
    ex = [s for s in sched.measured_steps if s.expert]
    work = sum(s.padded_dims[0] * s.padded_dims[1] * s.padded_dims[2]
               * mxu_passes(s.layout, s.width) for s in ex)
    macs = sum(s.dims[0] * s.dims[1] * s.dims[2] for s in ex)
    assert spans.last("lower.expert_macs") == macs
    assert expert_padded_work_ratio.read(None) == pytest.approx(work / macs)


def test_arch_from_the_file_keeps_the_published_widths(built):
    from repro.configs import get_config

    cell = built[0]
    got = cell.builder.arch(cell.config)
    pub = get_config("mellum2_12b_a2_5b")
    for key in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "vocab_size", "n_experts", "top_k", "window", "rope_theta",
                "rope_yarn", "norm_eps"):
        assert getattr(got, key) == getattr(pub, key), key
    assert (got.n_layers, got.experts_here) == (4, 16)
    assert got.block_pattern == ("moe_local",) * 3 + ("moe",)
