"""Differential reconciliation: ``traced/<id>`` vs the ``arch/<id>`` formulas.

The hand-written serving formulas (``registry.arch_workload``) and the
jaxpr tracer (``trace.trace_workload``) describe the same forward pass
from opposite ends.  This module pins them against each other, op by op:

* every formula op is matched to a traced op by its *predicted* traced
  dims (:func:`expected_matmuls` -- the normative catalogue, mirrored in
  DESIGN.md Sec. 12);
* ``exact`` matches (identical m/k/n/width, so identical cost inputs)
  must agree to the cycle on every static backend
  (:data:`GATED_BACKENDS`);
* ``divergent`` matches carry a documented reason (flash chunking,
  per-expert capacity GEMMs, all-head SSD contraction, ...) and their
  deltas are recorded, never asserted;
* every *remaining* traced op must be explained by a lowering rule
  (:func:`_extra_note`) -- sibling projections, PV chunks, the other
  experts' products, cache movement -- or the gate fails.

:func:`run_diff` drives the full matrix and :func:`write_csv` emits the
``bench-artifacts/traced_vs_formula.csv`` artifact (per-op and TOTAL
rows per backend).  CLI: ``python -m repro trace-diff``.
"""
from __future__ import annotations

import csv
import dataclasses
import math
from typing import Optional, Sequence

from repro.core.params import PAPER_SYSTEM, SystemParams
from repro.workloads.backends import characterize
from repro.workloads.ir import Op, Workload
from repro.workloads.registry import ARCH_IDS, arch_workload, get_workload

__all__ = ["GATED_BACKENDS", "CSV_COLUMNS", "Expected", "OpRow",
           "expected_matmuls", "expected_vgg", "reconcile",
           "reconcile_vgg", "gate_failures", "run_diff", "write_csv"]

#: static backends on which an ``exact`` match must agree to the cycle
GATED_BACKENDS = ("analytic", "planner", "executor")


@dataclasses.dataclass(frozen=True)
class Expected:
    """Predicted traced counterpart of one formula op."""

    formula: str  # formula op name (arch_workload / _vgg_ops)
    kind: str  # "matmul" | "conv"
    dims: tuple  # matmul: (m, k, n, width); conv: (n, k)
    status: str  # "exact" | "divergent"
    note: str = ""


@dataclasses.dataclass(frozen=True)
class OpRow:
    """One CSV row: a formula/traced op pair (or one unmatched side)."""

    arch: str
    backend: str
    status: str  # exact | divergent | missing | traced-only | total
    op_formula: str
    op_traced: str
    kind: str
    m_formula: Optional[int] = None
    k_formula: Optional[int] = None
    n_formula: Optional[int] = None
    w_formula: Optional[int] = None
    m_traced: Optional[int] = None
    k_traced: Optional[int] = None
    n_traced: Optional[int] = None
    w_traced: Optional[int] = None
    bp_formula: Optional[float] = None
    bs_formula: Optional[float] = None
    bp_traced: Optional[float] = None
    bs_traced: Optional[float] = None
    bp_delta: Optional[float] = None
    bs_delta: Optional[float] = None
    unit: str = "cycles"  # cycles | us
    explained: bool = True
    note: str = ""


CSV_COLUMNS = [f.name for f in dataclasses.fields(OpRow)]


# ---------------------------------------------------------------------------
# The expected-dims catalogue (DESIGN.md Sec. 12)
# ---------------------------------------------------------------------------

def _flash_chunk(seq: int) -> int:
    """KV chunk used by ``models.layers.flash_attention``: the largest
    divisor of ``seq`` that is <= ``util.flash_chunk_default()``."""
    from repro.util import flash_chunk_default

    chunk = min(flash_chunk_default(), seq)
    while seq % chunk:
        chunk -= 1
    return chunk


def _capacity(cfg, tokens: int) -> int:
    """Slots per expert, as ``models.layers.expert_capacity`` computes
    them for ``tokens`` decode sequences."""
    return int(math.ceil(tokens * cfg.top_k * cfg.capacity_factor
                         / cfg.n_experts))


def expected_matmuls(cfg, *, tokens: int = 4096,
                     weight_bits: int = 4) -> list[Expected]:
    """Predicted traced dims for every ``arch_workload`` formula op, in
    formula order.  ``exact`` entries equal the formula's own dims;
    ``divergent`` entries are the documented lowering differences."""
    T, D, wb = tokens, cfg.d_model, weight_bits
    out: list[Expected] = []
    if cfg.family == "ssm":
        din = cfg.d_inner
        proj = 2 * din + 2 * cfg.ssm_state + cfg.ssm_heads
        out.append(Expected("in_proj", "matmul", (T, D, proj, wb), "exact"))
        out.append(Expected(
            "ssd_scan", "matmul",
            (T, cfg.ssm_state, cfg.ssm_heads * cfg.ssm_head_dim, 16),
            "divergent",
            "formula scores one SSM head (n=head_dim); the trace contracts "
            "all heads in one state-readout einsum (n = heads x head_dim)"))
        out.append(Expected("out_proj", "matmul", (T, din, D, wb), "exact"))
        return out
    if cfg.n_heads and cfg.n_kv_heads:
        chunk = _flash_chunk(T)
        group = cfg.n_heads // cfg.n_kv_heads
        out.append(Expected("qkv_proj", "matmul",
                            (T, D, cfg.qkv_dim, wb), "exact"))
        out.append(Expected(
            "attn_scores", "matmul",
            (T * cfg.n_kv_heads * chunk, cfg.head_dim, group, 16),
            "divergent",
            f"formula scores a dense TxT map; the trace is flash-chunked "
            f"(chunk={chunk}) per KV head, {group} query heads per KV "
            f"head"))
        out.append(Expected("o_proj", "matmul",
                            (T, cfg.n_heads * cfg.head_dim, D, wb),
                            "exact"))
    if cfg.n_experts:
        cap = _capacity(cfg, T)
        out.append(Expected("router", "matmul",
                            (T, D, cfg.n_experts, 16), "exact"))
        out.append(Expected(
            "expert_ffn", "matmul", (cap, D, cfg.d_ff, wb), "divergent",
            "formula scores a token-major top_k*T GEMM; the trace is one "
            f"GEMM per held expert over its {cap} capacity slots"))
    elif cfg.d_ff:
        out.append(Expected("ffn", "matmul", (T, D, cfg.d_ff, wb),
                            "exact"))
    if cfg.family == "hybrid":
        width = cfg.lru_width
        out.append(Expected("rg_lru_gates", "matmul",
                            (T, width, width, 16), "exact"))
    return out


def expected_vgg(which: str = "vgg16") -> list[Expected]:
    """Predicted traced dims for the Table-6 VGG formula ops."""
    from repro.models.vgg import VGG_BATCH, VGG_BLOCKS, VGG_FCS

    out: list[Expected] = []
    c_in = 3
    for bi, (c, s, reps) in enumerate(VGG_BLOCKS[which]):
        n_out = c * s * s * VGG_BATCH
        for r in range(reps):
            out.append(Expected(
                f"b{bi}c{r}", "conv", (n_out, 9 * c_in), "divergent",
                "formula counts the 3x3 spatial taps (k=9); the trace "
                "contracts taps x C_in"))
            c_in = c
    for fi, (k, n) in enumerate(VGG_FCS):
        out.append(Expected(
            f"fc{fi}", "matmul", (VGG_BATCH, k, n, 16), "divergent",
            "formula scores one image (m=1); the trace batches "
            f"{VGG_BATCH} images"))
    return out


def _extra_note(op: Op, cfg, tokens: int,
                weight_bits: int) -> Optional[str]:
    """Explain a traced op with no formula counterpart; None = unexplained
    (gate failure)."""
    if op.kind == "compute":
        return ("activation/normalization arithmetic the formulas fold "
                "into control_intensity")
    if op.kind == "movement":
        return "KV/state cache update; the formulas model compute only"
    if op.kind != "matmul":
        return None
    T, D, wb = tokens, cfg.d_model, weight_bits
    fdims = {D, cfg.qkv_dim, cfg.n_heads * cfg.head_dim, cfg.d_ff,
             cfg.padded_vocab, cfg.lru_width, cfg.n_experts}
    if cfg.ssm_state:
        fdims |= {cfg.d_inner,
                  2 * cfg.d_inner + 2 * cfg.ssm_state + cfg.ssm_heads}
    fdims.discard(0)
    if (op.m == T and op.k in fdims and op.n in fdims
            and op.width in (wb, 16)):
        return ("per-token linear projection (sibling/down/head of a "
                "formula op)")
    chunks = {_flash_chunk(T)}
    if cfg.enc_seq:  # cross-attention reads the encoder sequence
        chunks.add(_flash_chunk(cfg.enc_seq))
    if cfg.window:  # local layers read a window-slot ring buffer
        chunks.add(_flash_chunk(min(cfg.window, T)))
    if cfg.n_heads and cfg.n_kv_heads:
        group = cfg.n_heads // cfg.n_kv_heads
        for chunk in chunks:
            if (op.width == 16 and op.m == T * cfg.n_kv_heads * chunk
                    and op.k == cfg.head_dim and op.n == group):
                return f"flash-attention score chunk (chunk={chunk})"
            if (op.width == 16 and op.m == T * cfg.n_heads
                    and op.k == chunk and op.n == cfg.head_dim):
                return f"flash-attention PV chunk (chunk={chunk})"
    if cfg.ssm_state:
        if (op.width == 16 and op.k == 1 and op.n == cfg.ssm_state
                and op.m == T * cfg.d_inner):
            return "SSD state outer-product update (rank-1 per channel)"
    if cfg.n_experts and op.expert and op.width == wb:
        cap = _capacity(cfg, T)
        if (op.m, op.k, op.n) == (cap, D, cfg.d_ff):
            return "expert up/gate projection (expert_ffn sibling)"
        if (op.m, op.k, op.n) == (cap, cfg.d_ff, D):
            return "expert down projection"
    return None


# ---------------------------------------------------------------------------
# Matching + cost rows
# ---------------------------------------------------------------------------

def _op_dims(op: Op) -> tuple:
    if op.kind == "conv":
        return (op.n, op.k)
    return (op.m, op.k, op.n, op.width)


def _match(traced: Workload,
           expected: Sequence[Expected]) -> tuple[dict, set]:
    """{formula_index: traced_index | None}, consumed traced indices.
    First unconsumed traced op with exactly the predicted dims wins."""
    consumed: set[int] = set()
    pairs: dict[int, Optional[int]] = {}
    for fi, exp in enumerate(expected):
        hit = None
        for ti, op in enumerate(traced.ops):
            if (ti not in consumed and op.kind == exp.kind
                    and _op_dims(op) == exp.dims):
                hit = ti
                break
        if hit is not None:
            consumed.add(hit)
        pairs[fi] = hit
    return pairs, consumed


def _cost(report, idx: int, pallas: bool) -> tuple:
    """(bp, bs) of op `idx` in a backend Report; (None, None) if the
    backend skipped it."""
    opr = report.ops[idx]
    if not opr.supported:
        return None, None
    if pallas:
        return opr.bp_us, opr.bs_us
    return opr.bp_cycles, opr.bs_cycles


def _delta(a, b):
    if a is None or b is None:
        return None
    d = b - a
    return round(d, 3) if isinstance(d, float) else d


def reconcile(arch_id: str, *, tokens: int = 4096, weight_bits: int = 4,
              backends: Sequence[str] = GATED_BACKENDS,
              sys: SystemParams = PAPER_SYSTEM,
              traced: Optional[Workload] = None) -> list[OpRow]:
    """Per-op rows (plus a TOTAL row per backend) for one architecture."""
    from repro.configs import get_config
    from repro.models.registry import traced_workload

    cfg = get_config(arch_id)
    formula = arch_workload(cfg, tokens=tokens, weight_bits=weight_bits)
    if traced is None:
        traced = traced_workload(cfg, tokens=tokens,
                                 weight_bits=weight_bits)
    expected = expected_matmuls(cfg, tokens=tokens,
                                weight_bits=weight_bits)
    names = [e.formula for e in expected]
    assert names == [op.name for op in formula.ops], \
        f"catalogue out of sync with arch_workload: {names}"

    def extra(op):
        return _extra_note(op, cfg, tokens, weight_bits)

    return _rows(arch_id, formula, traced, expected, extra, backends, sys)


def reconcile_vgg(which: str = "vgg16", *,
                  backends: Sequence[str] = GATED_BACKENDS,
                  sys: SystemParams = PAPER_SYSTEM) -> list[OpRow]:
    """Rows for traced VGG vs the Table-6 conv/fc formula workload."""
    from repro.models.vgg import traced_vgg

    formula = get_workload(which)
    traced = traced_vgg(which)
    expected = expected_vgg(which)

    def extra(op):
        if op.kind == "compute":
            return "relu / max-pool arithmetic outside the conv formulas"
        return None

    return _rows(which, formula, traced, expected, extra, backends, sys)


def _rows(arch: str, formula: Workload, traced: Workload,
          expected: Sequence[Expected], extra_note, backends,
          sys) -> list[OpRow]:
    pairs, consumed = _match(traced, expected)
    reports_f = characterize(formula, backends, sys)
    reports_t = characterize(traced, backends, sys)
    rows: list[OpRow] = []
    for backend in reports_f:
        rep_f, rep_t = reports_f[backend], reports_t[backend]
        pallas = backend == "pallas"
        unit = "us" if pallas else "cycles"
        tot_f = [0.0, 0.0]
        tot_t = [0.0, 0.0]

        def add(tot, bp, bs):
            if bp is not None:
                tot[0] += bp
            if bs is not None:
                tot[1] += bs

        for fi, exp in enumerate(expected):
            fop = formula.ops[fi]
            bp_f, bs_f = _cost(rep_f, fi, pallas)
            add(tot_f, bp_f, bs_f)
            ti = pairs[fi]
            if ti is None:
                rows.append(OpRow(
                    arch=arch, backend=backend, status="missing",
                    op_formula=fop.name, op_traced="", kind=fop.kind,
                    m_formula=fop.m, k_formula=fop.k, n_formula=fop.n,
                    w_formula=fop.width, bp_formula=bp_f, bs_formula=bs_f,
                    unit=unit, explained=False,
                    note=f"no traced op with predicted dims {exp.dims}"))
                continue
            top = traced.ops[ti]
            bp_t, bs_t = _cost(rep_t, ti, pallas)
            add(tot_t, bp_t, bs_t)
            rows.append(OpRow(
                arch=arch, backend=backend, status=exp.status,
                op_formula=fop.name, op_traced=top.name, kind=fop.kind,
                m_formula=fop.m, k_formula=fop.k, n_formula=fop.n,
                w_formula=fop.width, m_traced=top.m, k_traced=top.k,
                n_traced=top.n, w_traced=top.width, bp_formula=bp_f,
                bs_formula=bs_f, bp_traced=bp_t, bs_traced=bs_t,
                bp_delta=_delta(bp_f, bp_t), bs_delta=_delta(bs_f, bs_t),
                unit=unit, explained=True, note=exp.note))
        for ti, top in enumerate(traced.ops):
            if ti in consumed:
                continue
            bp_t, bs_t = _cost(rep_t, ti, pallas)
            add(tot_t, bp_t, bs_t)
            note = extra_note(top)
            rows.append(OpRow(
                arch=arch, backend=backend, status="traced-only",
                op_formula="", op_traced=top.name, kind=top.kind,
                m_traced=top.m, k_traced=top.k, n_traced=top.n,
                w_traced=top.width, bp_traced=bp_t, bs_traced=bs_t,
                unit=unit, explained=note is not None,
                note=note or "UNEXPLAINED traced op"))
        rows.append(OpRow(
            arch=arch, backend=backend, status="total", op_formula="TOTAL",
            op_traced="TOTAL", kind="", bp_formula=round(tot_f[0], 3),
            bs_formula=round(tot_f[1], 3), bp_traced=round(tot_t[0], 3),
            bs_traced=round(tot_t[1], 3),
            bp_delta=_delta(tot_f[0], tot_t[0]),
            bs_delta=_delta(tot_f[1], tot_t[1]), unit=unit,
            note=f"{len(formula.ops)} formula ops vs "
                 f"{len(traced.ops)} traced ops"))
    return rows


def gate_failures(rows: Sequence[OpRow]) -> list[str]:
    """Hard failures: unexplained traced ops, unmatched formula ops, or
    an ``exact`` pair whose static-backend cycles differ."""
    fails = []
    for r in rows:
        where = f"{r.arch}/{r.backend}"
        if not r.explained:
            who = r.op_traced or r.op_formula
            fails.append(f"{where}: {r.status} op {who!r}: {r.note}")
        elif (r.status == "exact" and r.backend in GATED_BACKENDS
              and (r.bp_delta or r.bs_delta)):
            fails.append(
                f"{where}: exact op {r.op_formula!r} disagrees "
                f"(bp {r.bp_delta:+} bs {r.bs_delta:+} {r.unit})")
    return sorted(set(fails))


def run_diff(archs: Optional[Sequence[str]] = None, *,
             tokens: int = 4096, weight_bits: int = 4,
             backends: Sequence[str] = GATED_BACKENDS,
             pallas_archs: Sequence[str] = (), include_vgg: bool = True,
             sys: SystemParams = PAPER_SYSTEM
             ) -> tuple[list[OpRow], list[str]]:
    """Reconcile ``archs`` (default: every arch) + VGG; -> (rows, failures)."""
    rows: list[OpRow] = []
    for arch in archs or ARCH_IDS:
        bks = tuple(backends)
        if arch in pallas_archs:
            bks += ("pallas",)
        rows += reconcile(arch, tokens=tokens, weight_bits=weight_bits,
                          backends=bks, sys=sys)
    if include_vgg:
        rows += reconcile_vgg(backends=backends, sys=sys)
    return rows, gate_failures(rows)


def write_csv(rows: Sequence[OpRow], path) -> None:
    with open(path, "w", newline="") as fh:
        writer = csv.writer(fh)
        writer.writerow(CSV_COLUMNS)
        for r in rows:
            writer.writerow(
                ["" if v is None else v
                 for v in dataclasses.astuple(r)])
