"""Backend protocol + the four evaluation backends over the workload IR.

Every backend answers the same question -- "what does this workload cost?"
-- through a different lens, behind one protocol::

    class Backend(Protocol):
        name: str
        def supports(self, workload) -> bool
        def estimate(self, workload, sys=PAPER_SYSTEM) -> Report

* :class:`AnalyticBackend`  -- the paper's closed-form cycle model
  (``core.cost_model`` / ``core.microkernels``): per-op
  load/compute/readout in both static layouts.
* :class:`PlannerBackend`   -- compiles the workload DAG into an
  executable ``repro.plan`` LayoutPlan (per-step BP/BS assignment with
  explicit transposes; chains == the legacy 2-state DP bit-for-bit):
  BP/BS/hybrid + schedule, optional executor replay (``execute=True``).
* :class:`ExecutorBackend`  -- lowers ops to ``repro.pim.programs``
  micro-op programs where available and reports *executed* cycle counts;
  matmul/conv MACs decompose into ``multu`` + ``vector_add`` programs.
  Documented executed-vs-analytic calibration deltas (DESIGN.md Sec. 8)
  surface in ``OpReport.note`` and ``Report.notes``.
* :class:`PallasBackend`    -- dispatches the grid-tiled ``kernels.ops``
  Pallas matmuls over the *whole op* (padded only to hardware-minimum
  tiles, true widths, honest ``supported=False`` for over-budget or
  over-width ops) and measures wall-clock (on CPU these are
  interpret-mode correctness-path timings, as in benchmarks/).

``Report.summary`` keys shared by the cycle backends: ``bp_cycles``,
``bs_cycles`` (static totals over supported ops) plus backend-specific
extras (``hybrid_cycles``/``schedule`` for the planner, ``coverage`` for
the executor).  ``OpReport.energy_nj`` is reserved: the source paper
publishes no energy model, so no backend populates it yet.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, Union, runtime_checkable

from repro.core.cost_model import Layout
from repro.core.params import SystemParams, PAPER_SYSTEM
from repro.workloads.ir import Op, Workload, op_cost

#: version of the Report/OpReport dict schema (bump on breaking field
#: changes; ``Report.from_dict`` refuses newer versions).  Every committed
#: bench artifact (characterize.json, plans.json, serve.json) carries this
#: same version inside the ``repro.artifacts`` envelope.
REPORT_SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class OpReport:
    """Per-op result row of one backend."""

    op: str
    kind: str
    supported: bool = True
    bp_cycles: Optional[int] = None
    bs_cycles: Optional[int] = None
    #: layout -> (load, compute, readout); analytic backend only
    breakdown: Optional[dict] = None
    #: wall-clock microseconds (Pallas backend)
    bp_us: Optional[float] = None
    bs_us: Optional[float] = None
    #: reserved -- the paper publishes no energy model (DESIGN.md Sec. 5)
    energy_nj: Optional[float] = None
    #: true (m, k, n) the op lowers to, and the dims actually run after
    #: hardware-minimum tile padding (Pallas backend; additive in schema
    #: v1 -- measurements must never misstate what was run)
    dims: Optional[tuple[int, int, int]] = None
    padded_dims: Optional[tuple[int, int, int]] = None
    note: str = ""

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d["breakdown"] is not None:
            d["breakdown"] = {k: list(v) for k, v in d["breakdown"].items()}
        for key in ("dims", "padded_dims"):
            if d[key] is not None:
                d[key] = list(d[key])
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "OpReport":
        d = dict(d)
        if d.get("breakdown"):
            d["breakdown"] = {k: tuple(v)
                              for k, v in d["breakdown"].items()}
        for key in ("dims", "padded_dims"):
            if d.get(key) is not None:
                d[key] = tuple(d[key])
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class Report:
    """One backend's estimate for one workload."""

    workload: str
    backend: str
    ops: tuple[OpReport, ...]
    summary: dict
    notes: tuple[str, ...] = ()

    def to_dict(self) -> dict:
        """Versioned dict form -- the one schema all bench-artifact
        consumers parse (round-trip pinned in tests/test_serve.py)."""
        return {
            "schema_version": REPORT_SCHEMA_VERSION,
            "workload": self.workload,
            "backend": self.backend,
            "ops": [op.to_dict() for op in self.ops],
            "summary": dict(self.summary),
            "notes": list(self.notes),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Report":
        ver = d.get("schema_version", REPORT_SCHEMA_VERSION)
        if ver > REPORT_SCHEMA_VERSION:
            raise ValueError(
                f"report schema v{ver} is newer than this reader "
                f"(v{REPORT_SCHEMA_VERSION})")
        return cls(workload=d["workload"], backend=d["backend"],
                   ops=tuple(OpReport.from_dict(o) for o in d["ops"]),
                   summary=dict(d["summary"]),
                   notes=tuple(d.get("notes", ())))


@runtime_checkable
class Backend(Protocol):
    """The protocol all evaluation surfaces implement.

    `sys` is explicit everywhere: backends never silently assume
    `PAPER_SYSTEM` beyond the default argument, so sweeps can re-cost the
    same workload under any geometry (tests/test_sweep.py pins that a
    non-default geometry actually changes reported cycles).
    """

    name: str

    def supports(self, workload: Workload) -> bool:
        """Can this backend say anything useful about the workload?"""
        ...

    def estimate(self, workload: Workload,
                 sys: SystemParams = PAPER_SYSTEM) -> Report:
        ...

    def estimate_many(self, workloads,
                      sys: SystemParams = PAPER_SYSTEM) -> list[Report]:
        """Batched estimates (one geometry, many workloads)."""
        ...


class _SequentialEstimateMany:
    """Default `estimate_many`: sequential `estimate` calls.

    Backends with a vectorizable cost surface override this (the analytic
    backend batches single-kernel workloads into one jitted evaluation via
    `repro.sweep.vectorized`); the DP/replay/wall-clock backends keep the
    loop -- their per-workload state is inherently sequential.
    """

    def estimate_many(self, workloads,
                      sys: SystemParams = PAPER_SYSTEM) -> list[Report]:
        return [self.estimate(w, sys) for w in workloads]


# ---------------------------------------------------------------------------
# Analytic
# ---------------------------------------------------------------------------

class AnalyticBackend(_SequentialEstimateMany):
    """Closed-form paper cost model: per-op CycleCost in both layouts."""

    name = "analytic"

    def supports(self, workload: Workload) -> bool:
        return True

    def estimate_many(self, workloads,
                      sys: SystemParams = PAPER_SYSTEM) -> list[Report]:
        """One jitted batched evaluation when every workload is a single
        Table-5 kernel op (the ``mk/*`` registry shape); bit-for-bit equal
        to the scalar `estimate` loop (pinned by tests/test_sweep.py).
        Mixed-op workloads fall back to the sequential default."""
        workloads = list(workloads)
        if not workloads or not all(
                len(w.ops) == 1 and w.ops[0].kind == "kernel"
                for w in workloads):
            return super().estimate_many(workloads, sys)
        from repro.sweep.vectorized import eval_points

        triples = tuple((w.ops[0].kernel, w.ops[0].n, w.ops[0].width)
                        for w in workloads)
        try:
            table = eval_points(triples, cols=sys.array.cols,
                                arrays=sys.num_arrays,
                                row_bw=sys.row_bandwidth_bits)
        except ValueError:
            # operating point exceeds the int32 vectorized range --
            # the python-int scalar path has no such limit
            return super().estimate_many(workloads, sys)
        out = []
        for w, cell in zip(workloads, table):
            op = w.ops[0]
            bd = {lay.value: tuple(int(x) for x in cell[i])
                  for i, lay in enumerate((Layout.BP, Layout.BS))}
            bp = sum(bd["BP"])
            bs = sum(bd["BS"])
            out.append(Report(
                workload=w.name, backend=self.name,
                ops=(OpReport(op=op.name, kind=op.kind, bp_cycles=bp,
                              bs_cycles=bs, breakdown=bd),),
                summary={"bp_cycles": bp, "bs_cycles": bs,
                         "bs_over_bp": bs / bp if bp else float("inf")}))
        return out

    def estimate(self, workload: Workload,
                 sys: SystemParams = PAPER_SYSTEM) -> Report:
        rows = []
        tot = {Layout.BP: 0, Layout.BS: 0}
        for op in workload.ops:
            costs = {lay: op_cost(op, lay, sys)
                     for lay in (Layout.BP, Layout.BS)}
            for lay, c in costs.items():
                tot[lay] += c.total
            rows.append(OpReport(
                op=op.name, kind=op.kind,
                bp_cycles=costs[Layout.BP].total,
                bs_cycles=costs[Layout.BS].total,
                breakdown={lay.value: (c.load, c.compute, c.readout)
                           for lay, c in costs.items()}))
        bp, bs = tot[Layout.BP], tot[Layout.BS]
        return Report(
            workload=workload.name, backend=self.name, ops=tuple(rows),
            summary={"bp_cycles": bp, "bs_cycles": bs,
                     "bs_over_bp": bs / bp if bp else float("inf")})


# ---------------------------------------------------------------------------
# Planner (hybrid DP)
# ---------------------------------------------------------------------------

class PlannerBackend(_SequentialEstimateMany):
    """Compile the workload DAG into an executable ``repro.plan``
    :class:`~repro.plan.ir.LayoutPlan` (per-step BP/BS assignment with
    explicit transposes at layout boundaries; linear chains reproduce the
    legacy 2-state DP bit-for-bit).

    ``execute=True`` additionally lowers the plan's executable ops to
    their ``pim.programs`` micro-op programs in the *assigned* layout and
    replays them on the simulated-array executor; the predicted (analytic)
    vs executed cycle pairs land in ``Report.notes`` (deltas must equal
    the documented Sec.-8 calibration catalogue).
    """

    name = "planner"

    def __init__(self, execute: bool = False):
        self.execute = execute

    def supports(self, workload: Workload) -> bool:
        return True

    def compile(self, workload: Workload,
                sys: SystemParams = PAPER_SYSTEM, **kwargs):
        """Compile the workload into its :class:`~repro.plan.ir.LayoutPlan`
        (the artifact ``estimate`` summarizes).  The serving path
        (``repro.serve.PlanService``) resolves this backend through
        :func:`get_backend` and calls ``compile`` per request."""
        from repro.plan import compile_plan

        return compile_plan(workload, sys, **kwargs)

    def estimate(self, workload: Workload,
                 sys: SystemParams = PAPER_SYSTEM) -> Report:
        from repro.plan import replay_plan

        p = self.compile(workload, sys)
        rows, notes = [], []
        for oi, op in enumerate(workload.ops):
            steps = [s for s in p.steps if s.op_index == oi]
            rows.append(OpReport(
                op=op.name, kind=op.kind,
                bp_cycles=sum(s.bp_cycles for s in steps),
                bs_cycles=sum(s.bs_cycles for s in steps),
                note="sched=" + "/".join(s.layout.value for s in steps)))
        if not p.feasible:
            bad = p.infeasible_steps
            notes.append(
                f"{len(bad)} step(s) overflow the {p.geometry.label()} "
                "row budget in their assigned layout (modelled via "
                f"explicit spills): {', '.join(s.phase for s in bad[:4])}"
                + (" ..." if len(bad) > 4 else ""))
        if self.execute:
            for r in replay_plan(p, workload, sys):
                if r["predicted"] is None:
                    notes.append(f"replay {r['op']} [{r['layout']}]: "
                                 f"executed={r['executed']} ({r['note']})")
                else:
                    notes.append(
                        f"replay {r['op']} [{r['layout']}]: "
                        f"predicted={r['predicted']} "
                        f"executed={r['executed']} delta={r['delta']:+d} "
                        f"(expected {r['expected_delta']:+d})")
        return Report(
            workload=workload.name, backend=self.name, ops=tuple(rows),
            summary={
                "bp_cycles": p.static_bp,
                "bs_cycles": p.static_bs,
                "hybrid_cycles": p.total_cycles,
                "hybrid_speedup": p.hybrid_speedup,
                "is_hybrid": p.is_hybrid,
                "n_transposes": p.n_transposes,
                "transpose_cycles": p.transpose_cycles_total,
                "best_static_layout": p.best_static_layout.value,
            },
            notes=tuple(notes))


# ---------------------------------------------------------------------------
# Executor (micro-op programs on the simulated array)
# ---------------------------------------------------------------------------

class ExecutorBackend(_SequentialEstimateMany):
    """Executed micro-op cycle counts (``repro.pim.programs``).

    Coverage: ``kernel`` ops with a builder in ``programs.BUILDERS`` run
    directly; ``matmul``/``conv`` MACs lower to k x ``multu`` +
    (k-1) x ``vector_add`` programs per output batch.  ``movement`` and
    bespoke ``compute`` ops have no micro-op program (the bus and the
    hand-calibrated crypto rounds are modelled analytically only) and are
    reported unsupported; ``summary["coverage"]`` is the supported-op
    fraction.
    """

    name = "executor"

    def supports(self, workload: Workload) -> bool:
        return any(self._op_supported(op) for op in workload.ops)

    @staticmethod
    def _op_supported(op: Op) -> bool:
        from repro.pim import programs as pr

        if op.kind in ("matmul", "conv"):
            return True
        return (op.kind == "kernel"
                and (op.kernel, Layout.BP) in pr.BUILDERS
                and (op.kernel, Layout.BS) in pr.BUILDERS)

    @staticmethod
    def _mac_cycles(op: Op, layout: Layout, sys: SystemParams) -> int:
        """k multiplies + (k-1) double-width accumulates per output,
        times capacity batches over the outputs."""
        from repro.pim import programs as pr

        k = op.k
        outs = op.m * op.n if op.kind == "matmul" else op.n
        mult = pr.build("multu", layout, width=op.width).cycles
        add = pr.build("vector_add", layout, width=2 * op.width).cycles
        batches = (sys.bp_batches(outs, op.width) if layout is Layout.BP
                   else sys.bs_batches(outs))
        return (k * mult + (k - 1) * add) * batches

    def estimate(self, workload: Workload,
                 sys: SystemParams = PAPER_SYSTEM) -> Report:
        from repro.pim import programs as pr

        rows, notes = [], []
        tot = {Layout.BP: 0, Layout.BS: 0}
        supported = 0
        for op in workload.ops:
            if op.kind == "kernel" and self._op_supported(op):
                cyc, note_parts = {}, []
                for lay in (Layout.BP, Layout.BS):
                    n_eff = op.n if op.kernel == "reduction" \
                        and lay is Layout.BP else None
                    prog = pr.build(op.kernel, lay, width=op.width, n=n_eff)
                    batches = (sys.bp_batches(op.n, op.width)
                               if lay is Layout.BP else sys.bs_batches(op.n))
                    cyc[lay] = prog.cycles * batches
                    if prog.expected_delta:
                        note_parts.append(
                            f"{lay.value}: delta={prog.expected_delta:+d} "
                            f"({prog.calibration_note})")
                note = "; ".join(note_parts)
                if note:
                    notes.append(f"{op.name}: {note}")
                rows.append(OpReport(op=op.name, kind=op.kind,
                                     bp_cycles=cyc[Layout.BP],
                                     bs_cycles=cyc[Layout.BS], note=note))
            elif op.kind in ("matmul", "conv"):
                cyc = {lay: self._mac_cycles(op, lay, sys)
                       for lay in (Layout.BP, Layout.BS)}
                rows.append(OpReport(
                    op=op.name, kind=op.kind, bp_cycles=cyc[Layout.BP],
                    bs_cycles=cyc[Layout.BS],
                    note="lowered to multu + vector_add programs"))
            else:
                why = ("no micro-op program for kernel "
                       f"{op.kernel!r}" if op.kind == "kernel" else
                       f"{op.kind} ops are modelled analytically only")
                rows.append(OpReport(op=op.name, kind=op.kind,
                                     supported=False, note=why))
                continue
            supported += 1
            tot[Layout.BP] += rows[-1].bp_cycles
            tot[Layout.BS] += rows[-1].bs_cycles
        return Report(
            workload=workload.name, backend=self.name, ops=tuple(rows),
            summary={"bp_cycles": tot[Layout.BP], "bs_cycles": tot[Layout.BS],
                     "coverage": supported / len(workload.ops),
                     "supported_ops": supported, "total_ops": len(workload.ops)},
            notes=tuple(notes))


# ---------------------------------------------------------------------------
# Pallas (measured wall-clock of the TPU-analogue kernels)
# ---------------------------------------------------------------------------

class PallasBackend(_SequentialEstimateMany):
    """Measure wall-clock of the grid-tiled Pallas kernels over the
    *whole op* in both layouts: the BP word kernel vs the fused BS
    bitpack-matmul at the op's **true** weight precision (one plane pass
    per bit -- never capped).  Each side is the step the schedule
    lowering makes of the op (``plan.pallas.step_for``: BP arrival, and
    a ``bp2bs`` boundary into BS), timed as ``plan.pallas.apply`` on
    weights ``plan.pallas.hold`` made (``apply_jit``) -- the dims, tiles, width limit
    and budget the compiled schedule program runs.  Both the true and
    the padded dims land in the ``OpReport`` so a report can never
    misstate what was run.  Ops either step cannot run -- no Pallas
    lowering for the op's kind, a width over the kernels' 32-plane
    limit, or padded MAC work over ``max_macs`` -- report
    ``supported=False`` with that step's note instead of a clamped or
    understated number.  Timings are the median of ``reps``
    post-warmup calls with ``block_until_ready`` (never a single cold
    wall-clock sample)."""

    name = "pallas"

    def __init__(self, reps: int = 5, max_macs: Optional[int] = None):
        self.reps = reps
        self.max_macs = max_macs

    def supports(self, workload: Workload) -> bool:
        return any(op.kind in ("matmul", "conv") for op in workload.ops)

    def estimate(self, workload: Workload,
                 sys: SystemParams = PAPER_SYSTEM) -> Report:
        import numpy as np
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops as kops
        from repro.plan.pallas import (DEFAULT_MAX_MACS, apply_jit, clock,
                                       hold, step_for)

        del sys  # wall-clock backend: the host, not the modelled system
        max_macs = (DEFAULT_MAX_MACS if self.max_macs is None
                    else self.max_macs)
        rng = np.random.default_rng(0)
        rows = []
        tot_bp = tot_bs = 0.0
        measured = 0
        for op in workload.ops:
            bp = step_for(op, Layout.BP, None, max_macs=max_macs)
            bs = step_for(op, Layout.BS, "bp2bs", fuse_pack=True,
                          max_macs=max_macs)
            bad = next((s for s in (bs, bp) if not s.measured), None)
            if bad is not None:
                rows.append(OpReport(
                    op=op.name, kind=op.kind, supported=False,
                    dims=bad.dims, padded_dims=bad.padded_dims,
                    note=bad.note))
                continue
            m, k, n = bp.dims
            bits = op.width
            x = jnp.asarray(rng.integers(-8, 8, (m, k), dtype=np.int32)
                            ).astype(jnp.int8)
            w = rng.integers(0, 1 << min(bits, 31), (k, n)).astype(np.int32)
            bp_w, bs_w = hold(bp, w), hold(bs, w)
            bp_us = clock(lambda: apply_jit(bp, x, bp_w), self.reps)
            bs_us = clock(lambda: apply_jit(bs, x, bs_w), self.reps)
            rec = kops.choose_layout(weight_bits=bits, m=m, n=n, k=k)
            rows.append(OpReport(
                op=op.name, kind=op.kind, bp_us=bp_us, bs_us=bs_us,
                dims=(m, k, n), padded_dims=bp.padded_dims,
                note=f"{m}x{k}x{n}@{bits}b "
                     f"padded_bp={'x'.join(map(str, bp.padded_dims))} "
                     f"padded_bs={'x'.join(map(str, bs.padded_dims))} "
                     f"bs={bs.kernel}; choose_layout={rec.value}"))
            tot_bp += bp_us
            tot_bs += bs_us
            measured += 1
        return Report(
            workload=workload.name, backend=self.name, ops=tuple(rows),
            summary={"bp_us": tot_bp, "bs_us": tot_bs,
                     "measured_ops": measured, "total_ops": len(workload.ops),
                     "coverage": measured / len(workload.ops)},
            notes=(f"wall-clock of Pallas kernels on {jax.default_backend()}"
                   " over full op dims (interpret mode on the CPU: a "
                   "correctness path, not a speed; see "
                   "benchmarks/pallas_bench)",)
            if measured else ())


# ---------------------------------------------------------------------------
# Registry + the single entry point
# ---------------------------------------------------------------------------

#: the registered name -> class table every construction site resolves
#: through (:func:`get_backend`); CLI ``--backends`` choices are generated
#: from it.  Register new backends here (or via :func:`register_backend`)
#: instead of importing classes directly -- direct backend imports are a
#: deprecated construction path (DESIGN.md Sec. 5).
BACKENDS: dict[str, type] = {
    "analytic": AnalyticBackend,
    "planner": PlannerBackend,
    "executor": ExecutorBackend,
    "pallas": PallasBackend,
}


def register_backend(name: str, cls: type) -> None:
    """Register a Backend class under ``name`` (overwrites allowed so
    tests can shadow a backend with an instrumented double)."""
    BACKENDS[name] = cls


def backend_names() -> list[str]:
    """Registered backend names, sorted (the CLI choice list)."""
    return sorted(BACKENDS)


def get_backend(spec: Union[str, Backend], **opts) -> Backend:
    """THE backend factory: resolve a registry name (with constructor
    options) or pass an already-built instance through.

    ``get_backend("planner", execute=True)`` ==
    ``PlannerBackend(execute=True)`` without importing the class --
    `__main__`, ``characterize``, benchmarks, and the serving path all
    construct backends this way.
    """
    if isinstance(spec, str):
        try:
            cls = BACKENDS[spec]
        except KeyError:
            raise KeyError(f"unknown backend {spec!r} "
                           f"(known: {', '.join(backend_names())})") from None
        return cls(**opts)
    if opts:
        raise TypeError("constructor options only apply to registry names, "
                        f"not already-built instances ({spec!r})")
    return spec


def characterize(workload: Union[str, Workload],
                 backends=("analytic", "planner"),
                 sys: SystemParams = PAPER_SYSTEM) -> dict[str, Report]:
    """THE entry point: one workload, many backends -> {backend: Report}.

    `workload` is a registry name (e.g. ``"vgg"``, ``"mk/multu"``,
    ``"arch/tinyllama_1_1b"``) or a :class:`Workload` instance; `backends`
    is a sequence of registry names and/or Backend instances.
    """
    from repro.workloads.registry import get_workload

    w = get_workload(workload) if isinstance(workload, str) else workload
    out: dict[str, Report] = {}
    for spec in backends:
        b = get_backend(spec)
        out[b.name] = b.estimate(w, sys)
    return out
