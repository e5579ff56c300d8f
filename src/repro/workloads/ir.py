"""Canonical workload IR: one description, many evaluation backends.

The paper's thesis is *workload-driven* characterization, but the repo
historically described workloads five incompatible ways (Table-5
``MicroKernel``s, hand-built ``core.apps`` phase lists, ``pim.programs``
micro-op programs, the advisor's ``OpTrace``, and the Pallas entry points).
This module is the one canonical representation the others now lower from:

* :class:`Op` -- one layout-homogeneous step of a workload, carrying dims,
  precision, control intensity, and footprint.  Five kinds:

  ========== ==============================================================
  ``kernel``    a Table-5 microkernel invocation (``kernel``, ``n`` elems,
                ``width``); costed by ``repro.core.microkernels``
  ``movement``  layout-neutral row-serial bus transfer of ``bits``
  ``compute``   explicit per-layout compute cycles (``bp_cycles`` /
                ``bs_cycles``) for bespoke phases (crypto rounds, spills)
  ``matmul``    ``y[m,n] = x[m,k] @ W[k,n]`` at ``width``-bit precision;
                ``chunk>0`` lowers to the chunked-tree dot-product phases
                (load / mac / out), ``chunk=0`` to a single streamed MAC
                phase (movement charged by explicit ``movement`` ops)
  ``conv``      ``n`` window MACs of ``k`` taps each (ES-BP window reuse vs
                EP-BS column replication; Challenge 3)
  ========== ==============================================================

* :class:`Workload` -- a DAG-ordered op sequence (list order = the one
  topological order the 2-state planner DP consumes).

Lowering rules (normative; see DESIGN.md Sec. 5):

* ``op_cost(op, layout)`` -> :class:`CycleCost` (load/compute/readout) is
  the analytic lowering; for ``kernel`` ops it is exactly
  ``microkernels.kernel_cost``, so the IR path reproduces the legacy
  numbers bit-for-bit (tests/test_workloads.py golden-equivalence suite).
* ``op_phases(op)`` -> planner :class:`Phase` list is the hybrid-DP
  lowering; ``Workload.to_phases`` concatenates it over the op sequence
  and is what the deprecated ``core.apps`` trace constructors now return.
* ``Op.features()`` -> ``taxonomy.WorkloadFeatures`` is the classification
  lowering used by ``core.advisor`` and ``kernels.ops.choose_layout``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

from repro.core import cost_model as cm
from repro.core.cost_model import CycleCost, Layout
from repro.core.params import SystemParams, PAPER_SYSTEM
from repro.core.planner import Phase
from repro.core.taxonomy import WorkloadFeatures

OP_KINDS = ("kernel", "movement", "compute", "matmul", "conv")


def matmul_working_set_bits(k: int, width: int) -> int:
    """Resident per-lane footprint of a weight-stationary k-deep dot
    product: the k-element weight column held in the array (the point of
    PIM -- compute where the weights live) plus the double-width
    accumulator with its log2(k) carry growth.  This is the footprint
    ``choose_layout`` feeds the Table-8 row-overflow rule, so deep
    contractions (large k) correctly flip the recommendation to BP
    (Challenge 2) instead of the old fixed ``width * 4`` placeholder.
    """
    acc_bits = 2 * width + max(1, math.ceil(math.log2(max(2, k))))
    return k * width + acc_bits


@dataclasses.dataclass(frozen=True)
class Op:
    """One layout-homogeneous step of a workload (fields per ``kind``)."""

    name: str
    kind: str
    # -- kernel ---------------------------------------------------------
    kernel: str = ""        # Table-5 microkernel name
    # -- dims -----------------------------------------------------------
    m: int = 1              # matmul: output rows (tokens / batch)
    k: int = 0              # matmul: contraction depth; conv: window taps
    n: int = 0              # matmul: output cols; conv/kernel: elements
    width: int = 16         # operand precision (bits)
    chunk: int = 64         # matmul: tree-split chunk (0 = streamed MAC)
    in_elems: Optional[int] = None  # conv: input elements (default n)
    # -- movement -------------------------------------------------------
    bits: float = 0.0
    # -- compute (explicit per-layout cycles) ---------------------------
    bp_cycles: int = 0
    bs_cycles: int = 0
    # -- planner footprint ----------------------------------------------
    rows_bp: int = 16
    rows_bs: int = 128
    # -- classification features (None = derived from dims) -------------
    control_intensity: float = 0.0
    bit_level_fraction: Optional[float] = None
    mixed_precision: bool = False
    working_set_bits: Optional[int] = None
    latency_critical: bool = False
    # -- provenance ------------------------------------------------------
    expert: bool = False    # matmul: one expert's product with its weights

    def __post_init__(self):
        if self.kind not in OP_KINDS:
            raise ValueError(f"unknown op kind {self.kind!r} "
                             f"(one of {OP_KINDS})")
        if self.kind == "kernel" and not self.kernel:
            raise ValueError(f"op {self.name!r}: kind='kernel' needs a "
                             "microkernel name")
        if self.kind in ("matmul", "conv") and (self.k < 1 or self.n < 1
                                                or self.m < 1):
            raise ValueError(
                f"op {self.name!r}: kind={self.kind!r} needs positive dims "
                f"(got m={self.m}, k={self.k}, n={self.n})")

    # ------------------------------------------------------------------
    def dop(self) -> int:
        """Degree of parallelism (concurrent independent word-level ops)."""
        if self.kind == "matmul":
            return max(1, self.m * self.n)
        if self.kind in ("conv", "kernel"):
            return max(1, self.n)
        return 1

    def to_dict(self) -> dict:
        """Canonical field dump (every field, declaration order) -- the
        serialization the serving plan cache content-addresses, so two
        structurally identical ops always hash identically."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Op":
        return cls(**d)

    def features(self) -> WorkloadFeatures:
        """Lower to the Table-8 feature vector (``taxonomy.classify``)."""
        blf = self.bit_level_fraction
        if blf is None:
            # low-bit operands are bit-level by construction; wider ops
            # default to word-level unless annotated
            blf = 1.0 if self.width <= 2 else 0.7 if self.width <= 4 else 0.0
        ws = self.working_set_bits
        if ws is None:
            if self.kind == "matmul":
                ws = matmul_working_set_bits(self.k, self.width)
            elif self.kind == "conv":
                ws = matmul_working_set_bits(max(1, self.k), self.width)
            else:
                ws = 3 * self.width  # two operands + result resident
        return WorkloadFeatures(
            precision_bits=self.width,
            dop=self.dop(),
            control_intensity=self.control_intensity,
            bit_level_fraction=blf,
            working_set_bits=ws,
            latency_critical=self.latency_critical,
            mixed_precision=self.mixed_precision,
        )


# ---------------------------------------------------------------------------
# Analytic lowering: Op -> CycleCost / planner Phases
# ---------------------------------------------------------------------------

def _matmul_chunked_cost(op: Op, layout: Layout,
                         sys: SystemParams) -> CycleCost:
    """Chunked-tree dot products (the `core.apps` GEMV/FC lowering):
    y[m,n] = x[m,k] @ W[k,n], each length-k dot split into `chunk`-way
    partial sums reduced by a tree."""
    w, chunk = op.width, min(op.chunk, op.k)
    dop = op.m * op.n * chunk
    outs = op.m * op.n
    load = sys.xfer_cycles(op.k * op.n * w + op.m * op.k * w)
    if layout is Layout.BP:
        comp = (op.k // chunk) * (cm.bp_mult(w) + cm.BP_ADD) \
            * sys.bp_batches(dop, w) \
            + cm.reduction_bp(chunk) * sys.bp_batches(outs, w)
    else:
        comp = (op.k // chunk) * (cm.bs_mult(w) + cm.bs_add(2 * w)) \
            * sys.bs_batches(dop) \
            + cm.reduction_bs(2 * w) * sys.bs_batches(outs)
    out = sys.xfer_cycles(outs * 2 * w)
    return CycleCost(load, comp, out)


def _matmul_streamed_compute(op: Op, layout: Layout,
                             sys: SystemParams) -> int:
    """Output-stationary MAC stream (the `core.apps` GEMM lowering): k
    multiply-accumulates per output, movement charged separately."""
    w, outs = op.width, op.m * op.n
    if layout is Layout.BP:
        return op.k * (cm.bp_mult(w) + cm.BP_ADD) * sys.bp_batches(outs, w)
    return op.k * (cm.bs_mult(w) + cm.bs_add(2 * w)) * sys.bs_batches(outs)


def _conv_cost(op: Op, layout: Layout, sys: SystemParams) -> CycleCost:
    """Window MACs: ES-BP reuses window elements via logical row
    addressing (1x load); EP-BS replicates across columns for the
    horizontal extent (2x load; Challenge 3)."""
    w, n_out, taps = op.width, op.n, op.k
    in_e = n_out if op.in_elems is None else op.in_elems
    if layout is Layout.BP:
        load = sys.xfer_cycles(in_e * w + taps * w * 512)
        comp = (taps * cm.bp_mult(w) + (taps - 1) * cm.BP_ADD) \
            * sys.bp_batches(n_out, w)
    else:
        load = sys.xfer_cycles(in_e * w * 2.0 + taps * w * 512)
        comp = (taps * cm.bs_mult(w) + (taps - 1) * cm.bs_add(2 * w)) \
            * sys.bs_batches(n_out)
    out = sys.xfer_cycles(n_out * 2 * w)
    return CycleCost(load, comp, out)


def op_cost(op: Op, layout: Layout,
            sys: SystemParams = PAPER_SYSTEM) -> CycleCost:
    """Analytic load/compute/readout of one op in one static layout."""
    layout = Layout(layout)
    if op.kind == "kernel":
        from repro.core.microkernels import kernel_cost
        return kernel_cost(op.kernel, layout, n=op.n, width=op.width, sys=sys)
    if op.kind == "movement":
        return CycleCost(sys.xfer_cycles(op.bits), 0, 0)
    if op.kind == "compute":
        c = op.bp_cycles if layout is Layout.BP else op.bs_cycles
        return CycleCost(0, c, 0)
    if op.kind == "matmul":
        if op.chunk > 0:
            return _matmul_chunked_cost(op, layout, sys)
        return CycleCost(0, _matmul_streamed_compute(op, layout, sys), 0)
    if op.kind == "conv":
        return _conv_cost(op, layout, sys)
    raise AssertionError(op.kind)


def op_phases(op: Op, sys: SystemParams = PAPER_SYSTEM) -> list[Phase]:
    """Planner lowering: one op -> 1..3 layout-choice points (Phases)."""
    rows = dict(rows_bp=op.rows_bp, rows_bs=op.rows_bs)
    if op.kind in ("kernel", "compute", "movement"):
        bp = op_cost(op, Layout.BP, sys)
        bs = op_cost(op, Layout.BS, sys)
        return [Phase(op.name, bp.total, bs.total, **rows)]
    if op.kind == "conv" or (op.kind == "matmul" and op.chunk > 0):
        bp = op_cost(op, Layout.BP, sys)
        bs = op_cost(op, Layout.BS, sys)
        return [
            Phase(f"{op.name}.load", bp.load, bs.load, **rows),
            Phase(f"{op.name}.mac", bp.compute, bs.compute, **rows),
            Phase(f"{op.name}.out", bp.readout, bs.readout, **rows),
        ]
    if op.kind == "matmul":  # chunk == 0: streamed MAC only
        return [Phase(op.name, _matmul_streamed_compute(op, Layout.BP, sys),
                      _matmul_streamed_compute(op, Layout.BS, sys), **rows)]
    raise AssertionError(op.kind)


# ---------------------------------------------------------------------------
# Workload
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Workload:
    """A DAG-ordered op sequence plus provenance metadata.

    ``deps`` are explicit dependence edges ``(producer, consumer)`` over
    op indices; empty means the default linear chain (op *i* feeds op
    *i+1*).  List order must stay a topological order either way -- the
    invariant every consumer (the 2-state planner, ``repro.plan``'s DAG
    scheduler, the executor lowering) relies on, so edges must point
    forward (``producer < consumer``).
    """

    name: str
    ops: tuple[Op, ...]
    source: str = "table6"  # "table5" | "table6" | "arch" | "traced"
    description: str = ""
    #: explicit DAG edges over op indices; () = linear chain
    deps: tuple[tuple[int, int], ...] = ()

    def __post_init__(self):
        if not self.ops:
            raise ValueError(f"workload {self.name!r} has no ops")
        for a, b in self.deps:
            if not (0 <= a < b < len(self.ops)):
                raise ValueError(
                    f"workload {self.name!r}: bad dep edge ({a}, {b}) -- "
                    f"need 0 <= producer < consumer < {len(self.ops)} "
                    "(list order is the topological order)")
        if len(set(self.deps)) != len(self.deps):
            dupes = sorted({e for e in self.deps if self.deps.count(e) > 1})
            raise ValueError(
                f"workload {self.name!r}: duplicate dep edge(s) {dupes} "
                "would double-charge the boundary transpose")
        # canonicalize: deps in sorted order, as plain int tuples --
        # `to_dict()` feeds the serving plan-cache hash, which must not
        # depend on trace iteration order (the jaxpr def-use walk emits
        # edges in discovery order)
        object.__setattr__(
            self, "deps",
            tuple(sorted((int(a), int(b)) for a, b in self.deps)))

    def to_dict(self) -> dict:
        """Canonical JSON-ready form (ops in DAG order, explicit deps).

        This is the normative workload-IR serialization: the serving
        layer's plan-cache key is ``sha256`` over this dict (plus geometry
        and scheduler source), so field additions extend it automatically
        and structurally identical workloads hash identically."""
        return {
            "name": self.name,
            "source": self.source,
            "description": self.description,
            "ops": [op.to_dict() for op in self.ops],
            "deps": [list(e) for e in self.deps],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Workload":
        return cls(name=d["name"], source=d.get("source", "table6"),
                   description=d.get("description", ""),
                   ops=tuple(Op.from_dict(o) for o in d["ops"]),
                   deps=tuple((a, b) for a, b in d.get("deps", ())))

    def edges(self) -> tuple[tuple[int, int], ...]:
        """Dependence edges: ``deps`` if given, else the linear chain."""
        if self.deps:
            return self.deps
        return tuple((i, i + 1) for i in range(len(self.ops) - 1))

    def to_phases(self, sys: SystemParams = PAPER_SYSTEM) -> list[Phase]:
        """Lower to the planner's phase sequence (hybrid-DP input).

        Note: ``compute``-kind op cycles are explicit constants baked by
        the workload author (the registry bakes them at PAPER_SYSTEM
        calibration); only ``kernel``/``movement``/``matmul``/``conv``
        ops re-lower under a non-default `sys`."""
        out: list[Phase] = []
        for op in self.ops:
            out.extend(op_phases(op, sys))
        return out

    def cost(self, layout: Layout,
             sys: SystemParams = PAPER_SYSTEM) -> CycleCost:
        """Static single-layout analytic cost (summed over ops)."""
        total = CycleCost(0, 0, 0)
        for op in self.ops:
            total = total + op_cost(op, layout, sys)
        return total


def workload(name: str, ops: Sequence[Op], source: str = "table6",
             description: str = "",
             deps: Sequence[tuple[int, int]] = ()) -> Workload:
    return Workload(name=name, ops=tuple(ops), source=source,
                    description=description, deps=tuple(deps))
