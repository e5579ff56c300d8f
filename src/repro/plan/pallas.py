"""Lower a :class:`LayoutPlan` to a measured Pallas kernel schedule.

``plan.lower`` replays a plan on the simulated CSA (micro-op programs);
this module is the *wall-clock* twin (DESIGN.md Sec. 14): the plan's
op-level schedule lowers to a sequence of Pallas kernel launches --
BP steps to the word matmul kernel, BS steps to the bitplane kernel,
layout boundaries to weight *repacks* (``bp2bs`` = bitpack, ``bs2bp`` =
bitunpack) -- so a hybrid plan runs as a measured kernel sequence, not
only as simulator programs.

The lowering contract:

* **Activations always flow in word (BP) form.**  The layout decision
  applies to the *stationary* weights -- exactly the paper's framing,
  where the array-resident operand carries the layout and the streamed
  operand is broadcast bit-parallel on the bitlines.
* **A layout boundary is a weight repack.**  When the plan's op-level
  layout flips BP->BS the incoming word weights are bitpacked (the
  transpose unit's read(M)+core+write(N) pass); BS->BP is a bitunpack.
  With ``fuse_pack=True`` (default) a ``bp2bs`` repack feeding a BS
  matmul is *folded into* the fused kernel -- no plane tensor is ever
  materialized, mirroring how a transpose unit feeds the array directly.
* **Only matmul/conv steps are measured.**  Conv lowers to the same
  im2col GEMV the ``ExecutorBackend`` prices (``(m, k, n) = (op.n,
  op.k, 1)``).  ``kernel``/``movement``/``compute`` ops have no Pallas
  kernel; they appear in the schedule as modelled-only rows so the
  sequence never silently drops plan steps.
* **Results are exact** (int32 wraparound semantics, see
  ``kernels/bitparallel_matmul.py``): ``run_schedule`` output is
  bit-identical to the unfused pack->matmul path and to the pim
  micro-op executor's MAC decomposition of the same op.

Ops whose *padded* MAC volume (times MXU passes: planes for BS, limbs
for BP -- :func:`mxu_passes`) exceeds ``max_macs`` are lowered as
modelled-only too -- an honest "too large to time here" note, never a
silently clamped measurement.

Every path that runs a lowered step -- the compiled program
(``plan.pallas_exec``), :func:`run_schedule`, :func:`time_schedule`,
``PallasBackend``, ``pallas-bench``, ``kernels.ops.planned_matmul`` --
makes it with :func:`step_for` and runs ``apply(step, x, hold(step, w))``;
no other code above the kernels calls them.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Optional

import jax
import numpy as np

from repro import spans
from repro.core.cost_model import Layout
from repro.plan.ir import LayoutPlan

#: kinds that lower to a Pallas matmul launch
_MEASURABLE = ("matmul", "conv")
#: widest weight the BS plane loop supports (uint32 plane words)
MAX_BS_WIDTH = 32
#: default padded-MAC budget per kernel launch (interpret-mode throughput
#: is ~10^8 MAC/s; 2^31 keeps a single launch under ~30 s)
DEFAULT_MAX_MACS = 2 ** 31


def mxu_passes(layout: Layout, width: int) -> int:
    """int8 MXU passes one step takes: a plane per bit for BS, a 7-bit
    limb per pass for BP (``kernels.bitparallel_matmul``)."""
    from repro.kernels.bitparallel_matmul import n_limbs

    return width if layout is Layout.BS else n_limbs(width)


@dataclasses.dataclass(frozen=True)
class PallasStep:
    """One op of the lowered schedule: a kernel launch or a modelled row."""

    op: str              #: workload op name
    kind: str            #: IR op kind
    layout: Layout       #: plan-assigned op-level layout
    width: int           #: weight precision (plane passes for BS)
    kernel: Optional[str]    #: Pallas kernel name; None => modelled-only
    repack: Optional[str]    #: ``bp2bs`` | ``bs2bp`` at this boundary
    dims: Optional[tuple[int, int, int]] = None         #: true (m, k, n)
    padded_dims: Optional[tuple[int, int, int]] = None  #: as padded/run
    note: str = ""
    expert: bool = False     #: one expert's product (``Op.expert``)

    @property
    def measured(self) -> bool:
        return self.kernel is not None

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["layout"] = self.layout.value
        d["measured"] = self.measured
        return d


@dataclasses.dataclass(frozen=True)
class PallasSchedule:
    """A plan lowered to an ordered Pallas kernel sequence."""

    workload: str
    steps: tuple[PallasStep, ...]
    fuse_pack: bool
    #: step-index dataflow edges (producer < consumer), copied from
    #: ``Workload.edges()`` at lowering (step i == op i); empty means
    #: "none declared" and falls back to the same linear chain the
    #: Workload IR defaults to
    deps: tuple[tuple[int, int], ...] = ()

    @property
    def measured_steps(self) -> tuple[PallasStep, ...]:
        return tuple(s for s in self.steps if s.measured)

    @property
    def n_repacks(self) -> int:
        return sum(1 for s in self.steps if s.repack)

    def edges(self) -> tuple[tuple[int, int], ...]:
        """Step-index dataflow edges, linear chain when none declared."""
        if self.deps:
            return self.deps
        return tuple((i, i + 1) for i in range(len(self.steps) - 1))

    def threaded_producers(self) -> dict[str, str]:
        """``{consumer op: producer op}`` for every measured step fed by
        an earlier *measured* step along :meth:`edges`.

        The dataflow contract shared by the chained executor
        (``plan.pallas_exec``), per-step :func:`run_schedule`, and the
        numpy :func:`reference_results`: a consumer's activation is its
        nearest measured producer's result through
        ``kernels.ops.thread_activations``.  Steps with no measured
        producer (entry steps, or steps fed only by modelled-only rows --
        there is no computed tensor to thread) consume synthetic
        operands instead.
        """
        measured = {i for i, s in enumerate(self.steps) if s.measured}
        best: dict[int, int] = {}
        for i, j in self.edges():
            if i in measured and j in measured and i < j:
                if best.get(j, -1) < i:
                    best[j] = i
        return {self.steps[j].op: self.steps[i].op
                for j, i in sorted(best.items())}

    def to_dict(self) -> dict:
        return {"workload": self.workload, "fuse_pack": self.fuse_pack,
                "n_repacks": self.n_repacks,
                "deps": [list(e) for e in self.deps],
                "steps": [s.to_dict() for s in self.steps]}


def _op_dims(op) -> tuple[int, int, int]:
    """(m, k, n) of the matmul a measurable op lowers to.

    Conv uses the ExecutorBackend lowering: ``op.n`` im2col output
    elements, each a ``op.k``-deep (taps x C_in) MAC chain -- a GEMV
    ``(op.n, op.k) @ (op.k, 1)``.  The pre-PR-9 ``(op.n, op.k, op.n)``
    mapping squared the output count.
    """
    if op.kind == "matmul":
        return (op.m, op.k, op.n)
    return (op.n, op.k, 1)


def _tiling(layout: Layout, fused: bool, m: int, k: int, n: int):
    from repro.kernels import tiling as tl

    if layout is Layout.BP:
        return tl.bp_tiling(m, k, n)
    return tl.fused_tiling(m, k, n) if fused else tl.bs_tiling(m, k, n)


def step_for(op, layout: Layout, repack: Optional[str], *,
             fuse_pack: bool = True,
             max_macs: int = DEFAULT_MAX_MACS) -> PallasStep:
    """The step ``op`` lowers to in ``layout`` behind ``repack``: its
    dims, the width limit, the tiling, the MAC budget and the kernel
    choice, or the modelled-only row that says why there is none."""
    if op.kind not in _MEASURABLE:
        return PallasStep(
            op=op.name, kind=op.kind, layout=layout, width=op.width,
            kernel=None, repack=repack,
            note="modelled only: no Pallas lowering for "
                 f"{op.kind!r} ops (DESIGN.md Sec. 14)")
    m, k, n = _op_dims(op)
    if layout is Layout.BS and op.width > MAX_BS_WIDTH:
        return PallasStep(
            op=op.name, kind=op.kind, layout=layout, width=op.width,
            kernel=None, repack=repack, dims=(m, k, n),
            note=f"unsupported: width {op.width} > {MAX_BS_WIDTH} "
                 "plane passes (uint32 plane words)")
    fused = fuse_pack and layout is Layout.BS and repack == "bp2bs"
    t = _tiling(layout, fused, m, k, n)
    passes = mxu_passes(layout, op.width)
    if t.padded_macs * passes > max_macs:
        return PallasStep(
            op=op.name, kind=op.kind, layout=layout, width=op.width,
            kernel=None, repack=repack, dims=(m, k, n),
            padded_dims=t.padded_dims,
            note=f"over budget: {t.padded_macs * passes} padded MACs "
                 f"> max_macs={max_macs} -- not timed")
    if layout is Layout.BP:
        kernel = "bitparallel_matmul"
    elif fused:
        kernel = "fused_bitserial_matmul"
    else:
        kernel = "bitserial_matmul"
    return PallasStep(
        op=op.name, kind=op.kind, layout=layout, width=op.width,
        kernel=kernel, repack=repack, dims=(m, k, n),
        padded_dims=t.padded_dims,
        note="repack folded into fused kernel" if fused else "",
        expert=op.expert)


def _bs2bp_in_program(step: PallasStep) -> bool:
    """A BP step whose weights arrive plane-resident: the plan-charged
    unpack runs in the program, not at hold time."""
    return step.repack == "bs2bp" and step.width <= MAX_BS_WIDTH


def hold(step: PallasStep, w):
    """Word-form weights ``[k, n]`` -> the device form ``step`` keeps
    resident: int8 limbs for BP, packed planes for BS and for a
    ``bs2bp`` step (the operand arrives plane-resident), the words
    themselves for a ``bp2bs`` step (the pack runs in :func:`apply`)."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    w = jnp.asarray(w)
    if step.layout is Layout.BP and not _bs2bp_in_program(step):
        return kops.bp_limbs(w, step.width)
    if step.layout is Layout.BS and step.repack == "bp2bs":
        return w
    return kops.pack_weights(w, step.width)


def apply(step: PallasStep, x, held):
    """int8 activations ``[m, k]`` against :func:`hold`'s weights ->
    int32 ``[m, n]``: the one kernel call of a lowered step.

    A ``bs2bp`` step unpacks its planes and splits them into limbs
    before the BP kernel; a fused ``bp2bs`` step runs the fused
    bitpack-matmul; an unfused one packs in flight.  Pure jnp over the
    raw kernels, so it traces into the compiled schedule program
    unchanged; one step on its own runs as :data:`apply_jit`.
    """
    from repro.kernels.bitpack import bitpack, bitunpack
    from repro.kernels.bitparallel_matmul import (bitparallel_matmul,
                                                  split_limbs)
    from repro.kernels.bitserial_matmul import bitserial_matmul
    from repro.kernels.fused_bitserial_matmul import fused_bitserial_matmul

    if not step.measured:
        raise ValueError(f"{step.op}: no kernel to run ({step.note})")
    if step.layout is Layout.BP:
        if _bs2bp_in_program(step):
            held = split_limbs(bitunpack(held, step.dims[1]), step.width)
        return bitparallel_matmul(x, held)
    if step.kernel == "fused_bitserial_matmul":
        return fused_bitserial_matmul(x, held, step.width)
    if step.repack == "bp2bs":
        held = bitpack(held, step.width)
    return bitserial_matmul(x, held)


#: :func:`apply` as a program of its own, the step static: how one step
#: runs outside the compiled schedule (per-step mode, per-step timings)
apply_jit = jax.jit(apply, static_argnums=0)


@spans.span("plan.lower")
def lower_plan_pallas(plan: LayoutPlan, workload, *,
                      fuse_pack: bool = True,
                      max_macs: int = DEFAULT_MAX_MACS) -> PallasSchedule:
    """Lower ``plan``'s op-level schedule to a Pallas kernel sequence."""
    current = plan.initial_layout
    steps: list[PallasStep] = []
    for op in workload.ops:
        layout = plan.layout_for(op.name)
        repack = None
        if current is not None and layout is not current:
            repack = "bp2bs" if layout is Layout.BS else "bs2bp"
        current = layout
        steps.append(step_for(op, layout, repack, fuse_pack=fuse_pack,
                              max_macs=max_macs))
    _count_expert_work(steps)
    return PallasSchedule(workload=workload.name, steps=tuple(steps),
                          fuse_pack=fuse_pack,
                          deps=tuple(workload.edges()))


def _count_expert_work(steps) -> None:
    """Counters of the measured expert steps, once per lowering:
    ``lower.expert_steps``, ``lower.expert_macs`` (true MACs) and
    ``lower.expert_mxu_work`` (padded MACs x :func:`mxu_passes`)."""
    experts = [s for s in steps if s.expert and s.measured]
    spans.count("lower.expert_steps", len(experts))
    spans.count("lower.expert_macs",
                sum(math.prod(s.dims) for s in experts))
    spans.count("lower.expert_mxu_work",
                sum(math.prod(s.padded_dims) * mxu_passes(s.layout, s.width)
                    for s in experts))


def synth_inputs(schedule: PallasSchedule, seed: int = 0) -> dict:
    """Random (x, w) operand pairs for every measured step.

    x: int8 activations; w: unsigned ``width``-bit words (int32 storage,
    full uint32 range at width 32 -- see ``util.rand_words``) -- the
    canonical word form both kernels consume.  Threaded steps ignore
    their synthetic x at execution; it is still generated so per-step and
    chained modes share one input pytree.
    """
    from repro.util import rand_words

    rng = np.random.default_rng(seed)
    out = {}
    for s in schedule.measured_steps:
        m, k, n = s.dims
        out[s.op] = (
            rng.integers(-128, 128, (m, k), dtype=np.int8),
            rand_words(rng, s.width, (k, n)),
        )
    return out


def _thread_np(y: np.ndarray, m: int, k: int) -> np.ndarray:
    """numpy twin of ``kernels.ops.thread_activations`` (bit-identical:
    same flatten/tile/truncate/reshape and the same mod-2^8 wrap)."""
    flat = y.reshape(-1)
    need = m * k
    if flat.size < need:
        flat = np.tile(flat, -(-need // flat.size))
    return flat[:need].reshape(m, k).astype(np.int8)


def run_schedule(schedule: PallasSchedule, inputs: dict, *,
                 thread: bool = True) -> dict:
    """Execute every measured step from the host; return
    {op: int32 [m, n] result}.

    ``inputs`` maps op name -> (x, w) with w in word form (see
    :func:`synth_inputs`).  Each step runs ``apply(s, x, hold(s, w))``,
    the step the compiled program (``plan.pallas_exec``) runs, repacks
    included, but one launch and one host round-trip at a time.

    ``thread=True`` (default) feeds each step's activation from its
    nearest measured producer along ``schedule.edges()`` via
    ``kernels.ops.thread_activations`` -- the same dataflow the chained
    executor compiles, making per-step mode its bit-exact differential
    reference (DESIGN.md Sec. 15).  ``thread=False`` runs every step on
    its own synthetic operands (the per-kernel differential mode the
    executor-vs-simulator tests use).
    """
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    producer = schedule.threaded_producers() if thread else {}
    results = {}
    for s in schedule.measured_steps:
        x, w = inputs[s.op]
        src = producer.get(s.op)
        if src in results:
            m, k, _ = s.dims
            x = kops.thread_activations(jnp.asarray(results[src]), m, k)
        results[s.op] = np.asarray(apply_jit(s, jnp.asarray(x), hold(s, w)))
    return results


def reference_results(schedule: PallasSchedule, inputs: dict, *,
                      thread: bool = True) -> dict:
    """Plain-integer references (int32 wraparound) for every measured
    step, with the same producer->consumer threading as
    :func:`run_schedule` (``thread=False`` for synthetic operands)."""
    producer = schedule.threaded_producers() if thread else {}
    out = {}
    for s in schedule.measured_steps:
        x, w = inputs[s.op]
        src = producer.get(s.op)
        if src in out:
            m, k, _ = s.dims
            x = _thread_np(out[src], m, k)
        out[s.op] = (x.astype(np.int64) @ w.astype(np.int64)).astype(
            np.int32)
    return out


def time_schedule(schedule: PallasSchedule, inputs: dict, *,
                  reps: int = 5) -> list[dict]:
    """Median-of-``reps`` wall-clock per measured step (plus modelled rows).

    Returns one record per schedule step: ``{op, kind, layout, kernel,
    repack, dims, padded_dims, width, us, note}`` -- ``us`` is None for
    modelled-only rows.  One warmup launch per step amortizes tracing.
    The timed call is :func:`apply` on weights :func:`hold` made before
    it: the per-step work of the compiled program, in-program repacks
    included.

    Timing is memoized by ``(padded_dims, width, kernel)`` within one
    call: a repeated layer (VGG-style fc0/fc1 at identical shape) would
    otherwise re-trace and re-warm a fresh closure per step for a number
    that is shape-determined anyway.  Memoized rows carry a note naming
    the step they reuse.
    """
    import jax.numpy as jnp

    rows = []
    memo: dict[tuple, tuple[float, str]] = {}
    for s in schedule.steps:
        rec = {"op": s.op, "kind": s.kind, "layout": s.layout.value,
               "kernel": s.kernel, "repack": s.repack, "dims": s.dims,
               "padded_dims": s.padded_dims, "width": s.width,
               "us": None, "note": s.note}
        if s.measured:
            memo_key = (s.padded_dims, s.width, s.kernel)
            hit = memo.get(memo_key)
            if hit is not None:
                rec["us"] = hit[0]
                memo_note = (f"timing memoized from {hit[1]} "
                             "(identical padded dims/width/path)")
                rec["note"] = (f"{rec['note']}; {memo_note}"
                               if rec["note"] else memo_note)
                rows.append(rec)
                continue
            x, w = inputs[s.op]
            x, held = jnp.asarray(x), hold(s, w)
            rec["us"] = clock(lambda: apply_jit(s, x, held), reps)
            memo[memo_key] = (rec["us"], s.op)
        rows.append(rec)
    return rows


def clock(fn, reps: int) -> float:
    """Median wall-clock microseconds of ``reps`` calls of ``fn`` after
    one warmup (trace + compile) call, each ended by
    ``block_until_ready``."""
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)
