"""Jitted public wrappers for the Pallas kernels + the layout-aware
quantized linear op the planner drives (the paper's technique as a
first-class kernel-selection decision).
"""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

from repro.core.cost_model import Layout
from repro.core.taxonomy import Recommendation, classify
from repro.kernels.bitpack import bitpack, bitunpack
from repro.kernels.bitparallel_matmul import bitparallel_matmul, split_limbs
from repro.kernels.bitserial_matmul import bitserial_matmul
from repro.kernels.fused_bitserial_matmul import fused_bitserial_matmul
from repro.workloads.ir import Op


def thread_activations(y: jax.Array, m: int, k: int) -> jax.Array:
    """Adapt a producer step's int32 ``[M', N']`` result into a consumer
    step's int8 ``[m, k]`` activation operand.

    The deterministic dataflow adapter of the chained executor
    (DESIGN.md Sec. 15): flatten, tile/truncate to ``m * k`` elements,
    reshape, and wrap to int8 -- activations always flow in word form,
    and int32 -> int8 is the mod-2^8 requantize numpy and XLA define
    identically.  The chained program, per-step ``run_schedule``, and the
    numpy ``reference_results`` all use this exact adapter, which is what
    keeps the three bit-exact with real (not synthetic) dataflow between
    steps.  Pure jnp, so it traces into the one jitted schedule program.
    """
    flat = y.reshape(-1)
    need = m * k
    if flat.shape[0] < need:
        flat = jnp.tile(flat, -(-need // flat.shape[0]))
    return flat[:need].reshape(m, k).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits",))
def pack_weights(w: jax.Array, bits: int):
    """BP -> BS layout conversion (the transpose unit)."""
    return bitpack(w, bits)


@functools.partial(jax.jit, static_argnames=("bits",))
def bp_limbs(w: jax.Array, bits: int):
    """Word-form weights -> the int8 limb stack the BP kernel reads."""
    return split_limbs(w, bits)


@functools.partial(jax.jit, static_argnames=("k",))
def unpack_weights(planes: jax.Array, k: int | None = None):
    """BS -> BP layout conversion (strips bitpack's K padding)."""
    return bitunpack(planes, k)


@jax.jit
def matmul_bs(x: jax.Array, planes: jax.Array):
    """BS bitplane matmul over packed planes (:func:`pack_weights`)."""
    return bitserial_matmul(x, planes)


@jax.jit
def matmul_bp(x: jax.Array, limbs: jax.Array):
    """BP word matmul over resident int8 limbs (:func:`bp_limbs`)."""
    return bitparallel_matmul(x, limbs)


@functools.partial(jax.jit, static_argnames=("bits",))
def matmul_bs_fused(x: jax.Array, w: jax.Array, bits: int):
    """One-kernel BS path: packs plane slices in VMEM and accumulates the
    plane loop without materializing the ``[bits, K/32, N]`` artifact.
    Bit-exact with ``pack_weights`` -> ``matmul_bs``."""
    return fused_bitserial_matmul(x, w, bits)


def choose_layout(*, weight_bits: int, m: int, n: int, k: int,
                  mixed_precision: bool = False) -> Recommendation:
    """Layout advisor for one quantized matmul (Table-8 features).

    Builds a canonical IR matmul op and classifies its feature lowering.
    The resident working set is derived from the *actual* operand
    footprint of the weight-stationary k-deep dot product
    (``ir.matmul_working_set_bits``: the k-element weight column plus the
    double-width accumulator) -- so deep contractions overflow the
    128-row BS column and correctly flip the recommendation to BP
    (Challenge 2).  The old implementation hardcoded ``weight_bits * 4``
    and ignored k entirely.
    """
    op = Op(name="matmul", kind="matmul", m=m, k=k, n=n, width=weight_bits,
            bit_level_fraction=1.0 if weight_bits <= 2 else
            0.7 if weight_bits <= 4 else 0.2,
            mixed_precision=mixed_precision)
    return classify(op.features()).recommendation


def planned_matmul(x: jax.Array, w: jax.Array, *, weight_bits: int,
                   plan=None, op_name: str | None = None,
                   fuse_pack: bool = False):
    """Dispatch x @ w to the BS (bitplane) or BP (word) kernel per a
    compiled :class:`repro.plan.ir.LayoutPlan` -- the same plan the cost
    model priced.  ``plan.layout_for(op_name)`` picks the kernel; with no
    plan, fall back to the Table-8 advisor (:func:`choose_layout`).
    The product runs as the lowered step of a schedule does
    (``plan.pallas.step_for`` / ``hold`` / ``apply``): BS weights arrive
    in word form (a ``bp2bs`` step), and ``fuse_pack=True`` folds that
    repack into the BS kernel itself (no materialized plane tensor).
    w: unsigned ints < 2^weight_bits, [K, N].  Returns (y, Layout)."""
    from repro.plan.pallas import apply, hold, step_for

    m, k = x.shape
    n = w.shape[1]
    if plan is not None:
        layout = plan.layout_for(op_name)
    else:
        rec = choose_layout(weight_bits=weight_bits, m=m, n=n, k=k)
        layout = Layout.BS if rec == Recommendation.BS else Layout.BP
    op = Op(name=op_name or "matmul", kind="matmul", m=m, k=k, n=n,
            width=weight_bits)
    step = step_for(op, layout, "bp2bs" if layout is Layout.BS else None,
                    fuse_pack=fuse_pack, max_macs=sys.maxsize)
    return apply(step, x, hold(step, w)), layout
