"""Bit-serial (bitplane) matmul Pallas kernel -- the TPU-native BS layout.

The paper's BS column ALU processes one bit-position of every element per
cycle.  The TPU analogue is *bit-slicing*: an unsigned `bits`-wide weight
matrix is stored as `bits` 1-bit planes (32 K-rows packed per uint32 word),
and y = x @ W is computed plane-by-plane:

    y = sum_b 2^b * (x @ plane_b)

Each plane's product is a binary-matrix contraction: the kernel unpacks the
plane tile in VMEM (shift+mask -- the "sense amplifier read" of the slice)
and feeds the MXU with a 0/1 int8 operand against int8 activations (the
MXU multiplies integers only as int8 x int8 -> int32).  Low-precision weights cost
proportionally fewer plane passes -- exactly the BS latency scaling
(Table 2: N-bit -> N cycles), while dense full-width BP costs one pass.

Grid: (M/bm, N/bn, Kg/bkg) -- the whole problem, with K streamed in
packed-group blocks along the sequential axis and partial sums carried in
a VMEM int32 accumulator (flash-attention-style streaming; f32
accumulation would round un-clamped K, see bitparallel_matmul).  Arbitrary
(M, N, K) are padded to the hardware-minimum tile multiples only
(``kernels.tiling.bs_tiling``) and the true result sliced back out.
Results are exact integers mod 2^32 (int32 wraparound arithmetic agrees
with the unbounded-integer reference mod 2^32 at any width <= 32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform
from repro.kernels.tiling import bs_tiling


def _kernel(x_ref, planes_ref, o_ref, acc_ref, *, bits: int, bk: int,
            k_steps: int):
    # x_ref: [bm, bk] int8 ; planes_ref: [bits, bk//32, bn] uint32
    # o_ref / acc_ref: [bm, bn] int32
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # int8 MXU operand
    shifts = jnp.arange(32, dtype=jnp.uint32)
    acc = acc_ref[...]
    for b in range(bits):  # bit-serial plane loop
        packed = planes_ref[b]  # [bk//32, bn] uint32
        bits_kn = ((packed[:, None, :] >> shifts[None, :, None])
                   & jnp.uint32(1))  # [bk//32, 32, bn]
        plane = bits_kn.reshape(bk, -1).astype(jnp.int8)
        acc = acc + (jax.lax.dot(x, plane,
                                 preferred_element_type=jnp.int32) << b)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def bitserial_matmul(x: jax.Array, planes: jax.Array, *,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 512) -> jax.Array:
    """x: int8 [M, K]; planes: uint32 [bits, ceil(K/32), N] -> int32
    [M, N].  A K short of whole 32-row groups (``bitpack``'s zero-padded
    tail) is padded here."""
    if x.dtype != jnp.int8:
        raise TypeError(f"MXU activations must be int8, got {x.dtype}")
    M, K = x.shape
    bits, Kg, N = planes.shape
    # bitpack zero-pads ragged K into whole 32-row groups; those zero plane
    # rows kill whatever x carries there, so padding x up is exact too.
    assert Kg * 32 >= K, (K, Kg)
    K = Kg * 32
    t = bs_tiling(M, K, N, block_m=block_m, block_n=block_n,
                  block_k=block_k)
    if (t.pm, t.pk) != x.shape:
        x = jnp.pad(x, ((0, t.pm - M), (0, t.pk - x.shape[1])))
    pkg = t.pk // 32
    if (pkg, t.pn) != (Kg, N):
        # zero plane groups / columns contribute nothing to the dot
        planes = jnp.pad(planes, ((0, 0), (0, pkg - Kg), (0, t.pn - N)))
    gm, gn, k_steps = t.grid
    bkg = t.bk // 32
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, bk=t.bk, k_steps=k_steps),
        grid=(gm, gn, k_steps),
        in_specs=[
            pl.BlockSpec((t.bm, t.bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bits, bkg, t.bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((t.bm, t.bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t.pm, t.pn), jnp.int32),
        # VMEM accumulator persisted across the sequential K axis
        scratch_shapes=[pltpu.VMEM((t.bm, t.bn), jnp.int32)],
        interpret=platform.interpret(),
        name="bitserial_matmul",
    )(x, planes)
    return out[:M, :N] if (t.pm, t.pn) != (M, N) else out
