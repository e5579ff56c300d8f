"""Bit-transposition (packing) Pallas kernel -- the on-chip transpose unit.

Converts word-layout (BP) weights into bitplane (BS) layout: words [K, N]
with values < 2^bits become uint32 planes [bits, ceil(K/32), N]. This is
the hardware transposer of paper Sec. 4.1 as a TPU kernel; the hybrid
executor charges its cost exactly like the paper charges
read(M)+core+write(N).

K need not be a multiple of 32: the packer zero-pads the K axis to the
next multiple (zero rows pack to zero bits, so downstream bit-serial
contractions are unaffected) and :func:`bitunpack` strips the padding on
the way back (round-trip pinned in tests/test_kernels.py).

Grid: (bits, Kg/bg, N/bn): each program packs ``bg`` groups of 32 rows
for one bit position.  Blocks obey the TPU tiling rule: ``bg`` is a
multiple of 8 groups or the whole Kg, ``bn`` a multiple of 128 lanes or
the whole N.  The 32 rows of a group combine by a signed int32 sum of
distinct powers of two (Mosaic reduces no unsigned integers), which is
the packed word's bit pattern; it is bitcast to uint32 outside the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import platform


def _kernel(w_ref, o_ref):
    b = pl.program_id(0)
    w = w_ref[...]  # [bg, 32, bn] int32 words
    row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    bit = jax.lax.shift_right_logical(w, b) & 1
    o_ref[0] = jnp.sum(bit << row, axis=1)


def _block(dim: int, quantum: int, want: int) -> int:
    """Largest multiple of ``quantum`` <= ``want`` dividing ``dim``, or
    the whole ``dim`` when none does (the tiling rule's two options)."""
    for blk in range(want - want % quantum, 0, -quantum):
        if dim % blk == 0:
            return blk
    return dim


def bitpack(w: jax.Array, bits: int, *, block_groups: int = 8,
            block_n: int = 256) -> jax.Array:
    """w: unsigned words [K, N] (values < 2^bits, any integer dtype) ->
    uint32 [bits, ceil(K/32), N]; K is zero-padded to the next multiple
    of 32."""
    K, N = w.shape
    pad = -K % 32
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    Kg = (K + pad) // 32
    # int32 bit patterns: the uint32 -> int32 convert wraps losslessly
    words = w.astype(jnp.int32).reshape(Kg, 32, N)
    bg = _block(Kg, 8, block_groups)
    bn = _block(N, 128, block_n)
    packed = pl.pallas_call(
        _kernel,
        grid=(bits, Kg // bg, N // bn),
        in_specs=[pl.BlockSpec((bg, 32, bn), lambda b, g, n: (g, 0, n))],
        out_specs=pl.BlockSpec((1, bg, bn), lambda b, g, n: (b, g, n)),
        out_shape=jax.ShapeDtypeStruct((bits, Kg, N), jnp.int32),
        interpret=platform.interpret(),
        name="bitpack",
    )(words)
    return jax.lax.bitcast_convert_type(packed, jnp.uint32)


def bitunpack(planes: jax.Array, k: int | None = None) -> jax.Array:
    """Inverse of :func:`bitpack`: uint32 planes [bits, Kg, N] -> words
    [k, N] (uint32), stripping the zero rows the packer added
    (``k`` defaults to the full ``Kg * 32``)."""
    bits, Kg, N = planes.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    rows = ((planes[:, :, None, :] >> shifts[None, None, :, None])
            & jnp.uint32(1)).reshape(bits, Kg * 32, N)
    words = jnp.zeros((Kg * 32, N), jnp.uint32)
    for b in range(bits):
        words = words | (rows[b] << jnp.uint32(b))
    return words if k is None else words[:k]
