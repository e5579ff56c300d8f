"""Bit-parallel (word-level) integer matmul Pallas kernel -- the BP layout.

Words stay horizontal: the weight word is consumed whole, in as few MXU
passes as the MXU's operand width allows, with K-blocked accumulation in
a VMEM scratch accumulator.  The kernel is grid-tiled over the *whole*
problem: arbitrary (M, K, N) are padded only up to the hardware-minimum
tile multiples (``kernels.tiling``), never clamped down to a
representative tile, and the true result is sliced back out (zero padding
is exact for integer contractions).

The TPU MXU multiplies integers only as int8 x int8 -> int32.  So an
unsigned ``bits``-wide word is stored as ``ceil(bits / 7)`` 7-bit *limbs*
(:func:`split_limbs`), each non-negative in int8, and the kernel runs one
MXU pass per limb, shifted into the int32 accumulator:

    x @ w = sum_l (x @ limb_l) << 7l        (mod 2^32)

Words of up to 7 bits are one pass; 8 and 16 bits take 2 and 3 passes,
32 bits take 5.  BP at 8 bits or more is therefore not one MXU pass on
this chip -- a layout cost in its own right, next to BS's one pass per
bit.  Accumulation is int32 (``preferred_element_type``), not float32:
un-clamped K reaches depths where f32's 24-bit mantissa silently rounds
integer partial sums (K=4096 int8 products exceed 2^24).

Grid: (M/bm, N/bn, K/bk) with the K axis sequential ("arbitrary") so the
accumulator scratch carries across K steps -- the same streaming-
accumulation idiom as ``kernels/flash_attention.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform
from repro.kernels.tiling import bp_tiling

#: bits per BP limb: the widest unsigned value an int8 MXU operand holds
LIMB_BITS = 7


def n_limbs(bits: int) -> int:
    """MXU passes (int8 limbs) a ``bits``-wide unsigned word takes."""
    return -(-bits // LIMB_BITS)


def split_limbs(w: jax.Array, bits: int) -> jax.Array:
    """Unsigned ``bits``-wide words [K, N] (any integer dtype; width 32
    may arrive as int32 bit patterns) -> int8 limbs [n_limbs(bits), K, N],
    least significant first -- the resident form of BP weights."""
    u = w.astype(jnp.uint32)
    return jnp.stack([((u >> (LIMB_BITS * i)) & ((1 << LIMB_BITS) - 1))
                      .astype(jnp.int8) for i in range(n_limbs(bits))])


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, limbs: int, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc = acc_ref[...]
    for l in range(limbs):
        acc = acc + (jax.lax.dot(x, w_ref[l],
                                 preferred_element_type=jnp.int32)
                     << (LIMB_BITS * l))
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def bitparallel_matmul(x: jax.Array, w: jax.Array, *,
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 128) -> jax.Array:
    """x: int8 [M, K]; w: int8 limbs [L, K, N] from :func:`split_limbs`,
    or one int8 matrix [K, N] (a single signed pass) -> int32 [M, N]
    (exact mod 2^32)."""
    if w.ndim == 2:
        w = w[None]
    if x.dtype != jnp.int8 or w.dtype != jnp.int8:
        raise TypeError(f"MXU operands must be int8, got x {x.dtype}, "
                        f"w {w.dtype} (split words with split_limbs)")
    M, K = x.shape
    L, K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: x K={K}, w K={K2}")
    t = bp_tiling(M, K, N, block_m=block_m, block_n=block_n,
                  block_k=block_k)
    if (t.pm, t.pk) != (M, K):
        x = jnp.pad(x, ((0, t.pm - M), (0, t.pk - K)))
    if (t.pk, t.pn) != (K, N):
        w = jnp.pad(w, ((0, 0), (0, t.pk - K), (0, t.pn - N)))
    gm, gn, k_steps = t.grid
    out = pl.pallas_call(
        functools.partial(_kernel, limbs=L, k_steps=k_steps),
        grid=(gm, gn, k_steps),
        in_specs=[
            pl.BlockSpec((t.bm, t.bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((L, t.bk, t.bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((t.bm, t.bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t.pm, t.pn), jnp.int32),
        # VMEM accumulator persisted across the sequential K axis
        scratch_shapes=[pltpu.VMEM((t.bm, t.bn), jnp.int32)],
        interpret=platform.interpret(),
        name="bitparallel_matmul",
    )(x, w)
    return out[:M, :N] if (t.pm, t.pn) != (M, N) else out
