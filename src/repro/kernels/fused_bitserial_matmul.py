"""Fused bitpack + bit-serial matmul: one kernel, no plane artifact.

The unfused BS hot path is two passes -- ``bitpack`` materialises a
``[bits, K/32, N]`` uint32 plane tensor in HBM, then ``bitserial_matmul``
streams it back in.  This kernel fuses the pack into the matmul: each grid
step loads the *word* weight tile ``[bk, bn]``, slices plane ``b`` in VMEM
with a shift+mask (``(w >> b) & 1`` -- the bitpack inner loop, minus the
popcount packing that only existed to make an HBM-resident artifact), and
accumulates ``(x @ plane_b) << b`` into the int32 scratch carried across
the sequential K axis -- the flash-attention streaming idiom: no
intermediate tensor ever round-trips to HBM.

The layout story is unchanged -- the weight matrix is still *consumed*
bit-serially, ``bits`` MXU plane passes, so latency scales with precision
exactly as the unfused kernel (Table 2) -- only the pack pass stops being
a separately timed, separately stored artifact.  Activations must be
int8 and each 0/1 plane is cast to int8: the TPU MXU multiplies integers
only as int8 x int8 -> int32.  Weights must be unsigned ``bits``-wide
values (any int dtype holding them); results are
bit-exact with ``bitpack`` -> ``bitserial_matmul`` and with
``ref.bitserial_matmul_ref`` (int32 wraparound semantics, see
``bitparallel_matmul``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform
from repro.kernels.tiling import fused_tiling


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, bits: int, k_steps: int):
    # x_ref: [bm, bk] int8 ; w_ref: [bk, bn] unsigned words (int storage)
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...].astype(jnp.uint32)
    acc = acc_ref[...]
    for b in range(bits):  # in-register bitpack: slice plane b of the tile
        plane = ((w >> b) & jnp.uint32(1)).astype(jnp.int8)
        acc = acc + (jax.lax.dot(x, plane,
                                 preferred_element_type=jnp.int32) << b)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def fused_bitserial_matmul(x: jax.Array, w: jax.Array, bits: int, *,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128) -> jax.Array:
    """x: int8 [M, K]; w: unsigned ``bits``-wide words [K, N] -> int32
    [M, N]."""
    if not 1 <= bits <= 32:
        raise ValueError(f"bits must be in [1, 32], got {bits}")
    if x.dtype != jnp.int8:
        raise TypeError(f"MXU activations must be int8, got {x.dtype}")
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: x K={K}, w K={K2}")
    t = fused_tiling(M, K, N, block_m=block_m, block_n=block_n,
                     block_k=block_k)
    if (t.pm, t.pk) != (M, K):
        x = jnp.pad(x, ((0, t.pm - M), (0, t.pk - K)))
    if (t.pk, t.pn) != (K, N):
        w = jnp.pad(w, ((0, t.pk - K), (0, t.pn - N)))
    gm, gn, k_steps = t.grid
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, k_steps=k_steps),
        grid=(gm, gn, k_steps),
        in_specs=[
            pl.BlockSpec((t.bm, t.bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((t.bk, t.bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((t.bm, t.bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t.pm, t.pn), jnp.int32),
        # VMEM accumulator persisted across the sequential K axis
        scratch_shapes=[pltpu.VMEM((t.bm, t.bn), jnp.int32)],
        interpret=platform.interpret(),
        name="fused_bitserial_matmul",
    )(x, w)
    return out[:M, :N] if (t.pm, t.pn) != (M, N) else out
