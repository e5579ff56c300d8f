"""Flash attention Pallas kernel (compiled on a TPU, interpreted on the CPU).

The pure-JAX streaming attention in models/layers.py materializes the
per-chunk score/probability tensors at HLO boundaries -- the dominant memory
term in the train/prefill rooflines. This kernel keeps the q-tile, running
max/denominator and output accumulator in VMEM scratch across the sequential
KV axis, so HBM traffic is exactly q+k+v read once and o written once.

Grid: (batch*heads, Sq/bq, Sk/bk); KV axis sequential, scratch carries.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, k_steps: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0].astype(jnp.float32)  # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST) * scale
    if causal:
        iq = pl.program_id(1)
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, precision=jax.lax.Precision.HIGHEST)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == k_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """q: [B, Sq, H, D]; k, v: [B, Sk, H, D] (MHA; GQA callers repeat KV).
    Returns [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    scale = 1.0 / math.sqrt(D)
    k_steps = Sk // bk

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, k_steps=k_steps),
        grid=(B * H, Sq // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=platform.interpret(),
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
