"""Kernel micro-benchmarks: Pallas (interpret mode) vs pure-jnp oracle.

On this CPU container the numbers are correctness-path timings, not TPU
performance; the TPU roofline lives in benchmarks/roofline_bench.py.
Derived fields report the BS-vs-BP pass arithmetic the paper predicts
(b-bit weights => b plane passes vs ceil(b/7) int8 limb passes).  The
matmul and bitpack rows run the schedule's own step function
(``plan.pallas.step_for`` / ``hold`` / ``apply``) at the kernels'
default tiles.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_us
from repro.core.cost_model import Layout
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.plan.pallas import apply_jit, hold, mxu_passes, step_for
from repro.workloads.ir import Op


def kernels() -> list[str]:
    rows = []
    rng = np.random.default_rng(0)
    M, K, N = 64, 128, 128
    x = jnp.asarray(rng.integers(-32, 32, (M, K), dtype=np.int32)
                    ).astype(jnp.int8)

    def step(layout, bits):
        op = Op(name="mm", kind="matmul", m=M, k=K, n=N, width=bits)
        return step_for(op, layout, None)

    for bits in (1, 2, 4):
        w = jnp.asarray(rng.integers(0, 2 ** bits, (K, N), dtype=np.uint32))
        s = step(Layout.BS, bits)
        planes = hold(s, w)
        us = time_us(lambda: np.asarray(apply_jit(s, x, planes)), repeat=2)
        ok = bool(np.array_equal(
            np.asarray(apply_jit(s, x, planes)),
            np.asarray(ref.bitserial_matmul_ref(x.astype(jnp.int32),
                                                ref.bitpack_ref(w, bits)))))
        rows.append(emit(f"kern.bitserial_matmul.{bits}b", us,
                         f"plane_passes={bits};match={ok}"))
    w8 = jnp.asarray(rng.integers(0, 256, (K, N), dtype=np.int32))
    s = step(Layout.BP, 8)
    limbs = hold(s, w8)
    us = time_us(lambda: np.asarray(apply_jit(s, x, limbs)), repeat=2)
    ok = bool(np.array_equal(
        np.asarray(apply_jit(s, x, limbs)),
        np.asarray(ref.bitparallel_matmul_ref(x, w8))))
    rows.append(emit("kern.bitparallel_matmul.8b", us,
                     f"limb_passes={mxu_passes(Layout.BP, 8)};match={ok}"))

    w4 = jnp.asarray(rng.integers(0, 16, (K, N), dtype=np.uint32))
    s = step(Layout.BS, 4)
    us = time_us(lambda: np.asarray(hold(s, w4)), repeat=2)
    ok = bool(np.array_equal(np.asarray(hold(s, w4)),
                             np.asarray(ref.bitpack_ref(w4, 4))))
    rows.append(emit("kern.bitpack.4b", us, f"transpose_unit;match={ok}"))

    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    us = time_us(lambda: np.asarray(
        flash_attention(q, k, v, block_q=64, block_k=64)), repeat=2)
    close = bool(np.allclose(
        np.asarray(flash_attention(q, k, v, block_q=64, block_k=64)),
        np.asarray(ref.flash_attention_ref(q, k, v)), rtol=2e-5, atol=2e-5))
    rows.append(emit("kern.flash_attention", us, f"match={close}"))
    return rows


ALL = [kernels]
