"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (hypothesis) in
interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.bitpack import bitpack, bitunpack
from repro.kernels.bitparallel_matmul import bitparallel_matmul
from repro.kernels.bitserial_matmul import bitserial_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels import ops


def _rand_words(rng, K, N, bits):
    return jnp.asarray(rng.integers(0, 2 ** bits, size=(K, N),
                                    dtype=np.uint32))


# ------------------------------------------------------------- bitpack -----

@settings(max_examples=12, deadline=None)
@given(bits=st.sampled_from([1, 2, 4, 8]),
       kg=st.integers(1, 4), n=st.sampled_from([8, 64, 96]))
def test_bitpack_matches_ref(bits, kg, n):
    rng = np.random.default_rng(bits * 100 + kg * 10 + n)
    w = _rand_words(rng, 32 * kg, n, bits)
    got = bitpack(w, bits)
    want = ref.bitpack_ref(w, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitpack_roundtrip():
    rng = np.random.default_rng(0)
    w = _rand_words(rng, 128, 64, 4)
    planes = bitpack(w, 4)
    back = ref.bitunpack_ref(planes, 128)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(w))


@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([1, 3, 8]),
       k=st.sampled_from([1, 17, 40, 63, 65]), n=st.sampled_from([8, 64]))
def test_bitpack_pads_ragged_k_and_unpack_strips(bits, k, n):
    """ISSUE-5 satellite: K need not be a multiple of 32 -- the packer
    zero-pads, bitunpack strips the padding, and the padded planes feed
    the BS matmul unchanged (zero rows contribute nothing)."""
    rng = np.random.default_rng(bits * 1000 + k * 10 + n)
    w = _rand_words(rng, k, n, bits)
    planes = bitpack(w, bits)
    assert planes.shape == (bits, -(-k // 32), n)
    back = bitunpack(planes, k)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(w))
    # padded rows really are zero
    full = np.asarray(bitunpack(planes))
    assert not full[k:].any()
    # ragged-K matmul through the padded planes == integer reference
    m = 8
    x = jnp.asarray(rng.integers(-8, 8, size=(m, k), dtype=np.int32))
    got = ops.matmul_bs(x.astype(jnp.int8), planes)
    want = np.asarray(x) @ np.asarray(w).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(got), want)


# -------------------------------------------------- bit-serial matmul ------

@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([1, 2, 4]),
       m=st.sampled_from([8, 32]), kg=st.integers(1, 3),
       n=st.sampled_from([16, 64]))
def test_bitserial_matmul_matches_ref(bits, m, kg, n):
    rng = np.random.default_rng(bits + m + kg + n)
    K = 32 * kg
    x = jnp.asarray(rng.integers(-64, 64, size=(m, K), dtype=np.int32)
                    ).astype(jnp.int8)
    w = _rand_words(rng, K, n, bits)
    planes = ref.bitpack_ref(w, bits)
    got = bitserial_matmul(x, planes, block_m=min(32, m), block_n=min(64, n))
    want = ref.bitserial_matmul_ref(x.astype(jnp.int32), planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ bit-parallel matmul ------

@settings(max_examples=10, deadline=None)
@given(m=st.sampled_from([16, 64]), k=st.sampled_from([32, 128, 160]),
       n=st.sampled_from([16, 128]))
def test_bitparallel_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int32)
                    ).astype(jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int32)
                    ).astype(jnp.int8)
    got = bitparallel_matmul(x, w, block_m=16, block_n=16, block_k=32)
    want = ref.bitparallel_matmul_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bs_equals_bp_semantics():
    """Both layouts compute the same GEMM (the paper's iso-function claim)."""
    rng = np.random.default_rng(7)
    K, N, bits = 64, 32, 4
    x = jnp.asarray(rng.integers(0, 16, (8, K), dtype=np.int32)).astype(
        jnp.int8)
    w = _rand_words(rng, K, N, bits)
    planes = ref.bitpack_ref(w, bits)
    y_bs = bitserial_matmul(x, planes, block_m=8, block_n=32)
    y_bp = bitparallel_matmul(x, w.astype(jnp.int8), block_m=8,
                              block_n=16, block_k=32)
    np.testing.assert_array_equal(np.asarray(y_bs), np.asarray(y_bp))


# --------------------------------------------------- flash attention -------

@settings(max_examples=8, deadline=None)
@given(b=st.sampled_from([1, 2]), sq=st.sampled_from([32, 64]),
       h=st.sampled_from([1, 2]), d=st.sampled_from([32, 64]),
       causal=st.booleans())
def test_flash_attention_matches_ref(b, sq, h, d, causal):
    rng = np.random.default_rng(b + sq + h + d)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_vs_layers_streaming_attention():
    """The Pallas kernel and the pure-JAX streaming softmax agree."""
    from repro.models.layers import flash_attention as jflash
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 4, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 4, 32)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    b = jflash(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# -------------------------------------------- layout-aware dispatch --------

def test_layout_aware_matmul_dispatch():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(0, 8, (128, 64), dtype=np.int32)).astype(
        jnp.int8)
    w2 = _rand_words(rng, 64, 128, 2)   # 2-bit, high DoP -> BS
    y, layout = ops.planned_matmul(x, w2, weight_bits=2)
    assert layout.value == "BS"
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(x.astype(jnp.int32) @ w2.astype(jnp.int32)))

    w8 = _rand_words(rng, 64, 128, 8)   # 8-bit words -> BP
    y8, layout8 = ops.planned_matmul(x, w8.astype(jnp.int32) - 0,
                                     weight_bits=8)
    assert layout8.value == "BP"
    # lossless: unsigned 8-bit words no longer wrap through int8 (PR 9)
    np.testing.assert_array_equal(
        np.asarray(y8),
        np.asarray(x.astype(jnp.int32) @ w8.astype(jnp.int32)))


# ----------------------------------------- grid tiling (un-clamped) --------

def test_tiling_pads_only_to_hardware_minimum():
    from repro.kernels import tiling as tl

    t = tl.bp_tiling(1, 100, 10)
    assert t.dims == (1, 100, 10)
    assert t.padded_dims == (32, 128, 128)   # BP hw minimum, not 128^3
    assert t.grid == (1, 1, 1)
    big = tl.bp_tiling(300, 4096, 512)
    assert big.padded_dims == (384, 4096, 512)
    gm, gn, ks = big.grid   # (M tiles, N tiles, K steps)
    assert (gm * big.bm, ks * big.bk, gn * big.bn) == big.padded_dims
    # unfused BS streams packed uint32 groups: K minimum is 256 words
    bs = tl.bs_tiling(1, 100, 10)
    assert bs.padded_dims == (32, 256, 128)


def test_grid_tiled_equals_single_tile():
    """A problem that fits one tile gives the same result grid-tiled."""
    rng = np.random.default_rng(21)
    M, K, N = 96, 256, 192
    x = jnp.asarray(rng.integers(-128, 128, (M, K), dtype=np.int32)
                    ).astype(jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (K, N), dtype=np.int32)
                    ).astype(jnp.int8)
    one = bitparallel_matmul(x, w, block_m=96, block_n=192, block_k=256)
    grid = bitparallel_matmul(x, w, block_m=32, block_n=128, block_k=128)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(grid))

    bits = 4
    wq = _rand_words(rng, K, N, bits)
    planes = bitpack(wq, bits)
    one = bitserial_matmul(x, planes, block_m=96, block_n=192, block_k=256)
    grid = bitserial_matmul(x, planes, block_m=32, block_n=128, block_k=256)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(grid))


def test_unclamped_deep_k_is_exact_int32():
    """Regression for the f32-accumulator era: at K=4096 the integer
    partial sums exceed f32's 24-bit mantissa, so only the int32
    accumulation path stays bit-exact once ops run un-clamped."""
    rng = np.random.default_rng(4096)
    M, K, N = 8, 4096, 128
    # same-sign operands: partial sums grow monotonically past 2^24
    x = jnp.asarray(rng.integers(64, 128, (M, K), dtype=np.int32)
                    ).astype(jnp.int8)
    w = jnp.asarray(rng.integers(64, 128, (K, N), dtype=np.int32)
                    ).astype(jnp.int8)
    got = np.asarray(bitparallel_matmul(x, w))
    want = np.asarray(x).astype(np.int64) @ np.asarray(w).astype(np.int64)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # and the magnitudes really do exercise the f32-unsafe range
    assert np.abs(want).max() > (1 << 24)


# ------------------------------------- fused bitpack-matmul (ISSUE 9) ------

@settings(max_examples=12, deadline=None)
@given(bits=st.sampled_from([1, 4, 8, 16]),
       m=st.sampled_from([1, 8, 33]),
       k=st.sampled_from([17, 100, 256]),
       n=st.sampled_from([10, 64, 129]))
def test_fused_matches_unfused_and_ref(bits, m, k, n):
    """Differential suite: one-kernel fused bitpack-matmul == the unfused
    pack_weights -> matmul_bs pipeline == the plain-integer reference --
    ragged K, signed activations, widths {1, 4, 8, 16}."""
    rng = np.random.default_rng(bits * 7919 + m * 131 + k * 17 + n)
    x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int32)
                    ).astype(jnp.int8)
    w = jnp.asarray(rng.integers(0, 1 << bits, (k, n)).astype(np.int32))
    fused = np.asarray(ops.matmul_bs_fused(x, w, bits))
    planes = ops.pack_weights(w.astype(jnp.uint32), bits)
    unfused = np.asarray(ops.matmul_bs(x, planes))
    want = (np.asarray(x).astype(np.int64)
            @ np.asarray(w).astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(fused, want)
    np.testing.assert_array_equal(unfused, want)


def test_planned_matmul_fuse_pack_dispatch():
    """fuse_pack=True routes the BS side through the fused kernel and
    stays bit-exact with the unfused plan path."""
    from repro.core.cost_model import Layout

    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.integers(0, 8, (128, 64), dtype=np.int32)).astype(
        jnp.int8)
    w = _rand_words(rng, 64, 128, 2).astype(jnp.int32)
    y_f, lay_f = ops.planned_matmul(x, w, weight_bits=2, fuse_pack=True)
    y_u, lay_u = ops.planned_matmul(x, w, weight_bits=2, fuse_pack=False)
    assert lay_f is Layout.BS and lay_u is Layout.BS
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))


@pytest.mark.parametrize("bits", [8, 16, 32])
def test_bp_limb_split_is_exact(bits):
    """Words of 8 bits or more run as ceil(bits/7) int8 MXU passes; the
    shifted limb sum is bit-exact mod 2^32 with the plain-integer oracle,
    over the full uint32 range at width 32."""
    from repro.kernels.bitparallel_matmul import n_limbs
    from repro.util import rand_words

    rng = np.random.default_rng(bits)
    m, k, n = 40, 300, 130
    x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
    w = rand_words(rng, bits, (k, n))
    if bits == 32:
        assert (w < 0).any()           # the top bit is exercised
    limbs = ops.bp_limbs(jnp.asarray(w), bits)
    assert limbs.dtype == jnp.int8 and limbs.shape == (n_limbs(bits), k, n)
    assert n_limbs(bits) == -(-bits // 7)
    got = np.asarray(bitparallel_matmul(x, limbs))
    want = np.asarray(ref.bitparallel_matmul_ref(x, jnp.asarray(w)))
    np.testing.assert_array_equal(got, want)


def test_platform_helper_refuses_unknown_backend(monkeypatch):
    import jax

    from repro.kernels import platform

    assert platform.interpret() is True      # the test process: CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        platform.interpret()


def test_kernels_require_int8_mxu_operands():
    x = jnp.zeros((8, 64), jnp.int32)
    w = jnp.zeros((64, 16), jnp.int32)
    with pytest.raises(TypeError, match="int8"):
        bitparallel_matmul(x, w)
    with pytest.raises(TypeError, match="int8"):
        ops.matmul_bs_fused(x, w, 4)


# --------------------------------------- pallas-bench regression gate ------

def test_pallas_bench_regression_gate():
    from repro.kernels.bench import check_pallas_regression

    base = {"cases": [{"name": "gemv/w4/bp", "us": 10000.0},
                      {"name": "gemv/w4/bs_fused", "us": 500.0}]}
    ok, msg = check_pallas_regression(
        {"cases": [{"name": "gemv/w4/bp", "us": 11000.0}]}, base)
    assert ok and "0 regression" in msg
    # >50% over a super-floor baseline fails (exit-3 path in the CLI)
    ok, msg = check_pallas_regression(
        {"cases": [{"name": "gemv/w4/bp", "us": 16000.0}]}, base)
    assert not ok and "gemv/w4/bp" in msg
    # sub-floor baselines never gate: 4x over 500us is runner jitter
    ok, _ = check_pallas_regression(
        {"cases": [{"name": "gemv/w4/bs_fused", "us": 2000.0}]}, base,
        floor_us=2000.0)
    assert ok
    # unknown cases (new shapes/widths) pass with a note
    ok, msg = check_pallas_regression(
        {"cases": [{"name": "new/w1/bp", "us": 9e9}]}, base)
    assert ok and "1 new" in msg
