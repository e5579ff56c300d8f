"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Compile-only: the TPU compiler is asked to build each kernel for a
described (not attached) ``v5e:2x2`` topology, at the FFN up-projection
of ``configs/stablelm_1_6b.py`` (a 4096-token decode step: M=4096,
K=d_model=2048, N=d_ff=5632).  Nothing runs, so this proves that Mosaic
accepts the kernels -- MXU operand dtypes, block shapes, VMEM use -- and
nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import platform
from repro.kernels.bitpack import bitpack
from repro.kernels.bitparallel_matmul import bitparallel_matmul, n_limbs
from repro.kernels.bitserial_matmul import bitserial_matmul
from repro.kernels.fused_bitserial_matmul import fused_bitserial_matmul


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the platform helper to the compiled (non-interpret) path:
    the process's own backend is the CPU."""
    monkeypatch.setattr(platform, "interpret", lambda: False)


def _ffn_shape():
    cfg = get_config("stablelm_1_6b")
    return 4096, cfg.d_model, cfg.d_ff


def _compile_text(fn, *args) -> str:
    # a fresh jit per call: no trace cached from an interpret-mode run
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bits", [4, 8, 16, 32])
def test_bitparallel_compiles(one_chip, compiled_kernels, bits):
    m, k, n = _ffn_shape()
    x = jax.ShapeDtypeStruct((m, k), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((n_limbs(bits), k, n), jnp.int8,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(bitparallel_matmul, x, w)


def test_fused_bitserial_compiles(one_chip, compiled_kernels):
    m, k, n = _ffn_shape()
    x = jax.ShapeDtypeStruct((m, k), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
    text = _compile_text(lambda a, b: fused_bitserial_matmul(a, b, 4), x, w)
    assert "tpu_custom_call" in text


def test_unfused_bitserial_compiles(one_chip, compiled_kernels):
    m, k, n = _ffn_shape()
    x = jax.ShapeDtypeStruct((m, k), jnp.int8, sharding=one_chip)
    planes = jax.ShapeDtypeStruct((4, k // 32, n), jnp.uint32,
                                  sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(bitserial_matmul, x, planes)


def test_bitpack_compiles(one_chip, compiled_kernels):
    _, k, n = _ffn_shape()
    w = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(lambda a: bitpack(a, 4), w)
