"""Cross-cutting knobs: scan unrolling (HLO cost counting) and remat.

XLA's cost analysis counts a `while` body ONCE, not x trip-count, so a
scanned-layers model under-reports FLOPs/bytes/collectives. The dry-run's
counting pass therefore lowers reduced-depth configs with
``REPRO_UNROLL_SCANS=1`` -- every `util.scan` becomes a Python loop, the HLO
contains no while ops, and cost analysis is exact -- then extrapolates
linearly in depth (layers are homogeneous). See launch/dryrun.py.

Also home to the content-address provenance primitives shared by the
caching layers (sweep grid, plan cache, executable cache) -- this module
sits below every subsystem, so none of them has to import another just
to fingerprint sources.
"""
from __future__ import annotations

import hashlib
import inspect
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_REMAT = False


def source_fingerprint(*modules, digest_len: int = 16) -> str:
    """sha256 over the concatenated source of ``modules``.

    The provenance half of every content address in the repo (sweep
    cache, plan cache, executable cache): editing any fingerprinted
    module changes the address, so stale cached artifacts can never be
    served after a code change.
    """
    h = hashlib.sha256()
    for mod in modules:
        h.update(inspect.getsource(mod).encode())
    return h.hexdigest()[:digest_len]


def rand_words(rng: np.random.Generator, width: int, shape) -> np.ndarray:
    """Unsigned ``width``-bit weight words in the canonical int32 storage
    (the word form every kernel path consumes).

    ``width >= 32`` draws the full uint32 range and reinterprets the bits
    as int32: the old ``1 << min(width, 31)`` bound could never generate
    the top bit, so width-32 paths were only ever exercised at 31-bit
    range.  The signed view is lossless -- every kernel path agrees
    mod 2^32 (DESIGN.md Sec. 14), so a negative int32 is just the same
    32-bit word.
    """
    if width >= 32:
        raw = rng.integers(0, 1 << 32, shape, dtype=np.uint64)
        return raw.astype(np.uint32).view(np.int32)
    return rng.integers(0, 1 << width, shape).astype(np.int32)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and
    wins: nothing else is set.  Otherwise the cache lives at a fixed
    path inside the checkout (``.jax-cache/``) -- the path is part of
    what a cached entry is found by, so it never names a temporary
    directory, a pid or a time.  Entry points call this (``python -m
    repro``, ``chip_smoke.py``); tests do not.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[2] / ".jax-cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_remat(value: bool) -> None:
    """Per-layer rematerialization for the training step (set by
    make_train_step before tracing)."""
    global _REMAT
    _REMAT = bool(value)


def remat_enabled() -> bool:
    return _REMAT


def unroll_scans() -> bool:
    return os.environ.get("REPRO_UNROLL_SCANS", "") == "1"


def flash_chunk_default() -> int:
    return int(os.environ.get("REPRO_FLASH_CHUNK", "512"))


def attn_bf16_matmuls() -> bool:
    """Perf lever: bf16 QK/PV matmuls with f32 softmax state (the paper's
    precision-vs-layout trade applied to attention operand width)."""
    return os.environ.get("REPRO_ATTN_BF16", "") == "1"


def fused_attention_accounting() -> bool:
    """Perf lever: account flash-internal tensors as VMEM-resident (the
    Pallas kernel in kernels/flash_attention.py), excluding them from the
    boundary-bytes memory term."""
    return os.environ.get("REPRO_FUSED_ATTN", "") == "1"


def bf16_allreduce_barrier() -> bool:
    """Perf lever: optimization_barrier after residual adds, preventing XLA
    from hoisting the rms_norm f32 convert above the row-parallel psum
    (which doubles TP all-reduce wire bytes)."""
    return os.environ.get("REPRO_AR_BF16", "") == "1"


def scan(f, init, xs, length=None):
    """lax.scan, or an unrolled Python loop under REPRO_UNROLL_SCANS=1."""
    if not unroll_scans():
        return lax.scan(f, init, xs, length=length)
    if xs is None:
        n = length
    else:
        n = jax.tree.leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(n):
        xi = None if xs is None else jax.tree.map(lambda a: a[i], xs)
        carry, y = f(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked
