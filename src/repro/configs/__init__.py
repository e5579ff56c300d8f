"""Assigned architecture configs (--arch <id>) + input-shape registry.

Every config reproduces the published dims exactly; vocab sizes are padded
to a multiple of 256 at the embedding (base.ArchConfig.padded_vocab).
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.models.base import ArchConfig

ARCH_IDS = [
    "mamba2_780m",
    "dbrx_132b",
    "llama4_maverick_400b_a17b",
    "yi_6b",
    "tinyllama_1_1b",
    "mistral_nemo_12b",
    "stablelm_1_6b",
    "internvl2_2b",
    "recurrentgemma_2b",
    "whisper_small",
    "mellum2_12b_a2_5b",
]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

#: long_500k needs sub-quadratic attention; pure full-attention archs skip
#: it (DESIGN.md Sec. 4).
LONG_CONTEXT_ARCHS = {"mamba2_780m", "recurrentgemma_2b"}


def get_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.config()


def all_cells() -> list[tuple[str, str]]:
    """Every (arch x shape) cell; long_500k marked runnable or skip."""
    cells = []
    for a in ARCH_IDS:
        for s in SHAPES:
            cells.append((a, s))
    return cells


def cell_runnable(arch_id: str, shape: str) -> bool:
    if shape == "long_500k":
        return arch_id in LONG_CONTEXT_ARCHS
    return True


def reduced_config(cfg: ArchConfig) -> ArchConfig:
    """Smoke-test scale: same family/structure, tiny dims."""
    kw = dict(
        n_layers=min(cfg.n_layers, 4),
        d_model=64,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=512,
        head_dim=16,
        rope_theta=cfg.rope_theta,
        dtype="float32",
    )
    import jax.numpy as jnp
    kw["dtype"] = jnp.float32
    if cfg.family == "ssm":
        kw.update(n_heads=4, n_kv_heads=0, ssm_state=16, ssm_head_dim=16,
                  ssm_expand=2, ssm_chunk=8)
        kw["n_layers"] = 2
    else:
        kw.update(n_heads=4, n_kv_heads=max(1, min(cfg.n_kv_heads, 2)))
    if cfg.n_experts:
        kw.update(n_experts=4, top_k=min(cfg.top_k, 2),
                  moe_every=cfg.moe_every)
        kw["n_layers"] = 2 * cfg.moe_every
    if cfg.block_pattern:
        kw.update(window=8, block_pattern=cfg.block_pattern)
        kw["n_layers"] = len(cfg.block_pattern)  # one full block
    if cfg.family == "hybrid":
        kw.update(lru_width=64, conv_width=cfg.conv_width)
        kw["n_layers"] = len(cfg.block_pattern) + 2  # one full block + tail
    if cfg.family == "audio":
        kw.update(enc_layers=2, enc_seq=8)
        kw["n_layers"] = 2
    if cfg.family == "vlm":
        kw.update(enc_seq=4)
        kw["n_layers"] = 2
    return dataclasses.replace(cfg, **kw)
