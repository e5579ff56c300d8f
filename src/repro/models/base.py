"""Model substrate: arch configs, parameter structures, initialization.

A model is described by an :class:`ArchConfig` plus a *parameter structure*
-- a pytree of :class:`ParamSpec` leaves carrying shape, dtype, sharding
spec, and initializer. The same structure drives:
  * random init (smoke tests, real training),
  * abstract init (`jax.ShapeDtypeStruct`, dry-run -- no allocation),
  * sharding assignment (`NamedSharding` per leaf for pjit in/out shardings).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.dist.sharding import resolve_pspec

VOCAB_PAD_MULTIPLE = 256  # Megatron convention


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One assigned architecture (exact published dims; see configs/)."""

    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1  # MoE layer stride (llama4: every 2nd layer)
    capacity_factor: float = 1.25
    # the experts this chip holds, [expert_lo, expert_lo + n_experts_here);
    # the router still scores all n_experts (0 => every expert is held)
    expert_lo: int = 0
    n_experts_here: int = 0
    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    conv_width: int = 4
    # --- hybrid (recurrentgemma) ---
    window: int = 0  # local-attention window
    # repeating sub-layer kinds, e.g. ("rec", "rec", "attn_local") or
    # ("moe_local",) * 3 + ("moe",); see transformer.block_layout
    block_pattern: tuple = ()
    lru_width: int = 0
    # --- enc-dec / multimodal frontends (stubs provide embeddings) ---
    enc_layers: int = 0
    enc_seq: int = 0  # frames (whisper) / patches (internvl2)
    # --- common ---
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    # YaRN on the full-attention layers: (factor, original_max_positions,
    # beta_fast, beta_slow, attention_factor); () => plain RoPE
    rope_yarn: tuple = ()
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # attention sharding policy: "heads" if n_heads % model_shards == 0
    # else "sequence" (context parallel / KV all-gather)
    attn_policy: str = "heads"
    # long-context support: sub-quadratic families run long_500k
    subquadratic: bool = False

    @property
    def padded_vocab(self) -> int:
        m = VOCAB_PAD_MULTIPLE
        return (self.vocab_size + m - 1) // m * m

    @property
    def experts_here(self) -> int:
        return self.n_experts_here or self.n_experts

    @property
    def qkv_dim(self) -> int:
        return (self.n_heads + 2 * self.n_kv_heads) * self.head_dim

    @property
    def d_inner(self) -> int:  # mamba2
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS)."""
        from repro.models import registry  # late import, avoids cycle
        return registry.param_count(self)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One parameter leaf: shape/dtype/partitioning/initializer."""

    shape: tuple
    dtype: Any
    pspec: tuple  # symbolic PartitionSpec entries (see resolve_pspec)
    init: str = "normal"  # normal | zeros | ones | embed | small
    fan_in: Optional[int] = None

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh,
                             resolve_pspec(self.pspec, mesh, self.shape))


def materialize(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    fan_in = spec.fan_in or (spec.shape[-2] if len(spec.shape) >= 2
                             else spec.shape[-1])
    scale = {"normal": 1.0 / math.sqrt(max(1, fan_in)),
             # d_model^-0.5 keeps tied-head logits at unit scale
             "embed": 1.0 / math.sqrt(spec.shape[-1]),
             "small": 0.02}[spec.init]
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale
            ).astype(spec.dtype)


def init_params(structure, rng: jax.Array):
    """Materialize a ParamSpec pytree into real arrays."""
    leaves, treedef = jax.tree.flatten(
        structure, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))
    vals = [materialize(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(structure):
    """ShapeDtypeStruct pytree (dry-run: no allocation)."""
    return jax.tree.map(lambda s: s.abstract(), structure,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_shardings(structure, mesh: Mesh):
    return jax.tree.map(lambda s: s.sharding(mesh), structure,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_bytes(structure) -> int:
    leaves = jax.tree.leaves(structure,
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in leaves)


def param_count_of(structure) -> int:
    leaves = jax.tree.leaves(structure,
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(math.prod(s.shape) for s in leaves)
