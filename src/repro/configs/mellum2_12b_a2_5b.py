"""mellum2-12b-a2.5b [moe]: 28L d_model=2304 32H (GQA kv=4, head_dim 128)
vocab=98304, every MLP sparse: 64 experts top-8 (softmax, top-k renormalised),
SwiGLU experts of width 896, no shared expert; attention repeats sliding,
sliding, sliding, full (window 1024, RoPE theta 5e5, YaRN x16 on the full
layers) [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]."""
import jax.numpy as jnp
from repro.models.base import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="mellum2_12b_a2_5b", family="moe",
        n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, d_ff=896,
        vocab_size=98304, head_dim=128,
        n_experts=64, top_k=8, moe_every=1,
        window=1024, block_pattern=("moe_local",) * 3 + ("moe",),
        rope_theta=500_000.0,
        # factor, original_max_position_embeddings, beta_fast, beta_slow,
        # attention_factor -- the config's full_attention rope_parameters
        rope_yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
        attn_policy="heads", dtype=jnp.bfloat16,
    )
