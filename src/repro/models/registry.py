"""Model registry: family -> (param_structure, forward_train, decode_step,
cache_structure), plus analytic parameter/FLOP accounting for the roofline.
"""
from __future__ import annotations

import math
from types import SimpleNamespace

from repro.models.base import ArchConfig, param_count_of


def model_fns(cfg: ArchConfig) -> SimpleNamespace:
    if cfg.family == "ssm":
        from repro.models import mamba2 as m
    elif cfg.family == "audio":
        from repro.models import whisper as m
    else:  # dense | moe | hybrid | vlm share the decoder stack
        from repro.models import transformer as m
    return SimpleNamespace(
        param_structure=m.param_structure,
        cache_structure=m.cache_structure,
        forward_train=m.forward_train,
        forward_hidden=m.forward_hidden,
        forward_logits=m.forward_logits,
        decode_step=m.decode_step,
    )


def traced_workload(cfg: ArchConfig, *, tokens: int = 4096,
                    phase: str = "decode", weight_bits: int = 4,
                    scan_mode: str = "once", kv_len: int | None = None):
    """Trace the family's real forward pass into a Workload DAG.

    ``phase="decode"``: one decode step over ``tokens`` concurrent
    sequences with a ``kv_len``-long KV cache (default ``tokens``; local
    layers hold ``min(window, kv_len)`` slots) -- the operating point of
    the hand-written ``arch/<id>`` serving formulas, so the two are
    directly comparable (``repro.workloads.trace_diff``).
    ``phase="prefill"``: ``forward_hidden`` over one ``tokens``-long
    sequence.

    Tracing is abstract (``jax.ShapeDtypeStruct`` pytrees): full-size
    models trace without allocating a single parameter.  Weight matrices
    (>=2-D leaves at the model dtype) resolve to ``weight_bits``; the
    RG-LRU gate matrices stay at model precision, matching the 16-bit
    ``rg_lru_gates`` formula op.  Each held expert's product with its own
    weights is one matmul op (``Op.expert``).
    """
    import jax
    import jax.numpy as jnp

    from repro.models.base import abstract_params
    from repro.workloads.trace import param_path_widths, trace_workload

    if phase not in ("decode", "prefill"):
        raise ValueError(f"phase must be 'decode' or 'prefill', "
                         f"got {phase!r}")
    fns = model_fns(cfg)
    params = abstract_params(fns.param_structure(cfg))
    pmap = param_path_widths(params, weight_bits=weight_bits,
                             dtype=cfg.dtype,
                             exclude=("a_gate", "input_gate"))
    experts = ()
    if cfg.n_experts:
        from repro.models.transformer import expert_param_paths
        experts = tuple(f"0/{p}" for p in expert_param_paths(cfg))
    if phase == "decode":
        cache = abstract_params(fns.cache_structure(
            cfg, batch=tokens, max_len=tokens if kv_len is None else kv_len))
        tok = jax.ShapeDtypeStruct((tokens, 1), jnp.int32)

        def fn(p, c, t):
            return fns.decode_step(cfg, p, c, t)
        args = (params, cache, tok)
    else:
        batch = {"tokens": jax.ShapeDtypeStruct((1, tokens), jnp.int32)}
        if cfg.family == "audio":
            batch["frames"] = jax.ShapeDtypeStruct(
                (1, cfg.enc_seq, cfg.d_model), cfg.dtype)

        def fn(p, b):
            return fns.forward_hidden(cfg, p, b)
        args = (params, batch)
    return trace_workload(
        fn, *args, precision_map=pmap, name=f"traced/{cfg.name}",
        source="traced", scan_mode=scan_mode, expert_paths=experts,
        description=(f"{cfg.name} jaxpr-traced {phase} step "
                     f"({tokens} tokens, int{weight_bits} weights"
                     + ("" if kv_len is None else f", {kv_len} KV")
                     + ")"))


def param_count(cfg: ArchConfig) -> int:
    """Exact parameter count from the parameter structure."""
    return param_count_of(model_fns(cfg).param_structure(cfg))


def active_param_count(cfg: ArchConfig) -> int:
    """Parameters touched per token (MoE: top_k of n_experts experts).
    Used for MODEL_FLOPS = 6 * N_active * D (dense) in the roofline."""
    total = param_count(cfg)
    if not cfg.n_experts:
        return total
    st = model_fns(cfg).param_structure(cfg)
    expert_leaves = 0
    for blk in st["blocks"]:
        mlp = blk.get("mlp", {})
        for name in ("w_gate", "w_up", "w_down"):
            if name in mlp:
                expert_leaves += math.prod(mlp[name].shape)
    active_frac = cfg.top_k / cfg.n_experts
    return int(total - expert_leaves * (1 - active_frac))


def model_flops(cfg: ArchConfig, tokens: int, *, train: bool = True) -> float:
    """6*N_active*D for training (fwd+bwd), 2*N_active*D for inference."""
    n = active_param_count(cfg)
    return (6.0 if train else 2.0) * n * tokens
