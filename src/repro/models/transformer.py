"""Decoder-only transformer family: dense (yi, tinyllama, mistral-nemo,
stablelm), MoE (dbrx, llama4-maverick, mellum2), and the LM backbone reused
by the VLM/audio/hybrid models.

Layers are stacked along a leading block axis and executed with `lax.scan`
(small HLO, O(1) compile cost in depth). A block is a repeating pattern of
sub-layers (`block_layout`), so MoE-every-2 (llama4), sliding/full attention
patterns (mellum2) and hybrid patterns (recurrentgemma) reuse the same
machinery.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import shard
from repro import util
from repro.models import layers as L
from repro.models.base import ArchConfig, ParamSpec


# ------------------------------------------------------------- structure ---

#: sub-layer kinds -> (attention kind, MLP kind); 'local' attention keeps a
#: ``window``-slot ring-buffer cache, 'full' one of the whole context
SUBLAYERS = {
    "dense": ("full", "dense"),
    "moe": ("full", "moe"),
    "attn_local": ("local", "dense"),
    "moe_local": ("local", "moe"),
    "rec": ("rec", "dense"),
}


def block_layout(cfg: ArchConfig) -> tuple[list[str], list[str]]:
    """(repeating block layout, tail layout). Entries: keys of
    :data:`SUBLAYERS`; ``cfg.block_pattern`` gives them per layer."""
    if cfg.block_pattern:
        pat = list(cfg.block_pattern)
        n_full = cfg.n_layers // len(pat)
        tail_n = cfg.n_layers - n_full * len(pat)
        return pat, pat[:tail_n]
    if cfg.n_experts and cfg.moe_every == 2:
        assert cfg.n_layers % 2 == 0
        return ["dense", "moe"], []
    if cfg.n_experts:
        return ["moe"], []
    return ["dense"], []


def _attn_params(cfg: ArchConfig, n: int) -> dict:
    D, hd = cfg.d_model, cfg.head_dim
    qkv = cfg.qkv_dim
    dt = cfg.dtype
    return {
        "wqkv": ParamSpec((n, D, qkv), dt, (None, None, "model"), fan_in=D),
        "wo": ParamSpec((n, cfg.n_heads * hd, D), dt,
                        (None, "model", None), fan_in=cfg.n_heads * hd),
    }


def _mlp_params(cfg: ArchConfig, n: int) -> dict:
    D, F, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    return {
        "wi_gate": ParamSpec((n, D, F), dt, (None, None, "model"), fan_in=D),
        "wi_up": ParamSpec((n, D, F), dt, (None, None, "model"), fan_in=D),
        "wo": ParamSpec((n, F, D), dt, (None, "model", None), fan_in=F),
    }


def _moe_params(cfg: ArchConfig, n: int) -> dict:
    """The router scores all ``n_experts``; expert weights are held for
    ``experts_here`` of them."""
    D, F, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    E, Eh = cfg.n_experts, cfg.experts_here
    return {
        "router": ParamSpec((n, D, E), jnp.float32, (None, None, None),
                            init="small"),
        "w_gate": ParamSpec((n, Eh, D, F), dt, (None, "data", None, "model"),
                            fan_in=D),
        "w_up": ParamSpec((n, Eh, D, F), dt, (None, "data", None, "model"),
                          fan_in=D),
        "w_down": ParamSpec((n, Eh, F, D), dt, (None, "data", "model", None),
                            fan_in=F),
    }


#: expert weight leaves of an MoE sub-layer's ``mlp`` params
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def expert_param_paths(cfg: ArchConfig) -> tuple[str, ...]:
    """Key paths (``blocks/<i>/mlp/<leaf>``, ``tail/...``) of every expert
    weight in :func:`param_structure`."""
    layout, tail = block_layout(cfg)
    return tuple(f"{group}/{i}/mlp/{leaf}"
                 for group, kinds in (("blocks", layout), ("tail", tail))
                 for i, kind in enumerate(kinds)
                 if SUBLAYERS[kind][1] == "moe" for leaf in EXPERT_LEAVES)


def _rec_params(cfg: ArchConfig, n: int) -> dict:
    """RG-LRU recurrent block (recurrentgemma)."""
    D, W, dt = cfg.d_model, cfg.lru_width, cfg.dtype
    return {
        "wx": ParamSpec((n, D, W), dt, (None, None, "model"), fan_in=D),
        "wgate": ParamSpec((n, D, W), dt, (None, None, "model"), fan_in=D),
        "conv_w": ParamSpec((n, cfg.conv_width, W), dt,
                            (None, None, "model"), init="small"),
        "a_param": ParamSpec((n, W), jnp.float32, (None, "model"),
                             init="small"),
        "w_input_gate": ParamSpec((n, W, W), dt, (None, None, "model"),
                                  fan_in=W),
        "w_a_gate": ParamSpec((n, W, W), dt, (None, None, "model"), fan_in=W),
        "wo": ParamSpec((n, W, D), dt, (None, "model", None), fan_in=W),
    }


def _sublayer_params(cfg: ArchConfig, kind: str, n: int) -> dict:
    D, dt = cfg.d_model, cfg.dtype
    ln = lambda: ParamSpec((n, D), dt, (None, None), init="ones")  # noqa: E731
    attn, mlp = SUBLAYERS[kind]
    mixer = ({"rec": _rec_params(cfg, n)} if attn == "rec"
             else {"attn": _attn_params(cfg, n)})
    body = _moe_params(cfg, n) if mlp == "moe" else _mlp_params(cfg, n)
    return {"ln1": ln(), **mixer, "ln2": ln(), "mlp": body}


def param_structure(cfg: ArchConfig):
    layout, tail = block_layout(cfg)
    per = len(layout)
    n_blocks = (cfg.n_layers - len(tail)) // per
    V, D, dt = cfg.padded_vocab, cfg.d_model, cfg.dtype
    st = {
        "embedding": ParamSpec((V, D), dt, ("model", None), init="embed"),
        "final_ln": ParamSpec((D,), dt, (None,), init="ones"),
        "blocks": [
            _sublayer_params(cfg, kind, n_blocks) for kind in layout
        ],
    }
    if tail:
        st["tail"] = [_sublayer_params(cfg, kind, 1) for kind in tail]
    if not cfg.tie_embeddings:
        st["lm_head"] = ParamSpec((D, V), dt, (None, "model"), fan_in=D)
    return st


# ----------------------------------------------------------------- cache ---

def cache_structure(cfg: ArchConfig, batch: int, max_len: int):
    """Decode cache as a ParamSpec pytree (zeros init / abstract dry-run).

    Full-attention layers hold ``max_len`` positions; local ones a ring
    buffer of ``min(window, max_len)`` slots.  MoE configs add
    ``moe_dropped``: the (token, expert) pairs the last step dropped past
    capacity, summed over layers."""
    layout, tail = block_layout(cfg)
    per = len(layout)
    n_blocks = (cfg.n_layers - len(tail)) // per
    K, hd, dt = cfg.n_kv_heads, cfg.head_dim, cfg.dtype

    def kv(n, length):
        return {
            "k": ParamSpec((n, batch, length, K, hd), dt,
                           (None, "batch", None, None, None), init="zeros"),
            "v": ParamSpec((n, batch, length, K, hd), dt,
                           (None, "batch", None, None, None), init="zeros"),
        }

    def sub(kind, n):
        attn = SUBLAYERS[kind][0]
        if attn == "full":
            return kv(n, max_len)
        if attn == "local":
            return kv(n, min(cfg.window, max_len))
        W = cfg.lru_width
        return {
            "h": ParamSpec((n, batch, W), jnp.float32,
                           (None, "batch", "model"), init="zeros"),
            "conv": ParamSpec((n, batch, cfg.conv_width - 1, W), dt,
                              (None, "batch", None, "model"),
                              init="zeros"),
        }

    st = {"len": ParamSpec((batch,), jnp.int32, ("batch",), init="zeros"),
          "blocks": [sub(kind, n_blocks) for kind in layout]}
    if cfg.n_experts:
        st["moe_dropped"] = ParamSpec((), jnp.int32, (), init="zeros")
    if tail:
        st["tail"] = [sub(kind, 1) for kind in tail]
    return st


# ---------------------------------------------------------------- forward --

def _take_layer(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _apply_sublayer(cfg, kind, p, x, *, positions, cache):
    """One residual sub-layer. Returns (x, new_cache, pairs the MoE
    dropped past capacity, or None without an MoE)."""
    from repro.models import recurrent  # late import (rec blocks)

    attn, mlp = SUBLAYERS[kind]
    new_cache = cache
    if attn == "rec":
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        h, new_cache = recurrent.rg_lru_block(cfg, p["rec"], h, cache=cache)
        x = x + h
    else:
        local = attn == "local"
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        attn_cache = None if cache is None else \
            {"k": cache["k"], "v": cache["v"], "len": cache["len"]}
        h, kv_new = L.gqa_attention(
            cfg, p["attn"], h, positions=positions, cache=attn_cache,
            window=cfg.window if local else 0,
            yarn=() if local else cfg.rope_yarn)
        if kv_new is not None:
            new_cache = {"k": kv_new["k"], "v": kv_new["v"]}
        x = x + h
    if util.bf16_allreduce_barrier():
        x = lax.optimization_barrier(x)  # keep TP psums in bf16
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    dropped = None
    if mlp == "moe":
        h, dropped = L.moe_block(cfg, p["mlp"], h)
    else:
        h = L.swiglu_mlp(p["mlp"], h)
    x = x + h
    if util.bf16_allreduce_barrier():
        x = lax.optimization_barrier(x)
    return x, new_cache, dropped


def _run_blocks(cfg, params, x, *, positions, cache=None):
    """Scan the repeating blocks, then the tail. Returns (x, new_cache)."""
    layout, tail = block_layout(cfg)

    def block_fn(xc, blk):
        x, step_len, dropped = xc
        blk_params, blk_cache = blk
        new_caches = []
        for kind, p, c in zip(layout, blk_params,
                              blk_cache or [None] * len(layout)):
            if c is not None:
                c = dict(c)
                c["len"] = step_len
            x, nc, d = _apply_sublayer(cfg, kind, p, x, positions=positions,
                                       cache=c)
            if d is not None:
                dropped = dropped + d
            if nc is not None:
                nc = {k: v for k, v in nc.items() if k != "len"}
            new_caches.append(nc)
        return (x, step_len, dropped), new_caches

    blk_caches = cache["blocks"] if cache is not None else None
    step_len = cache["len"] if cache is not None else None
    dropped = jnp.int32(0)
    if cache is None:
        def scan_fn(x, blk_params):
            (x, _, _), _ = block_fn((x, None, dropped), (blk_params, None))
            return x, None
        if util.remat_enabled():
            scan_fn = jax.checkpoint(
                scan_fn, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = util.scan(scan_fn, x, params["blocks"])
        new_cache = None
    else:
        def scan_fn(carry, xs):
            blk_params, blk_cache = xs
            return block_fn(carry, (blk_params, blk_cache))
        (x, _, dropped), new_blk_caches = util.scan(
            scan_fn, (x, step_len, dropped),
            (params["blocks"], blk_caches))
        new_cache = {"len": step_len + x.shape[1],
                     "blocks": new_blk_caches}

    if tail:
        tail_caches = cache.get("tail") if cache is not None else None
        new_tail = []
        for i, kind in enumerate(tail):
            p = _take_layer(params["tail"][i], 0)
            c = None
            if tail_caches is not None:
                c = dict(_take_layer(tail_caches[i], 0))
                c["len"] = step_len
            x, nc, d = _apply_sublayer(cfg, kind, p, x, positions=positions,
                                       cache=c)
            if nc is not None:  # restore the leading block axis
                nc = {k: v[None] for k, v in nc.items() if k != "len"}
            if d is not None:
                dropped = dropped + d
            new_tail.append(nc)
        if new_cache is not None:
            new_cache["tail"] = new_tail
    if new_cache is not None and cfg.n_experts:
        new_cache["moe_dropped"] = dropped
    return x, new_cache


def _logits_fn(cfg, params):
    table = params["embedding"].T if cfg.tie_embeddings else params["lm_head"]

    def fn(x):
        logits = x @ table
        logits = shard(logits, "batch", None, "model")
        v = jnp.arange(logits.shape[-1])
        return jnp.where(v[None, None, :] < cfg.vocab_size,
                         logits, L.NEG_INF)
    return fn


def forward_hidden(cfg: ArchConfig, params, batch):
    """Final hidden states for the token positions (prefix stripped)."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params, tokens, cfg.d_model)
    if "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(x.dtype)
        x = jnp.concatenate([pre, x], axis=1)
    x = shard(x, "batch", None, None)
    positions = jnp.arange(x.shape[1])
    x, _ = _run_blocks(cfg, params, x, positions=positions)
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if "prefix_embeds" in batch:
        x = x[:, batch["prefix_embeds"].shape[1]:]
    return x


def forward_train(cfg: ArchConfig, params, batch):
    """batch: tokens [B,S], labels [B,S], mask [B,S] (+ optional
    'prefix_embeds' [B,P,D] for VLM-style prefixes)."""
    x = forward_hidden(cfg, params, batch)
    return L.chunked_cross_entropy(_logits_fn(cfg, params), x,
                                   batch["labels"], batch["mask"])


def forward_logits(cfg: ArchConfig, params, batch):
    return _logits_fn(cfg, params)(forward_hidden(cfg, params, batch))


def decode_step(cfg: ArchConfig, params, cache, tokens):
    """One decode step: tokens [B, 1] -> (logits [B, 1, Vp], new cache)."""
    B, S = tokens.shape
    x = L.embed_tokens(params, tokens, cfg.d_model)
    positions = cache["len"][:, None] + jnp.arange(S)[None, :]
    x, new_cache = _run_blocks(cfg, params, x, positions=positions,
                               cache=cache)
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = _logits_fn(cfg, params)(x)
    return logits, new_cache
