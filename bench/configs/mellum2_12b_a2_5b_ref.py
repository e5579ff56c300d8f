"""Plain float32 reference of Mellum2-12B-A2.5B: full forward and decode.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no kernels, no cache, no batching tricks, nothing imported
from the program.  It follows the published config.json
(hf:JetBrains/Mellum2-12B-A2.5B-Instruct): RMSNorm, GQA with separate q/k/v
projections, RoPE with theta from ``rope_parameters`` (YaRN on the
full-attention layers, plain on the sliding ones), a causal sliding window
of ``sliding_window`` positions (the current one included) on the sliding
layers, and every MLP sparse: a softmax router over all experts, top-k,
the top-k weights renormalised, SwiGLU experts, no shared expert.  Not
applied, as the published config has none: QK-norm, attention bias.

Expert share: the weights hold experts ``expert_lo .. expert_lo + n - 1``
of the router's ``E``; the router scores all ``E`` and only pairs routed
to a held expert contribute.  ``capacity_factor=None`` is dropless;
otherwise the tokens fed to the model in one call form one routing group
(``groups``: the length of each call, in positions), each held expert has
``ceil(group tokens * k * capacity_factor / E)`` slots, and pairs past
them are dropped in token order (sequence-major, then position) -- the
program's rule.

Parameters (float32 arrays), ``L`` layers:
``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]``, ``layers``: a
list of ``{ln1 [D], wq [D, H*hd], wk [D, K*hd], wv [D, K*hd], wo [H*hd,
D], ln2 [D], router [D, E], w_gate [n, D, F], w_up [n, D, F], w_down
[n, F, D]}``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_tables(positions, hd: int, rope: dict):
    """(cos, sin) [S, hd/2] for one section of ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def dim_of(rotations):
            return (hd * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        lo = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
        hi = min(math.ceil(dim_of(float(rope["beta_slow"]))), hd - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0.0, 1.0)
        keep = 1.0 - ramp
        inv = inv / factor * (1.0 - keep) + inv * keep
        scale = float(rope["attention_factor"])
    ang = jnp.asarray(positions, f32)[:, None] * jnp.asarray(inv, f32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin):
    """x [B, S, heads, hd], rotate-half convention."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(x, p, cfg, kind: str, block: int):
    """One attention sub-layer over the whole sequence, queries in blocks
    of ``block`` positions."""
    B, S, _ = x.shape
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    rope = cfg["rope_parameters"][kind]
    cos, sin = rope_tables(np.arange(S), hd, rope)
    q = apply_rope((x @ p["wq"]).reshape(B, S, H, hd), cos, sin)
    k = apply_rope((x @ p["wk"]).reshape(B, S, K, hd), cos, sin)
    v = (x @ p["wv"]).reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd)   # query head h reads KV head h // G
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    outs = []
    for a in range(0, S, block):
        qb = q[:, a:a + block]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / math.sqrt(hd)
        qpos = np.arange(a, a + qb.shape[1])[:, None]
        kpos = np.arange(S)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(jnp.asarray(mask), s, -jnp.inf)
        outs.append(jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1),
                               v))
    return jnp.concatenate(outs, axis=1).reshape(B, S, H * hd) @ p["wo"]


def moe(x, p, cfg, *, expert_lo: int, capacity_factor, groups):
    """The sparse MLP; -> (output [B, S, D], pairs dropped per group)."""
    B, S, D = x.shape
    E = p["router"].shape[1]
    n = p["w_gate"].shape[0]
    k = cfg["num_experts_per_tok"]
    out = jnp.zeros_like(x)
    dropped = []
    a = 0
    for g in groups:
        xt = x[:, a:a + g].reshape(B * g, D)       # sequence-major tokens
        probs = jax.nn.softmax(xt @ p["router"], axis=-1)
        top, sel = jax.lax.top_k(probs, k)
        gate = top / jnp.sum(top, axis=-1, keepdims=True)
        sel = np.asarray(sel)
        cap = (None if capacity_factor is None else
               math.ceil(B * g * k * capacity_factor / E))
        taken = np.zeros(n, np.int64)
        use = np.zeros((B * g, k), bool)
        for t in range(B * g):
            for j in range(k):
                e = sel[t, j] - expert_lo
                if 0 <= e < n:
                    if cap is None or taken[e] < cap:
                        use[t, j] = True
                    taken[e] += 1
        dropped.append(int(sum(max(0, c - cap) for c in taken))
                       if cap is not None else 0)
        y = jnp.zeros_like(xt)
        for e in range(n):
            w = jnp.sum(jnp.where(jnp.asarray(use & (sel == e + expert_lo)),
                                  gate, 0.0), axis=-1)
            h = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
            y = y + w[:, None] * (h @ p["w_down"][e])
        out = out.at[:, a:a + g].set(y.reshape(B, g, D))
        a += g
    return out, dropped


def hidden(params, tokens, cfg, *, expert_lo: int = 0,
           capacity_factor=None, groups=None, block: int = 512):
    """Final normed hidden states [B, S, D] of every position, and the
    pairs dropped per routing group of every layer (``[layer][group]``).

    ``groups`` (default: the whole sequence as one) are the lengths of the
    calls that fed the model the sequence; they matter only with a
    ``capacity_factor``."""
    tokens = np.asarray(tokens)
    S = tokens.shape[1]
    groups = list(groups or [S])
    assert sum(groups) == S, (groups, S)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], f32)[tokens]
        drops = []
        for p, kind in zip(params["layers"], cfg["layer_types"]):
            x = x + attention(rms_norm(x, p["ln1"], eps), p, cfg, kind, block)
            y, d = moe(rms_norm(x, p["ln2"], eps), p, cfg,
                       expert_lo=expert_lo, capacity_factor=capacity_factor,
                       groups=groups)
            x = x + y
            drops.append(d)
        return rms_norm(x, params["final_norm"], eps), drops


def forward(params, tokens, cfg, **kw):
    """Logits [B, S, V] of every position, and the drops of :func:`hidden`."""
    x, drops = hidden(params, tokens, cfg, **kw)
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"], drops


def decode_logits(params, tokens, cfg, **kw):
    """Next-token logits [B, V] after the whole of ``tokens`` (the last
    position of :func:`forward`), and the drops."""
    x, drops = hidden(params, tokens, cfg, **kw)
    with jax.default_matmul_precision("highest"):
        return x[:, -1] @ params["lm_head"], drops


def from_program(params, n_layers: int, n_heads: int, n_kv_heads: int,
                 head_dim: int):
    """The program's parameter pytree (stacked ``blocks``, fused ``wqkv``)
    as this module's float32 layout -- a reshuffle of plain arrays."""
    cast = lambda a: jnp.asarray(a, f32)  # noqa: E731
    per = len(params["blocks"])
    q_end, k_end = n_heads * head_dim, (n_heads + n_kv_heads) * head_dim
    layers = []
    for i in range(n_layers):
        b = params["blocks"][i % per]
        j = i // per
        wqkv = cast(b["attn"]["wqkv"][j])
        mlp = b["mlp"]
        layers.append({
            "ln1": cast(b["ln1"][j]), "ln2": cast(b["ln2"][j]),
            "wq": wqkv[:, :q_end], "wk": wqkv[:, q_end:k_end],
            "wv": wqkv[:, k_end:], "wo": cast(b["attn"]["wo"][j]),
            "router": cast(mlp["router"][j]),
            "w_gate": cast(mlp["w_gate"][j]), "w_up": cast(mlp["w_up"][j]),
            "w_down": cast(mlp["w_down"][j])})
    return {"embed": cast(params["embedding"]),
            "final_norm": cast(params["final_ln"]),
            "lm_head": cast(params["lm_head"]), "layers": layers}
