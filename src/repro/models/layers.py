"""Shared neural layers: norms, RoPE, streaming flash attention, GQA
projections, dense MLP, and index-dispatch MoE.

All functions are pure (params passed explicitly) and insert activation
sharding constraints via `repro.dist.sharding.shard` (no-ops off-mesh).
Attention never materializes the full S x S score matrix: KV is processed in
chunks with a running (max, denom, accum) softmax state -- the standard
flash algorithm expressed in pure JAX (a Pallas TPU kernel with the same
contract lives in repro/kernels/flash_attention.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.dist.sharding import shard
from repro import util

NEG_INF = -1e30


# ------------------------------------------------------------------- norms

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(dt) * weight


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps)).astype(dt) * weight + bias


# -------------------------------------------------------------------- RoPE

def yarn_frequencies(freqs: jax.Array, hd: int, theta: float,
                     yarn: tuple) -> jax.Array:
    """YaRN's blend of RoPE frequencies: dimensions that rotate fewer
    than ``beta_slow`` times over the original context are interpolated
    by ``factor``, those over ``beta_fast`` kept, a linear ramp between."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def dim_of(rotations):
        return (hd * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(beta_fast)), 0)
    hi = min(math.ceil(dim_of(beta_slow)), hd - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float32) - lo) / (hi - lo),
                   0.0, 1.0)
    keep = jnp.asarray(1.0 - ramp)  # share of the original frequency
    return freqs / factor * (1.0 - keep) + freqs * keep


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn: tuple = ()) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S] or [S].  ``yarn``: (factor,
    original_max_positions, beta_fast, beta_slow, attention_factor), or ()
    for plain RoPE; its attention factor scales cos and sin."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn:
        freqs = yarn_frequencies(freqs, hd, theta, yarn)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------- streaming (flash) attention --

def flash_attention(
    q: jax.Array,          # [B, Sq, H, hd]
    k: jax.Array,          # [B, Sk, K, hd]
    v: jax.Array,          # [B, Sk, K, hd]
    *,
    causal: bool = True,
    q_offset: int | jax.Array = 0,
    kv_len: Optional[jax.Array] = None,  # [B] valid cache length
    window: int = 0,       # local attention window (0 => unbounded)
    chunk: int = 0,
    k_pos: Optional[jax.Array] = None,   # [Sk] position held in each slot
) -> jax.Array:
    """GQA flash attention with KV-chunk streaming softmax.

    ``k_pos`` gives each KV slot's true position (a ring buffer's slots
    are not in position order); a negative entry marks an empty slot.
    Without it, slot i holds position i.

    Memory: O(Sq * chunk) scores live, never O(Sq * Sk).
    """
    if not chunk:
        chunk = util.flash_chunk_default()
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    # largest divisor of Sk not exceeding the requested chunk (a naive
    # halving loop degrades e.g. Sk=1500 to chunk=4 => 375 scan bodies)
    chunk = min(chunk, Sk)
    while Sk % chunk:
        chunk -= 1
    n_chunks = Sk // chunk
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    q_pos = (jnp.asarray(q_offset) + jnp.arange(Sq))  # [Sq]

    ks = k.reshape(B, n_chunks, chunk, K, hd)
    vs = v.reshape(B, n_chunks, chunk, K, hd)
    ks = jnp.moveaxis(ks, 1, 0)  # [n, B, chunk, K, hd]
    vs = jnp.moveaxis(vs, 1, 0)

    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    o0 = jnp.zeros((B, K, G, Sq, hd), jnp.float32)

    bf16_mm = util.attn_bf16_matmuls()

    xs = (ks, vs, jnp.arange(n_chunks))
    if k_pos is not None:
        xs += (k_pos.reshape(n_chunks, chunk),)

    def body(carry, inp):
        m, l, o = carry
        kc, vc, idx = inp[:3]
        base = idx * chunk
        with jax.named_scope("flash_internal"):
            # "flash_internal" tags the kernel-private tensors (scores,
            # probabilities, softmax state): with the Pallas flash kernel
            # they live in VMEM, and the dry-run's fused-attention
            # accounting (REPRO_FUSED_ATTN=1) excludes them from HBM
            # traffic. See kernels/flash_attention.py + launch/dryrun.py.
            if bf16_mm:  # Perf-iteration lever: bf16 MXU ops, f32 state
                s = jnp.einsum("bqkgd,bckd->bkgqc", qg.astype(q.dtype), kc,
                               preferred_element_type=jnp.float32)
            else:
                s = jnp.einsum("bqkgd,bckd->bkgqc", qg,
                               kc.astype(jnp.float32))
            s = s * scale
            mask = jnp.ones((Sq, chunk), bool)
            if k_pos is None:
                kp = base + jnp.arange(chunk)  # [chunk]
            else:
                kp = inp[3]
                mask &= kp[None, :] >= 0
            if causal:
                mask &= q_pos[:, None] >= kp[None, :]
            if window:
                mask &= (q_pos[:, None] - kp[None, :]) < window
            if kv_len is not None:
                mask = mask[None] & (kp[None, None, :]
                                     < kv_len[:, None, None])
                s = jnp.where(mask[:, None, None], s, NEG_INF)
            else:
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            if bf16_mm:
                pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(v.dtype), vc,
                                preferred_element_type=jnp.float32)
            else:
                pv = jnp.einsum("bkgqc,bckd->bkgqd", p,
                                vc.astype(jnp.float32))
            o_new = o * alpha[..., None] + pv
        return (m_new, l_new, o_new), None

    (m, l, o), _ = util.scan(body, (m0, l0, o0), xs)
    out = o / jnp.maximum(l, 1e-20)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def attention_reference(q, k, v, *, causal=True, q_offset=0, kv_len=None,
                        window=0):
    """Quadratic reference used by tests (materializes S x S)."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    if kv_len is not None:
        full = mask[None] & (k_pos[None, None, :] < kv_len[:, None, None])
        s = jnp.where(full[:, None, None], s, NEG_INF)
    else:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd).astype(q.dtype)


# --------------------------------------------------------- GQA attention ---

def ring_positions(written, slots: int) -> jax.Array:
    """[slots] position held in each slot of a ring buffer after positions
    ``0 .. written - 1`` were written at ``position mod slots``; -1 where
    nothing was written yet."""
    last = written - 1
    pos = last - jnp.mod(last - jnp.arange(slots), slots)
    return jnp.where(pos >= 0, pos, -1)


def gqa_attention(cfg, p, x, *, positions, cache=None, layer_name="attn",
                  window: int = 0, chunk: int = 0, yarn: tuple = ()):
    """Full attention sub-block: QKV proj -> RoPE -> flash attn -> O proj.

    cache: None for train/prefill-from-scratch, else dict with
    {"k": [B, Smax, K, hd], "v": ..., "len": [B]} -- decode appends at
    position `len` and attends over the prefix.  With ``window`` set the
    cache is a ring buffer of ``Smax`` slots (``Smax <= window``, or the
    whole context): position ``t`` lives in slot ``t mod Smax`` and the
    mask reads each slot's true position.
    Returns (out, new_cache).
    """
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    policy = cfg.attn_policy

    qkv = x @ p["wqkv"]  # [B, S, (H + 2K) * hd]
    if policy == "heads":
        qkv = shard(qkv, "batch", None, "model")
    else:  # sequence policy: shard S, replicate heads
        qkv = shard(qkv, "batch", "model", None)
    q, kk, vv = jnp.split(qkv, [H * hd, (H + K) * hd], axis=-1)
    q = q.reshape(B, S, H, hd)
    kk = kk.reshape(B, S, K, hd)
    vv = vv.reshape(B, S, K, hd)
    q = rope(q, positions, cfg.rope_theta, yarn)
    kk = rope(kk, positions, cfg.rope_theta, yarn)

    if cache is None:
        if policy == "heads":
            q = shard(q, "batch", None, "model", None)
            kk = shard(kk, "batch", None, None, None)
            vv = shard(vv, "batch", None, None, None)
        else:
            # context parallelism: Q stays sequence-sharded, KV all-gathered
            q = shard(q, "batch", "model", None, None)
            kk = shard(kk, "batch", None, None, None)
            vv = shard(vv, "batch", None, None, None)
        out = flash_attention(q, kk, vv, causal=True, window=window,
                              chunk=chunk)
        new_cache = None
    elif window:
        kk = shard(kk, "batch", None, None, None).astype(cache["k"].dtype)
        vv = shard(vv, "batch", None, None, None).astype(cache["v"].dtype)
        if policy == "heads":
            q = shard(q, "batch", None, "model", None)
        out, new_cache = _ring_attention(q, kk, vv, cache, window, chunk)
    else:
        # decode: append S (=1) new token(s) at position cache["len"].
        # k/v arrive model-sharded from the QKV split; constrain them to the
        # cache's batch-only sharding FIRST so the update (and the cache)
        # never reshards (a stray constraint here costs a full-cache
        # all-gather per layer).
        kk = shard(kk, "batch", None, None, None)
        vv = shard(vv, "batch", None, None, None)
        idx = cache["len"][0]  # uniform decode step across batch
        ck = lax.dynamic_update_slice_in_dim(cache["k"],
                                             kk.astype(cache["k"].dtype),
                                             idx, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"],
                                             vv.astype(cache["v"].dtype),
                                             idx, axis=1)
        ck = shard(ck, "batch", None, None, None)
        cv = shard(cv, "batch", None, None, None)
        if policy == "heads":
            q = shard(q, "batch", None, "model", None)
        out = flash_attention(q, ck, cv, causal=True, q_offset=idx,
                              kv_len=cache["len"] + S, window=window,
                              chunk=chunk)
        new_cache = {"k": ck, "v": cv, "len": cache["len"] + S}

    out = out.reshape(B, S, H * hd)
    out = out @ p["wo"]
    out = shard(out, "batch", None, None)
    return out, new_cache


def _ring_attention(q, kk, vv, cache, window: int, chunk: int):
    """Decode ``S`` new tokens against a ring-buffer cache -> (out, cache).

    One token (decode) is written first, at ``len mod slots``, and attends
    over the ring.  Several (prefill) attend over the old ring followed by
    themselves, and then the last ``min(S, slots)`` of them are written.
    """
    S = q.shape[1]
    slots = cache["k"].shape[1]
    idx = cache["len"][0]  # uniform decode step across batch
    if S == 1:
        at = jnp.mod(idx, slots)
        ck = lax.dynamic_update_slice_in_dim(cache["k"], kk, at, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], vv, at, axis=1)
        ck = shard(ck, "batch", None, None, None)
        cv = shard(cv, "batch", None, None, None)
        out = flash_attention(q, ck, cv, causal=True, q_offset=idx,
                              window=window, chunk=chunk,
                              k_pos=ring_positions(idx + 1, slots))
    else:
        k_pos = jnp.concatenate([ring_positions(idx, slots),
                                 idx + jnp.arange(S)])
        out = flash_attention(
            q, jnp.concatenate([cache["k"], kk], axis=1),
            jnp.concatenate([cache["v"], vv], axis=1), causal=True,
            q_offset=idx, window=window, chunk=chunk, k_pos=k_pos)
        n = min(S, slots)
        at = jnp.mod(idx + S - n + jnp.arange(n), slots)
        ck = shard(cache["k"].at[:, at].set(kk[:, S - n:]),
                   "batch", None, None, None)
        cv = shard(cache["v"].at[:, at].set(vv[:, S - n:]),
                   "batch", None, None, None)
    return out, {"k": ck, "v": cv, "len": cache["len"] + S}


# ------------------------------------------------------------- dense MLP ---

def swiglu_mlp(p, x):
    h = x @ p["wi_gate"]
    g = x @ p["wi_up"]
    h = shard(h, "batch", None, "model")
    g = shard(g, "batch", None, "model")
    out = (jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * g) @ p["wo"]
    return shard(out, "batch", None, None)


def gelu_mlp(p, x):
    h = x @ p["wi"] + p.get("bi", 0)
    h = shard(h, "batch", None, "model")
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out = h @ p["wo"] + p.get("bo", 0)
    return shard(out, "batch", None, None)


# ------------------------------------------- MoE (index dispatch, EP) ------

def expert_capacity(cfg, tokens: int) -> int:
    """Slots per expert: ``ceil(tokens * top_k * capacity_factor / E)``."""
    return int(math.ceil(tokens * cfg.top_k * cfg.capacity_factor
                         / cfg.n_experts))


def moe_block(cfg, p, x):
    """Top-k MoE over the experts this chip holds, dispatched by index.

    The router scores all ``cfg.n_experts`` experts; the gate is the
    softmax over the top-k logits (the top-k probabilities renormalised).
    Each (token, choice) pair whose expert lies in the held range
    ``[expert_lo, expert_lo + experts_here)`` takes the next free one of
    that expert's :func:`expert_capacity` slots, in token order; pairs
    past capacity are dropped.  The slot tables come from a cumsum and
    scatters, the tokens reach their slots by a gather, each held expert
    runs its SwiGLU on its own slots with its own weights, and the
    gate-weighted results are scatter-added back to their tokens.  What
    absent experts would add is left out: the output is this chip's part
    of the layer.  Held experts are sharded over ``data`` (EP), their FF
    dim over ``model`` (TP).

    Returns (out [B, S, D], pairs dropped past capacity: int32 scalar).
    """
    B, S, D = x.shape
    k, n_here = cfg.top_k, cfg.experts_here
    T = B * S
    C = expert_capacity(cfg, T)
    xt = shard(x.reshape(T, D), "batch", None)

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)
    top, sel = lax.top_k(logits, k)                       # [T, k]
    gate = jax.nn.softmax(top, axis=-1).reshape(T * k)
    rel = sel.reshape(T * k) - cfg.expert_lo              # pairs, token order
    held = (rel >= 0) & (rel < n_here)
    hit = rel[:, None] == jnp.arange(n_here)[None, :]     # [T*k, n_here]
    order = jnp.cumsum(hit, axis=0, dtype=jnp.int32) - 1  # slot at each expert
    slot = jnp.take_along_axis(
        order, jnp.clip(rel, 0, n_here - 1)[:, None], axis=1)[:, 0]
    keep = held & (slot < C)
    dropped = jnp.sum(held & ~keep, dtype=jnp.int32)

    # (expert, slot) -> its token (T, a zero row, where empty) and gate
    row = jnp.where(keep, rel, n_here)                    # n_here: dropped
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(T * k)
    src = jnp.full((n_here, C), T, jnp.int32).at[row, slot].set(
        tok, mode="drop")
    weight = jnp.zeros((n_here, C), jnp.float32).at[row, slot].set(
        gate, mode="drop")

    xe = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])[src]
    xe = shard(xe, "data", None, None)                    # [n_here, C, D]
    h = shard(jnp.matmul(xe, p["w_gate"]), "data", None, "model")
    u = shard(jnp.matmul(xe, p["w_up"]), "data", None, "model")
    a = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    ye = shard(jnp.matmul(a, p["w_down"]), "data", None, None)
    ye = ye.astype(jnp.float32) * weight[..., None]
    out = jnp.zeros((T + 1, D), jnp.float32).at[src].add(ye)[:T]
    out = shard(out.astype(x.dtype), "batch", None)
    return out.reshape(B, S, D), dropped


# ----------------------------------------------------------- lm head/loss --

def embed_tokens(p, tokens, d_model):
    emb = jnp.take(p["embedding"], tokens, axis=0)
    return shard(emb, "batch", None, None)


def lm_logits(p, x, embedding=None):
    table = embedding if embedding is not None else p["lm_head"]
    logits = x @ table.T if embedding is not None else x @ table
    return shard(logits, "batch", None, "model")


def pim_quantized_linear(x, w, *, weight_bits: int, plan=None,
                         op_name: str | None = None):
    """Quantized linear dispatched by a compiled ``repro.plan`` layout
    plan -- the model layer consumes the same BP/BS decision the cost
    model priced (falling back to the Table-8 advisor when no plan is
    given).

    x: integer activations [..., K] (int8-range); w: unsigned words
    [K, N] with values < 2^weight_bits.  Returns (y [..., N] int32, the
    Layout actually dispatched).
    """
    from repro.kernels.ops import planned_matmul

    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    y, layout = planned_matmul(x2, w, weight_bits=weight_bits, plan=plan,
                               op_name=op_name)
    return y.reshape(lead + (w.shape[1],)), layout


def chunked_cross_entropy(logits_fn, x, labels, mask, chunk: int = 512):
    """CE over S in chunks so the [B, chunk, V] logits (vocab-sharded) are
    the only live logits tensor."""
    B, S, D = x.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk

    def body(carry, idx):
        tot, cnt = carry
        xs = lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=1)
        ls = lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        ms = lax.dynamic_slice_in_dim(mask, idx * chunk, chunk, axis=1)
        logits = logits_fn(xs).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * ms
        return (tot + nll.sum(), cnt + ms.sum()), None

    (tot, cnt), _ = util.scan(body, (jnp.float32(0), jnp.float32(0)),
                              jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)
