"""The expert layer told which experts it holds, ring-buffer caches beside
full ones, and the mellum2 decoder against its plain float32 reference
(``bench/configs/mellum2_12b_a2_5b_ref.py``), at small sizes on the CPU.

The reference is the benchmark's copy, loaded from its file: it imports
nothing from the program.
"""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs import get_config, reduced_config
from repro.models import init_params, registry
from repro.models import layers as L
from repro.models.base import init_params as init_p

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "mellum2_ref", ROOT / "bench" / "configs" / "mellum2_12b_a2_5b_ref.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

#: the config's rope_parameters (hf:JetBrains/Mellum2-12B-A2.5B-Instruct)
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
}
KINDS = {"moe_local": "sliding_attention", "moe": "full_attention"}


def _mellum(**kw):
    """The reduced mellum2 config (f32, 4 layers s,s,s,f, window 8)."""
    return dataclasses.replace(reduced_config(get_config(
        "mellum2_12b_a2_5b")), **kw)


def _dropless(cfg):
    """capacity_factor at which every expert has a slot for every token."""
    return dataclasses.replace(cfg, capacity_factor=cfg.n_experts / cfg.top_k)


def _ref_cfg(cfg) -> dict:
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps,
            "layer_types": [KINDS[k] for k in cfg.block_pattern],
            "sliding_window": cfg.window,
            "num_experts_per_tok": cfg.top_k, "rope_parameters": ROPE}


def _params(cfg, seed=0):
    fns = registry.model_fns(cfg)
    return fns, init_params(fns.param_structure(cfg), jax.random.key(seed))


def _decode(cfg, fns, params, tokens, prefill: int, max_len: int = 64):
    """Prefill ``prefill`` positions in one call, then one per call; ->
    (next-token logits after each call [B, calls, V], pairs dropped per
    call)."""
    B, S = tokens.shape
    cache = init_p(fns.cache_structure(cfg, B, max_len), jax.random.key(1))
    logits, cache = fns.decode_step(cfg, params, cache, tokens[:, :prefill])
    outs, drops = [logits[:, -1]], [int(cache["moe_dropped"])]
    for i in range(prefill, S):
        logits, cache = fns.decode_step(cfg, params, cache,
                                        tokens[:, i:i + 1])
        outs.append(logits[:, -1])
        drops.append(int(cache["moe_dropped"]))
    return np.stack([np.asarray(o) for o in outs], 1), drops, cache


@pytest.mark.parametrize("capacity_factor", [None, 1.25])
def test_decode_past_the_window_matches_the_reference(capacity_factor):
    """A prefill, then decode steps past the 8-position window (the ring
    buffers wrap), agree with the reference's full forward on the logits
    of every step, dropless and at capacity factor 1.25."""
    cfg = _mellum()
    cfg = (_dropless(cfg) if capacity_factor is None else
           dataclasses.replace(cfg, capacity_factor=capacity_factor))
    fns, params = _params(cfg)
    B, P, S = 4, 6, 20
    toks = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    got, _, _ = _decode(cfg, fns, params, toks, P)
    rp = ref.from_program(params, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim)
    want, _ = ref.forward(rp, toks, _ref_cfg(cfg),
                          capacity_factor=capacity_factor,
                          groups=[P] + [1] * (S - P))
    want = np.asarray(want)[:, P - 1:]
    V = cfg.vocab_size
    # float32 on both sides: what differs is the order of summation
    # (flash-chunked softmax, one fused qkv product); logits are O(1)
    np.testing.assert_allclose(got[..., :V], want[..., :V], rtol=0,
                               atol=1e-4)


def test_dropped_pairs_equal_the_reference_drop_count():
    """With few slots the layer drops pairs; the count the decode step
    returns equals the reference's, call by call."""
    cfg = dataclasses.replace(_mellum(), capacity_factor=0.5)
    fns, params = _params(cfg)
    B, P, S = 4, 6, 12
    toks = jax.random.randint(jax.random.key(4), (B, S), 0, cfg.vocab_size)
    _, drops, _ = _decode(cfg, fns, params, toks, P)
    rp = ref.from_program(params, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim)
    groups = [P] + [1] * (S - P)
    _, want = ref.forward(rp, toks, _ref_cfg(cfg), capacity_factor=0.5,
                          groups=groups)
    per_call = [sum(layer[g] for layer in want) for g in range(len(groups))]
    assert drops == per_call and sum(drops) > 0


def test_serve_session_records_dropped_pairs():
    """``ServeSession`` records each decode call's dropped pairs as counter
    ``moe.dropped_pairs``: the reference's drop counts for the same
    calls (the prompt, then each generated token)."""
    from repro.serve.decode import ServeSession

    cfg = dataclasses.replace(_mellum(), capacity_factor=0.5)
    fns, params = _params(cfg)
    sess = ServeSession(cfg, params, max_len=32)
    out = sess.generate([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12],
                         [13, 14, 15, 16]], max_new_tokens=3)
    assert [len(o) for o in out] == [7] * 4
    got = spans.recent("moe.dropped_pairs", 4)  # prefill + 3 steps
    rp = ref.from_program(params, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim)
    _, want = ref.forward(rp, np.asarray(out), _ref_cfg(cfg),
                          capacity_factor=0.5, groups=[4, 1, 1, 1])
    assert got == [sum(layer[g] for layer in want) for g in range(4)]
    assert sum(got) > 0


def test_expert_shares_add_up_to_the_whole_layer():
    """16 experts in 4 shares of 4: each share's partial output, dropless,
    and their sum equals the uncut reference layer and the uncut program
    layer."""
    whole = dataclasses.replace(_mellum(), n_experts=16, top_k=4)
    whole = _dropless(whole)
    _, params = _params(whole)
    p = jax.tree.map(lambda a: a[0], params["blocks"][0]["mlp"])
    x = jax.random.normal(jax.random.key(5), (3, 5, whole.d_model))
    parts = []
    for lo in range(0, 16, 4):
        share = dataclasses.replace(whole, expert_lo=lo, n_experts_here=4)
        ps = dict(p, **{k: p[k][lo:lo + 4]
                        for k in ("w_gate", "w_up", "w_down")})
        out, dropped = L.moe_block(share, ps, x)
        assert int(dropped) == 0
        parts.append(np.asarray(out))
    uncut, _ = L.moe_block(whole, p, x)
    rp = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    want, drops = ref.moe(x, rp, _ref_cfg(whole), expert_lo=0,
                          capacity_factor=None, groups=[5])
    np.testing.assert_allclose(sum(parts), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(sum(parts), np.asarray(uncut), atol=1e-5)
    assert drops == [0]


def test_capacity_is_ceil_of_routed_pairs():
    cfg = dataclasses.replace(get_config("mellum2_12b_a2_5b"),
                              capacity_factor=1.25)
    assert L.expert_capacity(cfg, 256) == 40 == math.ceil(256 * 8 * 1.25 / 64)


@pytest.mark.parametrize("max_len,slots", [(32, 8), (6, 6)])
def test_ring_buffers_on_sliding_layers_full_length_on_full(max_len, slots):
    cfg = _mellum()
    st = registry.model_fns(cfg).cache_structure(cfg, 2, max_len)
    shapes = [blk["k"].shape for blk in st["blocks"]]
    assert [s[2] for s in shapes] == [slots, slots, slots, max_len]
    assert st["moe_dropped"].shape == ()


def test_ring_positions_name_the_position_in_each_slot():
    got = np.asarray(L.ring_positions(jnp.int32(11), 4))
    assert got.tolist() == [8, 9, 10, 7]
    assert np.asarray(L.ring_positions(jnp.int32(2), 4)).tolist() == \
        [0, 1, -1, -1]


def _toy_trace(**kw):
    from repro.models.registry import traced_workload

    cfg = _mellum(n_experts=8, top_k=2, n_experts_here=4, expert_lo=4,
                  capacity_factor=1.25, **kw)
    return cfg, traced_workload(cfg, tokens=8, kv_len=32, weight_bits=8,
                                scan_mode="unroll")


def test_traced_decode_has_one_matmul_per_held_expert_and_projection():
    cfg, wl = _toy_trace()
    C = L.expert_capacity(cfg, 8)
    mm = [op for op in wl.ops if op.kind == "matmul"]
    experts = [op for op in mm if op.expert]
    D, F = cfg.d_model, cfg.d_ff
    assert len(experts) == cfg.n_layers * 4 * 3
    assert {(op.m, op.k, op.n, op.width) for op in experts} == {
        (C, D, F, 8), (C, F, D, 8)}
    # the rest: qkv, o, router per layer, the flash chunks, the LM head --
    # no dispatch, combine or routing-bookkeeping product
    others = {op.name.split("#")[0] for op in mm if not op.expert}
    assert others == {"wqkv", "wo", "router", "k", "v", "lm_head"}


def test_traced_decode_lowers_with_no_threaded_steps():
    from repro.core.cost_model import Layout
    from repro.plan import compile_plan, lower_plan_pallas

    _cfg, wl = _toy_trace()
    sched = lower_plan_pallas(compile_plan(wl, initial_layout=Layout.BP),
                              wl)
    assert sched.threaded_producers() == {}
    assert sum(s.expert for s in sched.measured_steps) == 48


def test_lowering_records_expert_counters_once():
    from repro.core.cost_model import Layout
    from repro.plan import compile_plan, lower_plan_pallas
    from repro.plan.pallas import mxu_passes

    _cfg, wl = _toy_trace()
    plan = compile_plan(wl, initial_layout=Layout.BP)
    names = ("lower.expert_steps", "lower.expert_macs",
             "lower.expert_mxu_work")
    before = [len(spans._records.get(n, ())) for n in names]
    sched = lower_plan_pallas(plan, wl)
    after = [len(spans._records.get(n, ())) for n in names]
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1]
    ex = [s for s in sched.measured_steps if s.expert]
    assert spans.last("lower.expert_steps") == len(ex) == 48
    assert spans.last("lower.expert_macs") == sum(
        math.prod(s.dims) for s in ex)
    assert spans.last("lower.expert_mxu_work") == sum(
        math.prod(s.padded_dims) * mxu_passes(s.layout, s.width)
        for s in ex)


def test_dense_lowering_records_zero_expert_work():
    from repro.core.cost_model import Layout
    from repro.models.registry import traced_workload
    from repro.plan import compile_plan, lower_plan_pallas

    cfg = reduced_config(get_config("tinyllama_1_1b"))
    wl = traced_workload(cfg, tokens=8, scan_mode="unroll")
    lower_plan_pallas(compile_plan(wl, initial_layout=Layout.BP), wl)
    assert spans.last("lower.expert_steps") == 0
    assert spans.last("lower.expert_mxu_work") == 0


@pytest.mark.parametrize("arch", ["dbrx_132b", "llama4_maverick_400b_a17b"])
def test_every_moe_config_shares_the_index_dispatch_layer(arch):
    """dbrx and llama4 trace their experts through the same layer: one
    matmul per expert and projection, capacity rows, no one-hot
    products."""
    from repro.models.registry import traced_workload

    cfg = get_config(arch)
    wl = traced_workload(cfg, tokens=512)
    experts = [op for op in wl.ops if op.kind == "matmul" and op.expert]
    C = L.expert_capacity(cfg, 512)
    assert len(experts) == 3 * cfg.n_experts
    assert {op.m for op in experts} == {C}
    assert not [op for op in wl.ops
                if op.kind == "matmul" and op.name.startswith("dot")]
