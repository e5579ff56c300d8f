"""Mellum2-12B-A2.5B: builder and work count (sizes in
``mellum2_12b_a2_5b.json``).

Traffic keys: ``phase`` (``decode``), ``batch`` (concurrent sequences,
one decode step each), ``kv_len`` (positions in each sequence's cache:
the full-attention layers hold all of them, the sliding ones a
``sliding_window``-slot ring buffer) and ``weight_bits``.
"""
from __future__ import annotations

import dataclasses

from bench import work

#: K and V stay at the model dtype in the traced decode step
KV_BITS = 16
#: the router's activations and weights stay at the model precision
ROUTER_BITS = 16
#: layer_types -> the program's sub-layer kinds (every MLP is sparse)
KINDS = {"sliding_attention": "moe_local", "full_attention": "moe"}


def _check(traffic: dict) -> None:
    if traffic.get("phase", "decode") != "decode":
        raise ValueError("mellum2_12b_a2_5b: only the decode phase is "
                         f"counted, got {traffic['phase']!r}")


def _layer_types(cfg: dict) -> list[str]:
    n = cfg["num_hidden_layers"]
    if set(cfg["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("mellum2_12b_a2_5b: every counted MLP is sparse")
    return cfg["layer_types"][:n]


def arch(cfg: dict):
    """The program's ArchConfig at the file's sizes and expert share."""
    from repro.configs import get_config

    yarn = cfg["rope_parameters"]["full_attention"]
    return dataclasses.replace(
        get_config("mellum2_12b_a2_5b"), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        n_experts=cfg["router_num_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_experts_here=cfg["num_experts"], expert_lo=cfg["expert_lo"],
        capacity_factor=cfg["capacity_factor"],
        window=cfg["sliding_window"],
        block_pattern=tuple(KINDS[t] for t in _layer_types(cfg)),
        rope_theta=float(cfg["rope_parameters"]["sliding_attention"]
                         ["rope_theta"]),
        rope_yarn=(float(yarn["factor"]),
                   yarn["original_max_position_embeddings"],
                   float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                   yarn["attention_factor"]),
        norm_eps=cfg["rms_norm_eps"])


def build(cfg: dict, traffic: dict):
    """The traced decode step: every layer of the file, and the LM head."""
    from repro.models.registry import traced_workload

    _check(traffic)
    return traced_workload(arch(cfg), tokens=traffic["batch"],
                           kv_len=traffic["kv_len"], phase="decode",
                           weight_bits=traffic["weight_bits"],
                           scan_mode="unroll")


def layers(cfg: dict, traffic: dict) -> list[work.Layer]:
    """The matmuls of one decode step, from the published layer table.

    Each of ``batch`` sequences attends over ``kv_len`` positions on a
    full layer and over ``sliding_window`` on a sliding one; scores and
    values read the K and V caches as their stationary operand, at
    ``KV_BITS``.  The experts count the work routed to the held ones:
    ``batch * top_k * held / router experts`` tokens through each held
    expert's projections on average, not the capacity slots the program
    pads them to.
    """
    _check(traffic)
    b, bits = traffic["batch"], traffic["weight_bits"]
    d, f, v = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["vocab_size"])
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    e, held = cfg["router_num_experts"], cfg["num_experts"]
    routed = b * cfg["num_experts_per_tok"] * held // e
    out = []
    for kind in _layer_types(cfg):
        length = (min(cfg["sliding_window"], traffic["kv_len"])
                  if kind == "sliding_attention" else traffic["kv_len"])
        kv_elems = b * length * kvh * hd
        out += [
            work.matmul("wqkv", b, d, (h + 2 * kvh) * hd, bits),
            work.layer("scores", macs=b * h * length * hd,
                       in_elems=b * h * hd, w_elems=kv_elems,
                       w_bits=KV_BITS, out_elems=b * h * length),
            work.layer("values", macs=b * h * length * hd,
                       in_elems=b * h * length, w_elems=kv_elems,
                       w_bits=KV_BITS, out_elems=b * h * hd),
            work.matmul("wo", b, h * hd, d, bits),
            work.matmul("router", b, d, e, ROUTER_BITS),
            work.layer("w_gate", macs=routed * d * f, in_elems=routed * d,
                       w_elems=held * d * f, w_bits=bits,
                       out_elems=routed * f),
            work.layer("w_up", macs=routed * d * f, in_elems=routed * d,
                       w_elems=held * d * f, w_bits=bits,
                       out_elems=routed * f),
            work.layer("w_down", macs=routed * f * d, in_elems=routed * f,
                       w_elems=held * f * d, w_bits=bits,
                       out_elems=routed * d),
        ]
    return out + [work.matmul("lm_head", b, d, v, bits)]
