"""stablelm-2-1.6b: builder and work count (sizes in ``stablelm_1_6b.json``).

Traffic keys: ``phase`` (``decode``), ``tokens`` (concurrent sequences,
each with a ``tokens``-long KV cache) and ``weight_bits``.
"""
from __future__ import annotations

import dataclasses

from bench import work

#: K and V stay at the model dtype in the traced decode step
KV_BITS = 16


def _check(traffic: dict) -> None:
    if traffic.get("phase", "decode") != "decode":
        raise ValueError("stablelm_1_6b: only the decode phase is "
                         f"counted, got {traffic['phase']!r}")


def arch(cfg: dict):
    """The program's ArchConfig at the file's sizes."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("stablelm_1_6b"), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"])


def build(cfg: dict, traffic: dict):
    """The traced decode step: every layer of the file, and the LM head."""
    from repro.models.registry import traced_workload

    _check(traffic)
    return traced_workload(arch(cfg), tokens=traffic["tokens"],
                           phase="decode",
                           weight_bits=traffic["weight_bits"],
                           scan_mode="unroll")


def layers(cfg: dict, traffic: dict) -> list[work.Layer]:
    """The matmuls of one decode step, from the published layer table.

    Each of ``tokens`` sequences attends over a ``tokens``-long cache.
    Attention scores and the probability-weighted values read the K and
    V caches as their stationary operand, at ``KV_BITS``.
    """
    _check(traffic)
    t, bits = traffic["tokens"], traffic["weight_bits"]
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    kv_elems = t * t * kvh * hd
    per_layer = [
        work.matmul("wqkv", t, d, (h + 2 * kvh) * hd, bits),
        work.layer("scores", macs=t * h * t * hd, in_elems=t * h * hd,
                   w_elems=kv_elems, w_bits=KV_BITS, out_elems=t * h * t),
        work.layer("values", macs=t * h * t * hd, in_elems=t * h * t,
                   w_elems=kv_elems, w_bits=KV_BITS, out_elems=t * h * hd),
        work.matmul("wo", t, h * hd, d, bits),
        work.matmul("w_gate", t, d, f, bits),
        work.matmul("w_up", t, d, f, bits),
        work.matmul("w_down", t, f, d, bits),
    ]
    return (per_layer * cfg["num_hidden_layers"]
            + [work.matmul("lm_head", t, d, v, bits)])
