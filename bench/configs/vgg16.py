"""VGG16 on CIFAR-10: builder and work count (sizes in ``vgg16.json``).

Traffic key: ``batch`` (images per forward step).
"""
from __future__ import annotations

from bench import work


def build(cfg: dict, traffic: dict):
    """The traced forward pass of the program's VGG16 at ``batch``."""
    from repro.models.vgg import VGG_BLOCKS, VGG_FCS, traced_vgg

    as_run = ([list(b) for b in VGG_BLOCKS["vgg16"]],
              [list(f) for f in VGG_FCS])
    if as_run != (cfg["blocks"], cfg["fcs"]):
        raise ValueError("vgg16.json differs from the layer table the "
                         "program traces (models/vgg.py)")
    return traced_vgg("vgg16", batch=traffic["batch"])


def layers(cfg: dict, traffic: dict) -> list[work.Layer]:
    """Every conv and FC layer of one forward step.

    A 3x3 SAME conv at spatial size ``s`` reads its ``s*s*C_in`` input
    per image once, its ``9*C_in*C_out`` weights, and writes
    ``s*s*C_out`` outputs -- the conv's own geometry, not the lowered
    GEMV's duplicated im2col rows.
    """
    b, bits, taps = traffic["batch"], cfg["weight_bits"], cfg["kernel_size"] ** 2
    out = []
    c_in = cfg["in_channels"]
    for bi, (c_out, s, reps) in enumerate(cfg["blocks"]):
        for r in range(reps):
            out.append(work.layer(
                f"b{bi}c{r}", macs=b * s * s * c_out * taps * c_in,
                in_elems=b * s * s * c_in, w_elems=taps * c_in * c_out,
                w_bits=bits, out_elems=b * s * s * c_out))
            c_in = c_out
    for fi, (k, n) in enumerate(cfg["fcs"]):
        out.append(work.matmul(f"fc{fi}", b, k, n, bits))
    return out
