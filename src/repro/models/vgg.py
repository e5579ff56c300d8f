"""Abstract VGG CIFAR-10 forward pass for the jaxpr tracer.

The Table-6 ``vgg13/16/19`` registry workloads are hand-written conv/fc
formulas (``workloads.registry._vgg_ops``).  This module provides the
*real* forward pass at the same operating point (batch-128 CIFAR-10) so
``workloads.trace.trace_workload`` can derive the same workload from a
jaxpr -- the traced-VGG-vs-formula check of the differential suite.

Parameters are ``jax.ShapeDtypeStruct`` pytrees (f32 -- the formula ops
are 16-bit default-width, and floats without a precision-map entry
resolve to 16); nothing is ever allocated.
"""
from __future__ import annotations

import math

__all__ = ["VGG_BLOCKS", "VGG_BATCH", "VGG_FCS", "abstract_inputs",
           "forward", "traced_vgg"]

#: (out_channels, input/output spatial, conv layers) per block -- the
#: same table the formula workload is built from (CIFAR-10, 32x32 input)
VGG_BLOCKS = {
    "vgg13": [(64, 32, 2), (128, 16, 2), (256, 8, 2), (512, 4, 2),
              (512, 2, 2)],
    "vgg16": [(64, 32, 2), (128, 16, 2), (256, 8, 3), (512, 4, 3),
              (512, 2, 3)],
    "vgg19": [(64, 32, 2), (128, 16, 2), (256, 8, 4), (512, 4, 4),
              (512, 2, 4)],
}
VGG_BATCH = 128  # batch inference, as in the formula workload

VGG_FCS = [(512, 512), (512, 512), (512, 10)]


def abstract_inputs(which: str = "vgg16", batch: int = VGG_BATCH):
    """(params, images) ShapeDtypeStruct pytrees for :func:`forward`."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    params: dict = {}
    c_in = 3
    for bi, (c, _s, reps) in enumerate(VGG_BLOCKS[which]):
        for r in range(reps):
            params[f"b{bi}c{r}"] = jax.ShapeDtypeStruct(
                (3, 3, c_in, c), f32)  # HWIO
            c_in = c
    for fi, (k, n) in enumerate(VGG_FCS):
        params[f"fc{fi}"] = jax.ShapeDtypeStruct((k, n), f32)
    images = jax.ShapeDtypeStruct((batch, 32, 32, 3), f32)  # NHWC
    return params, images


def forward(params, images, which: str = "vgg16"):
    """Conv blocks (3x3 SAME + relu, 2x2 max-pool per block) + FC head."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    x = images
    for bi, (_c, _s, reps) in enumerate(VGG_BLOCKS[which]):
        for r in range(reps):
            x = lax.conv_general_dilated(
                x, params[f"b{bi}c{r}"], window_strides=(1, 1),
                padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jax.nn.relu(x)
        x = lax.reduce_window(x, -jnp.inf, lax.max,
                              window_dimensions=(1, 2, 2, 1),
                              window_strides=(1, 2, 2, 1), padding="VALID")
    x = x.reshape(x.shape[0], math.prod(x.shape[1:]))
    for fi in range(len(VGG_FCS)):
        x = x @ params[f"fc{fi}"]
        if fi < len(VGG_FCS) - 1:
            x = jax.nn.relu(x)
    return x


def traced_vgg(which: str = "vgg16", batch: int = VGG_BATCH):
    """Trace :func:`forward` into a ``traced/<which>`` Workload (the
    registry's at batch 128; a smaller ``batch`` cuts scale, never the
    layer widths)."""
    from repro.workloads.trace import trace_workload

    params, images = abstract_inputs(which, batch)
    return trace_workload(
        lambda p, im: forward(p, im, which), params, images,
        name=f"traced/{which}", source="traced",
        description=f"{which.upper()} batch-{batch} CIFAR-10 "
                    "inference, jaxpr-traced")
