"""Production mesh definition.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build these meshes on a CPU host.

  single-pod: (data=16, model=16)        = 256 chips (one v5e pod)
  multi-pod : (pod=2, data=16, model=16) = 512 chips

Axis semantics: `pod` -- pure data parallelism across pods (gradient
all-reduce over DCI); `data` -- in-pod DP + ZeRO-1/FSDP/EP; `model` --
TP/SP.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-configurations).

    Every axis is Auto: ``dist.sharding.shard`` places activations with
    ``with_sharding_constraint``, which only accepts Auto axes, and
    ``jax.make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
