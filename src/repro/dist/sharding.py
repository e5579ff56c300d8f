"""Symbolic -> concrete sharding resolution and the trace-time mesh context.

Parameter and activation partitioning is written once, symbolically, in
`ParamSpec.pspec` tuples and `shard(...)` calls; this module maps those
symbols onto whatever mesh is actually present:

  * ``None``    -- replicated dim.
  * ``"batch"`` -- the data-parallel axes. Resolves to every DP mesh axis
    present, in mesh order (``("pod", "data")`` on the multi-pod mesh,
    ``"data"`` on a single pod), so the global batch shards over pods AND
    in-pod DP with one symbol.
  * any other string -- that mesh axis literally (``"model"``, ``"data"``,
    ``"pod"``).

Graceful degradation (the property the tests pin down): an axis absent
from the mesh is dropped, and an axis (or axis product) that does not
divide the dim is dropped -- the dim falls back toward replication instead
of raising. This is what lets the same model code run on the production
16x16 pod, the multi-pod 2x16x16 mesh, and an 8-device CPU test mesh.

`use_mesh(mesh)` installs the mesh for the duration of a trace;
`shard(x, *entries)` applies `with_sharding_constraint` against the current
mesh and is a silent no-op off-mesh (single-device tests, reference runs).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

#: Mesh axes that carry pure data parallelism, outermost first. ``"batch"``
#: resolves to whichever of these the current mesh actually has.
DATA_AXES = ("pod", "data")

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by the innermost `use_mesh`, or None."""
    stack = getattr(_state, "meshes", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Install `mesh` as the ambient mesh for `shard` constraints.

    Traces (jit lowering, `.lower()`) performed inside the block see the
    mesh; the context is thread-local so concurrent compiles don't leak
    meshes into each other. ``use_mesh(None)`` is a no-op, so callers
    with an optional mesh don't need a second code path.
    """
    if mesh is None:
        yield None
        return
    stack = getattr(_state, "meshes", None)
    if stack is None:
        stack = _state.meshes = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _resolve_entry(entry, dim: int, sizes: dict):
    """One pspec entry -> concrete axis name, tuple of names, or None."""
    if entry is None:
        return None
    names = list(entry) if isinstance(entry, (tuple, list)) else (
        list(DATA_AXES) if entry == "batch" else [entry])
    names = [n for n in names if n in sizes]
    # drop axes (outermost first) until the shard product divides the dim
    while names:
        prod = 1
        for n in names:
            prod *= sizes[n]
        if prod and dim % prod == 0:
            break
        names.pop(0)
    if not names:
        return None
    return names[0] if len(names) == 1 else tuple(names)


def resolve_pspec(symbolic_pspec, mesh: Mesh, shape) -> P:
    """Map a symbolic pspec tuple to a concrete `PartitionSpec` for `mesh`.

    `symbolic_pspec` has one entry per dim of `shape` (see module
    docstring). Entries resolving to axes absent from the mesh, or whose
    size product does not divide the dim, degrade to replication.
    """
    assert len(symbolic_pspec) == len(shape), (symbolic_pspec, shape)
    sizes = _axis_sizes(mesh)
    return P(*(_resolve_entry(e, d, sizes)
               for e, d in zip(symbolic_pspec, shape)))


def place_on_mesh(tree, structure, mesh: Optional[Mesh]):
    """Device-put a materialized ParamSpec pytree onto `mesh` with its
    resolved shardings; identity when `mesh` is None (single device)."""
    if mesh is None:
        return tree
    from repro.models.base import param_shardings  # late: avoids cycle
    return jax.device_put(tree, param_shardings(structure, mesh))


def shard(x: jax.Array, *entries) -> jax.Array:
    """Constrain `x` to the symbolic spec on the ambient mesh (no-op
    off-mesh). `entries` is one symbolic pspec entry per dim of `x`."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_pspec(tuple(entries), mesh, x.shape)
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) != auto:
        # with_sharding_constraint refers only to Auto axes, and
        # jax.make_mesh builds Explicit ones by default
        mesh = mesh.update(axis_types=auto)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
