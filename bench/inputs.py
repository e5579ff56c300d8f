"""The cell's operands, made on the device from ``--seed``.

The dataflow is read off the workload the configuration builds: every
matmul and conv op is one step, lowered as the schedule lowers it (a
matmul is ``[m, k] @ [k, n]``; a conv is the im2col GEMV
``[outputs, taps * C_in] @ [taps * C_in, 1]``).  Every step is an entry
step: its activations and weights are drawn here (the harness refuses a
schedule that feeds one step's result into another).  Step ``i``'s
operands come from
``fold_in(key(seed), i)``, so the one jitted call that makes every
operand at set-up and the per-step calls the reference makes after the
window draw the same numbers.
"""
from __future__ import annotations

import dataclasses
import functools

_MEASURED = ("matmul", "conv")


@dataclasses.dataclass(frozen=True)
class Step:
    index: int               #: position among the measured steps
    op: str
    m: int
    k: int
    n: int
    width: int               #: weight bits


def dataflow(workload) -> tuple[Step, ...]:
    """The matmul/conv steps of ``workload``, in order."""
    steps = []
    for op in workload.ops:
        if op.kind not in _MEASURED:
            continue
        m, k, n = (op.m, op.k, op.n) if op.kind == "matmul" else (
            op.n, op.k, 1)
        steps.append(Step(len(steps), op.name, m, k, n, op.width))
    return tuple(steps)


def base_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    import jax

    s = seed % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _draw(key, m: int, k: int, n: int, width: int):
    """(int8 [m, k] activations, unsigned ``width``-bit [k, n] weights in
    int32 storage)."""
    import jax
    import jax.numpy as jnp

    kx, kw = jax.random.split(key)
    x = jax.lax.bitcast_convert_type(
        jax.random.bits(kx, (m, k), jnp.uint8), jnp.int8)
    bits = jax.random.bits(kw, (k, n), jnp.uint32)
    if width < 32:
        bits = bits >> (32 - width)
    return x, jax.lax.bitcast_convert_type(bits, jnp.int32)


def make_all(seed: int, steps) -> dict:
    """``{op: (x, w)}`` for every step, in one jitted call."""
    import jax

    def draw_all(key):
        return {s.op: _draw(jax.random.fold_in(key, s.index), s.m, s.k,
                            s.n, s.width) for s in steps}
    return jax.jit(draw_all)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _draw_one(m, k, n, width):
    import jax

    return jax.jit(lambda key: _draw(key, m, k, n, width))


def make_one(seed: int, step: Step):
    """Step ``step``'s operands alone (the same numbers as
    :func:`make_all`)."""
    import jax

    key = jax.random.fold_in(base_key(seed), step.index)
    return _draw_one(step.m, step.k, step.n, step.width)(key)
