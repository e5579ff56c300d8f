"""The plain reference that decides ``correct``, and the comparison.

Every step is an exact integer matmul: int8 activations times unsigned
``width``-bit weights, summed in int32 with wraparound, computed here as
a plain XLA ``int32 @ int32`` on the operands of ``bench.inputs`` --
nothing of the program is imported.

The reference runs one step at a time after the window has closed and
the program's state is freed, so it adds nothing to the program's peak
memory.  ``act_bits=4`` is the control: the same reference with every
activation kept at its top four bits, the precision one step below the
configuration's int8 activations.
"""
from __future__ import annotations

import numpy as np

from bench import inputs as bin


def _lower(x, act_bits: int):
    """Keep the top ``act_bits`` of each int8 activation."""
    drop = 8 - act_bits
    return (x >> drop) << drop if drop else x


def results(seed: int, steps, *, act_bits: int = 8) -> dict:
    """``{op: int32 [m, n] numpy result}`` for every step."""
    import jax
    import jax.numpy as jnp

    dot = jax.jit(lambda a, b: jnp.matmul(a.astype(jnp.int32),
                                          b.astype(jnp.int32)))
    out = {}
    for s in steps:
        x, w = bin.make_one(seed, s)
        out[s.op] = np.asarray(dot(_lower(x, act_bits), w))
        del x, w
    return out


def wrong_elements(got: dict, want: dict) -> int:
    """Elements of ``want`` that ``got`` does not reproduce exactly; a
    step that is missing or of another shape counts whole."""
    bad = 0
    for op, w in want.items():
        g = got.get(op)
        g = None if g is None else np.asarray(g)
        if g is None or g.shape != w.shape:
            bad += w.size
        else:
            bad += int(np.count_nonzero(g != w))
    return bad
