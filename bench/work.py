"""Work counts of one model step, from a configuration's layer table.

A :class:`Layer` is one matmul or conv layer of the model as published:
its multiply-accumulates and the bytes it has to move at least -- its
own int8 input, its stationary operand (weights, or a KV cache) at its
bit width, and its int32 output.  ``mfu`` and ``kernel_roofline`` take
their work from here, never from the padded dims of a lowered schedule,
so a change of lowering or layout changes the time and not the
yardstick.
"""
from __future__ import annotations

import dataclasses

#: activations enter every matmul as int8 and leave as int32
IN_BYTES = 1
OUT_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    macs: int
    in_bytes: int
    w_bytes: int
    out_bytes: int

    @property
    def ops(self) -> int:
        return 2 * self.macs

    @property
    def bytes(self) -> int:
        return self.in_bytes + self.w_bytes + self.out_bytes


def layer(name: str, *, macs: int, in_elems: int, w_elems: int,
          w_bits: int, out_elems: int) -> Layer:
    return Layer(name, macs, in_elems * IN_BYTES,
                 -(-w_elems * w_bits // 8), out_elems * OUT_BYTES)


def matmul(name: str, m: int, k: int, n: int, bits: int) -> Layer:
    """``[m, k] @ [k, n]`` with ``bits``-wide weights."""
    return layer(name, macs=m * k * n, in_elems=m * k, w_elems=k * n,
                 w_bits=bits, out_elems=m * n)


def totals(layers) -> tuple[int, int]:
    """(ops, bytes) of one step."""
    return sum(l.ops for l in layers), sum(l.bytes for l in layers)
