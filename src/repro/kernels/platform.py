"""Where the Pallas kernels run, decided once from the JAX backend.

The CPU backend runs every kernel in the Pallas interpreter (the
correctness path of the tests); a TPU compiles them with Mosaic.  No
caller chooses: a chip run can never fall back to the interpreter, and a
CPU run never reaches a compiled kernel.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the CPU backend, False on a TPU; any other backend has no
    Pallas path here and raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas path for JAX backend {backend!r}: "
                       "the kernels are interpreted on 'cpu' and "
                       "compiled on 'tpu'")
