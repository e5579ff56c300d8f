"""The benchmark's own tests run on the CPU: ``python -m pytest bench/tests``."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402

import pytest  # noqa: E402

#: stablelm's layer structure at widths and a depth an interpreted kernel
#: runs fast
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256}
#: what run.py's device check would report on the chip
CHIP = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="session")
def tiny_cell():
    """``stablelm_1_6b.decode256_int4`` cut to a size the CPU holds."""
    from bench.spec import load_cell

    cell = load_cell(ROOT, "stablelm_1_6b.decode256_int4")
    return dataclasses.replace(
        cell, config=dict(cell.config, **TINY),
        traffic=dict(cell.traffic, tokens=8))
