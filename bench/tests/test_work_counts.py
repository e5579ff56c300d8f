"""The work counts come from each configuration's published layer table
and agree with the ops the program traces."""
from pathlib import Path

import pytest

from bench import inputs
from bench.spec import load_json

ROOT = Path(__file__).resolve().parents[2]


def _cell(config, traffic):
    """(traffic, workload, layer table) of ``config`` under the traffic
    file ``traffic`` -- a cell's parts, whether or not
    ``BENCHMARK.json`` names the cell."""
    import importlib

    cfg = load_json(ROOT / "bench" / "configs" / f"{config}.json")
    builder = importlib.import_module(f"bench.configs.{config}")
    if isinstance(traffic, str):
        traffic = load_json(ROOT / "bench" / "traffic" / f"{traffic}.json")
    return traffic, builder.build(cfg, traffic), builder.layers(cfg, traffic)


@pytest.mark.parametrize("traffic,macs", [
    ("decode256_int4", 374_735_896_576),
    ("decode256_int8", 374_735_896_576),
    ("decode64_int4", 92_476_014_592),
])
def test_stablelm_count_is_the_traced_matmuls(traffic, macs):
    _traffic, wl, layers = _cell("stablelm_1_6b", traffic)
    traced = sum(s.m * s.k * s.n for s in inputs.dataflow(wl))
    assert sum(l.macs for l in layers) == traced == macs


@pytest.mark.parametrize("batch,macs", [(4, 1_252_786_176),
                                        (16, 5_011_144_704)])
def test_vgg16_conv_macs_from_the_layer_table(batch, macs):
    _traffic, wl, layers = _cell("vgg16", {"batch": batch})
    convs = [l for l in layers if not l.name.startswith("fc")]
    traced = sum(op.n * op.k for op in wl.ops if op.kind == "conv")
    assert sum(l.macs for l in convs) == traced == macs


def test_vgg16_file_is_the_traced_table():
    from repro.models.vgg import VGG_BLOCKS, VGG_FCS

    cfg = load_json(ROOT / "bench" / "configs" / "vgg16.json")
    assert cfg["blocks"] == [list(b) for b in VGG_BLOCKS["vgg16"]]
    assert cfg["fcs"] == [list(f) for f in VGG_FCS]


def test_vgg16_conv_bytes_from_the_geometry_not_the_gemv():
    traffic, wl, layers = _cell("vgg16", {"batch": 4})
    first = layers[0]
    b = traffic["batch"]
    assert first.in_bytes == b * 32 * 32 * 3            # int8 images
    assert first.w_bytes == 9 * 3 * 64 * 16 // 8        # 16-bit weights
    assert first.out_bytes == b * 32 * 32 * 64 * 4      # int32 outputs
    gemv = inputs.dataflow(wl)[0]
    assert gemv.m * gemv.k == b * 32 * 32 * 64 * 27     # im2col rows
    assert first.in_bytes * 64 * 9 == gemv.m * gemv.k


def test_stablelm_attention_reads_the_kv_cache_at_16_bits():
    _traffic, _wl, layers = _cell("stablelm_1_6b", "decode256_int4")
    scores = next(l for l in layers if l.name == "scores")
    assert scores.w_bytes == 256 * 256 * 32 * 64 * 2
    assert next(l for l in layers if l.name == "lm_head").w_bytes == \
        2048 * 100352 // 2
