"""The model's useful int8 operations per step (2 x MACs of every matmul
and conv layer of the configuration's layer table) times the steps the
window completed, over the window, over the chip's int8 peak."""
from bench.work import totals


def read(run):
    ops, _ = totals(run.layers)
    rate = ops * len(run.step_s) / run.window_s
    return 100.0 * rate / run.peaks["int8_ops_per_s"]
