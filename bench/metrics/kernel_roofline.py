"""Share of its roofline that the step's Pallas kernels reach, pooled.

The least time of the step's matmul work, max(ops / int8 peak, bytes /
HBM bandwidth) with ops and bytes from the configuration's layer table,
over the summed device time of all Pallas kernel events per step."""
from bench.work import totals


def read(run):
    if run.trace is None or run.trace.kernel_s <= 0:
        return None
    ops, nbytes = totals(run.layers)
    p = run.peaks
    least = max(ops / p["int8_ops_per_s"], nbytes / p["hbm_bytes_per_s"])
    per_step = run.trace.kernel_s / len(run.step_s)
    return 100.0 * least / per_step
