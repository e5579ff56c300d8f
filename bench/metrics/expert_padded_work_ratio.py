"""MXU work of the lowered expert steps per unit of true expert work: the
program's counter ``lower.expert_mxu_work`` (padded MACs times MXU passes,
as ``padded_work_ratio`` counts them) over ``lower.expert_macs`` (true
MACs), both recorded once when the schedule was lowered.  It shows what
the expert GEMMs' capacity slots cost against the kernels' row tiles.  A program
that records neither reports nothing."""
from bench.program_spans import newest


def read(run):
    try:
        work, macs = (newest("lower.expert_mxu_work"),
                      newest("lower.expert_macs"))
    except LookupError:
        return None
    return work / macs if work is not None and macs else None
