"""MXU work the lowered schedule does per unit of true work: the sum of
padded MACs times MXU passes (a plane per bit for BS, a limb per 7 bits
for BP) over the sum of true MACs, over the measured steps.  A count of
the program's: it shows the lane padding and the passes a layout costs."""


def read(run):
    from repro.plan.pallas import mxu_passes

    padded = true = 0
    for s in run.schedule.measured_steps:
        pm, pk, pn = s.padded_dims
        m, k, n = s.dims
        padded += pm * pk * pn * mxu_passes(s.layout, s.width)
        true += m * k * n
    return padded / true if true else None
