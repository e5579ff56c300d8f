"""Set-up spent turning the configuration into a kernel schedule: the
spans ``workload.trace`` (jaxpr -> Workload), ``plan.compile`` (the
BP/BS layout plan) and ``plan.lower`` (plan -> Pallas schedule), in s."""
from bench.program_spans import newest

SPANS = ("workload.trace", "plan.compile", "plan.lower")


def read(run):
    got = [newest(name) for name in SPANS]
    if None in got:
        return None
    return sum(r.dur_ns for r in got) / 1e9
