"""Set-up spent by JAX building the schedule program: its trace, MLIR
lowering and backend compile or persistent-cache fetch, as recorded
inside span ``schedule.first_run`` (overlapping events count once),
in s."""
from bench.program_spans import newest


def read(run):
    r = newest("schedule.first_run")
    return None if r is None else r.compile_ns / 1e9
