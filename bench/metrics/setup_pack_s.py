"""Set-up spent in ``compile_schedule`` before the program is built:
operands, key, weight conversion into device residency and the entries'
host copies (span ``schedule.pack``), in s."""
from bench.program_spans import newest


def read(run):
    r = newest("schedule.pack")
    return None if r is None else r.dur_ns / 1e9
