"""The rest of span ``schedule.first_run``: placing the entries and the
first (warming) execution of the program, its compile left out, in s."""
from bench.program_spans import newest


def read(run):
    r = newest("schedule.first_run")
    return None if r is None else (r.dur_ns - r.compile_ns) / 1e9
