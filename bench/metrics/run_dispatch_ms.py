"""Mean per call over the window of span ``schedule.dispatch`` inside
``ScheduleExecutable.run()``: the jitted program's call up to its
return, in ms."""
from bench.program_spans import window_ms


def read(run):
    return window_ms(run, "schedule.dispatch")
