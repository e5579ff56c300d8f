"""Mean per call over the window of span ``schedule.fetch`` inside
``ScheduleExecutable.run()``: fetching every step's result to the host
(``np.asarray`` of each), in ms."""
from bench.program_spans import window_ms


def read(run):
    return window_ms(run, "schedule.fetch")
