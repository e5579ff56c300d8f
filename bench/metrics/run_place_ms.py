"""Mean per call over the window of span ``schedule.place`` inside
``ScheduleExecutable.run()``: placing the entry operands on the device
(``jnp.asarray`` of each; the copy may finish later), in ms."""
from bench.program_spans import window_ms


def read(run):
    return window_ms(run, "schedule.place")
