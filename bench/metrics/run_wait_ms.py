"""Mean per call over the window of span ``schedule.wait`` inside
``ScheduleExecutable.run()``: waiting for the device
(``jax.block_until_ready``), in ms."""
from bench.program_spans import window_ms


def read(run):
    return window_ms(run, "schedule.wait")
