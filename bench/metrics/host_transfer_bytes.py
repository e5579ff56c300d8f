"""Bytes one ``run()`` moves between host and device: the entry
operands placed (counter ``schedule.place_bytes``) plus the results
fetched (``schedule.fetch_bytes``), mean per call over the window."""
from bench.program_spans import window_mean


def read(run):
    placed = window_mean(run, "schedule.place_bytes")
    if placed is None:
        return None
    return placed + window_mean(run, "schedule.fetch_bytes")
