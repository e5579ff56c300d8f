"""Read the program's own spans and counters (``repro.spans``).

The per-layer metrics of the program's run and set-up paths read what
the program recorded in this process: the window's calls are the last
``run()`` calls before the readers run, so a run metric reads the last
``len(run.step_s)`` records; a set-up metric reads the newest one.  A
program without the recorder reports none of them.
"""
from __future__ import annotations

import statistics


def recorder():
    """``repro.spans``, or None in a program that lacks it."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans


def window_mean(run, name: str):
    """Mean over the window's calls of span ``name``'s duration in ns, or
    of counter ``name``'s value; None without the recorder."""
    spans = recorder()
    if spans is None:
        return None
    got = spans.recent(name, len(run.step_s))
    return statistics.fmean(getattr(r, "dur_ns", r) for r in got)


def window_ms(run, name: str):
    ns = window_mean(run, name)
    return None if ns is None else ns / 1e6


def newest(name: str):
    """The newest record of span ``name``; None without the recorder."""
    spans = recorder()
    return None if spans is None else spans.last(name)
