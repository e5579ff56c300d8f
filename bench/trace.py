"""Reduce a profiler trace of the measured window to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain :class:`Event` records; ``summarize`` reduces them:

* busy time -- the union of the intervals in which an operation ran on
  a device (the device plane's ``XLA Ops`` line), clipped to the window
  span the harness records on the host, averaged over the chips used;
* kernel time -- the summed device time of the Pallas kernels (events
  whose HLO op is a ``tpu_custom_call``), over all chips;
* the device operations that took most time, by the HLO instruction the
  trace names, with its op and result shape;
* the idle gaps, each attributed to the innermost span that covers its
  middle on the calling thread -- the thread whose line holds the
  harness's window span: what the caller was doing while the chip
  waited (placing inputs, dispatching, fetching results); where the
  caller only waits inside the harness's own span, the innermost span
  another host thread has open names the work it waits on.

On a TPU the ``XLA Ops`` events are named by their whole HLO text, e.g.
``%program.15 = s32[64,100352]{1,0:T(8,128)} custom-call(...),
custom_call_target="tpu_custom_call", ...``.  Host and device events
share one clock in the trace, in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict

#: host span the harness puts around the measured window
WINDOW_SPAN = "bench.window"
#: prefix of the harness's own host spans
HARNESS_SPANS = "bench."
#: the device plane line whose events are the operations that ran
DEVICE_OPS_LINE = "XLA Ops"
#: what marks a Pallas kernel among the device operations
KERNEL_MARK = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    kernel: bool = False     #: a Pallas kernel (device events only)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Summary:
    window_s: float
    busy_s: float            #: device busy time, mean over chips used
    kernel_s: float          #: Pallas kernel time, summed over chips
    chips: int               #: devices with an operation in the window
    device_ops: list         #: [[name, seconds]] most time first
    idle_gaps: list          #: [[host span, seconds]] most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def label(hlo: str) -> str:
    """``%program.15 = s32[64,100352]{1,0:...} custom-call(...), ...``
    -> ``program.15 custom-call s32[64,100352]``; other names as they
    are."""
    m = re.match(r"%(\S+) = (.*)", hlo)
    if not m:
        return hlo
    name, rhs = m.groups()
    op = re.search(r" ([a-z][\w-]*)\(", rhs)
    shape = re.match(r"\w+\[[\d,]*\]", rhs)
    return " ".join([name, op.group(1) if op else "?",
                     shape.group(0) if shape else "(tuple)"])


def load(path: str) -> list[Event]:
    """Every timed event of the trace at ``path`` (``.xplane.pb``, or
    gzipped as ``.xplane.pb.gz``): the device planes' ``XLA Ops`` and
    every host line."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in data.planes:
        device = is_device(plane.name)
        for line in plane.lines:
            if device and line.name != DEVICE_OPS_LINE:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                kernel = device and KERNEL_MARK in ev.name
                out.append(Event(plane.name, line.name,
                                 label(ev.name) if device else ev.name,
                                 ev.start_ns, ev.duration_ns, kernel))
    return out


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _top(totals: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def _innermost(host: list[Event], times: list[float]) -> list:
    """For each of the sorted ``times``, the shortest span of ``host``
    (sorted by start) that covers it, or None."""
    out = []
    active: list[Event] = []
    i = 0
    for t in times:
        while i < len(host) and host[i].start_ns <= t:
            active.append(host[i])
            i += 1
        active = [e for e in active if e.end_ns >= t]
        out.append(min(active, key=lambda e: e.dur_ns) if active else None)
    return out


def _attribute(caller: list[Event], others: list[Event], gaps) -> dict:
    """Seconds of idle gap per span covering each gap's middle: the
    innermost span on the calling thread; where that is only one of the
    harness's own spans (the caller waits inside ``run()``), it is
    followed by the innermost span any other host thread has open then
    (``bench.run > Linearize``: the runtime relaying out an input)."""
    gaps = sorted(gaps)
    mids = [(s + t) / 2 for s, t, _w in gaps]
    totals: dict = defaultdict(float)
    for (s, t, weight), own, other in zip(gaps, _innermost(caller, mids),
                                          _innermost(others, mids)):
        name = own.name if own is not None else "(no host span)"
        if name.startswith(HARNESS_SPANS) and other is not None:
            name = f"{name} > {other.name}"
        totals[name] += (t - s) / 1e9 * weight
    return totals


def summarize(events: list[Event]) -> Summary:
    windows = [e for e in events if e.name == WINDOW_SPAN
               and not is_device(e.plane)]
    if not windows:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    win = windows[0]
    w0, w1 = win.start_ns, win.end_ns

    per_chip = defaultdict(list)
    op_time: dict = defaultdict(float)
    kernel_ns = 0.0
    for e in events:
        if not is_device(e.plane):
            continue
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t <= s:
            continue
        per_chip[e.plane].append((s, t))
        op_time[e.name] += (t - s) / 1e9
        if e.kernel:
            kernel_ns += t - s
    if not per_chip:
        raise RuntimeError("no device operation ran in the window")
    busy = {p: _union(iv) for p, iv in per_chip.items()}
    busy_ns = sum(sum(t - s for s, t in b) for b in busy.values())

    host = sorted((e for e in events if not is_device(e.plane)
                   and e is not win), key=lambda e: e.start_ns)
    caller = [e for e in host if (e.plane, e.line) == (win.plane, win.line)]
    others = [e for e in host if (e.plane, e.line) != (win.plane, win.line)]
    idle = []
    for b in busy.values():
        edges = [w0] + [x for iv in b for x in iv] + [w1]
        idle += [(s, t, 1 / len(busy))
                 for s, t in zip(edges[0::2], edges[1::2]) if t > s]
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9 / len(busy),
        kernel_s=kernel_ns / 1e9, chips=len(busy),
        device_ops=_top(op_time), idle_gaps=_top(_attribute(caller, others, idle)))
