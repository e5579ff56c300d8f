"""One run of one cell: set-up, the measured window, the check.

1. Build the cell's workload from its configuration and traffic files.
2. ``compile_plan`` (BP arrival) -> ``lower_plan_pallas`` with no MAC
   budget -> ``compile_schedule`` on operands made on the device from the
   seed; every matmul and conv step must reach a kernel.
3. Warm up: ``compile_schedule`` runs the program once, then one more
   ``run()`` goes through the timed entry.  Everything up to here is
   ``setup_s``.
4. The window: ``ScheduleExecutable.run()`` back to back, one caller,
   until ``seconds`` have passed; every call is timed.  The results of
   one of the first ``EARLY`` calls, drawn from the seed, and of the
   window's last call are kept for the check: every seed keeps as much,
   for as long, so the seed does not change what the host allocates.
5. The device's peak memory is read, the program's state freed, and the
   plain reference (``bench.reference``) computed step by step; every
   kept result must equal it exactly.

With ``trace=True`` the window runs under the JAX profiler, and the
per-layer metrics are read from the trace (``bench.trace``).
"""
from __future__ import annotations

import gc
import random
import shutil
import statistics
import sys
import tempfile
import time
import types

from bench import inputs as bin
from bench import reference, trace as btrace
from bench.peaks import peaks

#: one of the window's first EARLY calls, drawn from the seed, is checked
EARLY = 4


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build(cell):
    """-> (workload, steps, schedule) of the cell."""
    from repro.core.cost_model import Layout
    from repro.plan import compile_plan, lower_plan_pallas

    workload = cell.builder.build(cell.config, cell.traffic)
    plan = compile_plan(workload, initial_layout=Layout.BP)
    sched = lower_plan_pallas(plan, workload, max_macs=sys.maxsize)
    left = [s.op for s in sched.steps
            if s.kind in ("matmul", "conv") and not s.measured]
    if left:
        raise RuntimeError(f"{cell.name}: matmul/conv steps left "
                           f"modelled: {left}")
    if sched.threaded_producers():
        raise RuntimeError(f"{cell.name}: the schedule feeds step results "
                           "into other steps; the reference has no such "
                           "dataflow")
    return workload, bin.dataflow(workload), sched


def compile_cell(sched, steps, seed: int):
    """The compiled program, warmed through ``run()``."""
    from repro.plan import compile_schedule

    exe = compile_schedule(sched, bin.make_all(seed, steps), seed=seed)
    exe.run()
    return exe


def window(exe, seconds: float, seed: int):
    """Drive ``exe.run()`` for ``seconds``: -> (call times in s, window
    in s, the kept results)."""
    import jax

    pick = random.Random(seed).randrange(EARLY)
    early = None
    times: list[float] = []
    with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.run"):
                out = exe.run()
            b = time.perf_counter()
            times.append(b - a)
            if len(times) - 1 == pick:
                early = out
            if b - t0 >= seconds:
                break
            del out
    kept = [out] if early is None or early is out else [early, out]
    return times, b - t0, kept


def traced_window(exe, seconds: float, seed: int):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            got = window(exe, seconds, seed)
        finally:
            jax.profiler.stop_trace()
        summary = btrace.summarize(btrace.load(btrace.find_xplane(log_dir)))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return got, summary


def memory_peak(chips: int):
    import jax

    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()[:chips]]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             device: dict, t0: float) -> dict:
    """One run of ``cell``; -> the result line's object.

    ``device`` is what the caller found (platform, kind, count);
    ``t0`` the ``time.perf_counter()`` at which the process started."""
    workload, steps, sched = build(cell)
    exe = compile_cell(sched, steps, seed)
    setup_s = time.perf_counter() - t0
    log(f"# {cell.name}: seed={seed} setup_s={setup_s:.3f} "
        f"steps={len(steps)} kernels="
        f"{','.join(s.kernel for s in sched.measured_steps)}")

    summary = None
    if trace:
        (times, window_s, kept), summary = traced_window(exe, seconds, seed)
    else:
        times, window_s, kept = window(exe, seconds, seed)
    peak = memory_peak(cell.chips)
    del exe
    gc.collect()

    t_ref = time.perf_counter()
    want = reference.results(seed, steps)
    wrong = [reference.wrong_elements(got, want) for got in kept]
    ref_s = time.perf_counter() - t_ref
    checks = {"wrong_elements": {"value": sum(wrong), "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    run = types.SimpleNamespace(
        cell=cell, workload=workload, schedule=sched, steps=steps,
        layers=cell.builder.layers(cell.config, cell.traffic),
        peaks=peaks(device["kind"]), setup_s=setup_s, step_s=times,
        window_s=window_s, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(times),
              "failed": sum(1 for w in wrong if w), "metrics": metrics,
              "device": dev}
    if summary is not None:
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks

    elems = sum(v.size for v in want.values())
    q = statistics.quantiles(times, n=4) if len(times) > 1 else times * 3
    log(f"# {cell.name}: call ms min {min(times) * 1e3:.2f} quartiles "
        f"{' '.join(f'{x * 1e3:.2f}' for x in q)} max "
        f"{max(times) * 1e3:.2f}; first {times[0] * 1e3:.2f}")
    log(f"# {cell.name}: {len(times)} calls in {window_s:.3f} s; checked "
        f"{len(kept)} calls x {len(want)} steps x {elems} elements "
        f"against the reference ({ref_s:.1f} s)")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result
