#!/usr/bin/env python3
"""Readings that set the limit of ``correct``: the program and its control.

    python bench/control.py --workload <cell> --seeds 1 2 3

For each seed, in this one process: the cell's compiled program is driven
through ``run()`` for a short window at the cell's own size and its kept
results are compared with the plain reference (the program's reading);
then the control -- the reference computed with int4 activations, one
precision step below the configuration's int8 -- is compared with the
same reference (the control's reading).  The limit of ``wrong_elements``
lies between the largest program reading and the smallest control
reading.  Prints one JSON line per seed and a summary line.  Needs a TPU,
as ``run.py`` does; the benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
#: the short window each seed's program is driven for
WINDOW_S = 1.0


def readings(cell, seeds, seconds: float) -> list[dict]:
    from bench import harness, reference

    _wl, steps, sched = harness.build(cell)
    rows = []
    for seed in seeds:
        exe = harness.compile_cell(sched, steps, seed)
        _times, _w, kept = harness.window(exe, seconds, seed)
        del exe
        gc.collect()
        want = reference.results(seed, steps)
        low = reference.results(seed, steps, act_bits=4)
        rows.append({
            "seed": seed, "calls_checked": len(kept),
            "program": sum(reference.wrong_elements(g, want) for g in kept),
            "control": reference.wrong_elements(low, want),
            "elements": sum(v.size for v in want.values())})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from bench.run import CACHE_DIR, device_check
    from bench.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device_check(cell.chips)
    rows = readings(cell, args.seeds, WINDOW_S)
    for r in rows:
        print(json.dumps(dict(r, workload=cell.name)), flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
