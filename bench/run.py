#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` name of ``BENCHMARK.json``.  The run needs
a TPU with at least the cell's ``chips``; without one it exits non-zero
and prints no result.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit.  The same numbers
end standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / "bench" / ".jax-cache"


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU -- JAX found {devs[0].platform!r}; "
                         "a measurement never runs elsewhere")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from bench.spec import load_cell

    cell = load_cell(ROOT, args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = device_check(cell.chips)

    from bench.harness import run_cell

    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), device=device, t0=T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
