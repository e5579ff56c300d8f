"""serve-bench: replay an arch traffic mix through the plan-serving path.

One run = sample ``n`` concurrent requests from a :class:`TrafficMix`,
compile each through :class:`PlanService` (content-addressed plan cache),
group the compiled decode steps with :class:`PhaseBatcher`, and execute
every group as ONE compiled Pallas schedule
(``plan.pallas_exec.compile_schedule``) -- so the artifact's execute
latencies are measured kernel wall-clock, not the pre-PR-10 analytic
float32 reduction.  The result dict -- p50/p99 plan-compile latency,
*warm* execute latency and executable-compile cost (split so the p99
gate sees the steady state), cache counters for both the plan cache and
the executable cache, batching and simulated-cycle totals -- is
committed to ``bench-artifacts/serve.json`` under the versioned artifact
envelope and gated in CI (p99 warm execute, regression budget + floor).

``python -m repro serve-bench [--quick]`` is the CLI entry.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence

import numpy as np

from repro.core.params import SystemParams, PAPER_SYSTEM
from repro.serve.batcher import DEFAULT_EXECUTE_BUDGET, PhaseBatcher
from repro.serve.plan_cache import PlanCache
from repro.serve.service import PlanService
from repro.serve.traffic import TrafficMix


def _percentiles(us: Sequence[float]) -> dict:
    if not us:
        return {"p50": 0.0, "p99": 0.0, "mean": 0.0, "max": 0.0}
    arr = np.asarray(us, np.float64)
    return {"p50": float(np.percentile(arr, 50)),
            "p99": float(np.percentile(arr, 99)),
            "mean": float(arr.mean()), "max": float(arr.max())}


def run_serve_bench(n_requests: int = 2048, *, seed: int = 0,
                    mix: Optional[TrafficMix] = None,
                    sys: SystemParams = PAPER_SYSTEM,
                    cache: Optional[PlanCache] = None,
                    cache_dir: Optional[str] = None, persist: bool = True,
                    max_batch: int = 64,
                    execute_budget: int = DEFAULT_EXECUTE_BUDGET) -> dict:
    """Replay the traffic mix; returns the serve.json payload dict.

    ``execute_budget`` is the per-launch padded-MAC budget for the Pallas
    execute path (``PhaseBatcher.execute``); plans whose steps exceed it
    run as modelled-only rows, counted in ``executables`` below.
    """
    mix = mix or TrafficMix.default()
    service = PlanService(sys, cache=cache, cache_dir=cache_dir,
                          persist=persist)
    batcher = PhaseBatcher(max_batch=max_batch,
                           execute_budget=execute_budget, seed=seed)

    t0 = time.perf_counter()
    requests = mix.sample(n_requests, seed=seed)
    compiled = service.compile_many(requests)
    compile_done = time.perf_counter()
    groups, rows = batcher.run(compiled)
    elapsed = time.perf_counter() - t0

    # per-request latency = its group's compiled-schedule wall-clock
    # (warm) / executable-compile cost (0 on an executable-cache hit)
    execute_us = [g.execute_us for g in groups for _ in g.members]
    execute_compile_us = [g.execute_compile_us for g in groups
                          for _ in g.members]
    compile_us = [c.compile_us for c in compiled]
    sizes = [g.size for g in groups]
    stats = service.cache.stats()

    return {
        "requests": n_requests,
        "seed": seed,
        "mix": mix.to_dict(),
        "distinct_plans_bound": mix.distinct_plans,
        "geometry": _geometry_dict(service.sys),
        "plan_compile_us": _percentiles(compile_us),
        "execute_us": _percentiles(execute_us),
        "execute_compile_us": _percentiles(execute_compile_us),
        "compile_phase_s": compile_done - t0,
        "elapsed_s": elapsed,
        "throughput_rps": n_requests / elapsed if elapsed else 0.0,
        "cache": stats,
        "executables": {
            **batcher.executables.stats(),
            "execute_budget": execute_budget,
            "measured_steps": sum(r["measured_steps"] for r in rows),
            "modelled_steps": sum(r["modelled_steps"] for r in rows),
            #: groups whose compiled program ran no kernel at all
            "groups_all_modelled": sum(1 for r in rows
                                       if not r["measured_steps"]),
        },
        "batches": {
            "count": len(groups),
            "signatures": len({g.signature for g in groups}),
            "mean_size": float(np.mean(sizes)) if sizes else 0.0,
            "max_size": max(sizes, default=0),
        },
        "simulated": {
            "machine_cycles": sum(r["machine_cycles"] for r in rows),
            "latency_cycles_max": max(
                (r["latency_cycles"] for r in rows), default=0),
            "transpose_cycles_saved": sum(
                r["transpose_cycles_saved"] for r in rows),
            "hybrid_plans": sum(1 for c in compiled if c.plan.is_hybrid),
        },
    }


def _geometry_dict(sys: SystemParams) -> dict:
    from repro.sweep.grid import Geometry

    return Geometry.from_system(sys).to_dict()


def check_regression(payload: dict, baseline_payload: dict,
                     threshold: float = 0.25,
                     metric: str = "execute_us", floor_us: float = 250.0
                     ) -> tuple[bool, str]:
    """CI gate: ``(ok, message)``; fails when the new p99 of ``metric``
    exceeds the committed baseline by more than ``threshold``.

    ``floor_us`` clamps the baseline: a committed p99 of ~70us doubling
    under shared-runner jitter is noise, not a regression, so p99s under
    ``floor_us * (1 + threshold)`` always pass and the gate targets
    systematic multi-x regressions (per-request execution creeping back,
    a plan blow-up in the batched step).
    """
    new = payload[metric]["p99"]
    old = baseline_payload[metric]["p99"]
    ref = max(old, floor_us)
    ratio = new / ref if ref else 0.0
    msg = (f"p99 {metric}: {new:.1f}us vs baseline {old:.1f}us "
           f"(x{ratio:.2f}, budget x{1 + threshold:.2f})")
    return ratio <= 1.0 + threshold, msg
