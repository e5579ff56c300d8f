"""pallas-bench: the measured wall-clock trajectory of the Pallas kernels.

One run times every (shape, width, kernel-path) case over the *full*
un-clamped problem -- the Table-5/Table-6 matmul shapes (GEMM 400^3,
GEMV 1x4096x512, the VGG classifier FCs) at weight widths {1, 4, 8, 16}
-- through three paths:

* ``bp``          -- the grid-tiled bit-parallel word kernel,
* ``bs_fused``    -- the one-kernel fused bitpack-matmul,
* ``bs_unfused``  -- bitpack -> the bitplane kernel with the pack pass
  *on* the timed path (the materialized-plane-artifact cost fusion
  removes; the fused-vs-unfused delta is the point of the comparison).

Each path is a lowered step (``plan.pallas.step_for``) run through the
schedule's own step function (``hold`` / ``apply``).

With ``chained=True`` the run also times whole-schedule execution for
the multi-step Table-6 apps (:data:`CHAINED_APPS`): the per-step host
dispatch of ``run_schedule`` vs the ONE-jitted-program executor of
``plan.pallas_exec`` (weights device-resident, step outputs threaded,
one host round-trip).  The ``chained/<app>/{per_step,chained}`` pair per
app is the measured cost of host-side schedule dispatch -- the delta a
real PIM controller never pays -- and both paths are asserted bit-exact
before their timings enter the artifact.

Each case is the median of ``reps`` post-warmup calls with
``block_until_ready``.  The payload is committed to ``BENCH_pallas.json``
under the ``repro.artifacts`` envelope and gated in CI by
:func:`check_pallas_regression` (per-case medians, noise-tolerant
threshold + floor, exit 3 on regression -- the serve-bench idiom).

On this CPU container the absolute numbers are interpret-mode
correctness-path timings, not TPU performance; the *trajectory* (ratios
across widths, fused vs unfused, and run-over-run regressions) is what
the gate protects.
"""
from __future__ import annotations

import sys
from typing import Optional

import numpy as np

#: Table-5/Table-6 matmul shapes (name, (m, k, n)) -- the full problem
#: sizes the un-clamped kernels now measure end to end.
BENCH_SHAPES: tuple[tuple[str, tuple[int, int, int]], ...] = (
    ("gemm", (400, 400, 400)),     # Table-5/6 GEMM (mk/gemm op)
    ("gemv", (1, 4096, 512)),      # Table-6 GEMV
    ("vgg_fc", (1, 512, 512)),     # VGG classifier fc0/fc1
    ("vgg_fc_out", (1, 512, 10)),  # VGG classifier fc2 (ragged N)
)
#: weight widths: the paper's low-precision sweep + full INT16
BENCH_WIDTHS: tuple[int, ...] = (1, 4, 8, 16)
#: quick (CI smoke) subset: the committed acceptance widths
QUICK_WIDTHS: tuple[int, ...] = (4, 8, 16)
#: apps for the chained-vs-per-step pair: the VGG classifier chains
#: (3 measured FC steps each; convs exceed any honest interpret-mode
#: budget and stay modelled) + the single-step GEMV control
CHAINED_APPS: tuple[str, ...] = ("vgg13", "vgg16", "vgg19", "gemv")


def run_chained_bench(*, apps=CHAINED_APPS, reps: int = 5, seed: int = 0,
                      max_macs: Optional[int] = None
                      ) -> tuple[list[dict], dict]:
    """Chained-vs-per-step pairs: ``(cases, per-app meta)``.

    ``chained/<app>/per_step`` times :func:`plan.pallas.run_schedule` --
    one jitted-wrapper dispatch, weight conversion, and host transfer
    per measured step.  ``chained/<app>/chained`` times the warm
    ``ScheduleExecutable.run()`` of the same schedule -- weights already
    device-resident, outputs threaded in-program, one host round-trip.
    Identical threaded dataflow on both paths, asserted bit-exact before
    either timing enters the artifact.
    """
    from repro.plan import (compile_plan, compile_schedule,
                            lower_plan_pallas, run_schedule, synth_inputs)
    from repro.plan.pallas import clock
    from repro.workloads import get_workload

    cases: list[dict] = []
    meta: dict = {}
    for app in apps:
        w = get_workload(app)
        kwargs = {} if max_macs is None else {"max_macs": max_macs}
        sched = lower_plan_pallas(compile_plan(w), w, **kwargs)
        n_meas = len(sched.measured_steps)
        if not n_meas:
            meta[app] = {"skipped": "no measured steps under budget"}
            continue
        inputs = synth_inputs(sched, seed=seed)
        per_us = clock(lambda: run_schedule(sched, inputs), reps)
        exe = compile_schedule(sched, inputs)
        chained_us = clock(exe.run, reps)
        per = run_schedule(sched, inputs)
        got = exe.run()
        for op, y in got.items():
            assert np.array_equal(y, per[op]), \
                f"chained/per-step divergence at {app}:{op}"
        base = {"app": app, "steps": n_meas,
                "width": sched.measured_steps[0].width}
        cases.append({**base, "name": f"chained/{app}/per_step",
                      "path": "per_step", "us": per_us})
        cases.append({**base, "name": f"chained/{app}/chained",
                      "path": "chained", "us": chained_us})
        meta[app] = {"steps": n_meas,
                     "modelled": len(sched.steps) - n_meas,
                     "compile_us": exe.compile_us,
                     "per_step_us": per_us, "chained_us": chained_us,
                     "speedup": per_us / chained_us}
    return cases, meta


def run_pallas_bench(*, quick: bool = False, reps: Optional[int] = None,
                     seed: int = 0,
                     shapes=None, widths=None, chained: bool = False,
                     chained_apps=None) -> dict:
    """Time every case; returns the BENCH_pallas.json payload dict.

    Each path is the step the schedule lowering makes of the shape
    (``plan.pallas.step_for``), timed as ``plan.pallas.apply`` on weights
    ``plan.pallas.hold`` made outside the timed call: ``bp`` a BP step,
    ``bs_fused`` and ``bs_unfused`` a ``bp2bs`` boundary into BS, fused or
    not -- so the unfused pack stays on the timed path.
    """
    import jax.numpy as jnp

    from repro.core.cost_model import Layout
    from repro.kernels import platform
    from repro.plan.pallas import apply_jit, clock, hold, step_for
    from repro.util import rand_words
    from repro.workloads.ir import Op

    if shapes is None:
        shapes = BENCH_SHAPES
    if reps is None:
        reps = 2 if quick else 5
    if widths is None:
        widths = QUICK_WIDTHS if quick else BENCH_WIDTHS
    rng = np.random.default_rng(seed)
    cases = []
    for shape_name, (m, k, n) in shapes:
        x = jnp.asarray(rng.integers(-8, 8, (m, k), dtype=np.int32)
                        ).astype(jnp.int8)
        for bits in widths:
            w = jnp.asarray(rand_words(rng, bits, (k, n)))
            op = Op(name=shape_name, kind="matmul", m=m, k=k, n=n,
                    width=bits)
            for path, layout, repack, fused in (
                    ("bp", Layout.BP, None, False),
                    ("bs_fused", Layout.BS, "bp2bs", True),
                    ("bs_unfused", Layout.BS, "bp2bs", False)):
                step = step_for(op, layout, repack, fuse_pack=fused,
                                max_macs=sys.maxsize)
                held = hold(step, w)
                cases.append({
                    "name": f"{shape_name}/w{bits}/{path}",
                    "shape": [m, k, n], "width": bits, "path": path,
                    "padded": list(step.padded_dims),
                    "us": clock(lambda: apply_jit(step, x, held), reps),
                })
    payload = {"reps": reps, "quick": quick,
               "interpret": platform.interpret(),
               "seed": seed, "cases": cases}
    if chained:
        ch_cases, ch_meta = run_chained_bench(
            apps=chained_apps or CHAINED_APPS, reps=reps, seed=seed)
        cases.extend(ch_cases)
        payload["chained"] = ch_meta
    return payload


def check_pallas_regression(payload: dict, baseline_payload: dict,
                            threshold: float = 0.5,
                            floor_us: float = 2000.0
                            ) -> tuple[bool, str]:
    """CI gate: ``(ok, message)``; fails when any case's median exceeds
    its committed baseline by more than ``threshold``.

    ``floor_us`` clamps the baseline: sub-millisecond interpret-mode
    medians double under shared-runner jitter without meaning anything,
    so cases under ``floor_us * (1 + threshold)`` always pass and the
    gate targets systematic multi-x regressions (a kernel falling off
    the grid-tiled path, a fusion silently re-materializing planes).
    Cases with no baseline entry (new shapes/widths) pass with a note.
    """
    base = {c["name"]: c for c in baseline_payload.get("cases", ())}
    failures, checked, new = [], 0, 0
    for c in payload.get("cases", ()):
        b = base.get(c["name"])
        if b is None:
            new += 1
            continue
        checked += 1
        ref = max(b["us"], floor_us)
        if c["us"] > ref * (1.0 + threshold):
            failures.append(f"{c['name']}: {c['us']:.0f}us vs baseline "
                            f"{b['us']:.0f}us (x{c['us'] / ref:.2f}, "
                            f"budget x{1 + threshold:.2f})")
    msg = (f"{checked} case(s) gated, {new} new, "
           f"{len(failures)} regression(s)")
    if failures:
        msg += " -- " + "; ".join(failures)
    return not failures, msg
