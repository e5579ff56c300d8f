#!/usr/bin/env python3
"""Bring-up smoke run of the plan -> schedule -> serve path on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the sharded simulator

Everything runs in this one process, through the entry points a user
calls, and every phase checks its results bit for bit:

(a) Device check: the first device must be a TPU.  With none, the run
    stops here with a non-zero exit -- it never carries on on the CPU.
(b) Kernels at a full width of stablelm-1.6b (the FFN up-projection of a
    4096-token decode step, 4096 x 2048 x 5632): BP at widths 4/8/16/32,
    fused BS at 4/8, unfused bitpack -> bitserial_matmul at 4, and the
    bitpack/bitunpack round trip, each against ``kernels/ref.py`` run as
    plain XLA on the same device.
(c) The main path: ``compile_plan`` -> ``lower_plan_pallas`` ->
    ``compile_schedule`` -> ``run()`` (twice) for the traced stablelm-1.6b
    decode step and the traced VGG16, both arriving in BP layout, with no
    matmul or conv step left modelled.  Every step is compared with a
    plain XLA integer reference of the same threaded dataflow.  Widths
    are the published ones; scale is cut to fit one 16 GB chip (see
    STABLELM_TOKENS and VGG_BATCH below).
(d) Serving: ``run_serve_bench`` pushes a few dozen requests through
    ``PlanService`` and ``PhaseBatcher.execute``; its payload is written
    under ``experiments/chip_smoke/``.

``--four-chips`` runs only the mesh-sharded ``run_batched`` of the
traced VGG16 critical class on random cells, and checks the final cell
states bit-identical to the same programs on one device, with the arrays
spread over all four devices.

Any failure exits non-zero.  The last line of a passing run is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
#: rows of the kernel-phase problem and the decode batch it stands for
FFN_TOKENS = 4096
#: decode batch (= KV length) of the traced stablelm step.  The traced
#: attention-score matmul is a (tokens * heads * chunk, head_dim, 1)
#: GEMV whose N pads to 128 lanes: at 4096 tokens its int32 output alone
#: would be 34 GB (the TPU compiler refuses it), so one 16 GB chip takes
#: the step at 512 tokens (~5 GB of transients).
STABLELM_TOKENS = 512
#: VGG16 inference batch (the registry's is 128); conv runs as the same
#: lane-padded GEMV, which at batch 128 needs ~50 GB (the compiler refuses)
VGG_BATCH = 16
SERVE_REQUESTS = 48
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_check(need: int) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU -- JAX found "
                         f"{dev.platform!r}; refusing to run elsewhere")
    if len(devs) < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU devices, "
                         f"found {len(devs)}")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    print(f"# (a) device: {info['kind']} x{info['count']} "
          f"({info['platform']}, jax {jax.__version__})", flush=True)
    return info


def _warm_us(fn, reps: int = 3) -> float:
    import jax

    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append((time.perf_counter() - t0) * 1e6)
    return sorted(samples)[len(samples) // 2]


def kernels_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops, ref
    from repro.kernels.bitparallel_matmul import n_limbs
    from repro.util import rand_words

    cfg = get_config("stablelm_1_6b")
    m, k, n = FFN_TOKENS, cfg.d_model, cfg.d_ff
    rng = np.random.default_rng(SEED)
    x = jax.device_put(rng.integers(-128, 128, (m, k), dtype=np.int8))
    ref_bp = jax.jit(ref.bitparallel_matmul_ref)
    same = jax.jit(lambda a, b: jnp.array_equal(a, b))

    def case(name, fn, want, passes):
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn())
        first_us = (time.perf_counter() - t0) * 1e6
        ok = bool(same(got, want))
        print(f"  {name:22s} {m}x{k}x{n} passes={passes:<2d} exact={ok} "
              f"first_us={first_us:.0f} warm_us={_warm_us(fn):.0f}",
              flush=True)
        check(ok, f"kernel {name} differs from kernels/ref.py")

    print("# (b) kernels at stablelm_1_6b FFN width", flush=True)
    words = {b: jax.device_put(rand_words(rng, b, (k, n)))
             for b in (4, 8, 16, 32)}
    for bits, w in words.items():
        want = ref_bp(x, w)
        limbs = ops.bp_limbs(w, bits)
        case(f"bp/w{bits}", lambda: ops.matmul_bp(x, limbs), want,
             n_limbs(bits))
        if bits <= 8:
            case(f"bs_fused/w{bits}",
                 lambda: ops.matmul_bs_fused(x, w, bits), want, bits)
    w4 = words[4]
    case("bs_unfused/w4", lambda: ops.matmul_bs(x, ops.pack_weights(w4, 4)),
         ref_bp(x, w4), 4)
    planes = ops.pack_weights(w4, 4)
    packed_ok = bool(same(planes, jax.jit(ref.bitpack_ref,
                                          static_argnums=1)(w4, 4)))
    back_ok = bool(same(ops.unpack_weights(planes, k),
                        w4.astype(jnp.uint32)))
    print(f"  bitpack/w4 exact={packed_ok} bitunpack round trip "
          f"exact={back_ok}", flush=True)
    check(packed_ok and back_ok, "bitpack/bitunpack differ from ref")


def xla_reference(schedule, inputs) -> dict:
    """Plain XLA integer reference of the schedule's threaded dataflow:
    int32 wraparound matmuls, activations threaded as
    ``plan.pallas.reference_results`` threads them."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import thread_activations

    dot = jax.jit(lambda a, b: jnp.matmul(a.astype(jnp.int32),
                                          b.astype(jnp.int32)))
    thread = jax.jit(thread_activations, static_argnums=(1, 2))
    producer = schedule.threaded_producers()
    out = {}
    for s in schedule.measured_steps:
        x, w = inputs[s.op]
        src = producer.get(s.op)
        if src in out:
            x = thread(out[src], s.dims[0], s.dims[1])
        out[s.op] = dot(jnp.asarray(x), jnp.asarray(w))
    return out


def main_path_phase(name: str, workload) -> None:
    import jax
    import numpy as np

    from repro import spans
    from repro.core.cost_model import Layout
    from repro.plan import (compile_plan, compile_schedule,
                            lower_plan_pallas, synth_inputs)

    plan = compile_plan(workload, initial_layout=Layout.BP)
    sched = lower_plan_pallas(plan, workload, max_macs=sys.maxsize)
    left = [s.op for s in sched.steps
            if s.kind in ("matmul", "conv") and not s.measured]
    check(not left, f"{name}: matmul/conv steps left modelled: {left}")
    kernels = sorted({s.kernel for s in sched.measured_steps})
    inputs = synth_inputs(sched, seed=SEED)
    exe = compile_schedule(sched, inputs, seed=SEED)
    first, second = exe.run(), exe.run()
    # the second call is warm: read its spans
    warm_us = spans.last("schedule.run").dur_ns / 1e3
    phases = " ".join(
        f"{p}_us={spans.last(f'schedule.{p}').dur_ns / 1e3:.0f}"
        for p in ("place", "dispatch", "wait", "fetch"))
    want = {op: np.asarray(y)
            for op, y in xla_reference(sched, inputs).items()}
    bad = [op for op in want
           if not (np.array_equal(first[op], want[op])
                   and np.array_equal(second[op], want[op]))]
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  {name}: n_measured={exe.n_measured} "
          f"n_modelled={exe.n_modelled} repacks={sched.n_repacks} "
          f"kernels={','.join(kernels)}", flush=True)
    print(f"  {name}: compile_us={exe.compile_us:.0f} "
          f"warm_run_us={warm_us:.0f} ({phases}) "
          f"params_bytes={exe.params_bytes} "
          f"entry_bytes={exe.entry_bytes} "
          f"peak_bytes_in_use={peak} exact_steps="
          f"{len(want) - len(bad)}/{len(want)}", flush=True)
    check(not bad, f"{name}: steps differ from the XLA reference: {bad}")


def serve_phase() -> None:
    from repro.artifacts import write_artifact
    from repro.serve import run_serve_bench

    payload = run_serve_bench(SERVE_REQUESTS, seed=SEED, persist=False)
    exes, batches = payload["executables"], payload["batches"]
    ex = payload["execute_us"]
    print(f"  serve: {payload['requests']} requests in "
          f"{batches['count']} groups; execute p50={ex['p50']:.0f}us "
          f"p99={ex['p99']:.0f}us", flush=True)
    print(f"  serve: executables compiled={exes['misses']} "
          f"hits={exes['hits']} measured_steps={exes['measured_steps']} "
          f"modelled_steps={exes['modelled_steps']} "
          f"groups_all_modelled={exes['groups_all_modelled']}", flush=True)
    path = ROOT / "experiments" / "chip_smoke" / "serve.json"
    write_artifact(str(path), "serve", payload,
                   generated_by="python chip_smoke.py")
    check(exes["hits"] + exes["misses"] == batches["count"],
          "serve: a batch group bypassed the executable path")
    check(exes["groups_all_modelled"] == 0,
          "serve: a batch group ran no kernel on the device")


def four_chip_phase() -> None:
    import numpy as np

    from repro.machine import execute_schedule, plan_machine
    from repro.machine.engine import default_mesh
    from repro.sweep import iso_area_family
    from repro.workloads import get_workload

    w = get_workload("traced/vgg16")
    geo = max(iso_area_family(), key=lambda g: g.arrays)
    sched = plan_machine(w, geo)
    mesh = default_mesh()
    check(mesh is not None, "four-chips: no multi-device mesh")
    t0 = time.perf_counter()
    sharded = execute_schedule(sched, w, mesh=mesh, collect_hlo=False)
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = execute_schedule(sched, w, mesh=None, collect_hlo=False)
    t_single = time.perf_counter() - t0
    print(f"# four-chips: traced/vgg16 critical class @ {geo.label()}, "
          f"{sharded['arrays_simulated']} arrays over "
          f"{sharded['mesh_devices']} devices ({t_sharded:.1f}s incl. "
          f"compile; one device {t_single:.1f}s)", flush=True)
    for prog, a, b in zip(sharded["programs"], sharded["states"],
                          single["states"]):
        devices = {s.device for s in a.cells.addressable_shards}
        equal = all(np.array_equal(np.asarray(p), np.asarray(q))
                    for p, q in zip(a, b))
        ones = float(np.asarray(a.cells).mean())
        print(f"  {prog['name']:12s} w{prog['width']:<2d} "
              f"arrays={prog['arrays']} cells={prog['rows']}x"
              f"{prog['cols']} devices={len(devices)} "
              f"ones={ones:.3f} identical={equal}", flush=True)
        check(len(devices) == mesh.devices.size,
              f"{prog['name']}: cells on {len(devices)} device(s)")
        check(equal, f"{prog['name']}: sharded state differs")
    check(len(sharded["states"]) == len(sharded["programs"]) > 0,
          "four-chips: no program ran")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded simulator on four "
                         "chips against one device")
    args = ap.parse_args()
    device = device_check(4 if args.four_chips else 1)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.util import use_compile_cache

    use_compile_cache()
    if args.four_chips:
        four_chip_phase()
    else:
        from repro.configs import get_config
        from repro.models.registry import traced_workload
        from repro.models.vgg import traced_vgg

        kernels_phase()
        print("# (c) plan -> schedule -> run at published widths",
              flush=True)
        main_path_phase(
            f"traced/stablelm_1_6b@{STABLELM_TOKENS}tok",
            traced_workload(get_config("stablelm_1_6b"),
                            tokens=STABLELM_TOKENS))
        main_path_phase(f"traced/vgg16@batch{VGG_BATCH}",
                        traced_vgg("vgg16", batch=VGG_BATCH))
        print("# (d) serving", flush=True)
        serve_phase()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
