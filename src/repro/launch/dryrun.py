import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record memory/cost/collective analysis.

MUST be run as its own process (the XLA flag above locks the device count
at first jax init -- which is why it is set before any other import).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama_1_1b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS, SHAPES, cell_runnable, get_config,
)
from repro.dist import hlo_analysis, hlo_bytes, roofline  # noqa: E402
from repro.dist.sharding import use_mesh  # noqa: E402
from repro.launch import inputs as I  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.models.base import (  # noqa: E402
    abstract_params, param_bytes, param_shardings,
)
from repro.optim import adamw  # noqa: E402
from repro.train.step import (  # noqa: E402
    make_prefill_step, make_serve_step, make_train_step,
)


def _abstract_moments(structure):
    ab = abstract_params(structure)
    mom = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), ab)
    return {"mu": mom, "nu": mom,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


# ----------------------------------------------------- counting pass -------
# XLA cost analysis counts while bodies ONCE (not x trip count), so the
# scanned full-depth program under-reports FLOPs/bytes/collectives. The
# counting pass lowers depth-1 and depth-2 configs with all scans UNROLLED
# (REPRO_UNROLL_SCANS=1: no while ops => exact costs) and extrapolates
# linearly in depth -- exact, since blocks are homogeneous.

import dataclasses as _dc  # noqa: E402


def _period(cfg) -> int:
    if cfg.block_pattern:
        return len(cfg.block_pattern)
    if cfg.n_experts and cfg.moe_every == 2:
        return 2
    return 1


def _n_full_blocks(cfg) -> int:
    return cfg.n_layers // _period(cfg)


def depth_config(cfg, k: int):
    """Same widths, k repeating blocks (a pattern's tail kept)."""
    per = _period(cfg)
    if cfg.block_pattern:
        tail = cfg.n_layers % per
        return _dc.replace(cfg, n_layers=per * k + tail)
    if cfg.family == "audio":
        return _dc.replace(cfg, n_layers=k, enc_layers=k)
    return _dc.replace(cfg, n_layers=per * k)


def _count_once(cfg_k, shape, mesh):
    os.environ["REPRO_UNROLL_SCANS"] = "1"
    os.environ["REPRO_FLASH_CHUNK"] = str(
        max(512, shape.seq_len // 32))
    try:
        jitted, args = build_cell(cfg_k, shape, mesh)
        compiled = jitted.lower(*args).compile()
        cost = compiled.cost_analysis()
        txt = compiled.as_text()
        stats = hlo_analysis.collect_collectives(txt, default_group=16)
        from repro import util as _util
        scope = "flash_internal" if _util.fused_attention_accounting() \
            else None
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes": hlo_bytes.boundary_bytes(txt, exclude_scope=scope),
                "bytes_hlo_raw": float(cost.get("bytes accessed", 0.0)),
                "wire_bytes": stats.total_wire_bytes,
                "wire_detail": stats.wire_bytes,
                "counts": stats.counts}
    finally:
        os.environ.pop("REPRO_UNROLL_SCANS", None)
        os.environ.pop("REPRO_FLASH_CHUNK", None)


def counting_pass(cfg, shape, mesh) -> dict:
    """Exact full-depth HLO costs via depth-1/2 unrolled lowerings."""
    nb = _n_full_blocks(cfg)
    c1 = _count_once(depth_config(cfg, 1), shape, mesh)
    c2 = _count_once(depth_config(cfg, 2), shape, mesh)
    out = {}
    for key in ("flops", "bytes", "wire_bytes"):
        out[key] = c1[key] + (nb - 1) * (c2[key] - c1[key])
    out["per_block"] = {k: c2[k] - c1[k]
                        for k in ("flops", "bytes", "wire_bytes")}
    out["depth1"] = c1
    out["depth2"] = c2
    out["n_full_blocks"] = nb
    return out


def build_cell(cfg, shape, mesh, *, remat=True, zero1=True):
    """Returns (jitted_fn, example_args) for one cell."""
    fns = registry.model_fns(cfg)
    structure = fns.param_structure(cfg)
    params_abs = abstract_params(structure)
    params_sh = param_shardings(structure, mesh)

    if shape.kind == "train":
        opt = adamw.AdamWConfig()
        step = make_train_step(cfg, opt, remat=remat)
        opt_abs = _abstract_moments(structure)
        opt_sh = adamw.moment_shardings(structure, mesh, zero1=zero1)
        bspecs = I.train_batch_specs(cfg, shape)
        bsh = I.batch_shardings(cfg, bspecs, mesh)
        jitted = jax.jit(step, in_shardings=(params_sh, opt_sh, bsh),
                         out_shardings=(params_sh, opt_sh, None))
        return jitted, (params_abs, opt_abs, bspecs)

    if shape.kind == "prefill":
        step = make_prefill_step(cfg)
        bspecs = I.train_batch_specs(cfg, shape)
        bspecs.pop("labels"), bspecs.pop("mask")
        bsh = I.batch_shardings(cfg, bspecs, mesh)
        jitted = jax.jit(step, in_shardings=(params_sh, bsh),
                         out_shardings=None)
        return jitted, (params_abs, bspecs)

    # decode
    step = make_serve_step(cfg)
    cache_struct = fns.cache_structure(cfg, shape.global_batch,
                                       shape.seq_len)
    cache_abs = abstract_params(cache_struct)
    cache_sh = param_shardings(cache_struct, mesh)
    tok = I.decode_token_specs(cfg, shape)
    tok_sh = I.batch_shardings(cfg, {"tokens": tok}, mesh)["tokens"]
    jitted = jax.jit(step, in_shardings=(params_sh, cache_sh, tok_sh),
                     out_shardings=(None, cache_sh))
    return jitted, (params_abs, cache_abs, tok)


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             outdir: str, verbose: bool = True, resume: bool = False,
             counting: bool = True) -> dict:
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "status": "pending"}
    if resume:
        path = os.path.join(outdir, mesh_name,
                            f"{arch_id}__{shape_name}.json")
        if os.path.exists(path):
            with open(path) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skipped"):
                if verbose:
                    print(f"resume: {arch_id} x {shape_name} already "
                          f"{prev['status']}")
                return prev
    if not cell_runnable(arch_id, shape_name):
        rec["status"] = "skipped"
        rec["reason"] = ("long_500k requires sub-quadratic attention; "
                         "this arch is pure full-attention (DESIGN.md §4)")
        _save(rec, outdir)
        return rec

    cfg = get_config(arch_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        with use_mesh(mesh):
            jitted, args = build_cell(cfg, shape, mesh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            print(mem)  # proves it fits
            cost = compiled.cost_analysis()
            print({k: cost.get(k) for k in ("flops", "bytes accessed")})
            hlo = compiled.as_text()
    except Exception as e:  # noqa: BLE001
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        _save(rec, outdir)
        if verbose:
            print(f"FAILED {arch_id} x {shape_name} [{mesh_name}]: "
                  f"{rec['error']}")
        return rec

    stats = hlo_analysis.collect_collectives(hlo, default_group=16)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mf = registry.model_flops(cfg, tokens, train=(shape.kind == "train"))

    # exact full-depth costs (scan-aware counting pass) -- inside the mesh
    # context so activation sharding constraints stay active. The roofline
    # table is single-pod only, so multi-pod runs may skip it.
    if counting:
        try:
            with use_mesh(mesh):
                counted = counting_pass(cfg, shape, mesh)
            flops, bytes_acc = counted["flops"], counted["bytes"]
            wire = counted["wire_bytes"]
            count_status = "counted"
        except Exception as e:  # noqa: BLE001
            counted = {"error": f"{type(e).__name__}: {e}"}
            flops = float(cost.get("flops", 0.0))
            bytes_acc = float(cost.get("bytes accessed", 0.0))
            wire = stats.total_wire_bytes
            count_status = "fallback_scan_once"
    else:
        counted = {"skipped": "multi-pod run (roofline is single-pod)"}
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        wire = stats.total_wire_bytes
        count_status = "not_counted"

    rl = roofline.Roofline(
        arch=arch_id, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops_per_chip=flops, hlo_bytes_per_chip=bytes_acc,
        collective_wire_bytes_per_chip=wire,
        model_flops_total=mf,
        collective_detail={"counts": stats.counts,
                           "wire_bytes": stats.wire_bytes,
                           "count_status": count_status},
    )
    mem_fields = {}
    for f in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "generated_code_size_in_bytes"):
        mem_fields[f] = getattr(mem, f, None)
    fns = registry.model_fns(cfg)
    pbytes = param_bytes(fns.param_structure(cfg))

    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        param_bytes_total=pbytes,
        param_bytes_per_chip_modelsharded=pbytes // 16,
        memory_analysis=mem_fields,
        cost_analysis={k: cost.get(k) for k in ("flops", "bytes accessed",
                                                "optimal_seconds")
                       if k in cost},
        collectives={"counts": stats.counts,
                     "bytes": stats.bytes_moved,
                     "wire_bytes": stats.wire_bytes},
        counting=counted,
        roofline=rl.to_dict(),
    )
    if verbose:
        print(roofline.summarize(rl))
    _save(rec, outdir)
    return rec


def _save(rec: dict, outdir: str):
    d = os.path.join(outdir, rec["mesh"])
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{rec['arch']}__{rec['shape']}.json"),
              "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose artifact already says ok/skipped")
    ap.add_argument("--no-counting", action="store_true",
                    help="skip the depth-1/2 counting pass (multi-pod runs)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, (
        "dry-run needs the forced 512-device host platform")

    cells = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ARCH_IDS for s in SHAPES])
    results = []
    for arch_id, shape_name in cells:
        print(f"=== {arch_id} x {shape_name} "
              f"[{'multi-pod' if args.multi_pod else 'single-pod'}] ===",
              flush=True)
        results.append(run_cell(arch_id, shape_name,
                                multi_pod=args.multi_pod, outdir=args.out,
                                resume=args.resume,
                                counting=not args.no_counting))
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    fail = [r for r in results if r["status"] == "failed"]
    print(f"\n{ok} ok / {sk} skipped / {len(fail)} failed")
    for r in fail:
        print(f"  FAILED: {r['arch']} x {r['shape']}: {r['error']}")
    raise SystemExit(1 if fail else 0)


if __name__ == "__main__":
    main()
