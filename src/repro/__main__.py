"""``python -m repro``: the workload-IR command line.

Subcommands:

* ``list``          -- registered workloads (Table-5 / Table-6 / arch) and
                       backends.
* ``characterize``  -- run one or more workloads through one or more
                       backends and print per-backend BP/BS/hybrid
                       reports.  ``--quick`` is the CI smoke mode: every
                       table5+table6 workload through the cycle backends,
                       summaries written to
                       ``bench-artifacts/characterize.json``.
                       ``--geometry RxCxA[@BW]`` re-costs under a
                       non-default system geometry.
* ``plan``          -- compile workloads into executable layout plans
                       (repro.plan): per-op BP/BS assignment with explicit
                       transposes, geometry feasibility, optional executor
                       replay (``--execute``).  ``--quick`` is the CI
                       smoke: every Table-6 app's plan to
                       ``bench-artifacts/plans.json``.
* ``sweep``         -- the design-space sweep engine (repro.sweep):
                       workloads x widths x iso-area geometries in one
                       jitted batched evaluation, content-hash cached;
                       writes ``bench-artifacts/sweep.json`` and
                       ``bench-artifacts/guidelines.json``.
* ``guidelines``    -- print the machine-derived layout guidelines
                       (crossover table + rules + hybrid-win set) and
                       write ``bench-artifacts/guidelines.json``.
* ``serve-bench``   -- layout-aware serving at scale: replay thousands of
                       simulated concurrent requests from the arch traffic
                       mix through per-request plan compilation
                       (content-addressed plan cache) and phase-grouped
                       continuous batching; every batch group executes as
                       ONE compiled Pallas schedule (plan.pallas_exec),
                       so p50/p99 execute latencies are warm measured
                       kernel wall-clock with executable-compile cost
                       split out, landing with cache counters in
                       ``bench-artifacts/serve.json``.  ``--baseline``
                       gates p99 warm execute latency against a committed
                       artifact (the CI bench-smoke regression check).
* ``pallas-bench``  -- time the grid-tiled Pallas kernels over the full
                       (un-clamped) Table-5/6 matmul shapes: BP word
                       kernel vs fused and unfused BS bitplane kernels
                       per weight width.  ``--chained`` adds the
                       chained-vs-per-step pair per multi-step app (ONE
                       jitted schedule program vs host dispatch).  Writes
                       ``BENCH_pallas.json`` (versioned envelope);
                       ``--baseline`` gates every per-case median against
                       the committed artifact (exit 3 on regression,
                       like serve-bench).
* ``trace-diff``    -- the differential harness: reconcile the
                       jaxpr-traced ``traced/<id>`` workloads against the
                       hand-written ``arch/<id>`` formulas op by op
                       (repro.workloads.trace_diff).  Writes
                       ``bench-artifacts/traced_vs_formula.csv`` and
                       exits non-zero on any unexplained per-op delta.
                       ``--quick`` is the CI smoke: the smallest arch
                       plus VGG.
* ``tables``        -- the model-reproduced paper tables (the golden
                       snapshot text; see tests/golden/paper_tables.txt).

Committed artifacts (characterize.json, plans.json, serve.json) share the
versioned ``repro.artifacts`` envelope:
``{"artifact": kind, "schema_version": N, "payload": ...}``.

Examples::

    python -m repro list
    python -m repro characterize vgg --backends analytic,planner,executor
    python -m repro characterize mk/multu aes --ops
    python -m repro characterize aes --geometry 128x512x64
    python -m repro characterize --quick
    python -m repro plan aes --initial-layout BP --steps
    python -m repro plan vgg --geometry 8x512x8192 --execute
    python -m repro plan --quick
    python -m repro sweep --widths 4,8,16,32
    python -m repro guidelines
    python -m repro serve-bench --requests 4096
    python -m repro serve-bench --quick --baseline bench-artifacts/serve.json
    python -m repro plan traced/vgg16 --initial-layout BP --pallas
    python -m repro pallas-bench --quick --baseline BENCH_pallas.json
    python -m repro list --source traced
    python -m repro characterize traced/tinyllama_1_1b --ops
    python -m repro trace-diff --quick
    python -m repro trace-diff --pallas-archs tinyllama_1_1b
"""
from __future__ import annotations

import argparse
import json
import os
import sys

def _artifact_dir() -> str:
    return os.environ.get("REPRO_BENCH_ARTIFACT_DIR", "bench-artifacts")


def _fmt_summary(summary: dict) -> str:
    parts = []
    for key, val in summary.items():
        if isinstance(val, float):
            parts.append(f"{key}={val:.3f}")
        else:
            parts.append(f"{key}={val}")
    return " ".join(parts)


def _print_report(report, show_ops: bool, max_ops: int = 24) -> None:
    print(f"  [{report.backend}] {_fmt_summary(report.summary)}")
    for note in report.notes:
        print(f"    note: {note}")
    if not show_ops:
        return
    shown = report.ops[:max_ops]
    for op in shown:
        if not op.supported:
            print(f"    {op.op:20s} {op.kind:9s} unsupported: {op.note}")
        elif op.bp_us is not None:
            print(f"    {op.op:20s} {op.kind:9s} "
                  f"bp={op.bp_us:9.1f}us bs={op.bs_us:9.1f}us  {op.note}")
        else:
            print(f"    {op.op:20s} {op.kind:9s} "
                  f"bp={op.bp_cycles:>12d} bs={op.bs_cycles:>12d}  {op.note}")
    if len(report.ops) > max_ops:
        print(f"    ... ({len(report.ops) - max_ops} more ops; "
              "use --json for the full report)")


def cmd_list(args) -> int:
    from repro.workloads import BACKENDS, list_workloads
    from repro.workloads.registry import ALIASES

    rows = list_workloads(args.source)
    width = max(len(r["name"]) for r in rows) + 2
    cur = None
    for r in rows:
        if r["source"] != cur:
            cur = r["source"]
            print(f"\n# source: {cur}")
        print(f"{r['name']:{width}s}{r['description']}")
    print("\n# aliases")
    for alias, target in sorted(ALIASES.items()):
        print(f"{alias:{width}s}-> {target}")
    print("\n# backends")
    print(", ".join(sorted(BACKENDS)))
    return 0


def _parse_geometry(text):
    """``ROWSxCOLSxARRAYS[@ROW_BW]`` -> SystemParams (e.g. 128x512x64)."""
    from repro.sweep import Geometry

    body, _, bw = text.partition("@")
    try:
        rows, cols, arrays = (int(p) for p in body.lower().split("x"))
        bw_bits = int(bw) if bw else 512
    except ValueError:
        raise SystemExit(
            f"error: bad --geometry {text!r} (want ROWSxCOLSxARRAYS[@BW], "
            "e.g. 128x512x64 or 128x512x512@512)") from None
    return Geometry(rows=rows, cols=cols, arrays=arrays,
                    row_bandwidth_bits=bw_bits).system()


def _resolve_system(geometry_text, arrays):
    """--geometry / --arrays -> SystemParams (arrays overrides the count
    so single-array and machine-level numbers share one CLI surface)."""
    import dataclasses

    from repro.core.params import PAPER_SYSTEM
    from repro.sweep import Geometry

    system = _parse_geometry(geometry_text) if geometry_text \
        else PAPER_SYSTEM
    if arrays:
        if arrays < 1:
            raise SystemExit(f"error: --arrays must be >= 1, got {arrays}")
        system = dataclasses.replace(
            Geometry.from_system(system), arrays=arrays).system()
    return system


def cmd_characterize(args) -> int:
    from repro.workloads import backend_names, characterize, workload_names

    spec = args.backends or ("analytic,planner,executor" if args.quick
                             else "analytic,planner")
    backends = [b.strip() for b in spec.split(",") if b.strip()]
    unknown = [b for b in backends if b not in backend_names()]
    if unknown:
        print(f"error: unknown backend(s) {', '.join(unknown)} "
              f"(registered: {', '.join(backend_names())})", file=sys.stderr)
        return 2
    names = list(args.workloads)
    if args.quick and not names:
        # CI smoke scope: the analytic registries (arch/ workloads need
        # the jax model stack and are opt-in by name)
        names = workload_names("table5") + workload_names("table6")
    if not names:
        print("error: no workloads given (or use --quick)", file=sys.stderr)
        return 2
    system = _resolve_system(args.geometry, args.arrays)
    artifact: dict[str, dict] = {}
    full: dict[str, dict] = {}
    for name in names:
        reports = characterize(name, backends=backends, sys=system)
        print(f"{name}:")
        for rep in reports.values():
            _print_report(rep, show_ops=args.ops)
        artifact[name] = {b: rep.summary for b, rep in reports.items()}
        if args.json:
            full[name] = {b: rep.to_dict() for b, rep in reports.items()}
    if args.quick:
        from repro.artifacts import write_artifact

        path = os.path.join(_artifact_dir(), "characterize.json")
        write_artifact(path, "characterize", artifact,
                       generated_by="python -m repro characterize --quick")
        print(f"\n# wrote per-workload per-backend summaries to {path}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(full, f, indent=1, sort_keys=True)
        print(f"# wrote full reports to {args.json}")
    return 0


def cmd_plan(args) -> int:
    from repro.core.cost_model import Layout
    from repro.plan import compile_plan, replay_plan
    from repro.workloads import get_workload, workload_names

    names = list(args.workloads)
    if args.quick and not names:
        names = workload_names("table6")
    if not names:
        print("error: no workloads given (or use --quick)", file=sys.stderr)
        return 2
    system = _resolve_system(args.geometry, args.arrays)
    init = Layout(args.initial_layout) if args.initial_layout else None
    artifact: dict[str, dict] = {}
    full: dict[str, dict] = {}
    for name in names:
        w = get_workload(name)
        p = compile_plan(w, system, initial_layout=init)
        sched = "".join("S" if lay is not Layout.BP else "P"
                        for lay in p.schedule)
        print(f"{name}: total={p.total_cycles} "
              f"static_bp={p.static_bp} static_bs={p.static_bs} "
              f"speedup={p.hybrid_speedup:.2f}x "
              f"n_transposes={p.n_transposes} feasible={p.feasible}")
        if args.steps:
            print(f"  schedule [P=BP S=BS]: {sched}")
            for s in p.steps:
                flag = "" if s.feasible else "  !row-overflow"
                print(f"  {s.phase:24s} {s.layout.value} "
                      f"{s.cycles:>12d}{flag}")
        d = p.to_dict(include_steps=not args.quick)
        if args.json:
            full[name] = p.to_dict()
        if args.pallas:
            from repro.plan import (lower_plan_pallas, synth_inputs,
                                    time_schedule)

            sched = lower_plan_pallas(p, w)
            rows = time_schedule(sched, synth_inputs(sched),
                                 reps=args.reps)
            d["pallas"] = {"fuse_pack": sched.fuse_pack,
                           "n_repacks": sched.n_repacks, "steps": rows}
            if args.json:
                full[name]["pallas"] = d["pallas"]
            for r in rows:
                tag = f" +{r['repack']}" if r["repack"] else ""
                if r["us"] is None:
                    print(f"  pallas {r['op']} [{r['layout']}{tag}]: "
                          f"-- ({r['note']})")
                else:
                    print(f"  pallas {r['op']} [{r['layout']}{tag}]: "
                          f"{r['kernel']} dims={r['dims']} "
                          f"padded={r['padded_dims']} "
                          f"median_us={r['us']:.0f}")
        if args.execute:
            rows = replay_plan(p, w, system)
            d["replay"] = rows
            if args.json:
                full[name]["replay"] = rows
            for r in rows:
                if r["predicted"] is None:
                    print(f"  replay {r['op']} [{r['layout']}]: "
                          f"executed={r['executed']} ({r['note']})")
                else:
                    ok = "OK" if r["delta"] == r["expected_delta"] \
                        else "UNEXPECTED"
                    print(f"  replay {r['op']} [{r['layout']}]: "
                          f"predicted={r['predicted']} "
                          f"executed={r['executed']} "
                          f"delta={r['delta']:+d} "
                          f"(expected {r['expected_delta']:+d}) {ok}")
        artifact[name] = d
    if args.quick:
        from repro.artifacts import write_artifact

        path = os.path.join(_artifact_dir(), "plans.json")
        write_artifact(path, "plans", artifact,
                       generated_by="python -m repro plan --quick")
        print(f"\n# wrote per-workload plan summaries to {path}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(full, f, indent=1, sort_keys=True)
        print(f"# wrote full plans to {args.json}")
    return 0


def _build_sweep_spec(args):
    from repro.sweep import SweepSpec, iso_area_family

    widths = tuple(int(w) for w in args.widths.split(",") if w.strip())
    geometries = iso_area_family()
    if args.geometries:
        geometries = geometries[:args.geometries]
    return SweepSpec.default(
        workloads=args.workloads or None, widths=widths,
        geometries=geometries, n_override=args.n)


def cmd_sweep(args) -> int:
    from repro.sweep import cache_stats, guidelines, run_sweep

    spec = _build_sweep_spec(args)
    result = run_sweep(spec, use_cache=not args.no_cache)
    print(f"sweep: {result.breakdown.shape[0]} workloads x 2 layouts x "
          f"{len(spec.widths)} widths x {len(spec.geometries)} geometries "
          f"({result.summary()['grid_points']} grid points)")
    print(f"cache: {'hit' if result.cache['hit'] else 'miss'} "
          f"(key {result.cache['key']})")
    g = guidelines(result, include_hybrid=not args.no_hybrid)
    for name in sorted(g["crossover"]):
        c = g["crossover"][name]
        ws = "/".join(str(w) for w in c["bs_win_widths"]) or "-"
        print(f"  {name:20s} crossover_width={c['crossover_width']:<3d} "
              f"bs_wins={ws}")

    os.makedirs(_artifact_dir(), exist_ok=True)
    gpath = os.path.join(_artifact_dir(), "guidelines.json")
    with open(gpath, "w") as f:
        json.dump(g, f, indent=1, sort_keys=True)
    spath = os.path.join(_artifact_dir(), "sweep.json")
    with open(spath, "w") as f:
        json.dump({"spec": spec.to_dict(), "summary": result.summary(),
                   "cache": result.cache,
                   "cache_stats": cache_stats(),
                   "elapsed_s": result.elapsed_s}, f, indent=1,
                  sort_keys=True)
    print(f"# wrote {gpath} and {spath}")
    if args.json:
        full = {"guidelines": g, "totals": result.totals.tolist(),
                "breakdown": result.breakdown.tolist(),
                "bs_feasible": result.bs_feasible.tolist(),
                "bp_feasible": result.bp_feasible.tolist()}
        with open(args.json, "w") as f:
            json.dump(full, f, indent=1, sort_keys=True)
        print(f"# wrote full surfaces to {args.json}")
    return 0


def cmd_guidelines(args) -> int:
    from repro.sweep import guidelines, guidelines_lines

    g = guidelines(use_cache=not args.no_cache)
    print("# crossover table (paper geometry; "
          "workload crossover_width bs_win_widths)")
    for line in guidelines_lines(g):
        print(line)
    print("\n# derived rules")
    for rule in g["rules"]:
        print(f"- {rule}")
    os.makedirs(_artifact_dir(), exist_ok=True)
    gpath = os.path.join(_artifact_dir(), "guidelines.json")
    with open(gpath, "w") as f:
        json.dump(g, f, indent=1, sort_keys=True)
    print(f"\n# wrote {gpath}")
    return 0


def cmd_serve_bench(args) -> int:
    from repro.artifacts import ArtifactError, read_artifact, write_artifact
    from repro.core.params import PAPER_SYSTEM
    from repro.serve import check_regression, run_serve_bench

    n = args.requests if args.requests else (1024 if args.quick else 2048)
    system = (_parse_geometry(args.geometry) if args.geometry
              else PAPER_SYSTEM)

    # read the baseline BEFORE the run: the committed artifact and this
    # run's output default to the same path (CI gates in place)
    baseline = None
    if args.baseline:
        try:
            baseline = read_artifact(args.baseline, "serve")
        except FileNotFoundError:
            print(f"# no baseline at {args.baseline}; gate skipped")
        except ArtifactError as e:
            print(f"error: bad baseline: {e}", file=sys.stderr)
            return 2

    payload = run_serve_bench(
        n, seed=args.seed, sys=system,
        cache_dir=args.cache_dir or None, persist=not args.no_cache,
        max_batch=args.max_batch, execute_budget=args.execute_budget)

    cache = payload["cache"]
    exes = payload["executables"]
    comp, execu = payload["plan_compile_us"], payload["execute_us"]
    ecomp = payload["execute_compile_us"]
    print(f"serve-bench: {n} requests, "
          f"{payload['distinct_plans_bound']} distinct operating points, "
          f"{payload['batches']['count']} batches "
          f"({payload['batches']['signatures']} layout phases)")
    print(f"  plan cache: {cache['hits']}/{cache['lookups']} served "
          f"(hit_rate={cache['hit_rate']:.3f} mem={cache['mem_hits']} "
          f"disk={cache['disk_hits']} miss={cache['misses']} "
          f"evict={cache['evictions']})")
    print(f"  executables: {exes['entries']} compiled "
          f"(hit_rate={exes['hit_rate']:.3f}), "
          f"{exes['measured_steps']} measured / "
          f"{exes['modelled_steps']} modelled step(s) "
          f"@ budget {exes['execute_budget']} padded MACs")
    print(f"  plan compile: p50={comp['p50']:.0f}us p99={comp['p99']:.0f}us")
    print(f"  execute (warm Pallas): p50={execu['p50']:.0f}us "
          f"p99={execu['p99']:.0f}us; "
          f"exe compile: p50={ecomp['p50']:.0f}us p99={ecomp['p99']:.0f}us")
    print(f"  throughput: {payload['throughput_rps']:.0f} req/s; "
          f"transposes amortized: "
          f"{payload['simulated']['transpose_cycles_saved']} cycles saved")

    path = os.path.join(_artifact_dir(), "serve.json")
    write_artifact(path, "serve", payload,
                   generated_by="python -m repro serve-bench")
    print(f"# wrote {path}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote full payload to {args.json}")

    if baseline is not None:
        ok, msg = check_regression(payload, baseline,
                                   threshold=args.regress_threshold,
                                   floor_us=args.regress_floor_us)
        print(f"# regression gate: {msg} -> {'OK' if ok else 'FAIL'}")
        if not ok:
            return 3
    return 0


def cmd_pallas_bench(args) -> int:
    from repro.artifacts import ArtifactError, read_artifact, write_artifact
    from repro.kernels.bench import (check_pallas_regression,
                                     run_pallas_bench)

    # read the baseline BEFORE the run (the serve-bench idiom: committed
    # artifact and fresh output may point at the same path)
    baseline = None
    if args.baseline:
        try:
            baseline = read_artifact(args.baseline, "pallas")
        except FileNotFoundError:
            print(f"# no baseline at {args.baseline}; gate skipped")
        except ArtifactError as e:
            print(f"error: bad baseline: {e}", file=sys.stderr)
            return 2

    shapes = None
    if args.shape:
        from repro.kernels.bench import BENCH_SHAPES
        known = dict(BENCH_SHAPES)
        bad = [s for s in args.shape if s not in known]
        if bad:
            print(f"error: unknown shape(s) {bad}; "
                  f"known: {sorted(known)}", file=sys.stderr)
            return 2
        shapes = tuple((s, known[s]) for s in args.shape)

    payload = run_pallas_bench(quick=args.quick, reps=args.reps,
                               seed=args.seed, shapes=shapes,
                               chained=args.chained)
    print(f"pallas-bench: {len(payload['cases'])} cases, "
          f"reps={payload['reps']} quick={payload['quick']}")
    for c in payload["cases"]:
        if "shape" in c:
            m, k, n = c["shape"]
            print(f"  {c['name']:24s} {m}x{k}x{n} "
                  f"padded={'x'.join(map(str, c['padded']))} "
                  f"median_us={c['us']:.0f}")
        else:  # chained-vs-per-step pair rows (whole-schedule timings)
            print(f"  {c['name']:24s} steps={c['steps']} w{c['width']} "
                  f"median_us={c['us']:.0f}")
    for app, m in payload.get("chained", {}).items():
        if "skipped" in m:
            print(f"  chained {app}: skipped ({m['skipped']})")
        else:
            print(f"  chained {app}: x{m['speedup']:.2f} vs per-step "
                  f"({m['steps']} measured step(s), "
                  f"compile {m['compile_us'] / 1e3:.0f}ms)")

    path = args.out or os.path.join(_artifact_dir(), "BENCH_pallas.json")
    write_artifact(path, "pallas", payload,
                   generated_by="python -m repro pallas-bench"
                                + (" --quick" if args.quick else ""))
    print(f"# wrote {path}")

    if baseline is not None:
        ok, msg = check_pallas_regression(
            payload, baseline, threshold=args.regress_threshold,
            floor_us=args.regress_floor_us)
        print(f"# regression gate: {msg} -> {'OK' if ok else 'FAIL'}")
        if not ok:
            return 3
    return 0


def cmd_trace_diff(args) -> int:
    from repro.workloads.trace_diff import run_diff, write_csv

    archs = list(args.archs)
    if args.quick and not archs:
        archs = ["tinyllama_1_1b"]
    backends = [b.strip() for b in args.backends.split(",") if b.strip()]
    pallas_archs = [a.strip() for a in (args.pallas_archs or "").split(",")
                    if a.strip()]
    rows, fails = run_diff(
        archs or None, tokens=args.tokens, weight_bits=args.weight_bits,
        backends=backends, pallas_archs=pallas_archs,
        include_vgg=not args.no_vgg)
    for r in rows:
        if r.status == "total":
            print(f"{r.arch:28s} [{r.backend:8s}] "
                  f"formula bp={r.bp_formula:.0f} bs={r.bs_formula:.0f}  "
                  f"traced bp={r.bp_traced:.0f} bs={r.bs_traced:.0f} "
                  f"{r.unit} ({r.note})")
    n_exact = sum(1 for r in rows if r.status == "exact")
    n_div = sum(1 for r in rows if r.status == "divergent")
    n_extra = sum(1 for r in rows if r.status == "traced-only")
    print(f"# {n_exact} exact pairs, {n_div} documented-divergent pairs, "
          f"{n_extra} traced-only rows (x backends)")
    out = args.out or os.path.join(_artifact_dir(),
                                   "traced_vs_formula.csv")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    write_csv(rows, out)
    print(f"# wrote {len(rows)} rows to {out}")
    if fails:
        for f in fails:
            print(f"FAIL: {f}", file=sys.stderr)
        print(f"# gate: {len(fails)} unexplained delta(s)", file=sys.stderr)
        return 3
    print("# gate: every formula op matched, every traced op explained, "
          "exact pairs agree to the cycle")
    return 0


def cmd_machine_bench(args) -> int:
    from repro.artifacts import write_artifact
    from repro.machine.bench import run_machine_bench
    from repro.sweep import iso_area_family

    geometries = None
    if args.geometries:
        geometries = iso_area_family()[:args.geometries]
    mesh = None
    if not args.no_execute:
        from repro.machine.engine import default_mesh

        mesh = default_mesh()
    payload = run_machine_bench(
        args.workload, quick=args.quick, geometries=geometries,
        execute=not args.no_execute, mesh=mesh,
        run_diff=not args.no_diff)
    for pt in payload["curve"]:
        if "error" in pt:
            print(f"{pt['geometry']:>16s} arrays={pt['arrays']:<5d} "
                  f"infeasible: {pt['error']}")
            continue
        tag = "  [executed]" if pt["executed"] else ""
        print(f"{pt['geometry']:>16s} arrays={pt['arrays']:<5d} "
              f"classes={pt['classes']} total={pt['total_cycles']:>10d} "
              f"(compute={pt['compute_cycles']} "
              f"movement={pt['movement_cycles']} "
              f"transpose={pt['transpose_cycles']}) "
              f"planner={pt['planner_total']} "
              f"delta={pt['delta_total']:+d}{tag}")
    ex = payload["executed"]
    if ex:
        print(f"# executed {ex['arrays_simulated']} simulated arrays "
              f"across {ex['mesh_devices']} device(s) @ {ex['geometry']}; "
              f"{len(ex['programs'])} distinct micro-op programs")
        if ex["io"]:
            io = ex["io"]
            print(f"# io reconciliation ({io['program']}): model "
                  f"{io['model_io_bytes']} B vs HLO boundary "
                  f"{io['hlo_boundary_bytes']} B "
                  f"(x{io['ratio']:.1f} host-side)")
    path = os.path.join(_artifact_dir(), "machine.json")
    write_artifact(path, "machine", payload,
                   generated_by="python -m repro machine-bench")
    print(f"# wrote machine scaling curve to {path}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote full payload to {args.json}")
    if payload["gate_failures"]:
        for msg in payload["gate_failures"]:
            print(f"FAIL: {msg}", file=sys.stderr)
        print(f"# gate: {len(payload['gate_failures'])} unexplained "
              "divergence(s)", file=sys.stderr)
        return 3
    print("# gate: analytic, planner, machine, and executed totals "
          "reconcile; every delta itemized")
    return 0


def cmd_tables(args) -> int:
    del args
    from repro.core.paper_tables import golden_snapshot

    print(golden_snapshot(), end="")
    return 0


def main(argv=None) -> int:
    from repro.serve.batcher import DEFAULT_EXECUTE_BUDGET

    ap = argparse.ArgumentParser(prog="python -m repro", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_list = sub.add_parser("list", help="registered workloads and backends")
    p_list.add_argument("--source",
                        choices=("table5", "table6", "arch", "traced"),
                        default=None)
    p_list.set_defaults(fn=cmd_list)

    p_char = sub.add_parser(
        "characterize", help="run workloads through backends")
    p_char.add_argument("workloads", nargs="*",
                        help="registry names (e.g. vgg, mk/multu, "
                             "arch/tinyllama_1_1b)")
    p_char.add_argument("--backends", default=None,
                        help="comma list: analytic,planner,executor,pallas "
                             "(default analytic,planner; --quick adds "
                             "executor)")
    p_char.add_argument("--ops", action="store_true",
                        help="print per-op rows, not just summaries")
    p_char.add_argument("--quick", action="store_true",
                        help="CI smoke: all table5+table6 workloads, "
                             "summaries to bench-artifacts/characterize.json")
    p_char.add_argument("--json", default=None, metavar="PATH",
                        help="dump full reports (per-op rows) as JSON")
    p_char.add_argument("--geometry", default=None, metavar="RxCxA[@BW]",
                        help="system geometry rows x cols x arrays "
                             "(optional @row-bus-bits), e.g. 128x512x64")
    p_char.add_argument("--arrays", type=int, default=0, metavar="N",
                        help="override the geometry's array count (machine "
                             "scale from the single-array CLI surface)")
    p_char.set_defaults(fn=cmd_characterize)

    p_plan = sub.add_parser(
        "plan", help="compile workloads into executable layout plans")
    p_plan.add_argument("workloads", nargs="*",
                        help="registry names (e.g. aes, vgg, mk/multu)")
    p_plan.add_argument("--geometry", default=None, metavar="RxCxA[@BW]",
                        help="system geometry rows x cols x arrays "
                             "(optional @row-bus-bits), e.g. 128x512x64")
    p_plan.add_argument("--arrays", type=int, default=0, metavar="N",
                        help="override the geometry's array count (machine "
                             "scale from the single-array CLI surface)")
    p_plan.add_argument("--initial-layout", default=None,
                        choices=("BP", "BS"),
                        help="layout the data arrives in (charges the "
                             "arrival transpose)")
    p_plan.add_argument("--steps", action="store_true",
                        help="print per-step schedule rows")
    p_plan.add_argument("--execute", action="store_true",
                        help="replay executable ops on the micro-op "
                             "executor (predicted vs executed cycles)")
    p_plan.add_argument("--pallas", action="store_true",
                        help="lower the plan to a Pallas kernel schedule "
                             "and time each measured step (median wall-"
                             "clock over --reps launches)")
    p_plan.add_argument("--reps", type=int, default=5,
                        help="timing repetitions per --pallas step "
                             "(default 5)")
    p_plan.add_argument("--quick", action="store_true",
                        help="CI smoke: all table6 apps, summaries to "
                             "bench-artifacts/plans.json")
    p_plan.add_argument("--json", default=None, metavar="PATH",
                        help="dump full plans (steps + transposes) as JSON")
    p_plan.set_defaults(fn=cmd_plan)

    p_sweep = sub.add_parser(
        "sweep", help="design-space sweep over workload x width x geometry")
    p_sweep.add_argument("workloads", nargs="*",
                         help="mk/* workload names (default: all mk/*)")
    p_sweep.add_argument("--widths", default="4,8,16,32",
                         help="comma list of operand widths")
    p_sweep.add_argument("--geometries", type=int, default=0, metavar="N",
                         help="use only the first N iso-area geometries "
                              "(default: the full family)")
    p_sweep.add_argument("--n", type=int, default=None,
                         help="override every workload's element count")
    p_sweep.add_argument("--no-cache", action="store_true",
                         help="skip the sweep-cache (force re-evaluation)")
    p_sweep.add_argument("--no-hybrid", action="store_true",
                         help="skip the Table-6 planner hybrid-win pass")
    p_sweep.add_argument("--quick", action="store_true",
                         help="CI smoke mode (the default grid is already "
                              "one jitted call; kept for CI symmetry)")
    p_sweep.add_argument("--json", default=None, metavar="PATH",
                         help="dump the full cost surfaces as JSON")
    p_sweep.set_defaults(fn=cmd_sweep)

    p_guide = sub.add_parser(
        "guidelines", help="machine-derived layout guidelines")
    p_guide.add_argument("--no-cache", action="store_true",
                         help="skip the sweep-cache (force re-evaluation)")
    p_guide.set_defaults(fn=cmd_guidelines)

    p_serve = sub.add_parser(
        "serve-bench",
        help="replay the arch traffic mix through per-request plan "
             "compilation, the content-addressed plan cache, and "
             "phase-grouped batching")
    p_serve.add_argument("--requests", type=int, default=0, metavar="N",
                         help="simulated concurrent requests "
                              "(default 2048; --quick default 1024)")
    p_serve.add_argument("--seed", type=int, default=0,
                         help="traffic-mix sampling seed")
    p_serve.add_argument("--quick", action="store_true",
                         help="CI smoke: 1024 requests (unless --requests)")
    p_serve.add_argument("--geometry", default=None, metavar="RxCxA[@BW]",
                         help="system geometry rows x cols x arrays "
                              "(optional @row-bus-bits), e.g. 128x512x64")
    p_serve.add_argument("--max-batch", type=int, default=64,
                         help="continuous-batching slot budget per group")
    p_serve.add_argument("--execute-budget", type=int,
                         default=DEFAULT_EXECUTE_BUDGET, metavar="MACS",
                         help="padded-MAC budget per Pallas launch on the "
                              "execute path; over-budget steps stay "
                              "modelled-only rows (default "
                              f"{DEFAULT_EXECUTE_BUDGET})")
    p_serve.add_argument("--cache-dir", default=None, metavar="DIR",
                         help="plan-cache directory (default "
                              "<artifact-dir>/plan-cache)")
    p_serve.add_argument("--no-cache", action="store_true",
                         help="disable the disk tier of the plan cache")
    p_serve.add_argument("--baseline", default=None, metavar="PATH",
                         help="committed serve.json to gate p99 execute "
                              "latency against (read before this run's "
                              "artifact is written)")
    p_serve.add_argument("--regress-threshold", type=float, default=0.25,
                         help="p99 execute-latency regression budget "
                              "(fraction over baseline; default 0.25)")
    p_serve.add_argument("--regress-floor-us", type=float, default=250.0,
                         help="timer-noise floor: baselines are clamped "
                              "up to this before the ratio, so sub-floor "
                              "p99s never gate (default 250)")
    p_serve.add_argument("--json", default=None, metavar="PATH",
                         help="dump the full payload (pre-envelope) as JSON")
    p_serve.set_defaults(fn=cmd_serve_bench)

    p_pb = sub.add_parser(
        "pallas-bench",
        help="time the grid-tiled Pallas kernels over the full "
             "Table-5/6 matmul shapes (BP vs fused/unfused BS per "
             "width); writes + gates BENCH_pallas.json")
    p_pb.add_argument("--quick", action="store_true",
                      help="CI smoke: reps=2, widths {4,8,16}")
    p_pb.add_argument("--reps", type=int, default=None,
                      help="timing repetitions per case "
                           "(default 5; --quick default 2)")
    p_pb.add_argument("--seed", type=int, default=0,
                      help="operand sampling seed")
    p_pb.add_argument("--shape", action="append", default=[],
                      metavar="NAME",
                      help="restrict to named bench shape(s) (e.g. "
                           "gemv, vgg_fc_out); repeatable; default all")
    p_pb.add_argument("--chained", action="store_true",
                      help="also time chained-vs-per-step schedule "
                           "execution (ONE jitted program via "
                           "plan.pallas_exec vs host dispatch) for the "
                           "multi-step Table-6 apps")
    p_pb.add_argument("--out", default=None, metavar="PATH",
                      help="artifact path (default "
                           "<artifact-dir>/BENCH_pallas.json)")
    p_pb.add_argument("--baseline", default=None, metavar="PATH",
                      help="committed BENCH_pallas.json to gate per-case "
                           "medians against (read before this run's "
                           "artifact is written); exit 3 on regression")
    p_pb.add_argument("--regress-threshold", type=float, default=0.5,
                      help="per-case median regression budget "
                           "(fraction over baseline; default 0.5)")
    p_pb.add_argument("--regress-floor-us", type=float, default=2000.0,
                      help="timer-noise floor: baselines are clamped up "
                           "to this before the ratio, so sub-floor "
                           "medians never gate (default 2000)")
    p_pb.set_defaults(fn=cmd_pallas_bench)

    p_diff = sub.add_parser(
        "trace-diff",
        help="reconcile traced/<id> workloads against the arch/<id> "
             "formulas (differential gate + CSV artifact)")
    p_diff.add_argument("archs", nargs="*",
                        help="arch ids (e.g. tinyllama_1_1b; default: "
                             "every arch)")
    p_diff.add_argument("--tokens", type=int, default=4096,
                        help="decode batch / KV length (default 4096, the "
                             "arch/<id> operating point)")
    p_diff.add_argument("--weight-bits", type=int, default=4,
                        help="weight precision (default 4)")
    p_diff.add_argument("--backends", default="analytic,planner,executor",
                        help="comma list of static backends (default "
                             "analytic,planner,executor)")
    p_diff.add_argument("--pallas-archs", default=None, metavar="IDS",
                        help="comma list of archs to additionally time "
                             "on the Pallas tile backend (us, recorded "
                             "but never gated)")
    p_diff.add_argument("--no-vgg", action="store_true",
                        help="skip the traced-VGG-vs-vgg16 cross-check")
    p_diff.add_argument("--quick", action="store_true",
                        help="CI smoke: smallest arch (tinyllama_1_1b) "
                             "+ VGG")
    p_diff.add_argument("--out", default=None, metavar="PATH",
                        help="CSV path (default "
                             "<artifact-dir>/traced_vs_formula.csv)")
    p_diff.set_defaults(fn=cmd_trace_diff)

    p_mach = sub.add_parser(
        "machine-bench",
        help="compile + execute a Table-6 app across the iso-area machine "
             "axis (MachineSchedule IR; three-way differential gate)")
    p_mach.add_argument("--workload", default="traced/vgg16",
                        help="registry name to scale (default traced/vgg16)")
    p_mach.add_argument("--quick", action="store_true",
                        help="CI smoke: 3 geometries (1024/512/128 arrays) "
                             "and a reduced differential scope")
    p_mach.add_argument("--geometries", type=int, default=0, metavar="N",
                        help="use only the first N iso-area geometries "
                             "(widest machines first)")
    p_mach.add_argument("--no-execute", action="store_true",
                        help="skip the functional batched simulation "
                             "(static accounting only)")
    p_mach.add_argument("--no-diff", action="store_true",
                        help="skip the analytic/planner/executed "
                             "differential harness")
    p_mach.add_argument("--json", default=None, metavar="PATH",
                        help="dump the full payload (pre-envelope) as JSON")
    p_mach.set_defaults(fn=cmd_machine_bench)

    p_tab = sub.add_parser("tables", help="model-reproduced paper tables")
    p_tab.set_defaults(fn=cmd_tables)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro.util import use_compile_cache

    use_compile_cache()
    sys.exit(main())
