"""Execute a :class:`MachineSchedule` on the batched micro-op simulator.

The multi-array execution engine closes the last loop of the machine
model: every compute step of the executed (critical) partition class is
lowered to its ``pim.programs`` micro-op program in the *assigned*
layout and replayed functionally across **all** of the machine's
simulated arrays via ``pim.executor.run_batched`` (``jit(vmap(...))``),
with the leading array axis sharded over the ``repro.dist`` data mesh.

Cycle accounting (static -- micro-op charges are data-independent):

* ``kernel`` ops: ``program.cycles x batches`` at the class geometry,
  differenced against the analytic compute formula; the pair must show
  exactly the documented Sec.-8 calibration delta or the row is
  *unexplained* (the harness gate).
* ``matmul`` / ``conv`` ops: the ``multu`` + ``vector_add`` MAC
  decomposition (the ``ExecutorBackend`` / ``replay_plan`` route); the
  decomposition intentionally differs from the analytic chunked-tree
  pricing, so the row's delta is itemized as explained, never gated.
* ``compute`` ops carry hand-calibrated cycles with no micro-op
  lowering; executed == scheduled by definition.

Movement reconciliation: the schedule's charged bus traffic (model
bytes) is reported next to the HLO-boundary bytes
(``dist.hlo_bytes.boundary_bytes``) of the largest lowered batched
computation -- the two accountings price different machines (the shared
CSA row bus vs the simulating host's HBM), so the reconciliation is a
sanity ratio, not an equality gate.
"""
from __future__ import annotations

from repro.core.cost_model import Layout
from repro.machine.ir import MachineSchedule
from repro.sweep.grid import Geometry
from repro.workloads.ir import Workload


def _batches(layout: Layout, n: int, width: int, sys) -> int:
    return sys.bp_batches(n, width) if layout is Layout.BP \
        else sys.bs_batches(n)


def _compute_layout(placed) -> Layout:
    """The layout of a placed op's compute step (.mac for matmul/conv)."""
    lays = placed.layouts
    if placed.kind in ("matmul", "conv") and len(lays) == 3:
        return Layout(lays[1])
    return Layout(lays[0]) if lays else Layout.BP


def execute_schedule(schedule: MachineSchedule, workload: Workload, *,
                     functional: bool = True, mesh=None,
                     collect_hlo: bool = True) -> dict:
    """Execute the schedule's critical class across every array group.

    Returns the executed-vs-scheduled record::

        {"rows": [...], "programs": [...], "arrays_simulated": int,
         "mesh_devices": int, "scheduled_compute": int,
         "executed_compute": int, "unexplained": [...], "io": {...}}

    ``functional=False`` keeps the static program-cycle accounting but
    skips the jax array simulation (identical numbers, no jax work).
    ``mesh`` shards the leading array axis of every batched run; the
    array count is padded up to a device multiple when needed.  The
    simulated cells start from seeded random bits, and ``states`` holds
    each program's final :class:`pim.executor.ExecState` (device arrays,
    in ``programs`` order).
    """
    from repro.pim import programs as pr

    crit = schedule.classes[schedule.exec_class]
    sys_p = crit.geometry.system()
    ops_by_index = {op.name: op for op in workload.ops}

    rows: list[dict] = []
    unexplained: list[str] = []
    #: Program -> number of simulated arrays that run it (all classes)
    prog_arrays: dict = {}

    def note_program(prog, op_name: str) -> None:
        arrays = sum(c.groups * c.arrays_per_group for c in schedule.classes
                     if c.plan is not None
                     and any(p.op == op_name and p.cls == c.index
                             for p in schedule.placed))
        prog_arrays[prog] = max(prog_arrays.get(prog, 0), arrays)

    for placed in schedule.exec_placed():
        op = ops_by_index[placed.op]
        scheduled = placed.compute_cycles
        layout = _compute_layout(placed)
        if op.kind == "kernel":
            if (op.kernel, layout) not in pr.BUILDERS:
                rows.append({
                    "op": op.name, "kind": op.kind, "layout": layout.value,
                    "shard_n": placed.shard_n, "scheduled": scheduled,
                    "executed": scheduled, "delta": 0, "expected_delta": 0,
                    "note": "no micro-op program; analytic charge",
                    "explained": True})
                continue
            n_eff = (placed.shard_n if layout is Layout.BP
                     and op.kernel == "reduction" else None)
            prog = pr.build(op.kernel, layout, width=op.width, n=n_eff)
            note_program(prog, op.name)
            batches = _batches(layout, placed.shard_n, op.width, sys_p)
            predicted = pr.analytic_compute(
                op.kernel, layout, op.width, n=placed.shard_n) * batches
            executed = prog.cycles * batches
            expected = prog.expected_delta * batches
            ok = executed - predicted == expected
            if not ok:
                unexplained.append(
                    f"{op.name} [{layout.value}]: executed-predicted = "
                    f"{executed - predicted}, documented delta = {expected}")
            if predicted != scheduled:
                # the plan priced this step with the same analytic recipe;
                # a mismatch means the decomposition drifted -- gate it
                ok = False
                unexplained.append(
                    f"{op.name} [{layout.value}]: scheduled compute "
                    f"{scheduled} != analytic route {predicted}")
            rows.append({
                "op": op.name, "kind": op.kind, "layout": layout.value,
                "shard_n": placed.shard_n, "scheduled": scheduled,
                "executed": executed, "delta": executed - predicted,
                "expected_delta": expected,
                "note": prog.calibration_note or "exact",
                "explained": ok})
        elif op.kind in ("matmul", "conv"):
            outs = (op.m * placed.shard_n if op.kind == "matmul"
                    else placed.shard_n)
            mult = pr.build("multu", layout, width=op.width)
            add = pr.build("vector_add", layout, width=2 * op.width)
            note_program(mult, op.name)
            note_program(add, op.name)
            batches = _batches(layout, outs, op.width, sys_p)
            executed = (op.k * mult.cycles
                        + (op.k - 1) * add.cycles) * batches
            rows.append({
                "op": op.name, "kind": op.kind, "layout": layout.value,
                "shard_n": placed.shard_n, "scheduled": scheduled,
                "executed": executed, "delta": executed - scheduled,
                "expected_delta": executed - scheduled,
                "note": "MAC decomposition (multu + vector_add); priced "
                        "analytically as a chunked tree -- itemized, "
                        "not gated",
                "explained": True})
        else:   # compute / movement: no micro-op lowering
            rows.append({
                "op": op.name, "kind": op.kind,
                "layout": placed.layouts[0] if placed.layouts else "",
                "shard_n": placed.shard_n, "scheduled": scheduled,
                "executed": scheduled, "delta": 0, "expected_delta": 0,
                "note": "no micro-op lowering; hand-calibrated charge",
                "explained": True})

    result = {
        "rows": rows,
        "scheduled_compute": sum(r["scheduled"] for r in rows),
        "executed_compute": sum(r["executed"] for r in rows),
        "unexplained": unexplained,
        "arrays_simulated": 0,
        "mesh_devices": 1,
        "programs": [],
        "states": [],
        "io": None,
    }
    if functional and prog_arrays:
        result.update(_run_programs(prog_arrays, crit.geometry, mesh,
                                    collect_hlo))
    return result


# ---------------------------------------------------------------------------
# Functional batched execution (mesh-sharded jit+vmap)
# ---------------------------------------------------------------------------

def _run_programs(prog_arrays: dict, geometry: Geometry, mesh,
                  collect_hlo: bool) -> dict:
    import jax
    import numpy as np

    from repro.pim.executor import make_runner, run_batched

    n_dev = 1
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        n_dev = mesh.devices.size
        sharding = NamedSharding(mesh, P(mesh.axis_names[0], None, None))

    programs = []
    states = []
    arrays_simulated = 0
    # host-drawn bits: the same cells whatever the mesh, so a sharded run
    # is comparable bit for bit with a single-device one
    rng = np.random.default_rng(0)
    biggest = None
    for prog, arrays in sorted(prog_arrays.items(),
                               key=lambda kv: kv[0].key):
        n_arrays = arrays
        if n_dev > 1 and n_arrays % n_dev:
            n_arrays += n_dev - n_arrays % n_dev   # pad to device multiple
        # the functional replay needs the program's row footprint; the
        # geometry's column width is kept (feasibility is recorded on the
        # plan, not re-enforced by the simulator)
        cols = geometry.cols
        if prog.layout is Layout.BP and cols % prog.width:
            cols += prog.width - cols % prog.width
        cells = jax.device_put(
            rng.random((n_arrays, prog.rows, cols)) < 0.5, sharding)
        state = run_batched(prog, cells)
        jax.block_until_ready(state)
        states.append(state)
        arrays_simulated = max(arrays_simulated, n_arrays)
        programs.append({
            "name": prog.name, "layout": prog.layout.value,
            "width": prog.width, "cycles": prog.cycles,
            "arrays": n_arrays, "rows": prog.rows, "cols": cols})
        if biggest is None or n_arrays * prog.rows > \
                biggest[1].shape[0] * biggest[1].shape[1]:
            biggest = (prog, cells)

    io = None
    if collect_hlo and biggest is not None:
        prog, cells = biggest
        hlo = jax.jit(jax.vmap(make_runner(prog))).lower(cells)\
            .compile().as_text()
        from repro.dist.hlo_bytes import boundary_bytes

        n, rows, cols = cells.shape
        model = {
            "cells_in": n * rows * cols,            # bool = 1 byte
            "cells_out": n * rows * cols,
            "carry_out": n * cols,
            "acc_out": n * 4,
        }
        model_total = sum(model.values())
        hlo_total = boundary_bytes(hlo)
        io = {
            "program": prog.name,
            "model_io_bytes": model_total,
            "model_io_breakdown": model,
            "hlo_boundary_bytes": hlo_total,
            "ratio": (hlo_total / model_total) if model_total else 0.0,
        }
    return {"programs": programs, "states": states,
            "arrays_simulated": arrays_simulated,
            "mesh_devices": n_dev, "io": io}


def default_mesh():
    """A 1-D ``("data",)`` mesh over every local device, or None on a
    single device (``shard`` degrades to a no-op either way).

    Lived in ``serve.bench`` until PR 10; the serve execute path now
    runs compiled Pallas schedules (single-program, no mesh reduction),
    so machine-bench owns the mesh helper."""
    import jax

    devs = jax.devices()
    if len(devs) < 2:
        return None
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devs), ("data",))
