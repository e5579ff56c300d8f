"""Compile a Workload DAG into a :class:`LayoutPlan`.

Two exact solvers behind one entry point (:func:`compile_plan`):

* **Linear chains** (every registered workload today) run the 2-state
  Viterbi DP -- the direct generalization of the legacy
  ``core.planner.plan`` loop, with identical iteration order and
  tie-breaking (BP preferred on equal cost), so plans over chains are
  bit-for-bit the legacy schedules (property-pinned in
  tests/test_plan.py).
* **General DAGs** (a workload with explicit ``deps`` edges) run an exact
  s-t min-cut: a 2-label assignment with direction-symmetric boundary
  costs (``transpose_cycles`` charges read+core+write both ways) is a
  binary submodular labeling, so max-flow gives the true optimum --
  verified against a 2^n brute-force oracle in tests/test_plan.py.

Switch-cost model (unchanged from the legacy DP): entering step *v* in a
layout different from its predecessor's charges
``transpose_cycles(v.rows_bp, v.rows_bs, direction)`` -- the *consumer*
step's footprint is what the on-chip transpose unit feeds and drains.
``initial_layout`` charges the same cost at every root step whose
assigned layout differs from the arrival layout.

Geometry feasibility: each step is checked against ``Geometry.rows`` --
Table-5 kernels via the ``live_words`` row model
(``SystemParams.bs_rows_required`` / ``bp_rows_required``), other ops via
their declared ``rows_bp``/``rows_bs`` footprint.  By default the verdict
is *recorded* on the plan (``LayoutPlan.feasible`` and per-step flags;
the cost model already charges explicit spill ops where the paper's
workloads overflow); ``enforce_feasibility=True`` turns it into a hard
constraint -- infeasible layouts are excluded from the search, and
:class:`PlanError` is raised when a step fits in neither layout.
"""
from __future__ import annotations

from collections import deque
from typing import Optional, Sequence

from repro import spans
from repro.core.cost_model import Layout
from repro.core.params import SystemParams, PAPER_SYSTEM
from repro.core.transpose import transpose_cycles
from repro.plan.ir import LayoutPlan, PlanStep, TransposeStep
from repro.sweep.grid import Geometry


class PlanError(ValueError):
    """No feasible layout assignment exists under the constraints."""


# ---------------------------------------------------------------------------
# Internal node form (one schedulable step before layout assignment)
# ---------------------------------------------------------------------------

class _Node:
    __slots__ = ("bp", "bs", "rows_bp", "rows_bs", "bp_ok", "bs_ok")

    def __init__(self, bp, bs, rows_bp, rows_bs, bp_ok=True, bs_ok=True):
        self.bp, self.bs = int(bp), int(bs)
        self.rows_bp, self.rows_bs = rows_bp, rows_bs
        self.bp_ok, self.bs_ok = bp_ok, bs_ok

    def cost(self, layout: Layout) -> int:
        return self.bp if layout is Layout.BP else self.bs

    def switch_cost(self, sys: SystemParams) -> int:
        # read + core + write; transpose_cycles is direction-symmetric in
        # total, so one weight serves both boundary orientations
        return transpose_cycles(self.rows_bp, self.rows_bs, "bp2bs", sys)


_LAYOUTS = (Layout.BP, Layout.BS)


def _unary(node: _Node, inf: int, enforce: bool) -> tuple[int, int]:
    bp = node.bp if (node.bp_ok or not enforce) else inf
    bs = node.bs if (node.bs_ok or not enforce) else inf
    return bp, bs


# ---------------------------------------------------------------------------
# Chain solver (the legacy 2-state DP, verbatim semantics)
# ---------------------------------------------------------------------------

def _solve_chain(nodes: Sequence[_Node], sys: SystemParams,
                 initial_layout: Optional[Layout],
                 inf: int, enforce: bool) -> list[Layout]:
    first = nodes[0]
    cost = {}
    back: list[dict[Layout, Layout]] = []
    for lay in _LAYOUTS:
        c = _unary(first, inf, enforce)[0 if lay is Layout.BP else 1]
        if initial_layout is not None and initial_layout != lay:
            c += first.switch_cost(sys)
        cost[lay] = c
    for i in range(1, len(nodes)):
        nd = nodes[i]
        u_bp, u_bs = _unary(nd, inf, enforce)
        sw = nd.switch_cost(sys)
        new_cost, back_i = {}, {}
        for lay in _LAYOUTS:
            u = u_bp if lay is Layout.BP else u_bs
            best, best_prev = None, None
            for prev in _LAYOUTS:
                c = cost[prev] + (0 if prev == lay else sw) + u
                if best is None or c < best:
                    best, best_prev = c, prev
            new_cost[lay] = best
            back_i[lay] = best_prev
        cost = new_cost
        back.append(back_i)
    end = min(_LAYOUTS, key=lambda lay: cost[lay])
    sched = [end]
    for back_i in reversed(back):
        sched.append(back_i[sched[-1]])
    sched.reverse()
    return sched


# ---------------------------------------------------------------------------
# DAG solver (exact binary labeling via s-t min-cut / Edmonds-Karp)
# ---------------------------------------------------------------------------

def _solve_dag(nodes: Sequence[_Node], edges: Sequence[tuple[int, int]],
               sys: SystemParams, initial_layout: Optional[Layout],
               inf: int, enforce: bool) -> list[Layout]:
    n = len(nodes)
    s, t = n, n + 1
    cap: list[dict[int, int]] = [dict() for _ in range(n + 2)]

    def add(u, v, c):
        if c <= 0:
            return
        cap[u][v] = cap[u].get(v, 0) + c
        cap[v].setdefault(u, 0)

    has_pred = set(v for _, v in edges)
    for v, nd in enumerate(nodes):
        u_bp, u_bs = _unary(nd, inf, enforce)
        if initial_layout is not None and v not in has_pred:
            # arrival-layout switch folded into the root's unary costs
            sw = nd.switch_cost(sys)
            if initial_layout is Layout.BS:
                u_bp += sw
            else:
                u_bs += sw
        add(s, v, u_bs)   # cut when v labeled BS (v on the sink side)
        add(v, t, u_bp)   # cut when v labeled BP (v on the source side)
    for u, v in edges:
        w = nodes[v].switch_cost(sys)
        add(u, v, w)
        add(v, u, w)

    # Edmonds-Karp: BFS augmenting paths on the residual graph
    while True:
        parent = {s: s}
        q = deque([s])
        while q and t not in parent:
            u = q.popleft()
            for v, c in cap[u].items():
                if c > 0 and v not in parent:
                    parent[v] = u
                    q.append(v)
        if t not in parent:
            break
        # bottleneck along the path
        bott, v = None, t
        while v != s:
            u = parent[v]
            c = cap[u][v]
            bott = c if bott is None else min(bott, c)
            v = u
        v = t
        while v != s:
            u = parent[v]
            cap[u][v] -= bott
            cap[v][u] += bott
            v = u

    # source side of the cut = BP
    seen = {s}
    q = deque([s])
    while q:
        u = q.popleft()
        for v, c in cap[u].items():
            if c > 0 and v not in seen:
                seen.add(v)
                q.append(v)
    return [Layout.BP if v in seen else Layout.BS for v in range(n)]


# ---------------------------------------------------------------------------
# Assembly shared by both solvers
# ---------------------------------------------------------------------------

def _assemble(nodes: Sequence[_Node], labels: Sequence[Layout],
              edges: Sequence[tuple[int, int]], sys: SystemParams,
              initial_layout: Optional[Layout]):
    """(transposes, total, static_bp, static_bs) for a solved labeling."""
    transposes = []
    has_pred = set(v for _, v in edges)
    for v, lay in enumerate(labels):
        if v not in has_pred and initial_layout is not None \
                and lay != initial_layout:
            direction = "bp2bs" if lay is Layout.BS else "bs2bp"
            transposes.append(TransposeStep(
                before_step=v, direction=direction,
                cycles=transpose_cycles(nodes[v].rows_bp, nodes[v].rows_bs,
                                        direction, sys)))
    for u, v in edges:
        if labels[u] != labels[v]:
            direction = "bp2bs" if labels[v] is Layout.BS else "bs2bp"
            transposes.append(TransposeStep(
                before_step=v, direction=direction,
                cycles=transpose_cycles(nodes[v].rows_bp, nodes[v].rows_bs,
                                        direction, sys)))
    transposes.sort(key=lambda tr: tr.before_step)
    total = sum(nd.cost(lay) for nd, lay in zip(nodes, labels)) \
        + sum(tr.cycles for tr in transposes)

    static_bp = sum(nd.bp for nd in nodes)
    static_bs = sum(nd.bs for nd in nodes)
    roots = [v for v in range(len(nodes)) if v not in has_pred]
    if initial_layout is Layout.BS:
        static_bp += sum(nodes[v].switch_cost(sys) for v in roots)
    if initial_layout is Layout.BP:
        static_bs += sum(nodes[v].switch_cost(sys) for v in roots)
    return tuple(transposes), total, static_bp, static_bs


def _solve(nodes, edges, sys, initial_layout, enforce):
    if enforce:
        for i, nd in enumerate(nodes):
            if not (nd.bp_ok or nd.bs_ok):
                raise PlanError(
                    f"step {i} fits the geometry in neither layout "
                    f"(rows_bp={nd.rows_bp}, rows_bs={nd.rows_bs}, "
                    f"array rows={sys.array.rows})")
    # the infeasibility sentinel must exceed ANY genuine assignment cost:
    # every unary plus a boundary switch per edge (a node with in-degree
    # > 1 can be charged its switch cost once per incoming edge) plus the
    # arrival switch at every root
    has_pred = set(v for _, v in edges)
    inf = 1 + sum(nd.bp + nd.bs for nd in nodes) \
        + sum(nodes[v].switch_cost(sys) for _, v in edges) \
        + sum(nd.switch_cost(sys) for v, nd in enumerate(nodes)
              if v not in has_pred)
    is_chain = list(edges) == [(i, i + 1) for i in range(len(nodes) - 1)]
    if is_chain:
        labels = _solve_chain(nodes, sys, initial_layout, inf, enforce)
    else:
        labels = _solve_dag(nodes, edges, sys, initial_layout, inf, enforce)
    if enforce:
        for i, (nd, lay) in enumerate(zip(nodes, labels)):
            ok = nd.bp_ok if lay is Layout.BP else nd.bs_ok
            if not ok:  # unreachable with a correct sentinel; hard guard
                raise PlanError(
                    f"solver assigned step {i} an infeasible layout "
                    f"({lay.value}) under enforce_feasibility")
    return labels


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def solve_phases(phases, sys: SystemParams = PAPER_SYSTEM,
                 initial_layout: Optional[Layout] = None):
    """Chain-solve a legacy ``core.planner.Phase`` list.

    The compatibility route ``core.planner.plan`` shims over; returns
    ``(labels, transposes, total, static_bp, static_bs)``.
    """
    nodes = [_Node(p.bp_cycles, p.bs_cycles, p.rows_bp, p.rows_bs)
             for p in phases]
    edges = [(i, i + 1) for i in range(len(nodes) - 1)]
    labels = _solve(nodes, edges, sys, initial_layout, enforce=False)
    transposes, total, st_bp, st_bs = _assemble(
        nodes, labels, edges, sys, initial_layout)
    return labels, transposes, total, st_bp, st_bs


def _step_feasibility(op, sys: SystemParams) -> tuple[bool, bool]:
    """(bp fits, bs fits) under the geometry's row budget.

    Table-5 kernels use the live-words row model the sweep feasibility
    masks use (DESIGN.md Sec. 9); other op kinds use their declared
    planner footprint.
    """
    if op.kind == "kernel":
        from repro.core.microkernels import MICROKERNELS

        lw = MICROKERNELS[op.kernel].live_words
        return (sys.bp_rows_required(lw) <= sys.array.rows,
                sys.bs_rows_required(lw, op.width) <= sys.array.rows)
    return op.rows_bp <= sys.array.rows, op.rows_bs <= sys.array.rows


@spans.span("plan.compile")
def compile_plan(workload, sys: SystemParams = PAPER_SYSTEM, *,
                 geometry: Optional[Geometry] = None,
                 initial_layout: Optional[Layout] = None,
                 enforce_feasibility: bool = False) -> LayoutPlan:
    """Compile a Workload (DAG) into an executable :class:`LayoutPlan`.

    ``geometry`` overrides ``sys`` with ``geometry.system()``; the plan
    records the geometry it was compiled against either way.
    """
    if geometry is not None:
        sys = geometry.system()
    from repro.workloads.ir import op_phases

    nodes: list[_Node] = []
    meta: list[tuple[int, str, str, str, bool, bool]] = []
    edges: list[tuple[int, int]] = []
    op_first: list[int] = []
    op_last: list[int] = []
    for oi, op in enumerate(workload.ops):
        bp_ok, bs_ok = _step_feasibility(op, sys)
        first = len(nodes)
        for ph in op_phases(op, sys):
            meta.append((oi, op.name, ph.name, op.kind, bp_ok, bs_ok))
            nodes.append(_Node(ph.bp_cycles, ph.bs_cycles,
                               ph.rows_bp, ph.rows_bs, bp_ok, bs_ok))
        op_first.append(first)
        op_last.append(len(nodes) - 1)
        # phases within an op are a dependent sub-chain
        edges.extend((i, i + 1) for i in range(first, len(nodes) - 1))
    for a, b in workload.edges():
        edges.append((op_last[a], op_first[b]))
    edges.sort()

    labels = _solve(nodes, edges, sys, initial_layout,
                    enforce=enforce_feasibility)
    transposes, total, st_bp, st_bs = _assemble(
        nodes, labels, edges, sys, initial_layout)

    steps = tuple(
        PlanStep(index=i, op_index=m[0], op=m[1], phase=m[2], kind=m[3],
                 layout=labels[i], bp_cycles=nd.bp, bs_cycles=nd.bs,
                 rows_bp=nd.rows_bp, rows_bs=nd.rows_bs,
                 bp_feasible=m[4], bs_feasible=m[5])
        for i, (nd, m) in enumerate(zip(nodes, meta)))
    return LayoutPlan(
        workload=workload.name, geometry=Geometry.from_system(sys),
        steps=steps, transposes=transposes, total_cycles=total,
        static_bp=st_bp, static_bs=st_bs, initial_layout=initial_layout)
