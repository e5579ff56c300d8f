"""Continuous batching of decode steps by shared layout phase.

Phase-grouping rule (DESIGN.md Sec. 11): two requests batch together iff
their compiled plans have the *identical per-step layout sequence*
(``CompiledRequest.signature``).  Members of a group are then in the same
layout at every boundary, so each boundary transpose runs **once per
group** on the shared transpose unit -- the batch stages every member's
operands through the same read(M)+core+write(N) pass -- instead of once
per request.  The amortized charge is the widest member's transpose total
(``max``), and the saving is ``sum - max``.

Simulated accounting (exact, host integers):

* ``latency_cycles``  = max member compute + amortized transposes
  (members decode in parallel across the machine's arrays);
* ``machine_cycles``  = sum member compute + amortized transposes
  (the throughput/occupancy charge).

``execute`` runs each group through the *measured Pallas path*: the
group's representative plan (the member whose schedule measures the most
padded MACs under ``execute_budget``; ties break toward the widest plan,
the group's latency bound) lowers to a
:class:`repro.plan.pallas.PallasSchedule` and compiles to ONE jitted
device program
(``plan.pallas_exec.compile_schedule``; weights device-resident, step
outputs threaded, repacks in-program).  The warm wall-clock of that
program is serve-bench's per-request execute latency; compile cost is
charged separately (``execute_compile_us``, zero on an executable-cache
hit) so the p99 gate sees the steady state.  Until PR 10 this was an
analytic float32 cycle reduction -- a proxy, not the kernels.

Ops the budget refuses (interpret mode is ~10^8 MAC/s; serving shapes
can exceed any honest window) stay modelled-only rows per the DESIGN.md
Sec. 14 contract -- the row reports ``measured_steps``/``modelled_steps``
so the artifact says exactly how much of each plan was run vs modelled.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

from repro.serve.service import CompiledRequest

#: default padded-MAC budget per serve-side kernel launch: admits the
#: short-context attention/classifier matmuls (a warm chained program is
#: tens of ms in interpret mode) while refusing the multi-second
#: long-context GEMMs -- honest refusal, never silent clamping
DEFAULT_EXECUTE_BUDGET = 2 ** 28


@dataclasses.dataclass
class BatchGroup:
    """Requests whose plans share one layout-phase signature."""

    signature: tuple[str, ...]
    members: list[CompiledRequest]

    #: warm wall-clock of the compiled schedule (filled by ``execute``)
    execute_us: Optional[float] = None
    #: executable compile cost (0.0 on an executable-cache hit)
    execute_compile_us: Optional[float] = None

    @property
    def size(self) -> int:
        return len(self.members)

    # ------------------------------------------------- exact host totals
    def member_compute_cycles(self) -> list[int]:
        """Per-member assigned-layout cycles, transposes excluded."""
        return [sum(s.cycles for s in m.plan.steps) for m in self.members]

    def member_transpose_cycles(self) -> list[int]:
        return [m.plan.transpose_cycles_total for m in self.members]

    @property
    def amortized_transpose_cycles(self) -> int:
        """One shared pass per boundary, sized by the widest member."""
        return max(self.member_transpose_cycles(), default=0)

    @property
    def transpose_cycles_saved(self) -> int:
        tr = self.member_transpose_cycles()
        return sum(tr) - (max(tr) if tr else 0)

    @property
    def latency_cycles(self) -> int:
        return max(self.member_compute_cycles(), default=0) \
            + self.amortized_transpose_cycles

    @property
    def machine_cycles(self) -> int:
        return sum(self.member_compute_cycles()) \
            + self.amortized_transpose_cycles


class PhaseBatcher:
    """Group compiled requests by layout-phase signature and execute
    each group as one compiled Pallas schedule (module doc).

    ``executables`` is the content-addressed executable cache shared
    across groups (constructed on demand); ``execute_budget`` is the
    per-launch padded-MAC budget passed to ``lower_plan_pallas``."""

    def __init__(self, max_batch: int = 64,
                 execute_budget: int = DEFAULT_EXECUTE_BUDGET,
                 executables=None, seed: int = 0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1 (got {max_batch})")
        self.max_batch = max_batch
        self.execute_budget = execute_budget
        self.seed = seed
        self._executables = executables

    @property
    def executables(self):
        if self._executables is None:
            from repro.plan.pallas_exec import ExecutableCache

            self._executables = ExecutableCache()
        return self._executables

    # ------------------------------------------------------------- group
    def group(self, compiled: Sequence[CompiledRequest]
              ) -> list[BatchGroup]:
        """Stable grouping: arrival order within a group is preserved and
        groups emit in first-arrival order; oversize groups split at
        ``max_batch`` (the continuous-batching slot budget)."""
        by_sig: dict[tuple[str, ...], list[CompiledRequest]] = {}
        for c in compiled:
            by_sig.setdefault(c.signature, []).append(c)
        out = []
        for sig, members in by_sig.items():
            for i in range(0, len(members), self.max_batch):
                out.append(BatchGroup(signature=sig,
                                      members=members[i:i + self.max_batch]))
        return out

    # ----------------------------------------------------------- execute
    def execute(self, group: BatchGroup, warmup: bool = True) -> dict:
        """Run the group's representative plan as one compiled Pallas
        schedule; record warm wall-clock + compile cost on the group.

        The representative is the member whose lowered schedule measures
        the MOST padded MACs under ``execute_budget`` -- the heaviest
        program the budget can honestly time (DESIGN.md Sec. 14 refuses
        over-budget steps, so the widest member of a mixed-token group
        usually lowers to all-modelled rows; picking it would "measure"
        an empty program).  Ties break toward the largest planned cycle
        total, the group's latency bound.  Exact cycle totals in the
        returned row still come from the host integers (the simulated
        accounting is layout math, not wall-clock).
        """
        from repro.plan.pallas import lower_plan_pallas, mxu_passes

        def measurable_macs(sched) -> int:
            total = 0
            for s in sched.measured_steps:
                m_p, k_p, n_p = s.padded_dims
                total += m_p * k_p * n_p * mxu_passes(s.layout, s.width)
            return total

        rep, sched, best = None, None, (-1, -1)
        for m in group.members:
            cand_sched = lower_plan_pallas(m.plan, m.workload,
                                           max_macs=self.execute_budget)
            cand = (measurable_macs(cand_sched), m.plan.total_cycles)
            if cand > best:
                rep, sched, best = m, cand_sched, cand
        exe, key, hit = self.executables.get_or_compile(
            sched, seed=self.seed)
        if warmup:  # steady-state: warm outside the timed window
            exe.run()
        t0 = time.perf_counter()
        exe.run()
        group.execute_us = (time.perf_counter() - t0) * 1e6
        group.execute_compile_us = 0.0 if hit else exe.compile_us

        return {
            "size": group.size,
            "execute_us": group.execute_us,
            "execute_compile_us": group.execute_compile_us,
            "executable_key": key,
            "executable_hit": hit,
            "representative": rep.request.arch,
            "measured_steps": exe.n_measured,
            "modelled_steps": exe.n_modelled,
            "latency_cycles": group.latency_cycles,
            "machine_cycles": group.machine_cycles,
            "transpose_cycles_saved": group.transpose_cycles_saved,
        }

    def run(self, compiled: Sequence[CompiledRequest]
            ) -> tuple[list[BatchGroup], list[dict]]:
        groups = self.group(compiled)
        return groups, [self.execute(g) for g in groups]
