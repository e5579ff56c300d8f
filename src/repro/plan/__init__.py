"""repro.plan: layout plans as a first-class, executable IR.

Public surface (see README.md in this directory and DESIGN.md Sec. 10)::

    from repro.plan import (
        LayoutPlan, PlanStep, TransposeStep,   # the plan IR
        compile_plan, PlanError,               # Workload DAG -> plan
        plan_programs, replay_plan,            # lowering + executor replay
    )

    p = compile_plan(get_workload("aes"))
    p.total_cycles, p.op_schedule(), p.feasible
    replay_plan(p, get_workload("aes"))        # predicted vs executed

    from repro.plan import lower_plan_pallas, run_schedule
    sched = lower_plan_pallas(p, get_workload("aes"))   # measured twin
    run_schedule(sched, synth_inputs(sched))            # per-step mode

    from repro.plan import compile_schedule              # chained mode
    exe = compile_schedule(sched)   # ONE jitted program, weights resident
    exe.run()                       # warm: one call of the program
    spans.last("schedule.run")      # its time (``repro.spans``)

CLI: ``python -m repro plan <workload> [--geometry RxCxA] [--execute]
[--pallas]``.
"""
from repro.plan.ir import (  # noqa: F401
    LayoutPlan,
    PlanStep,
    TransposeStep,
)
from repro.plan.lower import (  # noqa: F401
    plan_programs,
    replay_matches,
    replay_plan,
    step_program,
)
from repro.plan.pallas import (  # noqa: F401
    PallasSchedule,
    PallasStep,
    lower_plan_pallas,
    reference_results,
    run_schedule,
    synth_inputs,
    time_schedule,
)
from repro.plan.pallas_exec import (  # noqa: F401
    ExecutableCache,
    ScheduleExecutable,
    compile_schedule,
    schedule_key,
)
from repro.plan.scheduler import (  # noqa: F401
    PlanError,
    compile_plan,
    solve_phases,
)
