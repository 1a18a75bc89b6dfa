"""The paper's primary contribution: system & workload modeling plus
optimization-driven mapping/scheduling for the compute continuum."""

from repro.core.evaluator import (
    ObjectiveWeights,
    Schedule,
    evaluate_assignment,
    evaluate_population_batch,
    make_batched_fitness_fn,
)
from repro.core.api import (
    REGISTRY,
    AdaptationEvent,
    OrchestrationConfig,
    Orchestrator,
    Perturbation,
    Policy,
    PolicyRule,
    RunResult,
    Scenario,
    SolveReport,
    SolverCapabilities,
    SolverRegistry,
    compare_techniques,
    load_scenario,
    register_solver,
    run_scenario,
    scenario_from_json,
    solve,
    solve_problem,
    solve_problems,
)
from repro.core.system_model import (
    Cluster,
    DataCenter,
    Node,
    System,
    make_system,
    mri_system,
    synthetic_system,
    system_from_json,
    system_to_json,
)
from repro.core.validate import verify_schedule
from repro.core.workload_model import (
    ScheduleProblem,
    Task,
    Workflow,
    Workload,
    build_problem,
    montage_workflow,
    mri_w1,
    mri_w2,
    mri_workload,
    random_layered_workflow,
    synthetic_workload,
    testcase1_workloads,
    workload_from_json,
    workload_to_json,
)

__all__ = [
    "ALL_TECHNIQUES",
    "AdaptationEvent",
    "Cluster",
    "DataCenter",
    "Node",
    "ObjectiveWeights",
    "OrchestrationConfig",
    "Orchestrator",
    "Perturbation",
    "Policy",
    "PolicyRule",
    "REGISTRY",
    "RunResult",
    "Scenario",
    "Schedule",
    "ScheduleProblem",
    "SolveReport",
    "SolverCapabilities",
    "SolverRegistry",
    "System",
    "Task",
    "Workflow",
    "Workload",
    "build_problem",
    "compare_techniques",
    "load_scenario",
    "register_solver",
    "run_scenario",
    "scenario_from_json",
    "evaluate_assignment",
    "evaluate_population_batch",
    "make_batched_fitness_fn",
    "make_system",
    "montage_workflow",
    "mri_system",
    "mri_w1",
    "mri_w2",
    "mri_workload",
    "random_layered_workflow",
    "solve",
    "solve_problem",
    "solve_problems",
    "synthetic_system",
    "synthetic_workload",
    "system_from_json",
    "system_to_json",
    "testcase1_workloads",
    "verify_schedule",
    "workload_from_json",
    "workload_to_json",
]


def __getattr__(name: str):
    if name == "ALL_TECHNIQUES":
        # live view: includes techniques registered after package import
        from repro.core.api import REGISTRY as _reg

        return _reg.names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
