"""Schedule evaluation — the paper's timing model (Eq. 4–6) made executable.

Semantics (shared by every solver technique so results are comparable):

*capacity-aware core-granular list scheduling*: each node ``i`` owns
``R_i^1`` cores, each with its own free time.  A task ``j`` assigned to node
``i`` becomes *ready* at

    ready_j = max(release_j, max_{j' ∈ preds(j)} f_{j'} + d_t(j'→j))    (Eq. 12)

with the data-migration term of Eq. (5)

    d_t(j'→j) = R^3_{j'} / P^3_{a(j'), a(j)}   if a(j') ≠ a(j) else 0,

then starts at the earliest time ≥ ready_j when ``R^1_j`` cores are free and
occupies them for ``d_{ij}`` (Eq. 4).  Co-running under the core capacity is
allowed — this is required to reproduce the paper's Table VI optimum, where
W1/T2 and W2/T3 overlap on node N2 (12 + 32 ≤ 48 cores).

Execution itself lives one layer down, in :mod:`repro.engine`:

* :func:`evaluate_assignment` (here) wraps the ``oracle`` backend — the one
  incremental simulator in :mod:`repro.engine.sim` (ground truth for tests),
* :func:`make_fitness_fn` routes through the engine registry
  (:mod:`repro.engine.backends`): ``jax`` (shared jitted rank-select
  evaluator) or ``pallas`` (the TPU kernel), both bit-for-bit equal to the
  f32 oracle,
* the batched multi-instance API (:func:`make_batched_fitness_fn`,
  :func:`evaluate_population_batch`) pads instances into power-of-two shape
  buckets (one canonical :class:`repro.engine.packed.PackedProblem` per
  instance, memoized) and ``vmap``s across them — at most one XLA compile
  per bucket, ever.

The four packing helpers this module used to own moved to
``repro.engine.packed``; their old names remain importable here as
deprecation shims (PEP 562) that warn on access.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Sequence

import numpy as np

from repro.core.workload_model import BIG_PENALTY, ScheduleProblem
from repro.engine.packed import FITNESS_ARRAY_KEYS  # noqa: F401  (re-export)
from repro.engine.sim import commit_sorted, run_schedule  # noqa: F401  (re-export)


@dataclasses.dataclass(frozen=True)
class ObjectiveWeights:
    """Weights of the multi-objective function (Eq. 8):
    ``min α · Σ U_ij x_ij + β · C_max``."""

    alpha: float = 1.0
    beta: float = 1.0
    usage_mode: str = "fixed"  # "fixed" (U_j = R_j) | "weighted" (Eq. 3)


@dataclasses.dataclass
class Schedule:
    """Solver output — the Fig. 4 step-3 artifact (mapping + timing)."""

    assignment: np.ndarray  # [T] node index per task
    start: np.ndarray  # [T]
    finish: np.ndarray  # [T]
    makespan: float
    usage: float
    objective: float
    violations: int
    technique: str = ""
    solve_time: float = 0.0
    status: str = "feasible"

    def to_json(self, problem: ScheduleProblem, node_names: list[str] | None = None) -> dict:
        """Sorted schedule JSON for the executor (paper Fig. 4, step 3)."""
        order = np.argsort(self.start, kind="stable")
        entries = []
        for j in order:
            entries.append(
                {
                    "workflow": problem.workflow_names[problem.workflow_of[j]],
                    "task": problem.task_names[j],
                    "node": int(self.assignment[j])
                    if node_names is None
                    else node_names[int(self.assignment[j])],
                    "start": float(self.start[j]),
                    "end": float(self.finish[j]),
                }
            )
        return {
            "status": self.status,
            "technique": self.technique,
            "makespan": float(self.makespan),
            "resource_usage": float(self.usage),
            "objective": float(self.objective),
            "schedule": entries,
        }


def _usage_of(problem: ScheduleProblem, assignment: np.ndarray, weights: ObjectiveWeights) -> float:
    if weights.usage_mode == "weighted":
        u = problem.weighted_usage()
        return float(u[np.arange(problem.num_tasks), assignment].sum())
    return float(problem.usage.sum())


def constraint_violations(
    problem: ScheduleProblem,
    assignment: np.ndarray,
    finish: np.ndarray,
    *,
    dtype=np.float64,
) -> int:
    """Hard-constraint violation count for a timed schedule.

    Counts (a) tasks finishing past their deadline and (b) workflows whose
    total cost exceeds their budget.  With ``dtype=np.float32`` the
    comparisons use the same f32 quantities as the jax/pallas penalty terms
    (deadline lateness inside the makespan kernel, budget overage in the
    fitness objective), keeping the f32 backends' penalized objectives
    bit-identical to this oracle."""
    extra = 0
    if problem.deadline is not None:
        fin = np.asarray(finish, dtype=dtype)
        extra += int(np.sum(fin > problem.deadline.astype(dtype)))
    if problem.budget is not None:
        cost = problem.cost_matrix().astype(dtype)
        cost_t = cost[np.arange(problem.num_tasks), np.asarray(assignment, dtype=np.int64)]
        w_count = len(problem.workflow_names)
        mask = problem.workflow_of[None, :] == np.arange(w_count, dtype=np.int64)[:, None]
        wf_cost = np.sum(np.where(mask, cost_t[None, :], dtype(0)), axis=1)
        extra += int(np.sum(wf_cost > problem.budget.astype(dtype)))
    return extra


def evaluate_assignment(
    problem: ScheduleProblem,
    assignment: np.ndarray,
    weights: ObjectiveWeights = ObjectiveWeights(),
    technique: str = "",
    *,
    dtype=np.float64,
) -> Schedule:
    """Numpy oracle. ``assignment[j]`` = node index for topo-ordered task j.

    Timing comes from the one incremental simulator
    (:func:`repro.engine.sim.run_schedule`): sorted core-free rows (O(1)
    "earliest time c cores are free", O(cap) merge-insert commit) walking a
    CSR view of the dependency DAG.

    ``dtype=np.float32`` evaluates with f32 arithmetic in the same operation
    order as the JAX evaluator / Pallas kernel — bit-for-bit identical
    makespans (the equivalence-sweep tests rely on this).
    """
    assignment = np.asarray(assignment, dtype=np.int64)
    start, finish, violations = run_schedule(problem, assignment, dtype=dtype)
    if problem.has_constraints:
        violations = int(violations) + constraint_violations(
            problem, assignment, finish, dtype=dtype
        )
    makespan = float(finish.max(initial=0.0))
    usage = _usage_of(problem, assignment, weights)
    objective = weights.alpha * usage + weights.beta * makespan + BIG_PENALTY * violations
    return Schedule(
        assignment=assignment,
        start=start,
        finish=finish,
        makespan=makespan,
        usage=usage,
        objective=objective,
        violations=violations,
        technique=technique,
    )


# -----------------------------------------------------------------------------
# population / batched fitness — thin forwards into the engine registry
# -----------------------------------------------------------------------------


def fitness_from_arrays(assignments, arrays: dict, alpha, beta, usage_mode: str):
    """Back-compat alias for
    :func:`repro.engine.backends.population_fitness_from_arrays`."""
    from repro.engine.backends import population_fitness_from_arrays

    return population_fitness_from_arrays(assignments, arrays, alpha, beta, usage_mode)


def fitness_cache_sizes(usage_mode: str = "fixed") -> tuple[int, int]:
    """(single-instance, batched) XLA compile counts for the shared fitness
    cores — the recompile telemetry the sweep tests assert on."""
    from repro.engine.backends import fitness_cache_sizes as _sizes

    return _sizes(usage_mode)


def make_fitness_fn(
    problem: ScheduleProblem,
    weights: ObjectiveWeights = ObjectiveWeights(),
    core_cap: int | None = None,
    backend: str = "jnp",
) -> Callable:
    """Returns ``fitness(assignments[P, T]) -> (objective[P], makespan[P])``.

    ``backend`` names an engine from :data:`repro.engine.ENGINES`
    (``"jnp"``/``"jax"``, ``"pallas"``, ``"oracle"``, ``"auto"``, or any
    plugin).  All f32 backends agree bit for bit.
    """
    from repro.engine.backends import population_fitness_fn

    return population_fitness_fn(problem, weights, engine=backend, core_cap=core_cap)


def common_bucket(problems: Sequence[ScheduleProblem]):
    """Elementwise-max shape bucket covering every problem in the list."""
    from repro.engine.packed import common_bucket as _common

    return _common(problems)


def make_batched_fitness_fn(
    problems: Sequence[ScheduleProblem],
    weights: ObjectiveWeights = ObjectiveWeights(),
) -> Callable:
    """Batched fitness over a family of instances (one shape bucket):
    ``fitness(assignments [B, P, T_bucket]) -> (objective [B, P], makespan [B, P])``.

    Assignment rows for padded tasks must be 0;
    :func:`evaluate_population_batch` does this padding for you.  All calls
    with the same bucket — across sweeps, techniques, and problem families —
    share one compiled XLA program."""
    from repro.engine.backends import batched_population_fitness_fn

    return batched_population_fitness_fn(problems, weights, engine="jax")


def evaluate_population_batch(
    problems: Sequence[ScheduleProblem],
    populations: Sequence[np.ndarray],
    weights: ObjectiveWeights = ObjectiveWeights(),
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Evaluate per-instance candidate populations for a list of problems —
    see :func:`repro.engine.backends.evaluate_population_batch`."""
    from repro.engine.backends import evaluate_population_batch as _batch

    return _batch(problems, populations, weights, engine="jax")


# -----------------------------------------------------------------------------
# deprecation shims — packing moved to repro.engine.packed (PEP 562, same
# surface as the tested repro.core.solver shim)
# -----------------------------------------------------------------------------

_ENGINE_SHIMS = {
    "problem_to_jax": "legacy_jax_arrays",
    "problem_to_numpy_padded": "legacy_padded_arrays",
    "stack_problems": "legacy_stacked_arrays",
    "bucket_of": "bucket_of",
}


def __getattr__(name: str):
    target = _ENGINE_SHIMS.get(name)
    if target is not None:
        warnings.warn(
            f"repro.core.evaluator.{name} is deprecated; problem packing "
            "moved to repro.engine (use repro.engine.pack / PackedProblem)",
            DeprecationWarning,
            stacklevel=2,
        )
        from repro.engine import packed as _packed

        return getattr(_packed, target)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_ENGINE_SHIMS))
