"""Hypothesis property tests over the system's invariants:

* every technique emits a schedule satisfying Eq. (1/2/9/12) + capacity,
* the JAX population evaluator equals the numpy oracle,
* MILP (exact) is never beaten by any heuristic/metaheuristic,
* executor replay without perturbation reproduces the oracle timing.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import example, given, settings, strategies as st

from repro.core import (
    ObjectiveWeights,
    build_problem,
    evaluate_assignment,
    mri_system,
    random_layered_workflow,
    synthetic_system,
    verify_schedule,
    Workload,
)
from repro.core.evaluator import make_fitness_fn
from repro.core.heuristics import heft, olb
from repro.core.metaheuristics import ga
from repro.core.milp import solve_milp
from repro.core.simulator import execute


def _problem(num_tasks: int, num_nodes: int, seed: int, comm: bool):
    system = synthetic_system(num_nodes, seed=seed)
    wf = random_layered_workflow(
        num_tasks, seed=seed + 1, comm=comm, max_cores=4, feature_pool=("F1",)
    )
    return build_problem(system, Workload((wf,)))


@settings(max_examples=15, deadline=None)
@given(
    num_tasks=st.integers(3, 12),
    num_nodes=st.integers(2, 5),
    seed=st.integers(0, 1000),
    comm=st.booleans(),
)
def test_heuristics_always_valid(num_tasks, num_nodes, seed, comm):
    prob = _problem(num_tasks, num_nodes, seed, comm)
    for fn in (heft, olb):
        s = fn(prob)
        assert s.violations == 0
        assert verify_schedule(prob, s) == [], fn.__name__


@settings(max_examples=8, deadline=None)
@given(
    num_tasks=st.integers(3, 8),
    seed=st.integers(0, 500),
)
# HEFT's 26.5 beat a MILP that stopped at HiGHS's default 1e-4 gap (26.5002)
@example(num_tasks=8, seed=464)
def test_milp_dominates_heuristics(num_tasks, seed):
    prob = _problem(num_tasks, 3, seed, comm=True)
    w = ObjectiveWeights()
    m = solve_milp(prob, w, time_limit=20.0)
    if not m.status.startswith(("optimal",)):
        return  # timeout — no claim
    assert verify_schedule(prob, m) == []
    for fn in (heft, olb):
        h = fn(prob, w)
        assert m.objective <= h.objective + 1e-4, (m.objective, h.objective)


@settings(max_examples=10, deadline=None)
@given(
    num_tasks=st.integers(3, 15),
    num_nodes=st.integers(2, 5),
    seed=st.integers(0, 1000),
)
def test_jax_evaluator_equals_oracle(num_tasks, num_nodes, seed):
    prob = _problem(num_tasks, num_nodes, seed, comm=True)
    fit = make_fitness_fn(prob)
    rng = np.random.default_rng(seed)
    A = rng.integers(0, prob.num_nodes, (8, prob.num_tasks))
    obj, mk = fit(A)
    for k in range(8):
        ref = evaluate_assignment(prob, A[k])
        assert float(mk[k]) == pytest.approx(ref.makespan, rel=1e-4, abs=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    num_tasks=st.integers(3, 12),
    seed=st.integers(0, 1000),
)
def test_executor_replay_is_exact(num_tasks, seed):
    prob = _problem(num_tasks, 4, seed, comm=True)
    s = heft(prob)
    rep = execute(prob, s)
    assert rep.makespan == pytest.approx(s.makespan, rel=1e-9)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 200))
def test_ga_valid_on_random(seed):
    prob = _problem(10, 3, seed, comm=True)
    res = ga(prob, seed=seed, pop_size=16, generations=10)
    assert res.schedule.violations == 0
    assert verify_schedule(prob, res.schedule) == []
