"""The benchmark's generators and plain reference against the program, on the
CPU at small sizes: the copies draw the same instances as the program's
generators, the reference times schedules exactly as the program's f64
oracle does, and the critical-path bound is never above a makespan."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import generate  # noqa: E402
from program_inputs import problem_of  # noqa: E402
from reference import listsched  # noqa: E402


def _raw_workflow(wf) -> dict:
    """A program's workflow read back as ``generate.py``'s plain data."""
    return {"name": wf.name, "submission": float(wf.submission), "tasks": [
        {"name": t.name, "cores": float(t.cores), "data": float(t.data),
         "features": sorted(t.features), "work": float(t.work), "deps": list(t.deps)}
        for t in wf.tasks]}


def _oracle(problem, assignment):
    from repro.core.evaluator import evaluate_assignment

    return evaluate_assignment(problem, assignment)


def _random_assignments(rng, model, count, feasible_only):
    T, N = model["feasible"].shape
    if not feasible_only:
        return rng.integers(0, N, (count, T))
    out = np.empty((count, T), dtype=np.int64)
    for j in range(T):
        ok = np.flatnonzero(model["feasible"][j])
        out[:, j] = rng.choice(ok, count)
    return out


@pytest.mark.parametrize("seed", [3, 41, 2**31 + 5])
def test_generators_match_the_programs(seed):
    from repro.core import synthetic_system, synthetic_workload

    s = seed % 2**31
    program = synthetic_system(30, seed=s)
    mine = generate.synthetic_nodes(30, seed=s)
    assert [(n.name, n.cores, n.processing_speed, n.data_transfer_rate, sorted(n.features))
            for n in program.nodes] == [
        (n["name"], n["cores"], n["speed"], n["rate"], n["features"]) for n in mine]
    wl = synthetic_workload(40, seed=s)
    assert all(t.durations is None for w in wl.workflows for t in w.tasks)
    assert [_raw_workflow(w) for w in wl.workflows] == generate.synthetic_workflows(40, seed=s)


@pytest.mark.parametrize("seed", [0, 7, 19])
def test_reference_times_schedules_like_the_oracle(seed):
    nodes = generate.synthetic_nodes(12, seed=seed)
    wfs = generate.synthetic_workflows(25, seed=seed, num_workflows=2)
    problem = problem_of(nodes, wfs)
    model = listsched.build_model(nodes, wfs)
    assert model["names"] == problem.task_names
    np.testing.assert_array_equal(model["feasible"], problem.feasible)
    rng = np.random.default_rng(seed)
    for feasible_only in (True, False):
        pop = _random_assignments(rng, model, 6, feasible_only)
        ref = listsched.population_makespan(model, pop)
        for k in range(len(pop)):
            sched = _oracle(problem, pop[k])
            np.testing.assert_array_equal(ref["start"][k], sched.start)
            np.testing.assert_array_equal(ref["finish"][k], sched.finish)
            assert ref["invalid"][k] == sched.violations
            assert listsched.lower_bound(model) <= ref["makespan"][k]


def test_lower_precision_control_reads_a_gap():
    """The control (the reference in float32) differs from float64."""
    nodes = generate.synthetic_nodes(10, seed=2)
    wfs = generate.synthetic_workflows(30, seed=2)
    model = listsched.build_model(nodes, wfs)
    pop = _random_assignments(np.random.default_rng(2), model, 4, True)
    hi = listsched.population_makespan(model, pop)
    lo = listsched.population_makespan(model, pop, dtype=np.float32)
    gap = np.abs(lo["finish"].astype(np.float64) - hi["finish"]).max()
    assert 0 < gap < 1e-3 * hi["makespan"].max()
