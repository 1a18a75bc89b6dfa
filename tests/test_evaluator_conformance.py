"""The fitness evaluator against the numpy oracle over a grid of workflow
families x systems x regimes, and the GA sweep's bests on the same grid.

A case is one problem and a seeded population of candidate assignments.
The cases of one system and one regime are packed into one shape bucket and
scored together the way ``ga_sweep`` scores them: the population fitness
(``population_fitness_from_arrays``) vmapped over a ``stack_packed`` batch,
in one compiled call.  Each candidate's device makespan and violation count
must equal the f32 numpy oracle's bit for bit, and its makespan and
objective the f64 oracle's within 1e-4 relative.

Regimes:

* ``plain`` — default weights (fixed usage), the group's own bucket;
* ``rows`` — the plain problems and populations at a second bucket, whose
  predecessor rows hold 16 slots (the join of 40 takes three rows) and end
  in filler rows: every score equals the ``plain`` one bit for bit;
* ``deadline`` — a deadline on every workflow and a budget on the first
  (``Constraints``), tight enough that some candidates break them;
* ``release`` — every workflow submitted at its own time after 0;
* ``saturated`` — every task on one of its two smallest feasible nodes, so
  that concurrent core demand exceeds a node's cores and tasks wait for
  them;
* ``weighted`` — ``usage_mode="weighted"`` with other ``alpha``/``beta``.

``ga_sweep`` runs once per system over the plain problems of every family;
each best must be a valid schedule whose device score agrees with the f64
oracle's rescoring.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib

import numpy as np
import pytest

from repro.core import (
    ObjectiveWeights,
    Task,
    Workflow,
    Workload,
    build_problem,
    evaluate_assignment,
    montage_workflow,
    mri_system,
    mri_w1,
    mri_w2,
    random_layered_workflow,
    synthetic_system,
    synthetic_workload,
    verify_schedule,
)
from repro.core.evaluator import constraint_violations
from repro.core.metaheuristics import ga_sweep
from repro.core.workload_model import BIG_PENALTY, Constraints, stgs_workflows
from repro.core.workload_model import testcase1_workloads as _testcase1
from repro.engine import common_bucket, stack_packed
from repro.engine.packed import task_rows

SYSTEMS = ("synthetic", "mri", "small")
FAMILIES = ("layered", "montage3x4", "montage4x5", "stgs", "mri_w1", "mri_w2",
            "testcase1", "synthetic", "chain", "fanin40")
REGIMES = ("plain", "rows", "deadline", "release", "saturated", "weighted")
#: candidates a case scores: the first half drawn from each task's feasible
#: nodes (what the GA samples), the rest from every node (an infeasible
#: placement counts as a violation)
POP = 8
#: the ``rows`` regime's row width, and the filler rows after the last task
ROW_WIDTH = 16
FILLER_ROWS = 3
RTOL = 1e-4
WEIGHTS = {"weighted": ObjectiveWeights(alpha=0.5, beta=2.0, usage_mode="weighted")}
SWEEP = dict(pop_size=POP, generations=3, seed=17)

#: ``(system, family)`` pairs that cannot saturate a node: a chain runs one
#: task at a time, and MRI W2's concurrent T2 and T3 (12 + 32 cores) fit
#: side by side on the smallest nodes able to run each
_NO_SATURATION = ({(s, f) for s in SYSTEMS for f in ("chain", "mri_w1")}
                  | {("mri", "mri_w2"), ("small", "mri_w2")})


def _grid(system_name: str) -> list[tuple[str, str]]:
    """The ``(family, regime)`` cases of one system."""
    return [(f, r) for r in REGIMES for f in FAMILIES
            if r != "saturated" or (system_name, f) not in _NO_SATURATION]


CASES = [(s, f, r) for s in SYSTEMS for f, r in _grid(s)]


# -----------------------------------------------------------------------------
# systems and families
# -----------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _system(name: str):
    if name == "synthetic":
        return synthetic_system(12, seed=5)
    if name == "mri":
        return mri_system()
    from repro.topology import generate, resolve_spec

    return generate(resolve_spec(name))  # tiers and slow links


def _portable(wf: Workflow) -> Workflow:
    """An MRI workflow off the MRI system: its Table V durations, keyed by
    N1-N3 and equal on all three, become work at speed 1 (on the MRI system
    the problem would be the same)."""
    def task(t: Task) -> Task:
        if t.durations is None:
            return t
        (work,) = set(t.durations.values())
        return dataclasses.replace(t, durations=None, work=work)

    return dataclasses.replace(wf, tasks=tuple(map(task, wf.tasks)))


def _chain(n: int, seed: int) -> Workflow:
    rng = np.random.default_rng(seed)
    return Workflow("Wc", tuple(
        Task(f"C{k}", cores=float(rng.integers(1, 9)), data=float(rng.integers(1, 9)),
             features=frozenset({"F1"}), work=float(rng.integers(1, 9)),
             deps=(f"C{k - 1}",) if k else ())
        for k in range(n)))


def _fan_in(width: int, seed: int) -> Workflow:
    """``width`` sources, one join on all of them, one sink."""
    rng = np.random.default_rng(seed)
    sources = tuple(
        Task(f"S{k}", cores=float(rng.integers(1, 5)), data=float(rng.integers(1, 9)),
             features=frozenset({"F1"}), work=float(rng.integers(1, 9)))
        for k in range(width))
    join = Task("join", cores=4.0, data=3.0, features=frozenset({"F1"}), work=5.0,
                deps=tuple(t.name for t in sources))
    sink = Task("sink", cores=1.0, features=frozenset({"F1"}), work=2.0, deps=("join",))
    return Workflow("Wf", sources + (join, sink))


_FAMILY = {
    "layered": lambda: (random_layered_workflow(48, name="Wl", seed=11),),
    "montage3x4": lambda: (montage_workflow(3, 4, seed=1),),
    "montage4x5": lambda: (montage_workflow(4, 5, seed=2),),
    "stgs": lambda: tuple(stgs_workflows().values()),
    "mri_w1": lambda: (mri_w1(),),
    "mri_w2": lambda: (mri_w2(),),
    "testcase1": lambda: tuple(_testcase1().values()),
    "synthetic": lambda: synthetic_workload(40, seed=3, num_workflows=2).workflows,
    "chain": lambda: (_chain(24, seed=4),),
    "fanin40": lambda: (_fan_in(40, seed=5),),
}


# -----------------------------------------------------------------------------
# cases
# -----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Case:
    problem: object
    population: np.ndarray  # [POP, T] node per task
    weights: ObjectiveWeights


def _population(prob, rng: np.random.Generator, saturated: bool) -> np.ndarray:
    feasible = [np.flatnonzero(row) for row in prob.feasible]
    assert all(len(f) for f in feasible), "a task no node can run"
    if saturated:
        smallest = [f[np.argsort(prob.node_cores[f], kind="stable")[:2]] for f in feasible]
        return np.array([[rng.choice(s) for s in smallest] for _ in range(POP)])
    drawn = np.array([[rng.choice(f) for f in feasible] for _ in range(POP // 2)])
    anywhere = rng.integers(0, prob.num_nodes, (POP - POP // 2, prob.num_tasks))
    return np.concatenate([drawn, anywhere])


@functools.lru_cache(maxsize=None)
def _case(system_name: str, family: str, regime: str) -> Case:
    if regime == "rows":
        return _case(system_name, family, "plain")
    sysm = _system(system_name)
    wfs = _FAMILY[family]()
    if system_name != "mri":
        wfs = tuple(map(_portable, wfs))
    if regime == "release":
        wfs = tuple(dataclasses.replace(w, submission=2.5 + 1.5 * k) for k, w in enumerate(wfs))
    prob = build_problem(sysm, Workload(wfs))
    rng = np.random.default_rng(zlib.crc32(f"{system_name}/{family}/{regime}".encode()))
    pop = _population(prob, rng, regime == "saturated")
    if regime == "deadline":
        probe = evaluate_assignment(prob, pop[0])
        cost = prob.cost_matrix()[np.arange(prob.num_tasks), pop[0]]
        cons = Constraints(
            deadline={w.name: 0.8 * probe.makespan for w in wfs},
            budget={wfs[0].name: 0.9 * float(cost[prob.workflow_of == 0].sum())},
        )
        prob = build_problem(sysm, Workload(wfs), cons)
    return Case(prob, pop, WEIGHTS.get(regime, ObjectiveWeights()))


def _bucket(problems, regime: str):
    bucket = common_bucket(problems)
    if regime != "rows":
        return bucket
    t, n, c, _, _ = bucket
    extra = max(int(task_rows(p, ROW_WIDTH).sum()) - p.num_tasks for p in problems)
    return (t, n, c, ROW_WIDTH, t + extra + FILLER_ROWS)


def _padded(pop: np.ndarray, T: int) -> np.ndarray:
    out = np.zeros((pop.shape[0], T), np.int32)  # padded tasks pin to node 0
    out[:, : pop.shape[1]] = pop
    return out


@functools.lru_cache(maxsize=None)
def _evaluator(usage_mode: str, constrained: bool):
    """``(populations [B, P, T], stacked arrays, alpha, beta) -> (objective,
    makespan, penalty)``, each ``[B, P]``: the sweep's fitness vmapped over
    the instances, and the same fitness at zero weights, whose objective is
    ``BIG_PENALTY`` times the violation count."""
    import jax

    from repro.engine.backends import population_fitness_from_arrays

    def one(pop, arrays, alpha, beta):
        obj, mk = population_fitness_from_arrays(
            pop, arrays, alpha, beta, usage_mode, constrained)
        penalty, _ = population_fitness_from_arrays(
            pop, arrays, 0.0, 0.0, usage_mode, constrained)
        return obj, mk, penalty

    return jax.jit(jax.vmap(one, in_axes=(0, 0, None, None)))


@functools.lru_cache(maxsize=None)
def _group(system_name: str, regime: str):
    """The families of a regime on a system, their cases, and their problems
    stacked at one bucket."""
    fams = tuple(f for f, r in _grid(system_name) if r == regime)
    cases = [_case(system_name, f, regime) for f in fams]
    problems = [c.problem for c in cases]
    arrays, bucket = stack_packed(problems, _bucket(problems, regime))
    return fams, cases, arrays, bucket


def _score(system_name: str, regime: str, populations) -> dict:
    """``{family: (objective [P], makespan [P], violations [P])}`` of one
    population a family of the group, from one compiled call."""
    fams, cases, arrays, bucket = _group(system_name, regime)
    pops = np.stack([_padded(p, bucket[0]) for p in populations])
    w = cases[0].weights
    run = _evaluator(w.usage_mode, any(c.problem.has_constraints for c in cases))
    obj, mk, penalty = (np.asarray(x) for x in run(pops, arrays, w.alpha, w.beta))
    viol = np.rint(penalty / np.float32(BIG_PENALTY))
    return {f: (obj[b], mk[b], viol[b]) for b, f in enumerate(fams)}


@functools.lru_cache(maxsize=None)
def _scores(system_name: str, regime: str) -> dict:
    _, cases, _, _ = _group(system_name, regime)
    return _score(system_name, regime, [c.population for c in cases])


def _waits_for_cores(prob, sched) -> bool:
    """Whether some task starts after it is ready (its release, and every
    predecessor's finish plus transfer): it waited for cores."""
    a = sched.assignment
    ready = prob.release.astype(np.float64)
    for p, j in prob.edges:
        transfer = prob.data[p] / prob.dtr[a[p], a[j]] if a[p] != a[j] else 0.0
        ready[j] = max(ready[j], sched.finish[p] + transfer)
    return bool((sched.start > ready * (1 + 1e-9) + 1e-9).any())


@pytest.mark.parametrize("system_name,family,regime", CASES,
                         ids=["-".join(c) for c in CASES])
def test_evaluator_matches_the_oracle(system_name, family, regime):
    c = _case(system_name, family, regime)
    prob = c.problem
    obj, mk, viol = _scores(system_name, regime)[family]
    assert np.isfinite(mk).all()
    waited = broken = False
    for k, assignment in enumerate(c.population):
        s32 = evaluate_assignment(prob, assignment, c.weights, dtype=np.float32)
        assert np.float32(s32.makespan) == mk[k], (k, s32.makespan, mk[k])
        assert s32.violations == viol[k], (k, s32.violations, viol[k])
        s64 = evaluate_assignment(prob, assignment, c.weights)
        assert s64.makespan == pytest.approx(float(mk[k]), rel=RTOL)
        assert s64.objective == pytest.approx(float(obj[k]), rel=RTOL)
        waited |= _waits_for_cores(prob, s64)
        broken |= constraint_violations(prob, assignment, s64.finish) > 0
    if regime == "rows":
        bucket = _group(system_name, regime)[3]
        assert bucket[3] == ROW_WIDTH and bucket[4] > bucket[0]
        for got, plain in zip((obj, mk, viol), _scores(system_name, "plain")[family]):
            np.testing.assert_array_equal(got, plain)
    elif regime == "deadline":
        assert broken, "no candidate breaks a deadline or the budget"
    elif regime == "release":
        assert (prob.release > 0).all()
    elif regime == "saturated":
        assert waited, "no task waited for cores"


# -----------------------------------------------------------------------------
# the GA sweep over a mixed-family batch
# -----------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sweep(system_name: str):
    """``ga_sweep`` over the plain problem of every family, and each best
    scored on the device at the sweep's bucket (the plain group: the same
    problems in the same order)."""
    results = ga_sweep([_case(system_name, f, "plain").problem for f in FAMILIES], **SWEEP)
    bests = [np.tile(r.schedule.assignment, (POP, 1)) for r in results]
    return results, _score(system_name, "plain", bests)


@pytest.mark.parametrize("system_name,family", [(s, f) for s in SYSTEMS for f in FAMILIES],
                         ids=[f"{s}-{f}" for s in SYSTEMS for f in FAMILIES])
def test_ga_sweep_bests_are_sound(system_name, family):
    results, device = _sweep(system_name)
    res = results[FAMILIES.index(family)]
    prob, sched = _case(system_name, family, "plain").problem, res.schedule
    assert verify_schedule(prob, sched) == []
    assert sched.violations == 0
    again = evaluate_assignment(prob, sched.assignment)
    assert (again.makespan, again.objective) == (sched.makespan, sched.objective)
    obj, mk, viol = (x[0] for x in device[family])
    assert float(mk) == pytest.approx(sched.makespan, rel=RTOL)
    assert float(obj) == pytest.approx(sched.objective, rel=RTOL)
    assert viol == 0
    # elitism: no generation loses its best, and the returned best is at
    # least as good as the last generation's
    hist = np.asarray(res.history)
    assert (np.diff(hist) <= 0).all()
    assert obj <= hist[-1]
