"""The comparison that decides ``correct``, and the numbers it compares.

Every issued schedule is an answer that can be checked on its own: the
reference replays its assignment (``listsched``) and reads

* ``invalid`` — schedules that place a task where it cannot run, or that
  report violations themselves (limit 0: validity is exact);
* ``schedule_gap`` — the widest gap between a start or finish time the
  schedule reports and the reference's, over the reference's makespan;
* ``best_excess`` — by how much the reference objective of the returned best
  exceeds the best objective the device search reported for its last
  generation, over the reference's makespan.  With ``elite >= 1`` the last
  generation's best survives into the final population, so the returned
  best can be no worse; a best altered after it was chosen shows here;
* ``fitness_gap`` — the widest gap, either way, between the objective the
  engine's evaluator gives a returned best on the chip and the reference's,
  over the reference's makespan: a device fitness that reads too high or too
  low shows here;
* ``stalled`` — returned bests whose search did not improve on its first
  generation: the device's last-generation best, or the reference objective
  of the returned best, is not below the first generation's best (limit 0:
  a GA of 20 generations improves every 500-task instance by far more than
  rounding).

The control is the reference itself put in the program's place at the same
answers, one precision down: float32 timings for the float64 schedules, and
bfloat16 objectives in place of the device's float32 ones.
"""

from __future__ import annotations

import numpy as np

from reference import listsched


def in_reference_order(model: dict, task_names, vector) -> np.ndarray:
    """A per-task vector in the program's task order, re-indexed into the
    reference's (matched by ``workflow/task`` name)."""
    index = {name: g for g, name in enumerate(model["names"])}
    if len(task_names) != len(index) or set(task_names) != set(index):
        raise ValueError("the schedule's tasks are not the problem's tasks")
    out = np.empty(len(index), dtype=np.asarray(vector).dtype)
    out[[index[n] for n in task_names]] = vector
    return out


def _replay(model: dict, task_names, schedules, dtype=np.float64) -> dict:
    pop = np.stack([in_reference_order(model, task_names, s.assignment) for s in schedules])
    return listsched.population_makespan(model, pop, dtype=dtype)


def schedule_readings(model: dict, task_names, schedules, dtype=np.float64):
    """``(invalid, gap, reference replay)`` of schedules of one problem.

    ``dtype`` below float64 gives the control's readings: the reference's
    own timings in that precision in place of the schedules'."""
    ref = _replay(model, task_names, schedules)
    if dtype is np.float64:
        starts = np.stack([in_reference_order(model, task_names, s.start) for s in schedules])
        finishes = np.stack([in_reference_order(model, task_names, s.finish) for s in schedules])
        reported = np.array([s.violations for s in schedules])
    else:
        low = _replay(model, task_names, schedules, dtype=dtype)
        starts, finishes = low["start"], low["finish"]
        reported = np.zeros(len(schedules))
    gap = np.maximum(np.abs(starts.astype(np.float64) - ref["start"]).max(axis=1),
                     np.abs(finishes.astype(np.float64) - ref["finish"]).max(axis=1))
    invalid = int(np.sum((ref["invalid"] > 0) | (reported != 0)))
    return invalid, float((gap / ref["makespan"]).max()), ref


def objective(model: dict, makespan, weights: dict, dtype=np.float64):
    """Eq. 8 with the fixed usage term: alpha * sum of cores + beta * makespan."""
    usage = np.asarray(model["cores"], dtype=dtype).sum(dtype=dtype)
    return (dtype(weights["alpha"]) * usage
            + dtype(weights["beta"]) * np.asarray(makespan, dtype=dtype)).astype(np.float64)


def sweep_numbers(issued, weights: dict, *, control: bool = False) -> tuple[dict, float]:
    """``issued``: ``(model, task_names, [MHResult ...], device objectives)``
    per instance, the device objectives those the engine's evaluator gave
    each returned best.  Returns the numbers compared and the mean makespan
    over the bound."""
    invalid, gap, excess, fit_gap, stalled, ratios = 0, 0.0, -np.inf, 0.0, 0, []
    for model, names, results, device in issued:
        schedules = [r.schedule for r in results]
        bad, g, ref = schedule_readings(model, names, schedules,
                                        dtype=np.float32 if control else np.float64)
        invalid, gap, mk = invalid + bad, max(gap, g), ref["makespan"]
        exact = objective(model, mk, weights)
        first = np.array([float(r.history[0]) for r in results])
        last = np.array([float(r.history[-1]) for r in results])
        if control:
            import ml_dtypes

            low = listsched.population_makespan(
                model, np.stack([in_reference_order(model, names, s.assignment)
                                 for s in schedules]), dtype=ml_dtypes.bfloat16)
            last = device = objective(model, low["makespan"], weights, dtype=ml_dtypes.bfloat16)
        excess = max(excess, float(np.max((exact - last) / mk)))
        fit_gap = max(fit_gap, float(np.max(np.abs(np.asarray(device) - exact) / mk)))
        stalled += int(np.sum((last >= first) | (exact >= first)))
        ratios.extend(mk / listsched.lower_bound(model))
    numbers = {"invalid": invalid, "schedule_gap": gap, "best_excess": excess,
               "fitness_gap": fit_gap, "stalled": stalled}
    return numbers, float(np.mean(ratios))
