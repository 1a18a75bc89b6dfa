"""The Montage cell on the CPU at tiny size: the benchmark's copy of the
Montage generator draws the program's mosaics, the plain reference times a
mosaic's schedules as the program's f64 oracle does, and a rehearsal of
``montage-1k.sweep8`` (a 3x3 mosaic on 16 nodes) gives a result line of the
format's keys with ``correct`` true, which the control turns false."""

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import control  # noqa: E402
import generate  # noqa: E402
import generate_montage  # noqa: E402
import harness  # noqa: E402
from program_inputs import problem_of  # noqa: E402
from reference import listsched  # noqa: E402
from test_chipbench_reference import _raw_workflow  # noqa: E402

CELL = "montage-1k.sweep8"


def tiny_dag(name: str = CELL):
    """The cell at a size the CPU tests hold: a 3x3 mosaic (44 tasks, a join
    of 20 fits) on 16 nodes, 4 instances in groups of 2, a GA of 16
    candidates over 8 generations."""
    cell = harness.load_cell(name)
    cell.config = dict(
        cell.config, system=dict(cell.config["system"], nodes=16),
        workload=dict(cell.config["workload"], rows=3, cols=3, tasks=44),
        instance_seeds=[1, 2, 3, 4],
        solver=dict(cell.config["solver"], pop_size=16, generations=8))
    cell.traffic = dict(cell.traffic, group=2)
    return cell


@pytest.mark.parametrize("rows,cols,seed", [(3, 3, 0), (13, 14, 5), (4, 5, 2**31 + 9)])
def test_montage_copy_matches_the_programs(rows, cols, seed):
    from repro.core import montage_workflow

    program = montage_workflow(rows, cols, seed=seed, name="M")
    assert _raw_workflow(program) == generate_montage.montage_workflow(rows, cols, seed=seed,
                                                                       name="M")


def test_reference_times_mosaics_like_the_oracle():
    from repro.core.evaluator import evaluate_assignment

    nodes = generate.synthetic_nodes(12, seed=4)
    wfs = [generate_montage.montage_workflow(4, 5, seed=4, name="M")]
    problem = problem_of(nodes, wfs)
    model = listsched.build_model(nodes, wfs)
    assert model["names"] == problem.task_names
    rng = np.random.default_rng(4)
    pop = np.stack([rng.choice(np.flatnonzero(model["feasible"][j]), 5)
                    for j in range(problem.num_tasks)], axis=1)
    ref = listsched.population_makespan(model, pop)
    for k in range(len(pop)):
        sched = evaluate_assignment(problem, pop[k])
        np.testing.assert_array_equal(ref["start"][k], sched.start)
        np.testing.assert_array_equal(ref["finish"][k], sched.finish)
        assert listsched.lower_bound(model) <= ref["makespan"][k]


def test_result_line_keys_at_tiny_size():
    cell = tiny_dag()
    for traced in (False, True):
        result = harness.measure(cell, 2**33 + 71, 0.5, traced, t_process=time.time(),
                                 platform="cpu")
        line = json.loads(json.dumps(result))
        keys = ["correct", "attempted", "failed", "metrics", "device"]
        assert list(line) == keys + (["breakdown"] if traced else []) + ["checks"]
        assert line["correct"] is True and line["failed"] == 0, line["checks"]
        wanted = {m["name"] for m in (cell.per_layer if traced else cell.end_to_end)}
        if traced:
            assert set(line["metrics"]) <= wanted
            # the span args are host facts, so the CPU run reads them too
            assert line["metrics"]["pred_rows_per_task"]["value"] == 1.0
        else:
            assert set(line["metrics"]) == wanted


def test_per_layer_metrics_listed_for_the_montage_cell_alone():
    cell = harness.load_cell(CELL)
    new = {"dag_fitness_us", "dag_preds_us", "dag_fitness_roofline", "pred_rows_per_task"}
    assert new <= set(cell.readers)
    assert "fitness_roofline" not in cell.readers
    assert not new & set(harness.load_cell("table9-500.sweep8").readers)


def test_sound_run_passes_and_control_fails():
    r = control.readings(tiny_dag(), 2**33 + 53, 2)
    assert r["correct"], r
    assert not r["control_correct"], r
