"""The Montage mosaic family: the generator's structure (Bharathi et al.
2008, Juve et al. 2013), its determinism, its campaign family, and a
mosaic family solved end to end through the batched sweep and one GA
solve."""

from collections import Counter

import numpy as np
import pytest

from repro.core import (
    Workload,
    build_problem,
    montage_workflow,
    solve,
    synthetic_system,
    verify_schedule,
)
from repro.core.workload_model import montage_overlaps, topological_order


def test_montage_structure_at_13x14():
    wf = montage_workflow(13, 14, seed=7)
    kinds = Counter(t.name.split("_")[0] for t in wf.tasks)
    assert kinds == {"mProjectPP": 182, "mDiffFit": 649, "mConcatFit": 1, "mBgModel": 1,
                     "mBackground": 182, "mImgtbl": 1, "mAdd": 1, "mShrink": 1, "mJPEG": 1}
    assert len(wf.tasks) == 1019
    assert sum(len(t.deps) for t in wf.tasks) == 2679
    indeg = {t.name: len(t.deps) for t in wf.tasks}
    assert (indeg["mConcatFit"], indeg["mImgtbl"], indeg["mAdd"]) == (649, 182, 183)
    assert max(indeg.values()) == 649
    # acyclic, and listed in a topological order the problem keeps
    assert topological_order(wf.tasks) == list(range(len(wf.tasks)))
    assert all(t.cores == 1.0 and t.features == {"F1"} for t in wf.tasks)
    by_name = {t.name: t for t in wf.tasks}
    assert set(by_name["mAdd"].deps) == {"mImgtbl"} | {f"mBackground_{k}" for k in range(182)}
    assert by_name["mBackground_5"].deps == ("mBgModel", "mProjectPP_5")


def test_montage_overlaps_are_the_8_neighbour_pairs():
    rows, cols = 4, 5
    pairs = montage_overlaps(rows, cols)
    want = {(a, b) for a in range(rows * cols) for b in range(a + 1, rows * cols)
            if max(abs(a // cols - b // cols), abs(a % cols - b % cols)) == 1}
    assert len(pairs) == len(set(pairs)) == len(want)
    assert {tuple(sorted(p)) for p in pairs} == want


def test_montage_is_seeded():
    a, b = montage_workflow(4, 5, seed=3), montage_workflow(4, 5, seed=3)
    assert a == b
    c = montage_workflow(4, 5, seed=4)
    assert [t.deps for t in c.tasks] == [t.deps for t in a.tasks]
    assert [t.work for t in c.tasks] != [t.work for t in a.tasks]
    with pytest.raises(ValueError, match="two images"):
        montage_workflow(1, 1)


@pytest.mark.parametrize("path", ["ga_sweep", "solve"])
def test_montage_campaign_family_solves_end_to_end(path):
    from repro.campaigns.spec import WORKLOAD_FAMILIES, cell_system, cell_workload
    from repro.core.metaheuristics import ga_sweep

    assert "montage" in WORKLOAD_FAMILIES
    coords = [{"family": "montage", "rows": 3, "cols": 3, "seed": s, "nodes": 16}
              for s in (1, 2)]
    system = cell_system(coords[0])
    workloads = [cell_workload(c) for c in coords]
    assert workloads[0].num_tasks == 2 * 9 + 20 + 6
    if path == "ga_sweep":
        problems = [build_problem(system, w) for w in workloads]
        results = ga_sweep(problems, pop_size=16, generations=4, seed=0)
        scheduled = [(p, r.schedule) for p, r in zip(problems, results)]
    else:
        report = solve(system, workloads[0], technique="ga", pop_size=16, generations=4)
        scheduled = [(build_problem(system, workloads[0]), report.schedule)]
    for problem, schedule in scheduled:
        assert verify_schedule(problem, schedule) == []
        assert np.isfinite(schedule.makespan) and schedule.makespan < 1e9
    with pytest.raises(ValueError, match="rows"):
        cell_workload({"family": "montage", "rows": 3})


def test_montage_bucket_rows_stay_near_one_per_task():
    """The 13x14 mosaic's joins (649, 182, 183) at the bucket's row width
    add a few dozen rows to 1,024: within 10% of the tasks."""
    from repro.engine import bucket_of

    problem = build_problem(synthetic_system(8, seed=8),
                            Workload((montage_workflow(13, 14, seed=1),)))
    T, _, _, K, S = bucket_of(problem)
    assert (T, K) == (1024, 16)
    assert T < S <= 1.10 * T
