"""Snakemake I/O (Fig 5/6 dialect), the schedule JSON contract and the
monitor feedback loop."""

import json

import numpy as np
import pytest

from repro.core import build_problem, mri_system, mri_workload, solve_problem
from repro.core.monitor import MonitorState
from repro.core.simulator import execute
from repro.core.snakemake_io import load_config, parse_rules

FIG6_SNAKEFILE = """
rule T1: # dependencies
 input:
 experiment.conf
 output:
 product1.dat
 resources:
 mem_mb = [1024] # memory_required, (R2)
 features = ["F1", "F2"] # requested features
 data = 2GiB # estimated output size, (R3)
 duration = [1000] # usage, in seconds
 run:
 # Execute shell command/script

rule T2:
 input:
 product1.dat
 output:
 product2.dat
 resources:
 features = ["F1"]
"""


def test_parse_fig6_rules():
    wf = parse_rules(FIG6_SNAKEFILE)
    assert [t.name for t in wf.tasks] == ["T1", "T2"]
    t1, t2 = wf.tasks
    assert t1.memory == 1024
    assert t1.features == {"F1", "F2"}
    assert t1.data == 2.0
    assert t1.work == 1000.0
    assert t2.deps == ("T1",)  # inferred from product1.dat


def test_schedule_json_contract(tmp_path):
    prob = build_problem(mri_system(), mri_workload())
    rep = solve_problem(prob, "heft")
    obj = rep.schedule.to_json(prob, [n.name for n in mri_system().nodes])
    assert obj["makespan"] > 0
    assert len(obj["schedule"]) == prob.num_tasks
    # sorted by start time
    starts = [e["start"] for e in obj["schedule"]]
    assert starts == sorted(starts)
    path = tmp_path / "sched.json"
    path.write_text(json.dumps(obj))
    assert json.loads(path.read_text())["technique"] == "heft"


def test_load_combined_config(tmp_path):
    obj = {
        "nodes": {"N1": {"cores": [4], "features": ["F1"],
                         "processing_speed": [1.0], "data_transfer_rate": [10]}},
        "Workflow 1": {"tasks": {"T1": {"cores": [1], "duration": [5],
                                        "features": ["F1"], "dependencies": []}}},
    }
    p = tmp_path / "config.json"
    p.write_text(json.dumps(obj))
    system, workload = load_config(p)
    assert system.num_nodes == 1
    assert workload.num_tasks == 1


def test_monitor_feedback_improves_prediction():
    """Fig. 4 loop: solve → execute (slow node) → monitor updates P →
    re-solve predicts the observed reality."""
    system = mri_system()
    prob = build_problem(system, mri_workload())
    rep = solve_problem(prob, "heft")
    slow = np.array([1.0, 0.5, 1.0])  # N2 at half speed
    run1 = execute(prob, rep.schedule, speed_factors=slow)
    assert run1.slowdown > 1.2

    mon = MonitorState(smoothing=1.0)
    mon.update(system, prob, run1)
    system2 = mon.refreshed_system(system)
    assert system2.nodes[1].processing_speed == pytest.approx(0.5, rel=1e-6)
