"""System-model tests (paper §IV-B1, Table I/III/IV) and end-to-end behaviour
of the solve() entry point on the paper's own example."""

import json

import numpy as np
import pytest

from repro.core import (
    Node,
    mri_system,
    mri_workload,
    solve,
    synthetic_system,
    system_from_json,
    system_to_json,
    verify_schedule,
)


def test_node_tuple_definition():
    n = Node("n", {"cores": 8, "memory": 64}, frozenset({"F1", "F2"}),
             {"processing_speed": 2.0, "data_transfer_rate": 100.0})
    assert n.cores == 8 and n.memory == 64
    assert n.provides({"F1"}) and n.provides({"F1", "F2"})
    assert not n.provides({"F3"})  # Eq. (1)


def test_mri_system_matches_table4():
    s = mri_system()
    assert [n.name for n in s.nodes] == ["N1", "N2", "N3"]
    assert list(s.cores()) == [8, 48, 2572]
    assert s.nodes[0].features == {"F1"}
    assert s.nodes[2].features == {"F1", "F2", "F3"}
    assert s.dtr[0, 1] == 100.0
    assert np.isinf(s.dtr[1, 1])  # intra-node transfers free (Eq. 5, i≠i')


def test_system_json_roundtrip():
    s = mri_system()
    s2 = system_from_json(json.loads(json.dumps(system_to_json(s))))
    assert [n.name for n in s2.nodes] == [n.name for n in s.nodes]
    assert list(s2.cores()) == list(s.cores())
    assert s2.nodes[1].features == s.nodes[1].features


def test_fig7_example_parses():
    obj = {
        "nodes": {
            "Node1": {
                "cores": [4], "memory": [1024], "features": ["F1"],
                "processing_speed": [1024], "data_transfer_rate": [100],
            },
            "Node2": {"cores": 12},
        }
    }
    s = system_from_json(obj)
    assert s.nodes[0].cores == 4
    assert s.nodes[1].cores == 12
    assert s.nodes[0].provides({"F1"})


def test_solve_auto_on_mri_is_optimal():
    rep = solve(mri_system(), mri_workload(), technique="auto")
    assert rep.schedule.status.startswith("optimal")
    assert rep.schedule.makespan == pytest.approx(10.0, abs=1e-6)
    assert verify_schedule(rep.problem, rep.schedule) == []


def test_synthetic_system_feasible():
    s = synthetic_system(10, seed=3)
    assert s.num_nodes == 10
    assert all(n.cores >= 4 for n in s.nodes)
