"""The operation and byte count behind ``fitness_roofline``, against a
count made by hand on a small problem."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import roofline  # noqa: E402


def test_hand_count():
    # 3 tasks with at most 2 predecessors, 2 nodes of at most 4 cores;
    # 5 candidates, 2 evaluations, 3 instances
    per_step = (4 * 2 + 1) + 3 * 4 + 2 + 1  # ready, select and claim, start/finish, lookup
    assert roofline.step_ops(cmax=4, maxp=2) == per_step == 24
    ops, bytes_ = roofline.fitness_work(tasks=3, nodes=2, cmax=4, maxp=2, population=5,
                                        evaluations=2, instances=3)
    assert ops == 3 * 2 * 5 * 3 * 24
    # durations 3x2, rates 2x2, predecessors 3x2, core state 2x4, three task
    # vectors and one node vector: 35 words per instance; 30 candidates each
    # read as 3 words and written as 2
    assert bytes_ == 4 * (3 * 35 + 30 * (3 + 2))


def test_least_time_names_its_bound():
    peaks = {"ops_per_s": 1e12, "bytes_per_s": 1e9}
    assert roofline.least_seconds(10**12, 10**8, peaks) == (1.0, "compute")
    assert roofline.least_seconds(10**9, 10**9, peaks) == (1.0, "bytes")


def test_table9_call_is_bound_by_bytes():
    """One call of the sweep cell: 8 instances, 21 evaluations of 64."""
    import json

    peaks = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())
    ops, bytes_ = roofline.fitness_work(tasks=500, nodes=500, cmax=64, maxp=55, population=64,
                                        evaluations=21, instances=8)
    least, bound = roofline.least_seconds(ops, bytes_, peaks["TPU v5 lite"])
    assert bound == "bytes"
    assert least == pytest.approx(bytes_ / 8.19e11)
