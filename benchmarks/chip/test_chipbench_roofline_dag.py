"""The per-edge work count against the per-slot one: where every task has
exactly ``MAXP`` predecessors, both count the same operations and bytes."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import roofline  # noqa: E402
import roofline_dag  # noqa: E402


@pytest.mark.parametrize("tasks,nodes,cmax,maxp", [(3, 2, 4, 2), (500, 500, 64, 55),
                                                    (1019, 500, 64, 1)])
def test_per_edge_count_equals_per_slot_count_at_full_in_degree(tasks, nodes, cmax, maxp):
    sizes = dict(tasks=tasks, nodes=nodes, cmax=cmax, population=64, evaluations=21,
                 instances=8)
    assert roofline_dag.fitness_work(edges=tasks * maxp, **sizes) == roofline.fitness_work(
        maxp=maxp, **sizes)


def test_per_edge_count_by_hand():
    # 3 tasks, 2 edges, 2 nodes, CMAX 4; 5 candidates, 1 evaluation
    ops, bytes_ = roofline_dag.fitness_work(tasks=3, edges=2, nodes=2, cmax=4, population=5,
                                            evaluations=1, instances=1)
    assert ops == 5 * (4 * 2 + 3 * (3 * 4 + 4))
    assert bytes_ == 4 * (3 * 2 + 2 * 2 + 2 + 2 * 4 + 3 * 3 + 2) + 5 * 4 * (3 + 2)
