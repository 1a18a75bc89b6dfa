"""Operations and bytes of population fitness on a DAG, counted per edge.

``roofline.py`` charges every task ``MAXP`` predecessor slots, which on a
workflow with one wide join charges the join's width to every task.  This
counts the predecessors a task has: for one candidate and one evaluation,
each predecessor edge costs the co-location test, the transfer, the sum and
the running maximum (4 operations), and each task the maximum with its
release time, the core selection over ``CMAX`` core-free times, its start
and finish and its feasibility lookup (``3 * CMAX + 4``).  So an evaluation
of ``P`` candidates costs ``P * (4 * E + T * (3 * CMAX + 4))`` operations.

Bytes are ``roofline.fitness_work``'s with the ``E`` predecessor indices in
place of ``T x MAXP``.  Where every task has ``MAXP`` predecessors the two
counts are equal.  ``T``, ``E``, ``N`` and ``CMAX`` are the problem's own,
unpadded.
"""

from __future__ import annotations

from roofline import WORD


def fitness_work(*, tasks: int, edges: int, nodes: int, cmax: int, population: int,
                 evaluations: int, instances: int) -> tuple[int, int]:
    """``(operations, bytes)`` of ``evaluations`` population evaluations of
    ``population`` candidates on each of ``instances`` problems of ``tasks``
    tasks and ``edges`` predecessor edges."""
    candidates = instances * evaluations * population
    ops = candidates * (4 * edges + tasks * (3 * cmax + 4))
    problem = WORD * (tasks * nodes + nodes * nodes + edges + nodes * cmax + 3 * tasks + nodes)
    bytes_ = instances * problem + candidates * WORD * (tasks + 2)
    return ops, bytes_
