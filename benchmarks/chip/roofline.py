"""Operations and bytes that population fitness needs, from the algorithm.

The count follows list scheduling, not what a compiler emits, so a kernel or
a new representation that replaces today's evaluator is measured against the
same work.  For one candidate assignment and one task ``j`` on node ``i``:

* ready time: for each of the task's predecessor slots, a co-location test,
  the transfer ``data / rate``, the sum ``finish + transfer`` and a running
  maximum — 4 operations per slot, ``MAXP`` slots — and the maximum with the
  release time: ``4 * MAXP + 1``;
* core selection over node ``i``'s ``CMAX`` core-free times: the c-th
  smallest needs a comparison per core, and claiming the ``c`` earliest cores
  a comparison and a select per core: ``3 * CMAX``;
* start ``max(ready, kth)`` and finish ``start + duration``: 2;
* the feasibility lookup that counts violations: 1.

So a task step costs ``4 * MAXP + 3 * CMAX + 4`` operations, and an
evaluation of ``P`` candidates over ``T`` tasks ``P * T`` times that.

Bytes are the traffic no evaluator can avoid: each instance's problem is read
once per call (durations ``T x N``, link rates ``N x N``, predecessors
``T x MAXP``, the core-free state ``N x CMAX``, and four per-task or per-node
vectors, all 4 bytes wide), every candidate is read once per evaluation
(``P x T`` node indices) and its objective and makespan written (2 values).
Core-free times that a kernel keeps on chip between steps are not counted,
so no evaluator can need fewer bytes than this, and the share of the
roofline stays under 100%.

``T``, ``N``, ``CMAX`` and ``MAXP`` are the problem's own, unpadded: padding
is work the evaluator chose, and shows as a lower share.
"""

from __future__ import annotations

WORD = 4


def step_ops(cmax: int, maxp: int) -> int:
    """Operations of one candidate's list-scheduling step for one task."""
    return 4 * maxp + 1 + 3 * cmax + 2 + 1


def fitness_work(*, tasks: int, nodes: int, cmax: int, maxp: int, population: int,
                 evaluations: int, instances: int) -> tuple[int, int]:
    """``(operations, bytes)`` of ``evaluations`` population evaluations of
    ``population`` candidates on each of ``instances`` problems."""
    candidates = instances * evaluations * population
    ops = candidates * tasks * step_ops(cmax, maxp)
    problem = WORD * (tasks * nodes + nodes * nodes + tasks * maxp + nodes * cmax
                      + 3 * tasks + nodes)
    bytes_ = instances * problem + candidates * WORD * (tasks + 2)
    return ops, bytes_


def least_seconds(ops: int, bytes_: int, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    compute, memory = ops / peaks["ops_per_s"], bytes_ / peaks["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "bytes")
