"""Paper Table IX: time-to-solution scale test (nodes × tasks from 5×5 to
5000×5000) for MILP / MH / H.

The paper's serial-Python numbers: MILP solves only 5×5 (0.02 s); MH needs
77.8 s at 50×50 and 6513 s at 500×500; H reaches 5000×5000 in 560 s.  Our
adaptation vectorizes MH fitness in JAX — the side-by-side
is the §Perf "beyond-paper" evidence.  Default sizes cap at 500×500 to keep
`-m benchmarks.run` bounded; pass --full for the 5000×5000 heuristic row.
"""

from __future__ import annotations

import time
from pathlib import Path

from repro.core import build_problem, synthetic_system, synthetic_workload
from repro.core.heuristics import heft
from repro.core.metaheuristics import ga
from repro.core.milp import solve_milp

SIZES = [(5, 5), (50, 50), (500, 500)]
FULL_SIZES = SIZES + [(5000, 5000)]
SMOKE_SIZES = [(5, 5), (50, 50)]  # CI-sized subset — seconds, not minutes


def run(full: bool = False, sizes: list[tuple[int, int]] | None = None) -> list[tuple]:
    rows = []
    if sizes is None:
        sizes = FULL_SIZES if full else SIZES
    for n_nodes, n_tasks in sizes:
        system = synthetic_system(n_nodes, seed=n_nodes)
        workload = synthetic_workload(n_tasks, seed=n_tasks)
        prob = build_problem(system, workload)

        # MILP — only small instances (mirrors the paper's '-')
        if n_tasks <= 25:
            t0 = time.perf_counter()
            s = solve_milp(prob, time_limit=60.0)
            rows.append((f"table9_{n_nodes}x{n_tasks}_milp", (time.perf_counter() - t0) * 1e6,
                         f"makespan={s.makespan:.2f};status={s.status}"))
        else:
            rows.append((f"table9_{n_nodes}x{n_tasks}_milp", float("nan"), "skipped(size)"))

        # MH (GA, JAX-vectorized) — cap at 500×500 like the paper's '-' at 5000
        if n_tasks <= 500:
            t0 = time.perf_counter()
            r = ga(prob, seed=0, pop_size=32, generations=20)
            rows.append((f"table9_{n_nodes}x{n_tasks}_mh", (time.perf_counter() - t0) * 1e6,
                         f"makespan={r.schedule.makespan:.2f}"))
        else:
            rows.append((f"table9_{n_nodes}x{n_tasks}_mh", float("nan"), "skipped(size)"))

        # H (HEFT)
        t0 = time.perf_counter()
        s = heft(prob)
        rows.append((f"table9_{n_nodes}x{n_tasks}_h", (time.perf_counter() - t0) * 1e6,
                     f"makespan={s.makespan:.2f}"))
    return rows


def run_smoke(out_path: str | Path = "BENCH_table9.json") -> list[tuple]:
    """Small Table IX sizes + machine-readable ``BENCH_table9.json`` so every
    PR leaves a perf-trajectory data point behind (`benchmarks.run --smoke`).

    Since the campaign redesign this is a thin wrapper over the ``smoke``
    built-in campaign (:func:`repro.campaigns.builtin.run_smoke`) — same
    row names, same derived makespans, same JSON payload."""
    from repro.campaigns import builtin

    return builtin.run_smoke(out_path=out_path)


if __name__ == "__main__":
    import sys

    if "--smoke" in sys.argv:
        rows = run_smoke()
    else:
        rows = run(full="--full" in sys.argv)
    for r in rows:
        print(",".join(str(x) for x in r))
