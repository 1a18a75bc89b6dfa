"""Closed-loop batched GA search over workflows of any DAG shape: the
``ga_sweep`` driver's loop, checks and device re-scoring, on instances of
the Montage mosaic family (``generate_montage.py``) on Table IX nodes.

The configuration's ``workload`` names the mosaic's grid.  Each call's
fitness work is counted per predecessor edge (``roofline_dag.py``), since a
wide join would otherwise charge its width to every task.
"""

from __future__ import annotations

import functools

import numpy as np

import generate
import generate_montage
import roofline_dag
from drivers import ga_sweep
from program_inputs import problem_of


@functools.lru_cache(maxsize=None)
def instance(nodes: int, max_cores: int, rows: int, cols: int, seed: int):
    """One mosaic instance as plain data, and the program's problem."""
    raw = (generate.synthetic_nodes(nodes, seed=seed, max_cores=max_cores),
           [generate_montage.montage_workflow(rows, cols, seed=seed, name=f"M{rows}x{cols}")])
    return raw, problem_of(*raw)


class Driver(ga_sweep.Driver):
    def setup(self) -> None:
        sysc, wlc = self.config["system"], self.config["workload"]
        seeds = self.config["instance_seeds"]
        made = [instance(sysc["nodes"], sysc["max_cores"], wlc["rows"], wlc["cols"], s)
                for s in seeds]
        self.raw = [raw for raw, _ in made]
        self.problems = [problem for _, problem in made]
        if any(p.num_tasks != wlc["tasks"] for p in self.problems):
            raise ValueError(f"a {wlc['rows']}x{wlc['cols']} mosaic is not {wlc['tasks']} tasks")
        order = np.random.default_rng(generate.derive_seed(self.seed, 1)).permutation(len(seeds))
        size = self.traffic["group"]
        self.groups = [list(order[k:k + size]) for k in range(0, len(order), size)]

    def work(self, group: list[int]) -> tuple[int, int]:
        """Operations and bytes the fitness evaluations of one call need,
        counted per edge, summed over the group's instances."""
        ops = bytes_ = 0
        for i in group:
            nodes, wfs = self.raw[i]
            tasks = [t for w in wfs for t in w["tasks"]]
            o, b = roofline_dag.fitness_work(
                tasks=len(tasks), edges=sum(len(t["deps"]) for t in tasks), nodes=len(nodes),
                cmax=int(max(n["cores"] for n in nodes)), population=self.ga["pop_size"],
                evaluations=self.ga["generations"] + 1, instances=1)
            ops, bytes_ = ops + o, bytes_ + b
        return ops, bytes_
