"""Closed-loop batched GA search: one client calls ``ga_sweep`` on a group of
instances, waits for the schedules, and calls again.

The pool of instances is the configuration's fixed family; the seed splits it
into groups that alternate and gives every call its own GA seed.  The unit of
work is one call.  After the window every issued schedule is replayed by the
reference, and every returned best is scored again by the engine's evaluator
on the chip at the sweep's packed sizes; the comparison of the two decides
``correct``.
"""

from __future__ import annotations

import functools
import sys

import numpy as np

import generate
import roofline
from program_inputs import problem_of
from reference import compare, listsched


@functools.lru_cache(maxsize=None)
def instance(nodes: int, max_cores: int, tasks: int, workflows: int, task_cores: int,
             seed: int):
    """One instance of the family as plain data, and the program's problem."""
    raw = (generate.synthetic_nodes(nodes, seed=seed, max_cores=max_cores),
           generate.synthetic_workflows(tasks, seed=seed, num_workflows=workflows,
                                        max_cores=task_cores))
    return raw, problem_of(*raw)


@functools.lru_cache(maxsize=None)
def _batched_fitness(usage_mode: str, constrained: bool):
    """The engine's population evaluator, vmapped over instances as the GA
    sweep program runs it: ``(pop [B, P, T], arrays) -> (obj, makespan)``."""
    import jax
    from repro.engine import backends

    def one(pop, arrays, alpha, beta):
        return backends.population_fitness_from_arrays(pop, arrays, alpha, beta, usage_mode,
                                                       constrained)

    return jax.jit(jax.vmap(one, in_axes=(0, 0, None, None)))


class Driver:
    def __init__(self, config: dict, traffic: dict, *, seed: int, chips: int) -> None:
        self.config, self.traffic, self.seed, self.chips = config, traffic, seed, chips
        self.ga = dict(config["solver"])
        self.calls: list[tuple[list[int], list]] = []

    def setup(self) -> None:
        sysc, wlc = self.config["system"], self.config["workload"]
        seeds = self.config["instance_seeds"]
        made = [instance(sysc["nodes"], sysc["max_cores"], wlc["tasks"], wlc["workflows"],
                         wlc["max_cores"], s) for s in seeds]
        self.raw = [raw for raw, _ in made]
        self.problems = [problem for _, problem in made]
        order = np.random.default_rng(generate.derive_seed(self.seed, 1)).permutation(len(seeds))
        size = self.traffic["group"]
        self.groups = [list(order[k:k + size]) for k in range(0, len(order), size)]

    def _call(self, group: list[int], ga_seed: int) -> list:
        from repro.core.metaheuristics import ga_sweep

        return ga_sweep([self.problems[i] for i in group], shard=self.traffic["shard"],
                        seed=ga_seed, **self.ga)

    def warm_up(self) -> None:
        for g, group in enumerate(self.groups):
            self._call(group, generate.derive_seed(self.seed, 2, g))

    def unit(self, k: int) -> None:
        from repro import obs

        group = self.groups[k % len(self.groups)]
        ga_seed = generate.derive_seed(self.seed, 3, k)
        self.calls.append((group, self._call(group, ga_seed)))
        shards = obs.METRICS.snapshot()["gauges"].get("mh.ga_sweep.shards")
        if k == 0 and shards != min(self.chips, len(group)):
            print(f"chipbench: ga_sweep ran on {shards} shards on {self.chips} chips",
                  file=sys.stderr)

    def window_facts(self) -> dict:
        n = sum(len(group) for group, _ in self.calls)
        return {
            "schedules": n, "attempted": n, "failed": 0,
            # the GA program's modules, and the evaluation steps and the
            # fitness work of each call
            "modules": tuple(self.traffic["ga_program_modules"]),
            "fitness_steps_per_call": (self.ga["generations"] + 1)
            * self.config["workload"]["tasks"],
            "fitness_work_per_call": [self.work(g) for g, _ in self.calls],
        }

    def work(self, group: list[int]) -> tuple[int, int]:
        """Operations and bytes the fitness evaluations of one call need."""
        nodes = [self.raw[i][0] for i in group]
        wfs = [self.raw[i][1] for i in group]
        return roofline.fitness_work(
            tasks=self.config["workload"]["tasks"], nodes=self.config["system"]["nodes"],
            cmax=int(max(n["cores"] for ns in nodes for n in ns)),
            maxp=max(len(t["deps"]) for ws in wfs for w in ws for t in w["tasks"]),
            population=self.ga["pop_size"], evaluations=self.ga["generations"] + 1,
            instances=len(group))

    def check(self, control: bool = False) -> tuple[dict, float]:
        """Replay every issued schedule with the reference; returns the
        numbers compared and the mean makespan over the critical-path bound
        (with ``control``, the control's readings in place of the numbers)."""
        per_instance: dict[int, list] = {}
        for group, results in self.calls:
            for i, res in zip(group, results):
                per_instance.setdefault(i, []).append(res)
        device = self.device_objectives()
        issued = []
        for i, results in per_instance.items():
            issued.append((listsched.build_model(*self.raw[i]), self.problems[i].task_names,
                           results, device[i]))
        return compare.sweep_numbers(issued, self.config["weights"], control=control)

    def device_objectives(self) -> dict[int, list[float]]:
        """Per instance, in the order of its calls, the objective that the
        engine's evaluator gives each returned best on the chip: per call, a
        population of the GA's size per instance (the best, repeated) at the
        call's packed bucket; padded tasks sit on node 0, as in the sweep."""
        from repro.engine.packed import stack_packed

        w = self.config["weights"]
        out: dict[int, list[float]] = {}
        for group, results in self.calls:
            problems = [self.problems[i] for i in group]
            arrays, bucket = stack_packed(problems)
            pop = np.zeros((len(group), self.ga["pop_size"], bucket[0]), dtype=np.int32)
            for b, res in enumerate(results):
                pop[b, :, : len(res.schedule.assignment)] = res.schedule.assignment
            fitness = _batched_fitness(w["usage_mode"], any(p.has_constraints for p in problems))
            obj, _ = fitness(pop, arrays, float(w["alpha"]), float(w["beta"]))
            for i, value in zip(group, np.asarray(obj)[:, 0]):
                out.setdefault(i, []).append(float(value))
        return out
