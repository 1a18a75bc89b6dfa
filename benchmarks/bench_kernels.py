"""Kernel-layer microbenchmarks: µs/call for the jnp fitness evaluator and
one interpret-mode call of the Pallas makespan kernel at a reduced shape (a
functional-cost reference, not a TPU timing)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Workload, build_problem, random_layered_workflow, synthetic_system
from repro.engine import pack, population_fitness_fn
from repro.kernels.makespan import population_makespan_pallas


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> list[tuple]:
    rows = []
    rng = np.random.default_rng(0)

    # --- population fitness (the paper's MH hot spot) -------------------------
    system = synthetic_system(16, seed=0)
    wf = random_layered_workflow(128, seed=0, max_cores=8, feature_pool=("F1",))
    prob = build_problem(system, Workload((wf,)))
    fit = population_fitness_fn(prob, engine="jax")
    A = jnp.asarray(rng.integers(0, prob.num_nodes, (64, prob.num_tasks)), jnp.int32)
    us = _time(fit, A)
    rows.append(("fitness_jnp_128tx16n_pop64", us, f"cand_per_s={64 / (us / 1e6):.0f}"))

    jp = pack(prob, pad=False).device_arrays()
    small = jnp.asarray(rng.integers(0, prob.num_nodes, (8, prob.num_tasks)), jnp.int32)
    us = _time(
        lambda a: population_makespan_pallas(
            a, jp["durations"], jp["cores"], jp["data"], jp["feasible"],
            jp["release"], jp["pred_rows"], jp["dtr"], jp["init_free"], tile=8,
        ),
        small, iters=2, warmup=1,
    )
    rows.append(("fitness_pallas_interp_pop8", us, "interpret-mode functional check"))

    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
