"""Kernel-layer microbenchmarks: µs/call for the jnp oracle paths (the
CPU-measurable throughput proxies) and one interpret-mode Pallas call per
kernel at a reduced shape (functional-cost reference, not TPU timing)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Workload, build_problem, mri_system, random_layered_workflow, synthetic_system
from repro.engine import pack, population_fitness_fn
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.makespan import population_makespan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


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

    # --- attention -------------------------------------------------------------
    q = jnp.asarray(rng.standard_normal((1, 8, 1024, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 1024, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 1024, 64)), jnp.float32)
    fa = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    us = _time(fa, q, k, v)
    flops = 4 * 8 * 1024 * 1024 * 64 / 2  # causal
    rows.append(("attention_ref_1k", us, f"gflops_per_s={flops / us / 1e3:.1f}"))

    qq = q[:, :, :256]
    us = _time(
        lambda a, b, c: flash_attention_pallas(a, b, c, block_q=128, block_k=128),
        qq, k, v, iters=2, warmup=1,
    )
    rows.append(("attention_pallas_interp_256", us, "interpret-mode functional check"))

    # --- decode attention -------------------------------------------------------
    qd = jnp.asarray(rng.standard_normal((8, 8, 64)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((8, 2, 4096, 64)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((8, 2, 4096, 64)), jnp.float32)
    lens = jnp.full((8,), 4096, jnp.int32)
    da = jax.jit(lambda q, k, v, l: ref.decode_attention_ref(q, k, v, l))
    us = _time(da, qd, kc, vc, lens)
    bytes_read = 8 * 2 * 4096 * 64 * 4 * 2
    rows.append(("decode_ref_4k", us, f"gb_per_s={bytes_read / us / 1e3:.2f}"))

    # --- SSD scan ---------------------------------------------------------------
    x = jnp.asarray(rng.standard_normal((1, 2048, 8, 64)), jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.standard_normal((1, 2048, 8)), jnp.float32)) * 0.1 + 0.01
    Am = -jnp.abs(jnp.asarray(rng.standard_normal(8), jnp.float32)) - 0.2
    Bm = jnp.asarray(rng.standard_normal((1, 2048, 1, 64)), jnp.float32) * 0.3
    Cm = jnp.asarray(rng.standard_normal((1, 2048, 1, 64)), jnp.float32) * 0.3
    chunked = jax.jit(lambda *a: ref.ssd_scan_chunked_ref(*a, chunk=128))
    seq = jax.jit(lambda *a: ref.ssd_scan_ref(*a))
    us_c = _time(chunked, x, dt, Am, Bm, Cm)
    us_s = _time(seq, x, dt, Am, Bm, Cm, iters=2, warmup=1)
    rows.append(("ssd_chunked_2k", us_c, f"speedup_vs_sequential={us_s / us_c:.1f}x"))
    us_k = _time(
        lambda *a: ssd_scan_pallas(*a, chunk=128),
        x[:, :256], dt[:, :256], Am, Bm[:, :256], Cm[:, :256], iters=2, warmup=1,
    )
    rows.append(("ssd_pallas_interp_256", us_k, "interpret-mode functional check"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
