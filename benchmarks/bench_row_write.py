"""Time the evaluator's core-state row write on a TPU: XLA's scatter against
the row-DMA kernel (``repro.kernels.rowdma``), and the gather that reads
the same rows.

Each variant runs a loop of ``--steps`` steps over a ``[B, P, N, W]`` f32
state, as the evaluator's scan does, and writes (or reads) one row of each
of the ``B x P`` candidates a step, at a node that moves with the step.  A
loop that only makes the rows and indices is the baseline taken off every
variant.  Prints one JSON line per shape (``SHAPES``) with ns per row and
us per step.

    python benchmarks/bench_row_write.py [--steps 512] [--reps 5]

It refuses to run anywhere but a TPU.  ``--compile-only`` compiles every
variant for a described v5e and prints what each program holds instead.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import rowdma  # noqa: E402

#: (B, P, N, W): the Table IX cells (8 instances on one chip, 2 a chip on
#: four), a singleton solve, and an admission group's candidates
SHAPES = ((8, 64, 512, 128), (2, 64, 512, 128), (1, 64, 512, 128), (1, 16, 1024, 128))


def _step_inputs(t, B, P, N, W):
    """Rows and node indices of step ``t``: cheap, and new every step."""
    b = jnp.arange(B, dtype=jnp.int32)[:, None]
    p = jnp.arange(P, dtype=jnp.int32)[None, :]
    idx = (t * 7 + p * 13 + b * 5) % N
    rows = jnp.broadcast_to(t.astype(jnp.float32), (B, P, W)) + idx[..., None].astype(jnp.float32)
    return rows, idx


def _scatter(state, rows, idx):
    return jax.vmap(jax.vmap(lambda s, i, r: s.at[i].set(r)))(state, idx, rows)


def _dma(state, rows, idx, unroll):
    last = jnp.ones(state.shape[:1], bool)
    one = functools.partial(rowdma.row_dma, unroll=unroll)
    return jax.vmap(one)(state, rows, idx, last)


def _loop(write, steps):
    def run(state):
        B, P, N, W = state.shape

        def body(t, carry):
            state, acc = carry
            rows, idx = _step_inputs(t, B, P, N, W)
            if write == "gather":
                got = jax.vmap(jax.vmap(lambda s, i: s[i]))(state, idx)
                return state, acc + got
            if write == "none":
                return state, acc + rows
            if write == "scatter":
                return _scatter(state, rows, idx), acc
            return _dma(state, rows, idx, int(write.split("_u")[1])), acc

        acc0 = jnp.zeros((B, P, W), jnp.float32)
        return jax.lax.fori_loop(0, steps, body, (state, acc0))

    return jax.jit(run, donate_argnums=0)


#: ``dma_u<n>``: the kernel starting ``n`` copies per turn of its loop
VARIANTS = ("none", "gather", "scatter", "dma_u1", "dma_u8", "dma_u64")


def measure(shape, steps, reps):
    B, P, N, W = shape
    out = {"shape": list(shape), "rows_per_step": B * P, "steps": steps}
    best = {}
    for v in VARIANTS:
        run = _loop(v, steps)
        state = jnp.zeros(shape, jnp.float32)
        state, acc = run(state)  # compile and warm
        jax.block_until_ready((state, acc))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            state, acc = run(state)
            jax.block_until_ready((state, acc))
            times.append(time.perf_counter() - t0)
        best[v] = min(times)
        out[f"{v}_s"] = best[v]
    for v in VARIANTS[1:]:
        per_step = (best[v] - best["none"]) / steps
        out[f"{v}_us_per_step"] = per_step * 1e6
        out[f"{v}_ns_per_row"] = per_step * 1e9 / (B * P)
    # the kernel's result equals the scatter's
    a, _ = _loop("scatter", steps)(jnp.zeros(shape, jnp.float32))
    b, _ = _loop(f"dma_u{rowdma.UNROLL}", steps)(jnp.zeros(shape, jnp.float32))
    out["dma_equals_scatter"] = bool(jnp.array_equal(a, b))
    return out


def compile_only(shape, steps):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=SingleDeviceSharding(topo.devices[0]))
    out = {"shape": list(shape)}
    for v in VARIANTS:
        compiled = _loop(v, steps).lower(arg).compile()
        text = compiled.as_text()
        out[v] = {
            "custom_calls": text.count("tpu_custom_call"),
            "scatters": text.count(" scatter("),
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=512)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args(argv)
    if args.compile_only:
        for shape in SHAPES:
            print(json.dumps(compile_only(shape, args.steps)), flush=True)
        return 0
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    for shape in SHAPES:
        line = measure(shape, args.steps, args.reps)
        line["device"] = dev.device_kind
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
