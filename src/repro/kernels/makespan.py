"""Pallas TPU kernel: population schedule evaluation (metaheuristic fitness).

This is the paper's scale bottleneck (Table IX: serial GA fitness at 500×500
took 6513 s) laid out for the TPU rather than ported:

* the *population* is the lane axis — each grid step evaluates a tile of up
  to 128 candidate assignments, one candidate per vector lane, so every
  vector op of the task loop is batched over the tile;
* everything that depends only on the assignment (the per-candidate task
  duration, core count and the predecessor-transfer times of Eq. 5) is a
  gather, done by XLA before the kernel; the kernel runs only the true
  dependency chain — list scheduling over the tasks in topological order;
* that chain keeps its state in VMEM: the core-free times ``[N, CMAX, TILE]``
  and the finish times ``[T, TILE]`` never leave the chip during a tile;
* the node a candidate picked is selected by a masked pass over the node
  axis (a per-lane gather has no vector form), and the k-th-smallest core
  uses the comparison-rank rule of :mod:`repro.kernels.select` — the same
  values in the same order as the jnp reference and the numpy oracle, so all
  three agree bit for bit.

Two placement modes for the transfer times ``[T, MAXP, TILE]``:

* **resident** — the tile's block lives wholly in VMEM;
* **streamed** — the array stays in HBM and each task step double-buffers its
  ``[MAXP, TILE]`` slab into VMEM by async DMA, prefetching task ``j+1``
  while computing task ``j``.  VMEM then holds O(MAXP) instead of O(T·MAXP)
  transfer rows per tile.

``vmem_bytes`` gives one grid step's VMEM footprint; the dispatcher in
:mod:`repro.kernels.ops` picks the mode from it and falls back to the jnp
reference (``ref.population_makespan_ref``) outside the envelope.

On a TPU the kernel compiles with Mosaic; anywhere else it runs in the
Pallas interpreter (tests/test_kernels_makespan.py,
tests/test_fastpath_equivalence.py check it against both references, and
tests/test_tpu_compile.py compiles it for a v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: TPU vector lane width — the widest population tile of one grid step
LANES = 128
#: VMEM one grid step may use; v5e has 128 MiB per core, and the rest is
#: left to Mosaic's own scratch
VMEM_BUDGET = 96 << 20
#: SMEM for the predecessor ids and release times (1 MiB per core on v5e)
SMEM_BUDGET = 512 << 10

_NEG = -1e30


def interpret_mode() -> bool:
    """Pallas kernels compile natively on a TPU and run in the Pallas
    interpreter on every other platform."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(T: int, N: int, cmax: int, maxp: int, tile: int, stream: bool) -> int:
    """VMEM bytes one grid step allocates.  Mosaic pads the last two dims of
    every buffer to (8, 128) f32 tiles, and pipelined blocks are
    double-buffered."""
    lanes = _round_up(tile, LANES)
    c8, p8, t8 = _round_up(cmax, 8), _round_up(maxp, 8), _round_up(T, 8)
    words = N * c8 * lanes + c8 * lanes  # core-free state + gathered row
    words += 2 * 4 * t8 * lanes  # assign, duration, cores in; finish out
    words += 2 * (1 if stream else T) * p8 * lanes  # transfer times
    return 4 * words


def smem_bytes(T: int, maxp: int) -> int:
    """SMEM bytes for the flattened predecessor ids and the release times."""
    return 4 * T * (maxp + 1)


def _kernel(
    preds_ref,  # SMEM [T * MAXP] int32, -1 = no predecessor
    release_ref,  # SMEM [T] f32
    assign_ref,  # [T, TILE] int32 node per task and candidate
    dur_ref,  # [T, TILE] f32 duration of each task on its node
    take_ref,  # [T, TILE] f32 cores each task occupies on its node
    tt_ref,  # [T, MAXP, TILE] f32 block, or [G, T, MAXP, TILE] in HBM (streamed)
    init_ref,  # HBM [N, CMAX, TILE] f32 initial core-free times
    finish_ref,  # out [T, TILE] f32
    core_free,  # scratch [N, CMAX, TILE] f32
    row_buf,  # scratch [CMAX, TILE] f32
    init_sem,  # DMA semaphore
    *stream_scratch,  # streamed: tt slab double buffer [2, MAXP, TILE] + DMA sems (2,)
    maxp: int,
    stream: bool,
):
    tasks, tile = finish_ref.shape
    n_nodes, cmax, _ = core_free.shape
    init = pltpu.make_async_copy(init_ref, core_free, init_sem)
    init.start()
    finish_ref[...] = jnp.zeros((tasks, tile), jnp.float32)

    if stream:
        tt_buf, tt_sem = stream_scratch
        g = pl.program_id(0)

        def tt_dma(slot, j):
            return pltpu.make_async_copy(tt_ref.at[g, j], tt_buf.at[slot], tt_sem.at[slot])

        tt_dma(0, 0).start()
    init.wait()
    m_iota = jax.lax.broadcasted_iota(jnp.int32, (cmax, tile), 0)

    def step(j, carry):
        if stream:
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < tasks)
            def _prefetch():
                tt_dma(1 - slot, j + 1).start()

            tt_dma(slot, j).wait()

            def transfer(s):
                return tt_buf[slot, pl.ds(s, 1), :]
        else:

            def transfer(s):
                return tt_ref[j, pl.ds(s, 1), :]

        node = assign_ref[pl.ds(j, 1), :]  # [1, TILE]

        # --- ready time (Eq. 12 with Eq. 5 data migration) ------------------
        def pred(s, ready):
            q = preds_ref[j * maxp + s]
            arrive = finish_ref[pl.ds(jnp.maximum(q, 0), 1), :] + transfer(s)
            return jnp.where(q >= 0, jnp.maximum(ready, arrive), ready)

        ready = jnp.full((1, tile), release_ref[j], jnp.float32)
        ready = jax.lax.fori_loop(0, maxp, pred, ready)

        # --- core selection: start at the stable k-th smallest free time ----
        def gather(n, row):
            return jnp.where(node == n, core_free[n], row)

        row = jax.lax.fori_loop(0, n_nodes, gather, jnp.zeros((cmax, tile), jnp.float32))
        row_buf[...] = row

        def rank(m, acc):  # select.stable_ranks along the sublane axis
            other = row_buf[pl.ds(m, 1), :]
            before = (other < row) | ((other == row) & (m < m_iota))
            return acc + before.astype(jnp.float32)

        ranks = jax.lax.fori_loop(0, cmax, rank, jnp.zeros((cmax, tile), jnp.float32))
        take = take_ref[pl.ds(j, 1), :]
        kth = jnp.sum(jnp.where(ranks == take - 1.0, row, 0.0), axis=0, keepdims=True)
        fin = jnp.maximum(ready, kth) + dur_ref[pl.ds(j, 1), :]

        # --- state updates ----------------------------------------------------
        new_row = jnp.where(ranks < take, fin, row)

        def scatter(n, c):
            core_free[n] = jnp.where(node == n, new_row, core_free[n])
            return c

        jax.lax.fori_loop(0, n_nodes, scatter, 0)
        finish_ref[pl.ds(j, 1), :] = fin
        return carry

    jax.lax.fori_loop(0, tasks, step, 0)


@functools.partial(jax.jit, static_argnames=("tile", "stream", "interpret"))
def _population_makespan(
    assignments, durations, cores, data, feasible, release, pred_matrix, dtr,
    init_free, deadline, *, tile: int, stream: bool, interpret: bool,
):
    P, T = assignments.shape
    N = durations.shape[1]
    maxp = pred_matrix.shape[1]
    cmax = init_free.shape[1]
    if P % tile:
        raise ValueError(f"population {P} is not a multiple of tile {tile}")
    if not interpret and tile % LANES:
        # Mosaic tiles HBM and VMEM buffers in whole 128-lane rows
        raise ValueError(f"a TPU tile is a multiple of {LANES} lanes, got {tile}")
    f32 = jnp.float32
    # assignment-only gathers, the same expressions as
    # ref.population_makespan_ref, built directly with the population on the
    # minor (lane) axis: XLA:TPU compiles these gathers in about a second,
    # the same gathers population-major in tens of seconds
    a = assignments.astype(jnp.int32).T  # [T, P]
    dur = jnp.take_along_axis(durations.astype(f32), a, axis=1)  # [T, P]
    # padding entries are "never free" (+1e30); real cores start ≤ horizon
    node_cores = jnp.maximum(jnp.sum(init_free < 1e29, axis=1), 1).astype(f32)
    take = jnp.maximum(jnp.minimum(cores.astype(f32)[:, None], node_cores[a]), 1.0)
    psafe = jnp.maximum(pred_matrix.astype(jnp.int32), 0)  # [T, MAXP]
    p_nodes = a[psafe]  # [T, MAXP, P]
    node = a[:, None, :]
    rate = dtr.astype(f32)[p_nodes, node]
    tt = jnp.where(p_nodes == node, 0.0, data.astype(f32)[psafe][:, :, None] / rate)
    if stream:  # one contiguous [MAXP, TILE] slab per (tile, task) DMA
        tt = jnp.transpose(tt.reshape(T, maxp, P // tile, tile), (2, 0, 1, 3))
    init = jnp.broadcast_to(init_free.astype(f32)[:, :, None], (N, cmax, tile))

    def lane_block():
        return pl.BlockSpec((T, tile), lambda g: (0, g))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    scratch = [
        pltpu.VMEM((N, cmax, tile), f32),
        pltpu.VMEM((cmax, tile), f32),
        pltpu.SemaphoreType.DMA(()),
    ]
    if stream:
        scratch += [pltpu.VMEM((2, maxp, tile), f32), pltpu.SemaphoreType.DMA((2,))]
    limit = min(vmem_bytes(T, N, cmax, maxp, tile, stream) + (16 << 20), 120 << 20)
    finish = pl.pallas_call(
        functools.partial(_kernel, maxp=maxp, stream=stream),
        grid=(P // tile,),
        in_specs=[
            smem,
            smem,
            lane_block(),
            lane_block(),
            lane_block(),
            hbm if stream else pl.BlockSpec((T, maxp, tile), lambda g: (0, 0, g)),
            hbm,
        ],
        out_specs=lane_block(),
        out_shape=jax.ShapeDtypeStruct((T, P), f32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=limit
        ),
        interpret=interpret,
        name="population_makespan",
    )(
        pred_matrix.astype(jnp.int32).reshape(-1),
        release.astype(f32),
        a,
        dur,
        take,
        tt,
        init,
    )  # [T, P] finish times
    makespan = jnp.max(finish, axis=0, initial=0.0)
    feas = jnp.take_along_axis(feasible.astype(bool), a, axis=1)
    violations = jnp.sum(~feas, axis=0).astype(f32)
    if deadline is not None:
        late = finish > deadline.astype(f32)[:, None]
        violations = violations + jnp.sum(late, axis=0).astype(f32)
    return makespan, violations


def population_makespan_pallas(
    assignments: jax.Array,  # [P, T] int32
    durations: jax.Array,  # [T, N] f32
    cores: jax.Array,  # [T]
    data: jax.Array,  # [T] f32
    feasible: jax.Array,  # [T, N] bool
    release: jax.Array,  # [T] f32
    pred_matrix: jax.Array,  # [T, MAXP] int32
    dtr: jax.Array,  # [N, N] f32
    init_free: jax.Array,  # [N, CMAX] f32
    deadline: jax.Array | None = None,  # [T] f32 (1e30 = unconstrained)
    *,
    tile: int = LANES,
    stream: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(makespan[P], violations[P])``.  ``tile`` candidates share
    one grid step; the population is padded to a multiple of it with
    all-zero assignments, whose results are dropped.  ``stream=True`` keeps
    the transfer times in HBM and DMA-streams one task's slab per step."""
    P, T = assignments.shape
    pad = (-P) % tile
    if pad:
        assignments = jnp.concatenate(
            [assignments, jnp.zeros((pad, T), assignments.dtype)], axis=0
        )
    mk, viol = _population_makespan(
        assignments, durations, cores, data, feasible, release, pred_matrix,
        dtr, init_free, deadline, tile=tile, stream=stream, interpret=interpret_mode(),
    )
    return mk[:P], viol[:P]
