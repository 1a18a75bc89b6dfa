"""Pallas TPU kernel: write each candidate's core-state row back by DMA.

Each task step of the jnp evaluator (:mod:`repro.kernels.ref`) places every
candidate's task on one node and writes that node's new core-free row into
the ``[P, N, W]`` state.  XLA:TPU compiles ``state.at[i].set(row)`` under
the vmap over candidates to a scatter of one-row windows, issued one after
another: about 77 ns a row on a v5e, six times the gather that reads the
same row.  This kernel starts one HBM→HBM async copy per candidate row,
then waits for them all, so the copies overlap.  The state is aliased to
the output and written in place.

* Rows are stored ``W`` lanes wide, a multiple of 128 (:func:`row_width`):
  Mosaic moves whole lane tiles, and XLA already lays a 64-core row out in
  128 lanes, so the padding costs no memory.
* A batch of instances reaches one kernel through a ``custom_vmap`` rule
  that folds the vmapped axes into the leading one, so every row of every
  instance is one kernel's copy.  (``pallas_call``'s own batching of an
  HBM operand would loop over the instances and copy the whole state.)
* An instance whose ``last`` is false (a row of a task that places
  nothing yet) starts no copy and keeps its state.

:func:`write_rows` picks the path when the program is lowered: the kernel
on a TPU, ``.at[i].set`` everywhere else.  ``interpret=True`` runs the
kernel in the Pallas TPU interpreter (tests).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: TPU vector lane width: the unit of a row's stored width
LANES = 128
#: row copies started (and waited for) per turn of the kernel's loop: 64
#: a turn beat 8 and 1 on a v5e (``benchmarks/bench_row_write.py``)
UNROLL = 64


def row_width(cmax: int) -> int:
    """Stored width of a core-state row of ``cmax`` cores."""
    return -(-cmax // LANES) * LANES


def _kernel(idx_ref, last_ref, rows_ref, _state_ref, out_ref, sem, *, unroll):
    # idx_ref SMEM [B * P] int32, last_ref SMEM [B] int32; rows_ref HBM
    # [B, P, 1, W]; out_ref (aliased to _state_ref) HBM [B, P, N, W]
    B, P = rows_ref.shape[:2]

    def copy(b, p):
        node = idx_ref[b * P + p]
        return pltpu.make_async_copy(
            rows_ref.at[b, p], out_ref.at[b, p, pl.ds(node, 1)], sem
        )

    def each(act):  # act on the copy of every row of every writing instance
        def instance(b, c):
            @pl.when(last_ref[b] != 0)
            def _():
                def turn(q, c):  # ``unroll`` rows a turn (Mosaic unrolls all or none)
                    for k in range(unroll):
                        act(copy(b, q * unroll + k))
                    return c

                jax.lax.fori_loop(0, P // unroll, turn, 0)

            return c

        jax.lax.fori_loop(0, B, instance, 0)

    each(lambda c: c.start())  # every copy is in flight before any wait
    each(lambda c: c.wait())


@functools.partial(jax.jit, static_argnames=("interpret", "unroll"))
def _row_dma(state, rows, idx, last, *, interpret=False, unroll=UNROLL):
    """``state [B, P, N, W]`` with ``state[b, p, idx[b, p]] = rows[b, p]``
    for every instance ``b`` whose ``last[b]`` is true."""
    B, P, _, W = state.shape
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, unroll=math.gcd(unroll, P)),
        # varies over the mesh axes the state does, under shard_map
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype, vma=jax.typeof(state).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[any_space, any_space],
            out_specs=any_space,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        input_output_aliases={3: 0},
        interpret=pltpu.InterpretParams() if interpret else False,
        name="row_dma",
    )(
        idx.astype(jnp.int32).reshape(B * P),
        last.astype(jnp.int32),
        rows.reshape(B, P, 1, W),
        state,
    )


@functools.lru_cache(maxsize=None)
def _batched(interpret: bool, unroll: int = UNROLL):
    """:func:`_row_dma` with a vmap rule that joins a vmapped axis to the
    leading one: ``[A, B, ...]`` → ``[A * B, ...]``, one kernel for all."""

    @jax.custom_batching.custom_vmap
    def write(state, rows, idx, last):
        return _row_dma(state, rows, idx, last, interpret=interpret, unroll=unroll)

    @write.def_vmap
    def _fold(axis_size, in_batched, state, rows, idx, last):
        args = [
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((state, rows, idx, last), in_batched)
        ]
        out = write(*(x.reshape((-1,) + x.shape[2:]) for x in args))
        return out.reshape(args[0].shape), True

    return write


def row_dma(state, rows, idx, last, *, interpret=False, unroll=UNROLL):
    """The kernel for one instance: ``state [P, N, W]``, ``rows [P, W]``,
    ``idx [P]``, ``last`` a bool scalar.  Under vmap every instance's rows
    go to one kernel."""
    out = _batched(interpret, unroll)(
        state[None], rows[None], idx[None], jnp.reshape(last, (1,))
    )
    return jnp.reshape(out, state.shape)


def _scatter(state, rows, idx, last, old):
    """XLA's path: one ``.at[i].set`` per candidate under vmap."""
    if last is not None:
        rows = jnp.where(last, rows, old)
    return jax.vmap(lambda s, i, r: s.at[i].set(r))(state, idx, rows)


def _dma(state, rows, idx, last, old, *, interpret=False):
    """The TPU's path: the kernel, which leaves ``old`` unread."""
    del old
    return row_dma(state, rows, idx, True if last is None else last, interpret=interpret)


def write_rows(state, rows, idx, last=None, old=None):
    """``state [P, N, W]`` with candidate ``p``'s row ``idx[p]`` set to
    ``rows[p]`` (``[P, W]``) when ``last`` (a scalar; None = always) holds;
    ``old`` (``[P, W]``, the rows as read) is what XLA's path writes back
    when it does not.  A TPU lowers the DMA kernel, every other platform
    the scatter."""
    return jax.lax.platform_dependent(
        state, rows, idx, last, old, tpu=_dma, default=_scatter
    )
