"""The population fitness evaluator in jnp.

``population_makespan_ref`` list-schedules every candidate of a population
and returns its makespan and violations.  The compiled searches run it on
every platform unless the Pallas kernel (``makespan.py``) is asked for, and
it is the numerical contract that kernel must match.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import rowdma
from repro.kernels.select import kth_from_ranks, stable_ranks, update_from_ranks

_NEG = -1e30
#: what a stored core-state row holds beyond its node's ``CMAX`` cores
#: (never read)
_PAD = 1e30


def population_makespan_ref(
    assignments: jax.Array,  # [P, T] int32 (tasks topologically ordered)
    *,
    durations: jax.Array,  # [T, N] f32
    cores: jax.Array,  # [T] int32 (>= 1)
    data: jax.Array,  # [T] f32 output sizes
    feasible: jax.Array,  # [T, N] bool
    release: jax.Array,  # [T] f32
    pred_rows: jax.Array,  # [S, K] int32 predecessors of row_task, -1 padded
    dtr: jax.Array,  # [N, N] f32 (large finite instead of inf on diag)
    init_free: jax.Array,  # [N, Cmax] f32 (inf-padded beyond node cores)
    node_cores: jax.Array | None = None,  # [N] int32
    deadline: jax.Array | None = None,  # [T] f32 latest finish (1e30 = none)
    row_task: jax.Array | None = None,  # [S] int32 task of each row
    row_last: jax.Array | None = None,  # [S] bool the task's last row
) -> tuple[jax.Array, jax.Array]:
    """Capacity-aware core-granular list scheduling (see
    ``repro.core.evaluator`` for the semantics).  Returns
    ``(makespan[P], violations[P])``.

    ``deadline`` (when given) adds one violation per task finishing past its
    deadline — deadlines are checked here because finish times only exist
    inside the scheduling scan.

    Predecessors come in rows of ``K`` slots: a task's rows are contiguous
    and in task order, and each folds the ready terms of its predecessors
    into a running ready time; the task's last row (``row_last``) places it,
    and its earlier rows leave the core state and finish times as they are.
    ``max`` is exact, so the result is the one a single row of all the
    task's predecessors gives.  With ``S == T`` row ``s`` is task ``s``
    (``row_task`` and ``row_last`` are not read), and the scan is one step
    per task.

    The population is the minor axis, as in the Pallas kernel: one scan over
    the rows carries the finish times ``[T, P]``, and a row's predecessor
    terms are whole rows that every candidate shares.  Nothing fetches single
    elements by a candidate's node, which XLA:TPU does one element at a time
    (about 12 ns each on a v5e): a candidate's link rates are the row
    ``dtr.T[i]``, selected at the predecessors' nodes by a masked sum over
    the node axis, and its duration, core count and feasibility are selected
    the same way before the scan.  A masked sum of one value and zeros is
    that value, so every number is the one an indexed read gives.

    The core state ``[P, N, W]`` stores each row ``W`` lanes wide
    (:func:`repro.kernels.rowdma.row_width`); a step reads the first
    ``CMAX`` and writes every candidate's new row back through
    :func:`repro.kernels.rowdma.write_rows`: async row copies on a TPU,
    which skip a row whose ``row_last`` is false, XLA's scatter elsewhere.
    The trace-time counter ``engine.traced.row_dma`` counts evaluators
    traced with that write."""
    T, N = durations.shape
    if node_cores is None:
        # padding entries are "never free" (+1e30); real cores start ≤ horizon
        node_cores = jnp.sum(init_free < 1e29, axis=1).astype(jnp.int32)
        node_cores = jnp.maximum(node_cores, 1)
    a = assignments.astype(jnp.int32).T  # [T, P]
    P = a.shape[1]
    node_ids = jnp.arange(N, dtype=jnp.int32)
    on_node = a[:, None, :] == node_ids[:, None]  # [T, N, P]

    def at_node(table):  # [T, N] -> table[t, a[t, p]] as [T, P]
        return jnp.sum(jnp.where(on_node, table[:, :, None], 0), axis=1).astype(table.dtype)

    dur = at_node(durations)
    caps = at_node(jnp.broadcast_to(node_cores, (T, N)))
    take = jnp.maximum(jnp.minimum(cores[:, None], caps), 1)
    feas = jnp.any(on_node & feasible[:, :, None], axis=1)
    valid = pred_rows >= 0
    psafe = jnp.where(valid, pred_rows, 0)
    pred_data = data[psafe]  # [S, K]
    rate_rows = dtr.T  # rate_rows[i, n] = dtr[n, i]

    cmax = init_free.shape[1]
    width = rowdma.row_width(cmax)  # stored row width: whole lane tiles

    def place(core_free, i, ready, c, d):  # one candidate's node
        stored = core_free[i]
        row = stored[:cmax]
        # O(CMAX²) comparison-rank select — no sort, no gather/scatter;
        # shares the primitive (and thus bit-exact values) with the Pallas
        # kernel.
        ranks = stable_ranks(row)
        kth = kth_from_ranks(row, ranks, c)
        f = jnp.maximum(ready, kth) + d
        new = update_from_ranks(row, ranks, c, f)
        return jnp.pad(new, (0, width - cmax), constant_values=_PAD), stored, f

    def write(core_free, i, ready, c, d, last=None):
        # a task's earlier rows (``last`` false) claim no core
        new, stored, f = jax.vmap(place)(core_free, i, ready, c, d)
        return rowdma.write_rows(core_free, new, i, last, stored), f

    def fold(ready, fin, i, ps, ok, dp):  # ready after one row's predecessors
        with jax.named_scope("preds"):
            p_nodes = a[ps]  # [K, P]
            rows = rate_rows[i]  # [P, N]
            hit = p_nodes[:, :, None] == node_ids  # [K, P, N]
            # where, not a product with a one-hot: 0 * inf would be NaN
            rate = jnp.sum(jnp.where(hit, rows, 0.0), axis=-1)
            transfer = jnp.where(p_nodes == i, 0.0, dp[:, None] / rate)
            ready_terms = jnp.where(ok[:, None], fin[ps] + transfer, _NEG)
            return jnp.maximum(ready, jnp.max(ready_terms, axis=0, initial=-1e30))

    def step(carry, x):  # one row per task
        core_free, fin = carry  # [P, N, Cmax], [T, P]
        j, i, ps, ok, dp, r, d, c = x  # i, d, c [P]; ps, ok, dp [K]
        ready = fold(r, fin, i, ps, ok, dp)
        core_free, f = write(core_free, i, ready, c, d)
        return (core_free, fin.at[j].set(f)), None

    def row_step(carry, x):  # a task over one or more rows
        core_free, fin, acc = carry  # acc [P]: ready so far over the task's rows
        j, i, ps, ok, dp, r, d, c, last = x
        acc = fold(acc, fin, i, ps, ok, dp)
        core_free, f = write(core_free, i, jnp.maximum(r, acc), c, d, last)
        fin = fin.at[j].set(jnp.where(last, f, fin[j]))
        return (core_free, fin, jnp.where(last, _NEG, acc)), None

    # state shaped from the inputs, so under shard_map the carry varies over
    # the same mesh axes as what the scan writes into it
    fin0 = jnp.zeros_like(dur, dtype=jnp.float32)
    stored0 = jnp.pad(init_free, ((0, 0), (0, width - cmax)), constant_values=_PAD)
    core_free0 = jnp.broadcast_to(stored0, (P,) + stored0.shape)
    obs.METRICS.counter("engine.traced.row_dma").inc()  # trace-time count
    if pred_rows.shape[0] == T:
        xs = (jnp.arange(T), a, psafe, valid, pred_data, release, dur, take)
        (_, fin), _ = jax.lax.scan(step, (core_free0, fin0), xs)
    else:
        xs = (row_task, a[row_task], psafe, valid, pred_data, release[row_task],
              dur[row_task], take[row_task], row_last)
        acc0 = jnp.full_like(dur[0], _NEG)
        (_, fin, _), _ = jax.lax.scan(row_step, (core_free0, fin0, acc0), xs)
    makespan = jnp.max(fin, axis=0, initial=0.0)
    violations = jnp.sum(~feas, axis=0).astype(jnp.float32)
    if deadline is not None:
        violations = violations + jnp.sum(fin > deadline[:, None], axis=0).astype(jnp.float32)
    return makespan, violations
