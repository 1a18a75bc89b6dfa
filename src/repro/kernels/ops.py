"""Dispatch of the population fitness evaluator.

``population_makespan`` runs the jnp evaluator (``ref.py``) unless the
Pallas makespan kernel (``makespan.py``) is asked for:

* ``configure(use_pallas=...)`` or env ``REPRO_USE_PALLAS=1`` turns the
  Pallas path on, and ``force=True`` (the ``pallas`` engine) takes it for
  one call.  The platform alone decides how the kernel runs: natively on a
  TPU, in the Pallas interpreter anywhere else
  (:func:`repro.kernels.makespan.interpret_mode`).
* The call falls back to the jnp evaluator whenever the instance exceeds
  the kernel's VMEM/SMEM envelope, or a join spreads over more than one
  predecessor row.  The ``engine.traced.pallas`` / ``engine.traced.ref``
  counters count how often each path was *traced*: under ``jit`` that is
  once per compiled program, not once per call.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import makespan, ref


@dataclasses.dataclass
class KernelConfig:
    use_pallas: bool = bool(int(os.environ.get("REPRO_USE_PALLAS", "0")))


_CONFIG = KernelConfig()


def configure(use_pallas: bool | None = None) -> KernelConfig:
    global _CONFIG
    if use_pallas is not None:
        _CONFIG = dataclasses.replace(_CONFIG, use_pallas=use_pallas)
    return _CONFIG


def kernel_config() -> KernelConfig:
    return _CONFIG


def _makespan_mode(T: int, N: int, cmax: int, maxp: int) -> bool | None:
    """The kernel's placement of the transfer times — VMEM-resident
    (``False``) when it fits, else DMA-streamed (``True``) — or None when
    even the streamed kernel busts the VMEM or SMEM budget (→ jnp oracle).
    Every TPU tile is one 128-lane population slab."""
    if makespan.smem_bytes(T, maxp) > makespan.SMEM_BUDGET:
        return None
    for stream in (False, True):
        if makespan.vmem_bytes(T, N, cmax, maxp, makespan.LANES, stream) <= makespan.VMEM_BUDGET:
            return stream
    return None


def population_makespan(
    assignments: jax.Array,  # [P, T] int32
    *,
    durations: jax.Array,
    cores: jax.Array,
    data: jax.Array,
    feasible: jax.Array,
    release: jax.Array,
    pred_rows: jax.Array,
    dtr: jax.Array,
    init_free: jax.Array,
    deadline: jax.Array | None = None,
    row_task: jax.Array | None = None,
    row_last: jax.Array | None = None,
    force: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Dispatch: the Pallas kernel (resident → streamed) when enabled and
    within its envelope, else the jnp oracle.  ``force=True`` routes through
    the kernel regardless of the global config (the ``pallas`` engine
    backend) — the envelope fallback still applies.  The kernel reads one
    predecessor row per task, so a bucket whose joins take more rows than
    it has tasks is outside its envelope.  ``deadline`` ([T] latest finish,
    1e30 = unconstrained) folds late tasks into the violation count."""
    T = assignments.shape[1]
    N = durations.shape[1]
    cmax = init_free.shape[1]
    rows, maxp = pred_rows.shape
    if deadline is None:
        deadline = jnp.full((T,), 1e30, dtype=jnp.float32)
    use = (force or _CONFIG.use_pallas) and rows == T
    stream = _makespan_mode(T, N, cmax, maxp) if use else None
    # trace-time counts: under jit they record per compilation, not per
    # executed call
    if stream is not None:
        obs.METRICS.counter("engine.traced.pallas").inc()
        return makespan.population_makespan_pallas(
            assignments, durations, cores, data, feasible, release, pred_rows,
            dtr, init_free, deadline, stream=stream,
        )
    obs.METRICS.counter("engine.traced.ref").inc()
    return ref.population_makespan_ref(
        assignments,
        durations=durations,
        cores=cores,
        data=data,
        feasible=feasible,
        release=release,
        pred_rows=pred_rows,
        dtr=dtr,
        init_free=init_free,
        deadline=deadline,
        row_task=row_task,
        row_last=row_last,
    )

