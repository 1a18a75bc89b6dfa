"""Dispatch wrappers over the Pallas kernels and their jnp oracles.

Selection policy:

* ``configure(use_pallas=...)`` or env ``REPRO_USE_PALLAS=1`` turns the
  Pallas path on.  The platform alone decides how a kernel runs: natively on
  a TPU, in the Pallas interpreter anywhere else
  (:func:`repro.kernels.makespan.interpret_mode`).
* The default is the jnp oracle path — it is what the 512-device dry-run
  lowers (Pallas does not lower to the XLA:CPU backend), and its FLOPs match
  the kernel contract, so the roofline terms are representative.
* ``population_makespan`` falls back to the oracle whenever the instance
  exceeds the kernel's VMEM/SMEM envelope, or a join spreads over more
  than one predecessor row.  The ``engine.traced.pallas`` /
  ``engine.traced.ref`` counters count how often each path was *traced*:
  under ``jit`` that is once per compiled program, not once per call.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import makespan, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


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


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool | None = None,
) -> jax.Array:
    use = _CONFIG.use_pallas if use_pallas is None else use_pallas
    Sq, Skv = q.shape[2], k.shape[2]
    if use and Sq % min(block_q, Sq) == 0 and Skv % min(block_k, Skv) == 0:
        return flash_attention_pallas(
            q,
            k,
            v,
            causal=causal,
            window=window,
            softcap=softcap,
            scale=scale,
            block_q=block_q,
            block_k=block_k,
            interpret=makespan.interpret_mode(),
        )
    if Sq > 512 or Skv > 512:
        # blockwise jnp path (flash-equivalent memory behaviour under XLA)
        return _blockwise_attention_jnp(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    return ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
    )


def _blockwise_attention_jnp(
    q, k, v, *, causal, window, softcap, scale, block_q: int = 512
):
    """lax.map over query blocks against full K/V — bounds the live score
    tensor to [block_q, Skv] so 32k prefill fits without a Pallas kernel.
    Used by the dry-run lowering path."""
    B, H, Sq, D = q.shape
    if Sq % block_q != 0:
        return ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    Skv = k.shape[2]
    nq = Sq // block_q
    qb = q.reshape(B, H, nq, block_q, D)

    def one_block(args):
        qi, qblk = args
        offset = Skv - Sq + qi * block_q
        return ref.flash_attention_block(
            qblk, k, v, q_offset=offset, causal=causal, window=window,
            softcap=softcap, scale=scale,
        )

    out = jax.lax.map(one_block, (jnp.arange(nq), jnp.moveaxis(qb, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, Sq, D)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    softcap: float | None = None,
    scale: float | None = None,
    block_k: int = 512,
    use_pallas: bool | None = None,
) -> jax.Array:
    use = _CONFIG.use_pallas if use_pallas is None else use_pallas
    S = k_cache.shape[2]
    if use and S % min(block_k, S) == 0:
        return decode_attention_pallas(
            q,
            k_cache,
            v_cache,
            lengths,
            softcap=softcap,
            scale=scale,
            block_k=block_k,
            interpret=makespan.interpret_mode(),
        )
    return ref.decode_attention_ref(
        q, k_cache, v_cache, lengths, softcap=softcap, scale=scale
    )


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B_mat: jax.Array,
    C_mat: jax.Array,
    *,
    chunk: int = 128,
    use_pallas: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    use = _CONFIG.use_pallas if use_pallas is None else use_pallas
    L = x.shape[1]
    if use and L % min(chunk, L) == 0:
        return ssd_scan_pallas(
            x, dt, A, B_mat, C_mat, chunk=chunk, interpret=makespan.interpret_mode()
        )
    if L % min(chunk, L) == 0:
        return ref.ssd_scan_chunked_ref(x, dt, A, B_mat, C_mat, chunk=min(chunk, L))
    return ref.ssd_scan_ref(x, dt, A, B_mat, C_mat)
