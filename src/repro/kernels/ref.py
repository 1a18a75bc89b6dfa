"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` function defines the exact numerical contract its kernel must
match (tests sweep shapes/dtypes and ``assert_allclose`` kernel vs. oracle).
The oracles are also the production fallback path on backends without
Pallas lowering (the CPU dry-run lowers these).
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


# -----------------------------------------------------------------------------
# population_makespan — the paper's metaheuristic fitness hot spot
# -----------------------------------------------------------------------------

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


# -----------------------------------------------------------------------------
# flash attention (train / prefill)
# -----------------------------------------------------------------------------

def _attn_mask(sq: int, skv: int, *, causal: bool, window: int | None, q_offset: int = 0):
    """[sq, skv] boolean mask. ``window`` = sliding-window size (SWA / gemma2
    local layers): position q attends to kv in (q - window, q]."""
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    return mask


def flash_attention_ref(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """O(S²) reference attention with GQA, causal/window masking and logit
    softcapping (gemma2). All accumulation in f32."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    scale = D**-0.5 if scale is None else scale
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = _attn_mask(Sq, k.shape[2], causal=causal, window=window, q_offset=k.shape[2] - Sq)
    s = jnp.where(mask[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return o.reshape(B, H, Sq, D).astype(q.dtype)


def flash_attention_block(
    q_block: jax.Array,  # [B, H, bq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    *,
    q_offset,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """One query block against the full K/V at a (possibly traced) offset —
    the building block of the blockwise-jnp attention used by the dry-run."""
    B, H, bq, D = q_block.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = D**-0.5 if scale is None else scale
    # mixed-precision: f32 accumulation without materialized f32 K/V copies;
    # scale folded post-einsum (exact, no operand rounding)
    qg = q_block.reshape(B, Hkv, group, bq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    rows = jnp.arange(bq)[:, None] + q_offset
    cols = jnp.arange(Skv)[None, :]
    mask = jnp.ones((bq, Skv), dtype=bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, bq, D).astype(q_block.dtype)


# -----------------------------------------------------------------------------
# decode attention (single-token query vs. KV cache)
# -----------------------------------------------------------------------------

def decode_attention_ref(
    q: jax.Array,  # [B, H, D]
    k_cache: jax.Array,  # [B, Hkv, S, D]
    v_cache: jax.Array,  # [B, Hkv, S, D]
    lengths: jax.Array,  # [B] int32 — valid cache entries per sequence
    *,
    softcap: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    B, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = H // Hkv
    scale = D**-0.5 if scale is None else scale
    # mixed-precision einsums: f32 accumulation WITHOUT materializing f32
    # copies of the cache (§Perf: the upcast cost 2.5× the decode memory
    # term; the Pallas kernel accumulates in registers — this matches it).
    # Scale folded post-einsum (exact, no operand rounding).
    qg = q.reshape(B, Hkv, group, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    valid = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(valid, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D).astype(q.dtype)


# -----------------------------------------------------------------------------
# Mamba2 SSD scan
# -----------------------------------------------------------------------------

def ssd_scan_ref(
    x: jax.Array,  # [B, L, H, P]
    dt: jax.Array,  # [B, L, H]  (already softplus'd, > 0)
    A: jax.Array,  # [H]        (negative)
    B_mat: jax.Array,  # [B, L, G, N]
    C_mat: jax.Array,  # [B, L, G, N]
    *,
    init_state: jax.Array | None = None,  # [B, H, P, N]
) -> tuple[jax.Array, jax.Array]:
    """Sequential (exact) SSD recurrence:

        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_tᵀ ;   y_t = S_t C_tᵀ

    Returns (y [B,L,H,P], final_state [B,H,P,N]).  Heads are grouped over
    B/C (``G`` groups, ``H % G == 0``).  f32 state."""
    Bsz, L, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G
    Bh = jnp.repeat(B_mat, rep, axis=2)  # [B, L, H, N]
    Ch = jnp.repeat(C_mat, rep, axis=2)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, Bt, Ct = inp  # [B,H,P], [B,H], [B,H,N], [B,H,N]
        dA = jnp.exp(dtt * Af[None, :])  # [B,H]
        state = state * dA[..., None, None] + (dtt[..., None, None] * xt[..., None] * Bt[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", state, Ct)
        return state, y

    inputs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bh.astype(jnp.float32), 1, 0),
        jnp.moveaxis(Ch.astype(jnp.float32), 1, 0),
    )
    final, ys = jax.lax.scan(step, init_state, inputs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    return y, final


def ssd_scan_chunked_ref(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B_mat: jax.Array,
    C_mat: jax.Array,
    *,
    chunk: int = 64,
    init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD (state-space *duality* form, arXiv:2405.21060): intra-chunk
    attention-like matmuls + inter-chunk state recurrence.  Mathematically
    identical to :func:`ssd_scan_ref`; this is the matmul-dominant layout the
    Pallas kernel implements (MXU-friendly)."""
    Bsz, L, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G
    assert L % chunk == 0, (L, chunk)
    nC = L // chunk
    Bh = jnp.repeat(B_mat, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C_mat, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    # reshape to chunks: [B, nC, Q, H, ...]
    xq = xf.reshape(Bsz, nC, chunk, H, P)
    dq = dtf.reshape(Bsz, nC, chunk, H)
    Bq = Bh.reshape(Bsz, nC, chunk, H, N)
    Cq = Ch.reshape(Bsz, nC, chunk, H, N)

    a = dq * Af[None, None, None, :]  # log decay per step  [B,nC,Q,H]
    a_cs = jnp.cumsum(a, axis=2)  # inclusive cumsum within chunk

    def chunk_step(state, inp):
        xq_c, dq_c, Bq_c, Cq_c, a_c, acs_c = inp  # [B, Q, H, ...]
        # intra-chunk: y[i] += sum_{j<=i} C_i·B_j exp(acs_i - acs_j) dt_j x_j
        seg = acs_c[:, :, None, :] - acs_c[:, None, :, :]  # [B, Qi, Qj, H]
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(mask[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bihn,bjhn->bijh", Cq_c, Bq_c)
        m = cb * decay
        y_intra = jnp.einsum("bijh,bjh,bjhp->bihp", m, dq_c, xq_c)
        # inter-chunk: contribution of incoming state
        y_inter = jnp.einsum("bihn,bhpn,bih->bihp", Cq_c, state, jnp.exp(acs_c))
        # state update
        a_tot = acs_c[:, -1, :]  # [B, H]
        w = jnp.exp(a_tot[:, None, :] - acs_c) * dq_c  # [B, Q, H]
        ds = jnp.einsum("bjh,bjhp,bjhn->bhpn", w, xq_c, Bq_c)
        state = state * jnp.exp(a_tot)[..., None, None] + ds
        return state, y_intra + y_inter

    inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (xq, dq, Bq, Cq, a, a_cs))
    final, ys = jax.lax.scan(chunk_step, init_state, inputs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, L, H, P).astype(x.dtype)
    return y, final
