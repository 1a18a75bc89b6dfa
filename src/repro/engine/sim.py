"""The one incremental core-state simulator (paper Eq. 4–6, 12).

Every host-side execution of a schedule in this repo — the numpy oracle
(:func:`repro.core.evaluator.evaluate_assignment`), the HEFT/OLB list
schedulers, and the service's truth execution
(:func:`repro.core.simulator.execute`) — shares this module instead of
re-deriving its own core bookkeeping:

* :class:`CoreSim` — per-node core-free times kept *sorted ascending* at all
  times, so "earliest time c cores are free" is an O(1) row lookup and a
  commit is an O(CMAX) merge-insert (:func:`commit_sorted`) — no per-task
  sort;
* :func:`ready_times_all` — task j's ready time on *every* node at once
  (Eq. 12 with the Eq. 5 data-migration term), the vectorized f32
  reciprocal-rate pass that dominates HEFT at Table IX scale;
* :func:`run_schedule` — the full list-scheduling replay of a fixed
  assignment, with optional per-node speed factors and per-task jitter
  multipliers (the executor's perturbation model).  With ``dtype=float32``
  the arithmetic order matches the JAX evaluator and the Pallas kernel
  bit for bit; with default ``float64`` and no perturbation it *is* the
  oracle timing, so the simulator, the solvers, and the service's truth
  execution can never disagree about the model.
"""

from __future__ import annotations

import bisect

import numpy as np

from repro.core.workload_model import ScheduleProblem

_INF = 1e30  # finite stand-in for +inf (matches the device evaluators)


def _scalar_type(dtype):
    """The scalar a host loop computes ``dtype`` arithmetic in: Python's
    float for float64 (the same IEEE operations, without numpy's per-scalar
    overhead), else numpy's scalar of that dtype."""
    dtype = np.dtype(dtype)
    return float if dtype == np.float64 else dtype.type


def commit_sorted(row: np.ndarray, c: int, fill) -> np.ndarray:
    """Replace the ``c`` smallest entries of an ascending-sorted ``row`` with
    ``fill`` (≥ row[c-1] by construction) and return the row still sorted —
    an O(len) merge-insert, no re-sort."""
    rest = row[c:]
    pos = int(np.searchsorted(rest, fill))
    merged = np.empty_like(row)
    merged[:pos] = rest[:pos]
    merged[pos : pos + c] = fill
    merged[pos + c :] = rest[pos:]
    return merged


class CoreSim:
    """Per-node core-free-time state, every row sorted ascending.

    Two storage modes with one interface:

    * ``exact=True`` — the oracle / truth-executor flavor: one ragged row
      per node sized to its true capacity (``max(cap, 1)``), all cores
      modeled, memory = Σ caps.  Used by :func:`run_schedule`.
    * ``exact=False`` — the heuristics' flavor: a dense ``[N, CMAX]``
      matrix (+INF padding, CMAX capped at 512 like the device evaluators)
      supporting the vectorized all-nodes lookup :meth:`kth_free_all` that
      HEFT/OLB's per-task node scan needs.  Nodes wider than CMAX are
      modeled conservatively — starts may only be delayed, dependencies
      never break.
    """

    def __init__(
        self,
        problem: ScheduleProblem,
        *,
        dtype=np.float64,
        exact: bool = False,
    ) -> None:
        caps = problem.node_cores.astype(np.int64)
        self.caps = caps
        self.exact = exact
        if exact:
            self.cmax = int(max(caps.max(initial=1), problem.cores.max(initial=1), 1))
            self.width = np.maximum(caps, 1)
            zero = _scalar_type(dtype)(0.0)
            self._rows = [[zero] * max(int(c), 1) for c in caps]
        else:
            widest = int(min(caps.max(initial=1), 512))
            self.cmax = int(max(widest, problem.cores.max(initial=1), 1))
            self.width = np.minimum(np.maximum(caps, 1), self.cmax)
            self.free = np.full((problem.num_nodes, self.cmax), _INF, dtype=dtype)
            for i, c in enumerate(caps):
                self.free[i, : min(int(c), self.cmax)] = 0.0
            self._node_idx = np.arange(problem.num_nodes)

    def kth_free_all(self, c: np.ndarray) -> np.ndarray:
        """Earliest time each node has ``c_i`` cores free (``c``: [N] ≥ 1).
        Dense-mode only (the heuristics' vectorized node scan)."""
        idx = np.clip(c - 1, 0, self.cmax - 1)
        return self.free[self._node_idx, idx]

    def kth_free(self, i: int, c: int) -> float:
        """Earliest time node ``i`` has ``c`` cores free (clamped to the
        node's modeled width — a request beyond capacity reads the last real
        core)."""
        if self.exact:
            row = self._rows[i]
            return row[max(1, min(c, len(row))) - 1]
        c = max(1, min(c, int(self.width[i])))
        return self.free[i, c - 1]

    def commit(self, i: int, c: int, finish) -> None:
        if self.exact:
            # the list twin of commit_sorted: rows are short Python lists of
            # scalars, where numpy's per-call overhead would dominate
            row = self._rows[i]
            c = max(1, min(c, len(row)))
            rest = row[c:]
            pos = bisect.bisect_left(rest, finish)
            self._rows[i] = rest[:pos] + [finish] * c + rest[pos:]
        else:
            c = max(1, min(c, self.cmax))
            self.free[i] = commit_sorted(self.free[i], c, finish)


def ready_times_all(
    problem: ScheduleProblem,
    j: int,
    assignment: np.ndarray,
    finish: np.ndarray,
) -> np.ndarray:
    """Ready time of task j on every node ([N]), Eq. (12) with Eq. (5).

    One fused multiply-add-max over the CSR predecessor slice using the
    precomputed reciprocal-rate matrix (``problem.transfer_factor``) — no
    per-call division/finiteness test, f32 bandwidth.  This is the E×N term
    that dominates HEFT at Table IX scale (5000×5000: ~930k edges)."""
    N = problem.num_nodes
    indptr, indices = problem.pred_csr
    ps = indices[indptr[j] : indptr[j + 1]]
    ready = np.full(N, problem.release[j], dtype=np.float64)
    if ps.size == 0:
        return ready
    ips = assignment[ps]  # [k] predecessor nodes
    cand = problem.data[ps, None].astype(np.float32) * problem.transfer_factor[ips]
    if problem.transfer_penalty is not None:  # dead links: additive blocker
        cand += problem.transfer_penalty[ips]
    cand += finish[ps, None].astype(np.float32)
    return np.maximum(ready, cand.max(axis=0))


def run_schedule(
    problem: ScheduleProblem,
    assignment: np.ndarray,
    *,
    dtype=np.float64,
    speed_factors: np.ndarray | None = None,
    jitter_mults: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, int]:
    """Replay a fixed task→node assignment under the capacity-aware
    core-granular list-scheduling semantics; returns ``(start, finish,
    violations)``.

    ``speed_factors[i]`` multiplies node i's throughput and ``jitter_mults[j]``
    multiplies task j's duration (both optional) — the truth executor's
    perturbation model.  Without them this is the oracle timing; with
    ``dtype=float32`` it is bit-for-bit the JAX/Pallas evaluators'.
    """
    assignment = np.asarray(assignment, dtype=np.int64)
    T = problem.num_tasks
    tasks = np.arange(T)
    caps = problem.node_cores.astype(np.int64)
    durations = problem.durations
    if speed_factors is not None:
        factors = np.asarray(speed_factors)
        if np.any(factors != 1.0):  # x/1.0 is the identity — skip the copy
            durations = durations / np.maximum(factors, 1e-9)[None, :]
    durations = durations.astype(dtype, copy=False)
    data = problem.data.astype(dtype, copy=False)
    dtr = problem.dtr.astype(dtype, copy=False)
    indptr, indices = problem.pred_csr
    inf = dtype(_INF) if dtype is not np.float64 else _INF

    # the assignment is fixed, so every edge's transfer time, every task's
    # duration and core request are known before the walk: only the finish
    # times and the core state are sequential
    ips = assignment[indices]
    idst = assignment[np.repeat(tasks, np.diff(indptr))]
    rates = dtr[ips, idst]
    ok = np.isfinite(rates) & (rates > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        transfer = np.where(
            ips == idst, dtype(0.0), np.where(ok, data[indices] / np.where(ok, rates, 1), inf)
        )
    dur = durations[tasks, assignment]
    if jitter_mults is not None:
        dur = dur * jitter_mults[:T]
    violations = int(np.count_nonzero(~problem.feasible[tasks, assignment]))
    need = np.maximum(np.minimum(problem.cores, caps[assignment]), 1).astype(np.int64)

    scalar = _scalar_type(dtype)
    values = np.ndarray.tolist if scalar is float else list
    release, dur, transfer = (values(a) for a in
                              (problem.release.astype(dtype, copy=False), dur, transfer))
    ptr, preds, nodes, need = indptr.tolist(), indices.tolist(), assignment.tolist(), need.tolist()
    sim = CoreSim(problem, dtype=dtype, exact=True)
    start, finish = [scalar(0.0)] * T, [scalar(0.0)] * T
    for j in range(T):
        i = nodes[j]
        ready = release[j]
        for e in range(ptr[j], ptr[j + 1]):
            v = finish[preds[e]] + transfer[e]
            if v > ready:
                ready = v
        kth = sim.kth_free(i, need[j])
        s = ready if ready >= kth else kth
        f = scalar(s + dur[j])
        sim.commit(i, need[j], f)
        start[j], finish[j] = s, f
    return np.array(start, dtype=dtype), np.array(finish, dtype=dtype), violations


def accumulate_occupancy(
    frontier: np.ndarray,
    busy: np.ndarray,
    nodes: np.ndarray,
    starts: np.ndarray,
    finishes: np.ndarray,
) -> None:
    """Fold one execution's per-task windows into per-node occupancy state
    in place: ``frontier[i]`` becomes the latest finish seen on node i,
    ``busy[i]`` accumulates busy seconds.  The service's occupancy frontiers
    are views over this (no second bookkeeping implementation)."""
    np.maximum.at(frontier, nodes, finishes)
    np.add.at(busy, nodes, finishes - starts)
