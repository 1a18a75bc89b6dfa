"""The canonical device-ready problem representation.

One :class:`PackedProblem` replaces the scattered packing helpers that PRs
1–3 grew in ``repro.core.evaluator`` (exact-shape jnp packing, bucket
padding, instance stacking, shape buckets): every backend in
:mod:`repro.engine.backends` evaluates against this one artifact, and every
layer above (metaheuristics, admission batching, benchmarks) shares it.

Padding is *objective neutral* by construction:

* padded tasks have zero duration/data/usage, no predecessors, release 0
  and are feasible only on node 0 — assigned to any *real* node they finish
  at that node's current earliest core-free time (≤ makespan) and leave the
  core state untouched; population rows must pin them to node 0,
* padded nodes are infeasible for every real task and own no cores
  (``init_free`` all +INF), so a correct sampler never selects them.

:func:`pack` memoizes by ``(problem fingerprint, bucket, core_cap)`` in a
stats-tracking LRU (:func:`pack_cache`): a resubmission of a
content-identical problem — even one that misses the *solve* cache because
its weights or technique changed — reuses the padded arrays **and** the
already-transferred device buffers (``PackedProblem.device_arrays`` is
cached on the instance, which the LRU keeps alive).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from collections import OrderedDict
from typing import Any, Callable, Sequence

import numpy as np

from repro import obs
from repro.core.workload_model import ScheduleProblem, problem_fingerprint

_INF = 1e30

#: arrays consumed by the fitness cores (order-insensitive dict pytree)
FITNESS_ARRAY_KEYS = (
    "durations",
    "cores",
    "data",
    "feasible",
    "release",
    "pred_rows",
    "row_task",
    "row_last",
    "dtr",
    "init_free",
    "node_cores",
    "usage_fixed",
    "usage_weighted",
    # hard-constraint arrays (neutral when unconstrained: +INF deadlines and
    # budgets, zero costs — the penalty terms evaluate to exactly 0.0)
    "deadline",
    "cost",
    "wf",
    "wf_budget",
)

#: ``(T, N, CMAX, K, S)``: tasks, nodes, the core window, and the
#: predecessor rows — ``S`` rows of ``K`` slots each (see :func:`row_width`)
Bucket = tuple[int, int, int, int, int]

#: the widest predecessor row: a bucket whose largest in-degree fits one row
#: of this width keeps one row per task (``K`` = that in-degree, ``S = T``)
ROW_WIDTH_MAX = 64
#: the row width of a bucket with a wider join: the join spreads over
#: ``ceil(d / K)`` rows, and every row of the scan pays for ``K`` slots
JOIN_ROW_WIDTH = 16


def _round_up_pow2(x: int, floor: int = 4) -> int:
    x = max(int(x), 1)
    out = floor
    while out < x:
        out *= 2
    return out


def _cmax_of(problem: ScheduleProblem, core_cap: int | None) -> int:
    caps = problem.node_cores.astype(np.int64)
    cmax = int(core_cap if core_cap is not None else min(caps.max(initial=1), 512))
    return max(cmax, int(problem.cores.max(initial=1)), 1)


def row_width(maxp: int) -> int:
    """Predecessor slots per row for a bucket whose largest in-degree is
    ``maxp``: ``maxp`` itself up to :data:`ROW_WIDTH_MAX`, else
    :data:`JOIN_ROW_WIDTH`."""
    return maxp if maxp <= ROW_WIDTH_MAX else JOIN_ROW_WIDTH


def task_rows(problem: ScheduleProblem, width: int) -> np.ndarray:
    """Rows each task takes at ``width`` slots a row: ``max(1, ceil(d / width))``
    for in-degree ``d``."""
    indptr, _ = problem.pred_csr
    return np.maximum(1, -(-np.diff(indptr) // width))


def exact_bucket(problem: ScheduleProblem, core_cap: int | None = None) -> Bucket:
    """The problem's own shapes ``(T, N, CMAX, K, S)`` — no padding."""
    width = row_width(max(int(problem.pred_matrix.shape[1]), 1))
    return (
        problem.num_tasks,
        problem.num_nodes,
        _cmax_of(problem, core_cap),
        width,
        int(task_rows(problem, width).sum()),
    )


def common_bucket(problems: Sequence[ScheduleProblem], core_cap: int | None = None) -> Bucket:
    """One shape bucket ``(T, N, CMAX, K, S)`` for every problem in the list:
    ``T``, ``N``, ``CMAX`` and the largest in-degree rounded up to powers of
    two, so unequal instances share compiled programs; ``K`` from that
    in-degree (:func:`row_width`); ``S = T`` when every task fits one row,
    else ``T`` plus the most rows any instance's joins add, rounded up to a
    power of two."""
    exact = [exact_bucket(p, core_cap) for p in problems]
    t, n, cmax = (max(_round_up_pow2(b[d]) for b in exact) for d in range(3))
    maxp = max(_round_up_pow2(p.pred_matrix.shape[1], floor=1) for p in problems)
    width = row_width(maxp)
    extra = max(int(task_rows(p, width).sum()) - p.num_tasks for p in problems)
    return (t, n, cmax, width, t + (_round_up_pow2(extra, floor=8) if extra else 0))


def bucket_of(problem: ScheduleProblem, core_cap: int | None = None) -> Bucket:
    """:func:`common_bucket` of this problem alone."""
    return common_bucket([problem], core_cap)


@dataclasses.dataclass(frozen=True, eq=False)
class PackedProblem:
    """Frozen, padded, f32 dense problem — the engine's unit of work.

    The numpy arrays are read-only; device (jnp) copies are built lazily and
    cached on the instance, so one packed problem pays one host→device
    transfer no matter how many solves reuse it."""

    durations: np.ndarray  # [Tb, Nb] f32
    cores: np.ndarray  # [Tb] i32 (≥ 1)
    data: np.ndarray  # [Tb] f32
    feasible: np.ndarray  # [Tb, Nb] bool
    release: np.ndarray  # [Tb] f32
    pred_rows: np.ndarray  # [Sb, Kb] i32 predecessors of row_task, -1 padded
    row_task: np.ndarray  # [Sb] i32 task of each row (contiguous, task order)
    row_last: np.ndarray  # [Sb] bool the task's last row, which places it
    dtr: np.ndarray  # [Nb, Nb] f32, +INF for dead links
    init_free: np.ndarray  # [Nb, Cb] f32, +INF core padding
    node_cores: np.ndarray  # [Nb] i32
    usage_fixed: np.ndarray  # [Tb] f32
    usage_weighted: np.ndarray  # [Tb, Nb] f32
    deadline: np.ndarray  # [Tb] f32 latest finish per task (+INF = none)
    cost: np.ndarray  # [Tb, Nb] f32 cost of task j on node i (0 when unbudgeted)
    wf: np.ndarray  # [Tb] i32 workflow id per task (pad rows → first pad id)
    wf_budget: np.ndarray  # [Tb] f32 budget by workflow id row (+INF = none)
    bucket: Bucket
    num_tasks: int  # real tasks (≤ bucket[0])
    num_nodes: int  # real nodes (≤ bucket[1])
    cmax: int  # modeled core window (≤ bucket[2])
    dtype: str = "float32"
    fingerprint: str | None = None
    constrained: bool = False  # any non-trivial deadline/budget packed
    _device: dict[str, Any] | None = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def numpy_arrays(self) -> dict[str, np.ndarray]:
        """The fitness-core array dict (host copies, read-only views)."""
        return {k: getattr(self, k) for k in FITNESS_ARRAY_KEYS}

    @property
    def nbytes(self) -> int:
        """Host bytes held by the padded arrays (the cached device copies,
        once built, occupy roughly the same again)."""
        return sum(getattr(self, k).nbytes for k in FITNESS_ARRAY_KEYS)

    @property
    def on_device(self) -> bool:
        """Whether :meth:`device_arrays` has transferred the arrays yet."""
        return self._device is not None

    def device_arrays(self) -> dict[str, Any]:
        """jnp copies of :meth:`numpy_arrays`, transferred once and cached."""
        if self._device is None:
            import jax.numpy as jnp

            object.__setattr__(
                self,
                "_device",
                {k: jnp.asarray(getattr(self, k)) for k in FITNESS_ARRAY_KEYS},
            )
        return dict(self._device)  # type: ignore[arg-type]


def _build(
    problem: ScheduleProblem,
    bucket: Bucket,
    fingerprint: str | None,
    core_cap: int | None = None,
) -> PackedProblem:
    Tb, Nb, Cb, Kb, Sb = bucket
    T, N = problem.num_tasks, problem.num_nodes
    per_task = task_rows(problem, Kb)
    rows = int(per_task.sum()) + Tb - T  # a padded task takes one row
    if T > Tb or N > Nb or rows > Sb:
        raise ValueError(f"problem {T}x{N} ({rows} rows of {Kb}) exceeds bucket {bucket}")
    caps = problem.node_cores.astype(np.int64)
    if int(problem.cores.max(initial=1)) > Cb:
        raise ValueError(f"task core request exceeds bucket cmax {Cb}")

    durations = np.zeros((Tb, Nb), np.float32)
    durations[:T, :N] = problem.durations
    cores = np.ones(Tb, np.int32)
    cores[:T] = np.maximum(problem.cores, 1.0).astype(np.int32)
    data = np.zeros(Tb, np.float32)
    data[:T] = problem.data
    feasible = np.zeros((Tb, Nb), bool)
    feasible[:T, :N] = problem.feasible
    feasible[T:, 0] = True  # padded tasks live on node 0
    release = np.zeros(Tb, np.float32)
    release[:T] = problem.release
    pred_rows, row_task, row_last = _pred_rows(problem, per_task, Tb, Kb, Sb)
    dtr = np.ones((Nb, Nb), np.float32)
    dtr[:N, :N] = np.where(np.isfinite(problem.dtr), problem.dtr, _INF)
    init_free = np.full((Nb, Cb), _INF, np.float32)
    for i, c in enumerate(caps):
        init_free[i, : min(int(c), Cb)] = 0.0
    node_cores = np.ones(Nb, np.int32)
    node_cores[:N] = np.minimum(np.maximum(caps, 1), Cb)
    usage_fixed = np.zeros(Tb, np.float32)
    usage_fixed[:T] = problem.usage
    usage_weighted = np.zeros((Tb, Nb), np.float32)
    usage_weighted[:T, :N] = problem.weighted_usage()
    deadline = np.full(Tb, _INF, np.float32)
    if problem.deadline is not None:
        deadline[:T] = np.minimum(problem.deadline, _INF)
    cost = np.zeros((Tb, Nb), np.float32)
    # workflow ids: pad rows join a phantom workflow (first free id) whose
    # budget row is +INF and whose packed costs are 0 — penalty-neutral
    w_count = len(problem.workflow_names)
    wf = np.full(Tb, min(w_count, Tb - 1), np.int32)
    wf[:T] = problem.workflow_of
    wf_budget = np.full(Tb, _INF, np.float32)
    if problem.budget is not None:
        cost[:T, :N] = problem.cost_matrix()
        wf_budget[:w_count] = np.minimum(problem.budget, _INF)
    arrays = {
        "durations": durations,
        "cores": cores,
        "data": data,
        "feasible": feasible,
        "release": release,
        "pred_rows": pred_rows,
        "row_task": row_task,
        "row_last": row_last,
        "dtr": dtr,
        "init_free": init_free,
        "node_cores": node_cores,
        "usage_fixed": usage_fixed,
        "usage_weighted": usage_weighted,
        "deadline": deadline,
        "cost": cost,
        "wf": wf,
        "wf_budget": wf_budget,
    }
    for a in arrays.values():
        a.setflags(write=False)
    return PackedProblem(
        bucket=bucket,
        num_tasks=T,
        num_nodes=N,
        cmax=min(_cmax_of(problem, core_cap), Cb),
        fingerprint=fingerprint,
        constrained=problem.has_constraints,
        **arrays,
    )


def _pred_rows(problem: ScheduleProblem, per_task: np.ndarray, Tb: int, Kb: int, Sb: int):
    """The predecessor rows of a bucket: each real task's ``per_task`` rows
    in task order, its q-th predecessor in row ``q // Kb``, slot ``q % Kb``;
    then one empty row per padded task; then filler rows, which are no task's
    last row and so change nothing."""
    indptr, preds = problem.pred_csr
    T, indeg = problem.num_tasks, np.diff(indptr)
    first = np.concatenate([[0], np.cumsum(per_task)])  # each task's first row
    pred_rows = -np.ones((Sb, Kb), np.int32)
    q = np.arange(len(preds)) - np.repeat(indptr[:-1], indeg)
    pred_rows[np.repeat(first[:-1], indeg) + q // Kb, q % Kb] = preds
    row_task = np.full(Sb, Tb - 1, np.int32)
    row_task[: first[-1]] = np.repeat(np.arange(T), per_task)
    row_task[first[-1] : first[-1] + Tb - T] = np.arange(T, Tb)
    row_last = np.zeros(Sb, bool)
    row_last[first[1:] - 1] = True
    row_last[first[-1] : first[-1] + Tb - T] = True
    return pred_rows, row_task, row_last


@dataclasses.dataclass
class PackStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def snapshot(self) -> tuple[int, int, int]:
        return (self.hits, self.misses, self.evictions)

    def delta(self, before: tuple[int, int, int]) -> "PackStats":
        """Stats accumulated since ``before`` (a :meth:`snapshot` tuple).

        The one place the ``after - before`` idiom lives — the service
        summary, the campaign runner and the obs metrics delta all go
        through here."""
        return PackStats(*(b - a for a, b in zip(before, self.snapshot())))

    def to_json(self) -> dict[str, Any]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": self.hit_rate,
        }


class PackCache:
    """Entry- *and* byte-bounded LRU of pack key → packed entry.

    Lives *alongside* the service's solve cache: a submission that misses
    the solve cache (new weights, new technique) but names a
    content-identical problem still reuses the padded arrays and their
    device buffers.  ``max_bytes`` bounds retained *host* bytes (cached
    device copies roughly double the true footprint — sized accordingly);
    a single pack larger than the whole budget is served uncached rather
    than pinning the budget.

    The cache is *mesh-aware*: besides single-instance
    :class:`PackedProblem` entries it retains sharded stacked families
    (:class:`repro.engine.shard.ShardedStack`) whose device buffers stay
    resident one shard per mesh device; ``device_stats`` accumulates
    per-device hit/miss/resident-byte accounting, surfaced through the
    ``pack_cache`` metrics collector."""

    def __init__(self, capacity: int = 256, max_bytes: int = 1 << 30) -> None:
        if capacity < 1:
            raise ValueError("pack cache capacity must be >= 1")
        if max_bytes < 1:
            raise ValueError("pack cache max_bytes must be >= 1")
        self.capacity = capacity
        self.max_bytes = max_bytes
        self._entries: OrderedDict[tuple, Any] = OrderedDict()
        self._bytes = 0
        self.stats = PackStats()
        #: per-device accounting for mesh-resident entries
        #: (``{device: {hits, misses, resident_bytes}}``)
        self.device_stats: dict[str, dict[str, int]] = {}

    def get_or_build(self, key: tuple, builder: Callable[[], Any]) -> Any:
        packed = self._entries.get(key)
        if packed is not None:
            self._entries.move_to_end(key)
            self.stats.hits += 1
            return packed
        self.stats.misses += 1
        packed = builder()
        size = packed.nbytes
        if size > self.max_bytes:
            return packed  # too large to retain — build-and-release
        self._entries[key] = packed
        self._bytes += size
        while len(self._entries) > self.capacity or self._bytes > self.max_bytes:
            _, evicted = self._entries.popitem(last=False)
            self._bytes -= evicted.nbytes
            self._release_device_bytes(evicted)
            self.stats.evictions += 1
        return packed

    def _release_device_bytes(self, evicted: Any) -> None:
        for dev, nbytes in getattr(evicted, "device_nbytes", {}).items():
            d = self.device_stats.get(dev)
            if d is not None:
                d["resident_bytes"] = max(d["resident_bytes"] - nbytes, 0)

    def clear(self) -> None:
        for entry in self._entries.values():
            self._release_device_bytes(entry)
        self._entries.clear()
        self._bytes = 0

    @property
    def retained_bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._entries)


_PACK_CACHE = PackCache(
    int(os.environ.get("REPRO_PACK_CACHE_CAPACITY", "256")),
    int(os.environ.get("REPRO_PACK_CACHE_MAX_BYTES", str(1 << 30))),
)


def pack_cache() -> PackCache:
    """The process-wide pack LRU (every :func:`pack` call flows through it)."""
    return _PACK_CACHE


def _pack_cache_collector() -> dict[str, Any]:
    out: dict[str, Any] = {
        "hits": _PACK_CACHE.stats.hits,
        "misses": _PACK_CACHE.stats.misses,
        "evictions": _PACK_CACHE.stats.evictions,
        "entries": len(_PACK_CACHE),
        "retained_bytes": _PACK_CACHE.retained_bytes,
    }
    # mesh-aware residency: one sub-dict per device once anything sharded
    # has been stacked (absent on single-device hosts — keeps the metrics
    # snapshot byte-stable for unsharded runs)
    for dev, stats in sorted(_PACK_CACHE.device_stats.items()):
        for field, value in stats.items():
            out[f"device.{dev}.{field}"] = value
    return out


obs.METRICS.register_collector("pack_cache", _pack_cache_collector)


def pack(
    problem: ScheduleProblem,
    bucket: Bucket | None = None,
    *,
    core_cap: int | None = None,
    pad: bool = True,
    use_cache: bool = True,
) -> PackedProblem:
    """The canonical packing entry point.

    ``bucket=None`` picks the problem's pow2 bucket (``pad=False``: its
    exact shapes — the legacy unpadded layout).  Memoized by
    ``(fingerprint, bucket, core_cap)``; pass ``use_cache=False`` to force a
    rebuild (tests)."""
    if bucket is None:
        bucket = bucket_of(problem, core_cap) if pad else exact_bucket(problem, core_cap)
    # span per pack() call, hit or miss: trace structure must not depend on
    # cache temperature or replayed traces would not fingerprint identically
    with obs.TRACER.span(
        "engine.pack", cat="engine",
        args={"bucket": "x".join(str(d) for d in bucket)},
    ):
        if not use_cache:
            return _build(problem, bucket, None, core_cap)
        fingerprint = problem_fingerprint(problem)
        key = (fingerprint, bucket, core_cap)
        return _PACK_CACHE.get_or_build(
            key, lambda: _build(problem, bucket, fingerprint, core_cap)
        )


def stack_packed(
    problems: Sequence[ScheduleProblem], bucket: Bucket | None = None
) -> tuple[dict[str, Any], Bucket]:
    """Stack padded instances along a leading batch axis → jnp array dict
    (one shared bucket; see :func:`stack_device`).

    Single-device layout; :func:`repro.engine.shard.stack_packed_sharded`
    is the multi-device sibling that stripes the same leading axis across
    the local mesh with pad-to-shard-multiple semantics."""
    bucket = common_bucket(problems) if bucket is None else bucket
    return stack_device([pack(p, bucket) for p in problems]), bucket


def stack_device(packed: Sequence[PackedProblem]) -> dict[str, Any]:
    """Stack packed instances of one bucket on the device: each instance's
    arrays cross to the device once (:meth:`PackedProblem.device_arrays`,
    kept alive by the pack LRU), and a family that meets again is stacked
    there, in one dispatch, without a host copy."""
    return _stack_program()([pp.device_arrays() for pp in packed])


@functools.lru_cache(maxsize=None)
def _stack_program() -> Callable[[list[dict[str, Any]]], dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    def stack(members):
        return {k: jnp.stack([arrays[k] for arrays in members]) for k in FITNESS_ARRAY_KEYS}

    return jax.jit(stack)


# ---- legacy surfaces (served through repro.core.evaluator's warning shims) ---


def legacy_jax_arrays(problem: ScheduleProblem, core_cap: int | None = None) -> dict:
    """Exact-shape jnp array dict + ``cmax`` — the PR 1 packing layout."""
    packed = pack(problem, core_cap=core_cap, pad=False)
    out = packed.device_arrays()
    out["cmax"] = packed.cmax
    return out


def legacy_padded_arrays(problem: ScheduleProblem, bucket: Bucket) -> dict:
    """Padded numpy array dict for an explicit bucket — the PR 1 layout.

    Returns fresh *writable* copies (the legacy function allocated per
    call; the canonical cached arrays are read-only)."""
    return {k: v.copy() for k, v in pack(problem, bucket).numpy_arrays().items()}


def legacy_stacked_arrays(
    problems: Sequence[ScheduleProblem], bucket: Bucket | None = None
) -> tuple[dict[str, Any], Bucket]:
    return stack_packed(problems, bucket)
