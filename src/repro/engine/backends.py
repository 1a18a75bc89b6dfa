"""Backend-pluggable schedule evaluation behind one interface.

Mirrors the solver registry's capability pattern (PR 2): every way of
executing a schedule against a :class:`~repro.engine.packed.PackedProblem`
is a registered :class:`ScheduleEngine` carrying capability metadata —

* ``oracle`` — the numpy incremental simulator (:mod:`repro.engine.sim`);
  ground truth, per-task start/finish times, any dtype;
* ``jax`` — the jitted jnp population evaluator (XLA caches by
  shape, so every technique / sweep point in the same bucket shares one
  compiled program); also the vmapped multi-instance batch path;
* ``pallas`` — the TPU Pallas kernel (Mosaic on a TPU, the Pallas
  interpreter elsewhere), forced through the kernel inside its VMEM envelope.

All three are **bit-for-bit equivalent in f32** (``exact_f32``) — the
cross-backend sweep test asserts identical makespans and violation counts
on the same packed problem.  Out-of-tree backends (GPU sharding, energy
objectives, multi-host) register with ``@register_engine`` and are
immediately selectable via ``Scenario(engine=...)`` / solver ``backend=``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import numpy as np

from repro import obs
from repro.core.workload_model import BIG_PENALTY, ScheduleProblem
from repro.engine.packed import (
    FITNESS_ARRAY_KEYS,
    PackedProblem,
    bucket_of,
    pack,
    stack_packed,
)

_ALIASES = {"jnp": "jax", "numpy": "oracle"}


# -----------------------------------------------------------------------------
# shared jitted fitness cores (the jax backend's machinery; public because the
# GA sweep traces through them inside its own jitted program)
# -----------------------------------------------------------------------------


def _usage_term(arrays, assignments, usage_mode: str):
    import jax.numpy as jnp

    if usage_mode == "weighted":
        T = arrays["usage_weighted"].shape[0]
        return arrays["usage_weighted"][jnp.arange(T)[None, :], assignments].sum(axis=-1)
    return jnp.broadcast_to(arrays["usage_fixed"].sum(), assignments.shape[:1])


def _budget_overage(arrays, assignments):
    """Per-candidate count of workflows whose assignment's total cost exceeds
    their budget: ``(assignments [P, T]) -> overage [P] f32``.

    Pure gather + masked row reduction over the packed ``cost``/``wf``/
    ``wf_budget`` arrays — no host round-trip, no scatter (workflow sums are
    masked reductions so the float association matches the numpy oracle in
    :func:`repro.core.evaluator.constraint_violations`).  Shared verbatim by
    the jax fitness core and the pallas objective so both stay bit-identical
    in f32."""
    import jax.numpy as jnp

    T = arrays["cost"].shape[0]
    cost_t = arrays["cost"][jnp.arange(T)[None, :], assignments]  # [P, T]
    wf_rows = arrays["wf"][None, :] == jnp.arange(T)[:, None]  # [T(wf rows), T]
    wf_cost = jnp.sum(jnp.where(wf_rows[None], cost_t[:, None, :], 0.0), axis=-1)
    over = jnp.sum(wf_cost > arrays["wf_budget"][None, :], axis=-1)
    return over.astype(jnp.float32)


def population_fitness_from_arrays(
    assignments, arrays: dict, alpha, beta, usage_mode: str, constrained: bool = False
):
    """Unjitted fitness over packed problem arrays:
    ``(assignments [P, T]) -> (objective [P], makespan [P])``.

    The single implementation behind the jitted single-instance core, the
    vmapped batched core, and the batched metaheuristic sweeps.

    ``constrained=True`` (a static trace-time switch — unconstrained
    problems keep today's exact XLA program) threads packed deadlines into
    the makespan scan's violation count and adds the budget-overage penalty,
    so GA/PSO candidates are penalized inside the batched device path with
    no per-candidate host round-trip.

    The body runs under ``jax.named_scope("fitness")``: every compiled
    program that evaluates candidates carries a ``fitness`` segment in the
    ``op_name`` of the evaluator's operations, which is how a profiler trace
    tells them from the search around them.  Metadata only; no number
    changes."""
    import jax

    from repro.kernels import ref

    with jax.named_scope("fitness"):
        makespan, violations = ref.population_makespan_ref(
            assignments,
            durations=arrays["durations"],
            cores=arrays["cores"],
            data=arrays["data"],
            feasible=arrays["feasible"],
            release=arrays["release"],
            pred_rows=arrays["pred_rows"],
            dtr=arrays["dtr"],
            init_free=arrays["init_free"],
            node_cores=arrays["node_cores"],
            deadline=arrays["deadline"] if constrained else None,
            row_task=arrays["row_task"],
            row_last=arrays["row_last"],
        )
        if constrained:
            violations = violations + _budget_overage(arrays, assignments)
        usage = _usage_term(arrays, assignments, usage_mode)
        obj = alpha * usage + beta * makespan + BIG_PENALTY * violations
    return obj, makespan


@functools.lru_cache(maxsize=None)
def _population_core(usage_mode: str, constrained: bool = False) -> Callable:
    """Shared jitted ``(assignments, arrays, alpha, beta) -> (obj, mk)``.

    Problem arrays are *arguments*, not closure captures — XLA's jit cache
    keys on shapes, so every technique / sweep point with equal array shapes
    hits the same compiled executable (no per-instance re-jit)."""
    import jax

    return jax.jit(
        functools.partial(
            population_fitness_from_arrays, usage_mode=usage_mode, constrained=constrained
        )
    )


@functools.lru_cache(maxsize=None)
def _batched_population_core(usage_mode: str, constrained: bool = False) -> Callable:
    """Jitted ``vmap`` of the fitness core across a stacked instance axis:
    ``(assignments [B, P, T], arrays [B, ...], alpha, beta) -> ([B, P], [B, P])``."""
    import jax

    return jax.jit(
        jax.vmap(
            functools.partial(
                population_fitness_from_arrays, usage_mode=usage_mode, constrained=constrained
            ),
            in_axes=(0, 0, None, None),
        )
    )


@functools.lru_cache(maxsize=None)
def _sharded_batched_population_core(
    usage_mode: str, shards: int, constrained: bool = False
) -> Callable:
    """:func:`_batched_population_core` striped over the local device mesh.

    ``shard_map`` splits the leading (instance) axis into ``shards`` equal
    chunks, one per device; each device runs the identical vmapped fitness
    on its chunk, so results are bit-identical to the single-device core —
    only wall time changes.  ``shards == 1`` returns the unsharded core
    outright (same jitted callable, same XLA program — the degenerate mesh
    IS today's path)."""
    if shards <= 1:
        return _batched_population_core(usage_mode, constrained)
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.engine.shard import AXIS, instance_mesh

    vmapped = jax.vmap(
        functools.partial(
            population_fitness_from_arrays, usage_mode=usage_mode, constrained=constrained
        ),
        in_axes=(0, 0, None, None),
    )
    return jax.jit(
        jax.shard_map(
            vmapped,
            mesh=instance_mesh(shards),
            in_specs=(P(AXIS), P(AXIS), P(), P()),
            out_specs=(P(AXIS), P(AXIS)),
        )
    )


def fitness_cache_sizes(usage_mode: str = "fixed") -> tuple[int, int]:
    """(single-instance, batched) XLA compile counts for the shared fitness
    cores — the recompile telemetry the sweep tests assert on."""
    return (
        _population_core(usage_mode)._cache_size(),
        _batched_population_core(usage_mode)._cache_size(),
    )


def _jit_cache_collector() -> dict[str, int]:
    single_f, batched_f = fitness_cache_sizes("fixed")
    single_w, batched_w = fitness_cache_sizes("weighted")
    return {
        "single_fixed": single_f,
        "batched_fixed": batched_f,
        "single_weighted": single_w,
        "batched_weighted": batched_w,
        # distinct (usage_mode, shard-count) sharded wrappers built so far
        "sharded_cores": _sharded_batched_population_core.cache_info().currsize,
    }


obs.METRICS.register_collector("engine_jit_cache", _jit_cache_collector)


def _pad_population(assignments, tasks_bucket: int):
    """Pad population columns to the bucket's task axis; padded tasks are
    pinned to node 0 (the only node they are feasible on)."""
    import jax.numpy as jnp

    a = jnp.asarray(assignments)
    gap = tasks_bucket - a.shape[-1]
    if gap < 0:
        raise ValueError(f"population has {a.shape[-1]} task columns > bucket {tasks_bucket}")
    if gap:
        a = jnp.concatenate(
            [a, jnp.zeros(a.shape[:-1] + (gap,), a.dtype)], axis=-1
        )
    return a


# -----------------------------------------------------------------------------
# engine interface + registry
# -----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineCapabilities:
    """What a backend can do, declared at registration time.

    ``supports_population`` — evaluates [P, T] candidate batches natively;
    ``supports_batch`` — evaluates stacked multi-instance families in one
    program; ``exact_f32`` — participates in the bit-for-bit f32
    equivalence contract (and may substitute for any other exact backend)."""

    supports_population: bool = True
    supports_batch: bool = False
    exact_f32: bool = False


class ScheduleEngine:
    """One way of executing schedules against a :class:`PackedProblem`."""

    name: str = ""
    capabilities = EngineCapabilities()

    # ---- single schedule → full timing ---------------------------------------
    def evaluate(self, problem: ScheduleProblem, assignment, weights=None, technique: str = ""):
        """Canonical per-task timing (``Schedule``) — default: the oracle
        simulator, which is the only backend that materializes start/finish
        arrays (device backends produce makespans/objectives only)."""
        from repro.core.evaluator import ObjectiveWeights, evaluate_assignment

        return evaluate_assignment(
            problem, assignment, weights or ObjectiveWeights(), technique=technique
        )

    # ---- population fitness --------------------------------------------------
    def population_fitness(
        self, problem: ScheduleProblem, weights=None, *, core_cap: int | None = None
    ) -> Callable:
        """Returns ``fitness(assignments [P, T]) -> (objective [P], makespan [P])``."""
        raise NotImplementedError(f"engine {self.name!r} has no population path")

    def evaluate_population(self, problem: ScheduleProblem, assignments, weights=None):
        obj, mk = self.population_fitness(problem, weights)(assignments)
        return np.asarray(obj), np.asarray(mk)


class EngineRegistry:
    """Name → engine mapping with capability metadata (the evaluation-side
    twin of :class:`repro.core.api.SolverRegistry`)."""

    def __init__(self) -> None:
        self._entries: dict[str, ScheduleEngine] = {}

    def register(self, name: str, engine=None, *, overwrite: bool = False):
        """Register an engine instance (or decorate a ``ScheduleEngine``
        class, which is instantiated)."""

        def _add(obj):
            inst = obj() if isinstance(obj, type) else obj
            if name in self._entries and not overwrite:
                raise ValueError(f"engine {name!r} already registered")
            inst.name = name
            self._entries[name] = inst
            return obj

        return _add if engine is None else _add(engine)

    def unregister(self, name: str) -> None:
        self._entries.pop(name, None)

    def get(self, name: str) -> ScheduleEngine:
        resolved = resolve_engine(name)
        try:
            return self._entries[resolved]
        except KeyError:
            raise KeyError(
                f"unknown engine {name!r}; options {sorted(self._entries)}"
            ) from None

    def names(self) -> tuple[str, ...]:
        return tuple(self._entries)

    def capabilities(self, name: str) -> EngineCapabilities:
        return self.get(name).capabilities

    def __contains__(self, name: object) -> bool:
        return isinstance(name, str) and resolve_engine(name) in self._entries

    def __iter__(self):
        return iter(self._entries.values())


ENGINES = EngineRegistry()
"""The default process-wide engine registry (built-ins below)."""


def register_engine(name: str, *, registry: EngineRegistry | None = None, overwrite: bool = False):
    """Decorator: register a :class:`ScheduleEngine` subclass under ``name``.

    >>> @register_engine("my-gpu")
    ... class MyGpuEngine(ScheduleEngine):
    ...     capabilities = EngineCapabilities(supports_population=True)
    ...     ...
    """
    return (registry if registry is not None else ENGINES).register(
        name, overwrite=overwrite
    )


def default_engine() -> str:
    """The ``"auto"`` resolution: the Pallas kernel when the kernel config
    enables it, else the jnp evaluator (both f32-exact)."""
    from repro.kernels import ops as kops

    return "pallas" if kops.kernel_config().use_pallas else "jax"


def resolve_engine(name: str) -> str:
    """Resolve aliases (``jnp``→``jax``, ``numpy``→``oracle``) and ``auto``."""
    if name in ("auto", ""):
        return default_engine()
    return _ALIASES.get(name, name)


# -----------------------------------------------------------------------------
# built-in backends
# -----------------------------------------------------------------------------


@register_engine("oracle")
class OracleEngine(ScheduleEngine):
    """The numpy incremental simulator — ground truth.  ``dtype=float32``
    follows the device backends' operation order bit for bit."""

    capabilities = EngineCapabilities(
        supports_population=True, supports_batch=False, exact_f32=True
    )

    def evaluate(
        self, problem, assignment, weights=None, technique: str = "", *, dtype=np.float64
    ):
        from repro.core.evaluator import ObjectiveWeights, evaluate_assignment

        return evaluate_assignment(
            problem, assignment, weights or ObjectiveWeights(), technique=technique, dtype=dtype
        )

    def population_fitness(self, problem, weights=None, *, core_cap: int | None = None):
        from repro.core.evaluator import ObjectiveWeights

        w = weights or ObjectiveWeights()

        def fitness(assignments):
            A = np.asarray(assignments)
            obj = np.empty(A.shape[0], np.float64)
            mk = np.empty(A.shape[0], np.float32)
            for k in range(A.shape[0]):
                s = self.evaluate(problem, A[k], w, dtype=np.float32)
                obj[k], mk[k] = s.objective, np.float32(s.makespan)
            return obj, mk

        return fitness


@register_engine("jax")
class JaxEngine(ScheduleEngine):
    """The jitted jnp population evaluator over packed arrays —
    one compiled program per (shape bucket, usage mode), shared by every
    technique and sweep point."""

    capabilities = EngineCapabilities(
        supports_population=True, supports_batch=True, exact_f32=True
    )

    def population_fitness(self, problem, weights=None, *, core_cap: int | None = None):
        from repro.core.evaluator import ObjectiveWeights

        w = weights or ObjectiveWeights()
        # exact shapes for a single instance — padding to the pow2 bucket
        # would inflate every fitness call (the paper's hot loop) by up to
        # ~2x elements; bucket sharing only pays off on the *batched* path
        packed = (
            problem
            if isinstance(problem, PackedProblem)
            else pack(problem, core_cap=core_cap, pad=False)
        )
        arrays = packed.device_arrays()
        core = _population_core(w.usage_mode, packed.constrained)
        tb = packed.bucket[0]
        bucket, mode = packed.bucket, w.usage_mode

        def fitness(assignments):
            # compile-vs-execute split: a call during which JAX compiled
            # anything is a compile; the rest are steady-state executes
            with obs.FITNESS.measure("jax", bucket, mode):
                return core(_pad_population(assignments, tb), arrays, w.alpha, w.beta)

        return fitness

    def batched_fitness(
        self,
        problems: Sequence[ScheduleProblem],
        weights=None,
        *,
        shard: int | str | None = "auto",
    ):
        """Batched fitness over a family of instances (one shape bucket):
        ``fitness(assignments [B, P, Tb]) -> (objective [B, P], makespan [B, P])``.

        ``shard="auto"`` stripes the instance axis across all local devices
        (:mod:`repro.engine.shard`) when more than one is available; an int
        forces that shard count; ``None``/``1``/``"off"`` keeps the
        single-device vmapped path.  All choices are bit-identical in f32."""
        from repro.core.evaluator import ObjectiveWeights
        from repro.engine import shard as shard_mod

        w = weights or ObjectiveWeights()
        if shard == "auto":
            shards = shard_mod.choose_shards(len(problems))
        elif shard in (None, "off", ""):
            shards = 1
        else:
            shards = int(shard)
        if shards > 1:
            return shard_mod.sharded_batched_fitness(problems, w, shards=shards)
        arrays, bucket = stack_packed(problems)
        constrained = any(getattr(p, "has_constraints", False) for p in problems) or any(
            getattr(p, "constrained", False) for p in problems
        )
        core = _batched_population_core(w.usage_mode, constrained)

        def fitness(assignments):
            import jax.numpy as jnp

            with obs.FITNESS.measure("jax-batch", bucket, w.usage_mode):
                return core(jnp.asarray(assignments), arrays, w.alpha, w.beta)

        fitness.bucket = bucket  # type: ignore[attr-defined]
        fitness.num_instances = len(problems)  # type: ignore[attr-defined]
        fitness.shards = 1  # type: ignore[attr-defined]
        return fitness


@register_engine("pallas")
class PallasEngine(ScheduleEngine):
    """The Pallas TPU makespan kernel (the Pallas interpreter off-TPU),
    forced through the kernel inside its VMEM envelope; instances beyond the
    envelope fall back to the jnp oracle with identical f32 semantics."""

    capabilities = EngineCapabilities(
        supports_population=True, supports_batch=False, exact_f32=True
    )

    def population_fitness(self, problem, weights=None, *, core_cap: int | None = None):
        import jax.numpy as jnp

        from repro.core.evaluator import ObjectiveWeights
        from repro.kernels import ops as kops

        w = weights or ObjectiveWeights()
        packed = (
            problem
            if isinstance(problem, PackedProblem)
            else pack(problem, core_cap=core_cap, pad=False)
        )
        arrays = packed.device_arrays()
        tb = packed.bucket[0]

        def fitness(assignments):
            a = _pad_population(assignments, tb).astype(jnp.int32)
            with obs.FITNESS.measure("pallas", packed.bucket, w.usage_mode):
                return _pallas_obj(a)

        def _pallas_obj(a):
            makespan, violations = kops.population_makespan(
                a,
                durations=arrays["durations"],
                cores=arrays["cores"],
                data=arrays["data"],
                feasible=arrays["feasible"],
                release=arrays["release"],
                pred_rows=arrays["pred_rows"],
                dtr=arrays["dtr"],
                init_free=arrays["init_free"],
                deadline=arrays["deadline"] if packed.constrained else None,
                row_task=arrays["row_task"],
                row_last=arrays["row_last"],
                force=True,
            )
            # identical penalty expression to population_fitness_from_arrays —
            # the f32 cross-backend equivalence contract covers it
            if packed.constrained:
                violations = violations + _budget_overage(arrays, a)
            usage = _usage_term(arrays, a, w.usage_mode)
            obj = w.alpha * usage + w.beta * makespan + BIG_PENALTY * violations
            return obj, makespan

        return fitness


# -----------------------------------------------------------------------------
# module-level conveniences (what the solvers actually import)
# -----------------------------------------------------------------------------


def population_fitness_fn(
    problem: ScheduleProblem,
    weights=None,
    *,
    engine: str = "auto",
    core_cap: int | None = None,
    registry: EngineRegistry | None = None,
) -> Callable:
    """Registry-routed ``fitness(assignments [P, T]) -> (obj [P], mk [P])``."""
    reg = registry if registry is not None else ENGINES
    return reg.get(engine).population_fitness(problem, weights, core_cap=core_cap)


def batched_population_fitness_fn(
    problems: Sequence[ScheduleProblem],
    weights=None,
    *,
    engine: str = "jax",
    registry: EngineRegistry | None = None,
) -> Callable:
    """Registry-routed batched fitness over one instance family (requires a
    backend with ``supports_batch``)."""
    reg = registry if registry is not None else ENGINES
    eng = reg.get(engine)
    if not eng.capabilities.supports_batch:
        raise ValueError(f"engine {eng.name!r} does not support batched families")
    return eng.batched_fitness(problems, weights)  # type: ignore[attr-defined]


def evaluate_population_batch(
    problems: Sequence[ScheduleProblem],
    populations: Sequence[np.ndarray],
    weights=None,
    *,
    engine: str = "jax",
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Evaluate per-instance candidate populations for a list of problems.

    Instances are grouped into shape buckets; each bucket group is padded,
    stacked and evaluated by one vmapped XLA call (one compile per bucket,
    ever — the jit cache is module-global).  Returns, per instance, the
    ``(objective [P_i], makespan [P_i])`` pair in the input order."""
    from repro.engine.packed import _round_up_pow2

    if len(problems) != len(populations):
        raise ValueError("need one population per problem")
    groups: dict[tuple[int, int, int, int], list[int]] = {}
    pops = [np.asarray(p) for p in populations]
    for idx, problem in enumerate(problems):
        groups.setdefault(bucket_of(problem), []).append(idx)

    out: list[tuple[np.ndarray, np.ndarray] | None] = [None] * len(problems)
    for bucket, members in groups.items():
        Tb = bucket[0]
        pb = _round_up_pow2(max(pops[m].shape[0] for m in members))
        batch = np.zeros((len(members), pb, Tb), np.int32)
        for row, m in enumerate(members):
            pop = pops[m]
            batch[row, : pop.shape[0], : pop.shape[1]] = pop
        fitness = batched_population_fitness_fn(
            [problems[m] for m in members], weights, engine=engine
        )
        obj, mk = fitness(batch)
        obj, mk = np.asarray(obj), np.asarray(mk)
        for row, m in enumerate(members):
            P = pops[m].shape[0]
            out[m] = (obj[row, :P], mk[row, :P])
    return out  # type: ignore[return-value]
