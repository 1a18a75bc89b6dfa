"""`repro.engine` — the one place a schedule gets executed against a problem.

The paper defines a single rigorous system/workload model; this package owns
its single *executable* form and every way of evaluating a schedule against
it (the SPEC-RG layering: model → engine → solver → service):

* :mod:`repro.engine.packed` — the canonical, device-ready
  :class:`PackedProblem` (padded arrays, CSR preds, shape bucket, dtype
  policy), built once per ``(problem fingerprint, bucket)`` and memoized in a
  stats-tracking LRU (:func:`pack_cache`) so repeat packs skip both the
  padding work and the host→device transfer;
* :mod:`repro.engine.sim` — the one incremental core-state simulator
  (sorted free-rows + CSR ready-times) behind the numpy oracle, HEFT/OLB,
  and the service's truth execution;
* :mod:`repro.engine.backends` — the :class:`EngineRegistry` of
  :class:`ScheduleEngine` backends (``oracle`` / ``jax`` / ``pallas``),
  mirroring the solver registry's capability pattern.  The f32 backends are
  bit-for-bit equivalent (asserted by the cross-backend sweep tests);
* :mod:`repro.engine.shard` — the multi-device instance axis: batched
  families stripe across a 1-D local-device mesh via ``shard_map`` with
  pad-to-shard-multiple semantics, bit-identical to the single-device
  vmapped core (the pack LRU keeps the per-shard device buffers resident).

Solvers consume the engine through :func:`population_fitness_fn` /
:func:`evaluate_population_batch`; out-of-tree backends register with
``@register_engine("name")`` and are immediately routable by
``Scenario(engine=...)``.


Importing the package installs :mod:`repro.obs`'s ``jax.compile.*``
counters, which the engine's compile-vs-execute accounting reads.
"""

from repro import obs
from repro.engine.backends import (
    ENGINES,
    EngineCapabilities,
    EngineRegistry,
    ScheduleEngine,
    batched_population_fitness_fn,
    default_engine,
    evaluate_population_batch,
    fitness_cache_sizes,
    population_fitness_fn,
    population_fitness_from_arrays,
    register_engine,
    resolve_engine,
)
from repro.engine.packed import (
    FITNESS_ARRAY_KEYS,
    PackCache,
    PackedProblem,
    bucket_of,
    common_bucket,
    pack,
    pack_cache,
    stack_packed,
)
from repro.engine.shard import (
    ShardedStack,
    choose_shards,
    instance_mesh,
    local_device_count,
    sharded_batched_fitness,
    stack_packed_sharded,
)
from repro.engine.sim import CoreSim, commit_sorted, run_schedule

obs.install_compile_counters()

__all__ = [
    "ENGINES",
    "CoreSim",
    "EngineCapabilities",
    "EngineRegistry",
    "FITNESS_ARRAY_KEYS",
    "PackCache",
    "PackedProblem",
    "ScheduleEngine",
    "ShardedStack",
    "batched_population_fitness_fn",
    "bucket_of",
    "choose_shards",
    "commit_sorted",
    "common_bucket",
    "default_engine",
    "evaluate_population_batch",
    "fitness_cache_sizes",
    "instance_mesh",
    "local_device_count",
    "pack",
    "pack_cache",
    "population_fitness_fn",
    "population_fitness_from_arrays",
    "register_engine",
    "resolve_engine",
    "run_schedule",
    "sharded_batched_fitness",
    "stack_packed",
    "stack_packed_sharded",
]
