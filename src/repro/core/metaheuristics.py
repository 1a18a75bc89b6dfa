"""Meta-heuristic ("MH") techniques from the paper's Table VII —
GA, PSO, SA, ACO — **vectorized in JAX**.

This is the hardware adaptation of the paper's scaling bottleneck
(Table IX: GA at 500×500 took 6513 s serially): fitness evaluation of a
*population* of candidate assignments is embarrassingly parallel across
candidates, so every technique here evaluates its whole population through
the engine registry (:func:`repro.engine.population_fitness_fn` — the
``backend=`` kwarg names any registered engine: ``jax``, ``pallas``,
``oracle``, or a plugin), and the generation loop is a ``jax.lax.scan`` —
the entire optimizer jit-compiles to a single XLA program.

All techniques emit assignments only; canonical timing comes from the shared
numpy oracle so every technique is scored under identical semantics.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Sequence

import numpy as np

from repro import obs
from repro.core.evaluator import (
    ObjectiveWeights,
    Schedule,
    evaluate_assignment,
)
from repro.core.workload_model import ScheduleProblem
from repro.engine.packed import common_bucket, pack, stack_device

_NEG = -1e30


def population_fitness_fn(problem, weights=None, *, engine="auto", core_cap=None):
    """Registry-routed fitness (lazy import: repro.engine.backends imports
    this module's package during its own initialization)."""
    from repro.engine.backends import population_fitness_fn as _fn

    return _fn(problem, weights, engine=engine, core_cap=core_cap)


@dataclasses.dataclass
class MHResult:
    schedule: Schedule
    history: np.ndarray  # best objective per iteration


def _mask_logits(problem: ScheduleProblem):
    import jax.numpy as jnp

    return _sampling_logits()(jnp.asarray(problem.feasible))


@functools.lru_cache(maxsize=None)
def _sampling_logits() -> Callable:
    """``feasible [..., T, N] -> logits [..., T, N]`` on the device: 0 where
    a task may run, ``_NEG`` elsewhere.  A task that no node can run samples
    node 0 all the same (the fitness penalty then dominates and the
    candidate dies off); a padded task of a stack is feasible on node 0
    alone, so it pins there."""
    import jax
    import jax.numpy as jnp

    def logits(feasible):
        dead = ~feasible.any(axis=-1)
        safe = feasible.at[..., 0].set(feasible[..., 0] | dead)
        return jnp.where(safe, jnp.float32(0.0), jnp.float32(_NEG))

    return jax.jit(logits)


def _finish(
    problem: ScheduleProblem,
    weights: ObjectiveWeights,
    best_assignment: np.ndarray,
    technique: str,
    t0: float,
    history: np.ndarray,
) -> MHResult:
    """Rescore the best assignment with the host oracle (``mh.finish``)."""
    with obs.TRACER.span("mh.finish", cat="engine"):
        sched = evaluate_assignment(problem, best_assignment, weights, technique=technique)
    sched.solve_time = time.perf_counter() - t0
    return MHResult(schedule=sched, history=history)


# -----------------------------------------------------------------------------
# GA — Genetic Algorithm [24]
# -----------------------------------------------------------------------------

def _ga_loop(
    fitness: Callable,
    logits,
    key,
    *,
    pop_size: int,
    generations: int,
    tournament: int,
    mutation_rate,
    elite: int,
):
    """Pure-JAX GA generation loop → ``(best_assignment [T], history [G])``.

    Traceable end-to-end (no host round-trips), so it runs standalone for a
    single instance *and* under ``jit(vmap(...))`` for batched sweeps."""
    import jax
    import jax.numpy as jnp

    T = logits.shape[0]
    key, k0 = jax.random.split(key)
    pop = jax.random.categorical(k0, logits, axis=-1, shape=(pop_size, T)).astype(jnp.int32)

    def gen_step(carry, _):
        pop, key = carry
        obj, _mk = fitness(pop)
        key, kt, kc, km, kn = jax.random.split(key, 5)
        # elitism: indices of the best `elite`
        elite_idx = jnp.argsort(obj)[:elite]
        elites = pop[elite_idx]
        # tournament selection (two parents per child)
        cand = jax.random.randint(kt, (2, pop_size, tournament), 0, pop_size)
        winners = cand[
            jnp.arange(2)[:, None],
            jnp.arange(pop_size)[None, :],
            jnp.argmin(obj[cand], axis=-1),
        ]
        pa, pb = pop[winners[0]], pop[winners[1]]
        # uniform crossover
        xmask = jax.random.bernoulli(kc, 0.5, (pop_size, T))
        child = jnp.where(xmask, pa, pb)
        # mutation: resample feasible node
        mmask = jax.random.bernoulli(km, mutation_rate, (pop_size, T))
        fresh = jax.random.categorical(kn, logits, axis=-1, shape=(pop_size, T)).astype(jnp.int32)
        child = jnp.where(mmask, fresh, child)
        child = child.at[:elite].set(elites)
        return (child, key), jnp.min(obj)

    (pop, _), hist = jax.lax.scan(gen_step, (pop, key), None, length=generations)
    obj, _ = fitness(pop)
    return pop[jnp.argmin(obj)], hist


def ga(
    problem: ScheduleProblem,
    weights: ObjectiveWeights = ObjectiveWeights(),
    *,
    pop_size: int = 64,
    generations: int = 60,
    tournament: int = 4,
    mutation_rate: float = 0.08,
    elite: int = 2,
    seed: int = 0,
    backend: str = "jnp",
    shard: int | str | None = None,
) -> MHResult:
    # ``shard`` is accepted (and ignored) so scoped solver_options meant for
    # the batched ga_sweep don't crash a singleton solve of the same family
    del shard
    import jax

    t0 = time.perf_counter()
    fitness = population_fitness_fn(problem, weights, engine=backend)
    logits = _mask_logits(problem)
    best, hist = _ga_loop(
        fitness,
        logits,
        jax.random.PRNGKey(seed),
        pop_size=pop_size,
        generations=generations,
        tournament=tournament,
        mutation_rate=mutation_rate,
        elite=elite,
    )
    return _finish(problem, weights, np.asarray(best), "ga", t0, np.asarray(hist))


def _ga_sweep_one(
    usage_mode: str,
    pop_size: int,
    generations: int,
    tournament: int,
    elite: int,
    constrained: bool = False,
) -> Callable:
    """One instance's whole GA as a traceable function of its packed arrays
    — the body both sweep cores (vmapped and sharded) map over.

    ``constrained=True`` evaluates candidates with the deadline/budget
    penalty terms inside this traced fitness (see
    :func:`repro.engine.backends.population_fitness_from_arrays`) — the GA's
    penalty-and-repair constraint handling runs entirely on device."""
    from repro.engine.backends import population_fitness_from_arrays

    def one(arrays, logits, key, alpha, beta, mutation_rate):
        def fitness(pop):
            return population_fitness_from_arrays(
                pop, arrays, alpha, beta, usage_mode, constrained
            )

        return _ga_loop(
            fitness,
            logits,
            key,
            pop_size=pop_size,
            generations=generations,
            tournament=tournament,
            mutation_rate=mutation_rate,
            elite=elite,
        )

    return one


@functools.lru_cache(maxsize=None)
def _ga_sweep_core(
    usage_mode: str,
    pop_size: int,
    generations: int,
    tournament: int,
    elite: int,
    shards: int = 1,
    constrained: bool = False,
) -> Callable:
    """Jitted ``vmap`` of the whole GA over a stacked instance axis — one XLA
    program per shape bucket evaluates an entire scenario family.

    ``shards > 1`` wraps the vmapped sweep in ``shard_map`` over the local
    1-D device mesh (:mod:`repro.engine.shard`): the instance axis splits
    into one chunk per device and the chunks run concurrently.  Each row's
    computation is unchanged, so sharded schedules are bit-identical to the
    single-device sweep at fixed seed."""
    import jax

    one = _ga_sweep_one(usage_mode, pop_size, generations, tournament, elite, constrained)
    vmapped = jax.vmap(one, in_axes=(0, 0, 0, None, None, None))
    if shards <= 1:
        return jax.jit(vmapped)
    from jax.sharding import PartitionSpec as P

    from repro.engine.shard import AXIS, instance_mesh

    return jax.jit(
        jax.shard_map(
            vmapped,
            mesh=instance_mesh(shards),
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P()),
            out_specs=(P(AXIS), P(AXIS)),
        )
    )


def ga_sweep(
    problems: Sequence[ScheduleProblem],
    weights: ObjectiveWeights = ObjectiveWeights(),
    *,
    pop_size: int = 64,
    generations: int = 60,
    tournament: int = 4,
    mutation_rate: float = 0.08,
    elite: int = 2,
    seed: int = 0,
    shard: int | str | None = "auto",
) -> list[MHResult]:
    """Run the GA on a whole family of instances in ONE compiled XLA program.

    Instances are padded into a common shape bucket (see
    ``repro.engine.bucket_of``) and the generation loop is ``vmap``-ed across
    them — a Table IX size sweep or Fig. 11 quality grid no longer pays one
    trace/compile per point.  Per-result ``solve_time`` is the sweep wall
    time (the instances ran concurrently).

    With more than one local device the instance axis additionally stripes
    across the 1-D device mesh (``shard="auto"``; an int forces a shard
    count, ``"off"``/``None``/``1`` keeps everything on one device).  The
    per-instance PRNG streams and row computations are unchanged, so the
    sharded sweep's schedules are bit-identical to the single-device sweep
    at the same seed."""
    t0 = time.perf_counter()
    B = len(problems)
    # one span tree per call: everything before the device program is
    # ``prepare``, the program and its blocking fetch ``device``, and each
    # host rescoring ``mh.finish``
    with obs.TRACER.span("mh.ga_sweep", cat="engine", args={"instances": B}) as call:
        with obs.TRACER.span("mh.ga_sweep.prepare", cat="engine") as prepare:
            run, inputs, shards, bucket, h2d_bytes = _ga_sweep_inputs(
                problems, weights, pop_size, generations, tournament, elite, seed, shard
            )
            prepare.set(h2d_bytes=h2d_bytes)
        call.set(shards=shards, bucket="x".join(str(x) for x in bucket),
                 rows=bucket[4], tasks=bucket[0])
        with obs.TRACER.span("mh.ga_sweep.device", cat="engine"):
            best, hist = run(*inputs, weights.alpha, weights.beta, mutation_rate)
            best, hist = np.asarray(best)[:B], np.asarray(hist)[:B]
        obs.METRICS.counter("mh.ga_sweep.instances").inc(B)
        obs.METRICS.gauge("mh.ga_sweep.shards").set(shards)
        obs.METRICS.gauge("engine.pred_rows").set(bucket[4])
        return [
            _finish(
                problem,
                weights,
                best[b, : problem.num_tasks].astype(np.int64),
                "ga",
                t0,
                hist[b],
            )
            for b, problem in enumerate(problems)
        ]


def _ga_sweep_inputs(problems, weights, pop_size, generations, tournament, elite, seed,
                     shard):
    """The host work of a sweep call before its device program: the shard
    count, the stacked instances, the logits and the PRNG keys, on the
    device.  Returns ``(program, (arrays, logits, keys), shards, bucket,
    h2d_bytes)``; ``h2d_bytes`` counts what this call copies to the device
    (an instance already on the device, or a sharded stack resident in the
    pack LRU, is not copied)."""
    import jax
    import jax.numpy as jnp

    from repro.engine import shard as shard_mod

    B = len(problems)
    if shard == "auto":
        shards = shard_mod.choose_shards(B)
    elif shard in (None, "off", ""):
        shards = 1
    else:
        shards = int(shard)
    if shards > 1:
        stack = shard_mod.stack_packed_sharded(problems, shards=shards)
        arrays, bucket, Bp = stack.arrays, stack.bucket, stack.padded
        h2d_bytes = 0
    else:
        bucket = common_bucket(problems)
        packed = [pack(p, bucket) for p in problems]
        fresh = {id(pp): pp for pp in packed if not pp.on_device}
        h2d_bytes = sum(pp.nbytes for pp in fresh.values())
        arrays = stack_device(packed)
        Bp = B
    # pad-to-shard-multiple rows replicate instance 0, and so do its logits
    logits = _sampling_logits()(arrays["feasible"])
    constrained = any(p.has_constraints for p in problems)
    run = _ga_sweep_core(
        weights.usage_mode, pop_size, generations, tournament, elite, shards, constrained
    )
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), B))
    keys = np.concatenate([keys, np.repeat(keys[:1], Bp - B, axis=0)])
    if shards > 1:
        keys_dev = jax.device_put(keys, shard_mod.instance_sharding(shards))
    else:
        keys_dev = jnp.asarray(keys)
    h2d_bytes += keys.nbytes
    return run, (arrays, logits, keys_dev), shards, bucket, h2d_bytes


# -----------------------------------------------------------------------------
# PSO — Particle Swarm Optimization [26] (discrete: softmax-position decoding)
# -----------------------------------------------------------------------------

def pso(
    problem: ScheduleProblem,
    weights: ObjectiveWeights = ObjectiveWeights(),
    *,
    pop_size: int = 64,
    iterations: int = 60,
    inertia: float = 0.7,
    c1: float = 1.5,
    c2: float = 1.5,
    seed: int = 0,
    backend: str = "jnp",
) -> MHResult:
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    T, N = problem.num_tasks, problem.num_nodes
    fitness = population_fitness_fn(problem, weights, engine=backend)
    logits = _mask_logits(problem)
    key = jax.random.PRNGKey(seed)
    key, k0, k1 = jax.random.split(key, 3)
    pos = jax.random.normal(k0, (pop_size, T, N)) * 0.1
    vel = jnp.zeros_like(pos)

    def decode(p):
        return jnp.argmax(p + logits, axis=-1).astype(jnp.int32)

    obj0, _ = fitness(decode(pos))
    pbest_pos, pbest_obj = pos, obj0
    # device-side argmin/gather: int(...) here would block on a host sync
    # before the scan is even traced (dispatch stays async without it)
    g = jnp.argmin(obj0)
    gbest_pos, gbest_obj = pos[g], obj0[g]

    def step(carry, _):
        pos, vel, pbest_pos, pbest_obj, gbest_pos, gbest_obj, key = carry
        key, kr1, kr2 = jax.random.split(key, 3)
        r1 = jax.random.uniform(kr1, pos.shape)
        r2 = jax.random.uniform(kr2, pos.shape)
        vel2 = inertia * vel + c1 * r1 * (pbest_pos - pos) + c2 * r2 * (gbest_pos[None] - pos)
        pos2 = pos + vel2
        obj, _mk = fitness(decode(pos2))
        improved = obj < pbest_obj
        pbest_pos2 = jnp.where(improved[:, None, None], pos2, pbest_pos)
        pbest_obj2 = jnp.where(improved, obj, pbest_obj)
        gi = jnp.argmin(pbest_obj2)
        gbest_pos2 = jnp.where(pbest_obj2[gi] < gbest_obj, pbest_pos2[gi], gbest_pos)
        gbest_obj2 = jnp.minimum(pbest_obj2[gi], gbest_obj)
        return (pos2, vel2, pbest_pos2, pbest_obj2, gbest_pos2, gbest_obj2, key), gbest_obj2

    carry0 = (pos, vel, pbest_pos, pbest_obj, gbest_pos, gbest_obj, key)
    carry, hist = jax.lax.scan(step, carry0, None, length=iterations)
    gbest_pos = carry[4]
    best = np.asarray(decode(gbest_pos[None])[0])
    return _finish(problem, weights, best, "pso", t0, np.asarray(hist))


# -----------------------------------------------------------------------------
# SA — Simulated Annealing [20] (vectorized independent chains)
# -----------------------------------------------------------------------------

def sa(
    problem: ScheduleProblem,
    weights: ObjectiveWeights = ObjectiveWeights(),
    *,
    chains: int = 32,
    steps: int = 200,
    t_initial: float | None = None,
    cooling: float = 0.97,
    seed: int = 0,
    backend: str = "jnp",
) -> MHResult:
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    T = problem.num_tasks
    fitness = population_fitness_fn(problem, weights, engine=backend)
    logits = _mask_logits(problem)
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    state = jax.random.categorical(k0, logits, axis=-1, shape=(chains, T)).astype(jnp.int32)
    obj, _ = fitness(state)
    # default temp0 stays a device scalar: float(jnp.median(...)) would force
    # a blocking round-trip between the init fitness call and the scan
    if t_initial is not None:
        temp0 = jnp.asarray(float(t_initial), dtype=obj.dtype)
    else:
        temp0 = jnp.median(obj) * 0.05 + 1e-6

    def step(carry, it):
        state, obj, best_state, best_obj, key = carry
        temp = temp0 * cooling**it
        key, kt, kn, ka = jax.random.split(key, 4)
        tsel = jax.random.randint(kt, (chains,), 0, T)
        row_logits = logits[tsel]  # [chains, N]
        newnode = jax.random.categorical(kn, row_logits, axis=-1).astype(jnp.int32)
        prop = state.at[jnp.arange(chains), tsel].set(newnode)
        pobj, _mk = fitness(prop)
        accept = (pobj <= obj) | (
            jax.random.uniform(ka, (chains,)) < jnp.exp(-(pobj - obj) / jnp.maximum(temp, 1e-9))
        )
        state2 = jnp.where(accept[:, None], prop, state)
        obj2 = jnp.where(accept, pobj, obj)
        better = obj2 < best_obj
        best_state2 = jnp.where(better[:, None], state2, best_state)
        best_obj2 = jnp.where(better, obj2, best_obj)
        return (state2, obj2, best_state2, best_obj2, key), jnp.min(best_obj2)

    carry0 = (state, obj, state, obj, key)
    carry, hist = jax.lax.scan(step, carry0, jnp.arange(steps))
    best_state, best_obj = carry[2], carry[3]
    best = np.asarray(best_state[int(jnp.argmin(best_obj))])
    return _finish(problem, weights, best, "sa", t0, np.asarray(hist))


# -----------------------------------------------------------------------------
# ACO — Ant Colony Optimization [29]
# -----------------------------------------------------------------------------

def aco(
    problem: ScheduleProblem,
    weights: ObjectiveWeights = ObjectiveWeights(),
    *,
    ants: int = 48,
    iterations: int = 60,
    alpha: float = 1.0,
    beta: float = 1.0,
    rho: float = 0.15,
    seed: int = 0,
    backend: str = "jnp",
) -> MHResult:
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    T, N = problem.num_tasks, problem.num_nodes
    fitness = population_fitness_fn(problem, weights, engine=backend)
    logits = _mask_logits(problem)
    # heuristic desirability η = 1 / d_ij (shorter is better)
    eta = 1.0 / np.maximum(problem.durations, 1e-9)
    eta = jnp.asarray(eta / eta.max())
    key = jax.random.PRNGKey(seed)
    tau0 = jnp.ones((T, N))

    def step(carry, _):
        tau, best_a, best_obj, key = carry
        key, ks = jax.random.split(key)
        sample_logits = alpha * jnp.log(tau + 1e-12) + beta * jnp.log(eta + 1e-12) + logits
        pop = jax.random.categorical(ks, sample_logits, axis=-1, shape=(ants, T)).astype(jnp.int32)
        obj, _mk = fitness(pop)
        bi = jnp.argmin(obj)
        improved = obj[bi] < best_obj
        best_a2 = jnp.where(improved, pop[bi], best_a)
        best_obj2 = jnp.minimum(obj[bi], best_obj)
        # evaporation + elite deposit on the best-so-far trail
        onehot = jax.nn.one_hot(best_a2, N)
        tau2 = (1 - rho) * tau + rho * onehot * (1.0 + 1.0 / (1e-9 + best_obj2))
        return (tau2, best_a2, best_obj2, key), best_obj2

    carry0 = (tau0, jnp.zeros(T, dtype=jnp.int32), jnp.asarray(np.inf, dtype=jnp.float32), key)
    carry, hist = jax.lax.scan(step, carry0, None, length=iterations)
    best = np.asarray(carry[1])
    return _finish(problem, weights, best, "aco", t0, np.asarray(hist))


TECHNIQUES: dict[str, Callable[..., MHResult]] = {"ga": ga, "pso": pso, "sa": sa, "aco": aco}
