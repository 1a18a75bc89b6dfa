"""Faults planted in the running program, to show that the comparison which
decides ``correct`` catches them.  Each is a context manager that patches the
program in this process only and restores it on exit; nothing is written.

The GA sweep (``repro.core.metaheuristics``):

* ``stalled_step`` — every generation returns its population unchanged;
* ``half_batch`` — the second half of the instances get the first half's
  results;
* ``lost_exchange`` — only the first chip's slice of a striped sweep comes
  back; every other chip's rows repeat it;
* ``altered_answer`` — each returned best assignment is shifted by one task
  after the device chose it;
* ``inflated_fitness`` — the engine's population evaluator reads every
  objective 1% high, in the search and wherever else it runs.
"""

from __future__ import annotations

import contextlib

SWEEP = ("stalled_step", "half_batch", "lost_exchange", "altered_answer", "inflated_fitness")


@contextlib.contextmanager
def _patched(owner, name: str, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def _rows(transform):
    """Wrap the sweep core so that ``transform(best, hist, shards)`` edits its
    outputs on the device, after the search."""
    from repro.core import metaheuristics as mh

    core = mh._ga_sweep_core

    def faulty(usage_mode, pop_size, generations, tournament, elite, shards=1,
               constrained=False):
        run = core(usage_mode, pop_size, generations, tournament, elite, shards, constrained)

        def wrapped(*args):
            best, hist = run(*args)
            return transform(best, hist, shards)

        return wrapped

    return _patched(mh, "_ga_sweep_core", faulty)


def _repeat_first(best, hist, keep: int):
    import jax.numpy as jnp

    reps = -(-best.shape[0] // keep)
    return (jnp.concatenate([best[:keep]] * reps)[: best.shape[0]],
            jnp.concatenate([hist[:keep]] * reps)[: hist.shape[0]])


@contextlib.contextmanager
def _stalled_ga():
    import jax

    from repro.core import metaheuristics as mh

    def stalled(fitness, logits, key, *, pop_size, generations, **_):
        T = logits.shape[0]
        key, k0 = jax.random.split(key)
        pop = jax.random.categorical(k0, logits, axis=-1, shape=(pop_size, T)).astype("int32")
        obj, _ = fitness(pop)
        hist = jax.numpy.full((generations,), obj.min())
        return pop[obj.argmin()], hist

    mh._ga_sweep_core.cache_clear()
    try:
        with _patched(mh, "_ga_loop", stalled):
            yield
    finally:
        mh._ga_sweep_core.cache_clear()


@contextlib.contextmanager
def _inflated_fitness():
    import jax

    from repro.core import metaheuristics as mh
    from repro.engine import backends

    sound = backends.population_fitness_from_arrays

    def inflated(*args, **kwargs):
        obj, makespan = sound(*args, **kwargs)
        return obj * 1.01, makespan

    def retrace():
        mh._ga_sweep_core.cache_clear()
        jax.clear_caches()

    retrace()
    try:
        with _patched(backends, "population_fitness_from_arrays", inflated):
            yield
    finally:
        retrace()


def sweep_fault(name: str):
    import jax.numpy as jnp

    if name == "stalled_step":
        return _stalled_ga()
    if name == "half_batch":
        return _rows(lambda b, h, s: _repeat_first(b, h, max(b.shape[0] // 2, 1)))
    if name == "lost_exchange":
        return _rows(lambda b, h, s: _repeat_first(b, h, max(b.shape[0] // max(s, 1), 1)))
    if name == "altered_answer":
        return _rows(lambda b, h, s: (jnp.roll(b, 1, axis=-1), h))
    if name == "inflated_fitness":
        return _inflated_fitness()
    raise KeyError(name)
