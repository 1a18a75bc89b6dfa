"""Compile counters from JAX's own ``jax.monitoring`` events.

:func:`install_compile_counters` registers two listeners, once per process
(the ``repro.engine`` import calls it):

* ``jax.compile.requests`` — every XLA compile request
  (``/jax/core/compile/backend_compile_duration``): a program that missed
  JAX's in-memory jit caches, whether it was then compiled or read from
  the persistent compilation cache;
* ``jax.compile.cache_hits`` — the requests the persistent compilation
  cache served (``/jax/compilation_cache/cache_hits``);
* ``jax.compile.in.<span>`` — while tracing is enabled, each request also
  counts under the innermost open :mod:`repro.obs` span, which names the
  step that (re)compiled.

They are counters, not spans, so a trace's virtual fingerprint does not
depend on how warm the caches are.  ``jax`` is imported only when the
installer runs: importing :mod:`repro.obs` stays stdlib-only.
"""

from __future__ import annotations

from .metrics import CACHE_HITS, COMPILE_REQUESTS, METRICS
from .tracer import TRACER

__all__ = ["install_compile_counters"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_installed = False


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    # looked up by name on every event: ``METRICS.reset()`` replaces counters
    METRICS.counter(COMPILE_REQUESTS).inc()
    if TRACER.enabled:
        step = TRACER.innermost()
        if step is not None:
            METRICS.counter(f"jax.compile.in.{step}").inc()


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        METRICS.counter(CACHE_HITS).inc()


def install_compile_counters() -> None:
    """Register the ``jax.monitoring`` listeners; later calls do nothing."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _installed = True
