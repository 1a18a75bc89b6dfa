"""``repro.obs`` — unified tracing + metrics for the continuum reproduction.

Three planes, one import, stdlib-only (safe to import from every repro
module without cycles):

* **Tracing** (:mod:`.tracer`): nested spans on dual clocks — wall
  (``time.perf_counter``) and the service's deterministic virtual event
  clock — which also land in any ``jax.profiler`` trace, beside the device
  operations.  Zero-cost when disabled; deterministic span ids so traces
  replay bit-identically at a fixed seed.
* **Metrics** (:mod:`.metrics`): process-wide counters / gauges /
  fixed-bucket histograms plus collectors registered by owning modules
  (pack cache, jit caches), behind one ``snapshot()``/``delta()``
  surface; JAX compile-vs-execute attribution via :data:`FITNESS`, and
  compile counters from JAX's own events (:mod:`.jaxevents`).
* **Export** (:mod:`.export`): Chrome/Perfetto ``trace_event`` JSON,
  flat metrics JSON, and the ``telemetry`` block embedded in campaign
  results and ``BENCH_*.json`` artifacts.

Typical traced run::

    from repro import obs

    obs.enable_tracing()
    with obs.TRACER.span("my.workload", cat="demo"):
        ...
    obs.write_trace("out.json")          # open in ui.perfetto.dev
    obs.write_metrics("out.metrics.json")
"""

from __future__ import annotations

from .logs import logger, setup_logging
from .metrics import (
    FITNESS,
    METRICS,
    Counter,
    FitnessAccounting,
    Gauge,
    Histogram,
    MetricsRegistry,
    nearest_rank,
)
from .tracer import TRACER, Span, Tracer, traced, virtual_fingerprint
from .jaxevents import install_compile_counters
from .export import (
    flatten,
    summarize_trace,
    telemetry,
    trace_events,
    write_metrics,
    write_trace,
)

__all__ = [
    "TRACER",
    "Tracer",
    "Span",
    "traced",
    "virtual_fingerprint",
    "METRICS",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "nearest_rank",
    "FITNESS",
    "FitnessAccounting",
    "install_compile_counters",
    "trace_events",
    "write_trace",
    "telemetry",
    "write_metrics",
    "flatten",
    "summarize_trace",
    "logger",
    "setup_logging",
    "enable_tracing",
    "disable_tracing",
]


def enable_tracing() -> None:
    """Enable the global tracer (resets the span buffer + id sequence)."""
    TRACER.enable()


def disable_tracing() -> None:
    TRACER.disable()
