"""`repro.obs` acceptance suite (the PR 8 tentpole):

* tracer — span nesting/timing invariants, exception capture, the
  disabled-tracer zero-allocation fast path, dual-clock recording, and
  the deterministic virtual fingerprint (two traced chaos replays at the
  same seed hash identically);
* metrics — registry snapshot/delta arithmetic (gauges keep their
  "after" level), nearest-rank percentiles, fixed-bucket histograms,
  ``PackStats.delta``;
* JAX cost attribution — pinned compile-vs-execute split for one engine
  bucket, compile detection from JAX's own ``jax.monitoring`` events, and
  the ``jax.compile.*`` counters;
* the profiler's clock — spans land as host events in a ``jax.profiler``
  trace;
* exporters — Perfetto ``trace_event`` schema validity (round-trip
  through :func:`repro.obs.summarize_trace`), malformed-file rejection,
  and the ``telemetry`` block shape;
* logging — ``repro.*`` namespacing and idempotent setup.
"""

import json
import logging
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs import (
    FITNESS,
    METRICS,
    TRACER,
    FitnessAccounting,
    Histogram,
    MetricsRegistry,
    Tracer,
    nearest_rank,
    summarize_trace,
    telemetry,
    trace_events,
    virtual_fingerprint,
    write_metrics,
    write_trace,
)


@pytest.fixture(autouse=True)
def _pristine_tracer():
    """Every test starts and ends with the global tracer disabled."""
    TRACER.disable()
    yield
    TRACER.disable()


# ---------------------------------------------------------------------------
# tracer: spans, nesting, exceptions
# ---------------------------------------------------------------------------

def test_span_nesting_and_timing_invariants():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t", args={"k": 1}):
            pass
        with tr.span("inner2", cat="t"):
            pass
    outer, inner, inner2 = tr.spans
    assert [s.id for s in tr.spans] == [0, 1, 2]  # deterministic sequence
    assert outer.parent is None
    assert inner.parent == outer.id and inner2.parent == outer.id
    assert inner.args == {"k": 1}
    # children start no earlier than the parent and fit inside it
    assert inner.wall_t0 >= outer.wall_t0
    assert inner.wall_dur + inner2.wall_dur <= outer.wall_dur
    assert tr._stack == []  # balanced enter/exit


def test_enable_resets_ids_and_buffer():
    tr = Tracer()
    tr.enable()
    with tr.span("a"):
        pass
    tr.enable()
    with tr.span("b"):
        pass
    assert [s.name for s in tr.spans] == ["b"]
    assert tr.spans[0].id == 0


def test_span_records_exception_and_reraises():
    tr = Tracer()
    tr.enable()
    with pytest.raises(ValueError, match="boom"):
        with tr.span("failing"):
            raise ValueError("boom")
    assert tr.spans[0].args["error"] == "ValueError: boom"
    assert tr._stack == []  # exception path still pops the stack


def test_virtual_clock_recorded_and_restored():
    tr = Tracer()
    tr.enable()
    now = [10.0]
    prev = tr.set_virtual_clock(lambda: now[0])
    assert prev is None
    with tr.span("event"):
        now[0] = 12.5
    assert tr.set_virtual_clock(prev) is not None  # restore returns ours
    s = tr.spans[0]
    assert s.vt0 == 10.0 and s.vdur == 2.5
    tr.enable()
    with tr.span("no-clock"):
        pass
    assert tr.spans[0].vt0 is None  # outside a service run: wall view only


def test_timed_measures_wall_even_when_disabled():
    tr = Tracer()  # disabled
    with tr.timed("cell") as sp:
        sum(range(1000))
    assert sp.wall_us > 0.0
    assert tr.spans == []  # no span recorded while disabled
    tr.enable()
    with tr.timed("cell") as sp:
        pass
    assert sp.wall_us >= 0.0 and tr.spans[0].name == "cell"


def test_disabled_span_is_shared_noop_and_allocation_free():
    assert TRACER.span("a", cat="x") is TRACER.span("b")
    n0 = len(TRACER.spans)
    for _ in range(10):  # warm up any lazy caches before measuring
        with TRACER.span("hot"):
            pass
    import repro.obs.tracer as tracer_mod

    only_tracer = [tracemalloc.Filter(True, tracer_mod.__file__)]
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot().filter_traces(only_tracer)
    for _ in range(1000):
        with TRACER.span("hot"):
            pass
    snap2 = tracemalloc.take_snapshot().filter_traces(only_tracer)
    tracemalloc.stop()
    assert len(TRACER.spans) == n0
    # the disabled path performs no per-span allocation (shared _NOOP
    # singleton): anything tracemalloc attributes to the tracer module must
    # be O(1) interpreter incidentals (a cold frame object), never O(n) —
    # an allocating implementation would show >=1000 objects here
    grew = [s for s in snap2.compare_to(snap1, "lineno") if s.size_diff > 0]
    assert sum(s.count_diff for s in grew) < 50
    assert sum(s.size_diff for s in grew) < 4096


def test_traced_decorator_noop_when_disabled():
    calls = []

    @obs.traced("deco.fn", cat="t")
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2  # disabled: passthrough
    tr_spans_before = len(TRACER.spans)
    TRACER.enable()
    assert fn(2) == 3
    assert TRACER.spans[-1].name == "deco.fn"
    assert calls == [1, 2]
    assert tr_spans_before == 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_snapshot_delta_arithmetic():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.gauge("g").set(5)
    reg.histogram("h", bounds=(1.0, 10.0)).observe(0.5)
    before = reg.snapshot()
    reg.counter("c").inc(4)
    reg.gauge("g").set(7)
    reg.histogram("h").observe(3.0)
    d = MetricsRegistry.delta(before, reg.snapshot())
    assert d["counters"]["c"] == 4
    assert d["gauges"]["g"] == 7  # a gauge is a level, not a flow
    assert d["histograms"]["h"]["count"] == 1
    assert d["histograms"]["h"]["counts"] == [0, 1, 0]
    # None before → after passes through unchanged
    assert MetricsRegistry.delta(None, reg.snapshot())["counters"]["c"] == 5


def test_metrics_collectors_polled_at_snapshot_and_fault_isolated():
    reg = MetricsRegistry()
    state = {"n": 1}
    reg.register_collector("ok", lambda: dict(state))
    reg.register_collector("broken", lambda: 1 / 0)
    snap1 = reg.snapshot()
    state["n"] = 3
    snap2 = reg.snapshot()
    assert snap1["ok"]["n"] == 1 and snap2["ok"]["n"] == 3
    assert snap2["broken"]["error"].startswith("ZeroDivisionError")
    assert MetricsRegistry.delta(snap1, snap2)["ok"]["n"] == 2
    reg.reset()  # instruments cleared, collectors kept
    assert reg.snapshot()["counters"] == {} and "ok" in reg.snapshot()


def test_nearest_rank_is_always_an_observed_value():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert nearest_rank(xs, 50) == 2.0
    assert nearest_rank(xs, 100) == 4.0
    assert nearest_rank(xs, 1) == 1.0
    assert nearest_rank(range(1, 101), 95) == 95
    assert nearest_rank([7.5], 99) == 7.5
    with pytest.raises(ValueError):
        nearest_rank([], 50)
    with pytest.raises(ValueError):
        nearest_rank(xs, 0)
    # matches the numpy inverted-cdf method on a random sample
    rng = np.random.default_rng(0)
    sample = rng.normal(size=257)
    for q in (50, 90, 95, 99):
        assert nearest_rank(sample, q) == pytest.approx(
            float(np.percentile(sample, q, method="inverted_cdf")))


def test_histogram_buckets_and_percentiles():
    h = Histogram(bounds=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(v)
    assert h.counts == [1, 2, 1, 0, 1]
    assert h.count == 5 and h.min == 0.0005 and h.max == 5.0
    assert h.percentile(50) == 0.01  # bucket upper bound
    assert h.percentile(99) == 5.0  # overflow bucket reports the max
    j = h.to_json()
    assert j["count"] == 5 and j["counts"] == h.counts
    with pytest.raises(ValueError, match="sorted"):
        Histogram(bounds=(1.0, 0.5))


def test_pack_stats_delta():
    from repro.engine.packed import PackStats

    s = PackStats(hits=10, misses=4, evictions=1)
    d = s.delta((7, 4, 0))
    assert (d.hits, d.misses, d.evictions) == (3, 0, 1)
    assert d.hit_rate == 1.0


# ---------------------------------------------------------------------------
# JAX cost attribution
# ---------------------------------------------------------------------------

def _compile_event(seconds: float = 0.01) -> None:
    """What JAX records for one compile request (see ``obs.jaxevents``)."""
    import jax

    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", seconds)


def test_fitness_accounting_cache_growth_detection():
    import repro.engine  # noqa: F401 — installs the jax.compile.* counters

    acct = FitnessAccounting()

    def call(grow: bool) -> None:
        with acct.measure("fake", (4, 2, 8, 3), "fixed"):
            if grow:
                _compile_event()

    call(grow=True)   # compile: JAX compiled during the call
    call(grow=False)  # execute (nothing compiled)
    call(grow=False)
    table = acct.to_json()
    rec = table["fake|4x2x8x3|fixed"]
    assert rec["calls"] == 3 and rec["compiles"] == 1
    assert rec["execute_calls"] == 2  # calls - compiles: calls that compiled nothing
    assert rec["compile_us"] > 0.0 and rec["execute_us"] >= 0.0
    assert rec["execute_us_mean"] == pytest.approx(rec["execute_us"] / 2)
    acct.reset()
    assert acct.to_json() == {}


def test_engine_bucket_compile_vs_execute_split_pinned():
    """One engine bucket, N fitness calls: exactly one compile, N-1 cache
    hits — the pallas path attributes first-call autotune+build as compile."""
    from repro.core import ObjectiveWeights, Workload, build_problem, synthetic_system
    from repro.core.workload_model import random_layered_workflow
    from repro.engine import ENGINES, pack

    problem = build_problem(
        synthetic_system(3, seed=5),
        Workload((random_layered_workflow(9, seed=5, max_cores=4),)),
    )
    packed = pack(problem)
    fitness = ENGINES.get("pallas").population_fitness(packed, ObjectiveWeights())
    A = np.random.default_rng(0).integers(0, problem.num_nodes,
                                          (4, problem.num_tasks))
    FITNESS.reset()
    n = 3
    for _ in range(n):
        fitness(A)
    key = f"pallas|{'x'.join(str(d) for d in packed.bucket)}|fixed"
    rec = FITNESS.to_json()[key]
    assert rec["calls"] == n
    assert rec["compiles"] == 1  # first call per key builds the kernel
    assert rec["execute_calls"] == n - 1
    FITNESS.reset()


def test_engine_dispatch_counters_tick():
    before = METRICS.snapshot()
    from repro.core import ObjectiveWeights, Workload, build_problem, synthetic_system
    from repro.core.workload_model import random_layered_workflow
    from repro.engine import ENGINES, pack

    problem = build_problem(
        synthetic_system(3, seed=6),
        Workload((random_layered_workflow(8, seed=6, max_cores=4),)),
    )
    fitness = ENGINES.get("pallas").population_fitness(
        pack(problem), ObjectiveWeights())
    fitness(np.zeros((2, problem.num_tasks), dtype=np.int32))
    d = MetricsRegistry.delta(before, METRICS.snapshot())["counters"]
    # the pallas engine traced through the kernel path, not the jnp one
    assert d.get("engine.traced.pallas", 0) >= 1
    assert d.get("engine.traced.ref", 0) == 0
    assert not any(k.startswith("engine.dispatch.") for k in d)


def test_compile_counters_from_jax_events():
    """``jax.compile.requests`` counts JAX's compile requests (a fresh jit
    compile does), ``jax.compile.cache_hits`` the persistent cache's hits,
    and ``jax.compile.in.<span>`` the innermost open span's, while tracing
    is on; installing twice registers the listeners once."""
    import jax
    import jax.numpy as jnp

    obs.install_compile_counters()
    obs.install_compile_counters()
    x = jnp.zeros((7, 3, 5)).block_until_ready()  # a shape no other test compiles
    before = METRICS.snapshot()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    d = MetricsRegistry.delta(before, METRICS.snapshot())["counters"]
    assert d["jax.compile.requests"] == 1
    assert d["jax.compile.cache_hits"] >= 1  # the event above, and a real hit if any
    assert not any(k.startswith("jax.compile.in.") for k in d)  # tracing off

    TRACER.enable()
    before = METRICS.snapshot()
    with TRACER.span("outer"):
        with TRACER.span("step.that.compiles"):
            _compile_event()
        _compile_event()
    _compile_event()  # outside any span: counted in requests only
    d = MetricsRegistry.delta(before, METRICS.snapshot())["counters"]
    assert d["jax.compile.requests"] == 3
    assert d["jax.compile.in.step.that.compiles"] == 1
    assert d["jax.compile.in.outer"] == 1
    # counters, not spans: the trace holds only the two spans
    assert [s.name for s in TRACER.spans] == ["outer", "step.that.compiles"]


def test_spans_land_in_the_jax_profiler_trace(tmp_path):
    """With tracing on, each span is also a host event of its name in a
    ``jax.profiler`` trace, inside the enclosing profiler annotation and
    nested as the spans are; a span left by an exception closes its event
    too.  With tracing off nothing is annotated."""
    import jax
    from jax.profiler import ProfileData

    TRACER.enable()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("test.window"):
        with TRACER.span("obs.outer"):
            with TRACER.span("obs.inner"):
                sum(range(1000))
        with pytest.raises(ValueError):
            with TRACER.span("obs.failing"):
                raise ValueError("boom")
    TRACER.disable()
    with TRACER.span("obs.untraced"):
        pass
    jax.profiler.stop_trace()

    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    assert "obs.untraced" not in events
    (window,) = events["test.window"]
    (outer,) = events["obs.outer"]
    (inner,) = events["obs.inner"]
    (failing,) = events["obs.failing"]
    assert window[0] <= outer[0] <= inner[0] <= inner[1] <= outer[1] <= failing[0]
    assert failing[1] <= window[1]
    # the span's own wall time holds the annotation's
    spans = {s.name: s for s in TRACER.spans}
    assert (outer[1] - outer[0]) * 1e-9 <= spans["obs.outer"].wall_dur


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_perfetto_export_schema_and_summary(tmp_path):
    TRACER.enable()
    vclock = TRACER.set_virtual_clock(lambda: 42.0)
    try:
        with TRACER.span("outer", cat="test"):
            with TRACER.span("inner", cat="test", args={"k": "v"}):
                pass
    finally:
        TRACER.set_virtual_clock(vclock)
    p = write_trace(tmp_path / "t.json")
    obj = json.loads(p.read_text())
    assert obj["displayTimeUnit"] == "ms"
    evs = obj["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["ph"] for e in evs} <= {"M", "X"}
    assert all(isinstance(e["ts"], (int, float)) and e["dur"] >= 0 for e in xs)
    # both spans appear on the wall view (pid 1) and the virtual view (pid 2)
    assert sorted(e["pid"] for e in xs) == [1, 1, 2, 2]
    inner = next(e for e in xs if e["name"] == "inner" and e["pid"] == 1)
    assert inner["args"]["k"] == "v" and inner["args"]["parent"] == 0
    s = summarize_trace(p)
    assert s["wall_spans"] == 2 and s["virtual_spans"] == 2
    assert s["categories"]["test"]["count"] == 2
    assert {t["name"] for t in s["top_spans_us"]} == {"outer", "inner"}


def test_summarize_trace_rejects_malformed_files(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X", "ts": "zero"}]}))
    with pytest.raises(ValueError, match="ts/dur"):
        summarize_trace(bad)
    bad.write_text(json.dumps({"events": []}))
    with pytest.raises(ValueError, match="traceEvents"):
        summarize_trace(bad)


def test_telemetry_block_shape(tmp_path):
    before = METRICS.snapshot()
    METRICS.counter("t.obs.test").inc(2)
    block = telemetry(before)
    assert block["metrics"]["counters"]["t.obs.test"] == 2
    assert isinstance(block["engine_fitness"], dict)
    assert block["spans"] == 0  # tracer disabled
    p = write_metrics(tmp_path / "m.json", block)
    flat = json.loads(p.read_text())
    assert flat["metrics.counters.t.obs.test"] == 2


# ---------------------------------------------------------------------------
# determinism: traced chaos replay
# ---------------------------------------------------------------------------

def test_traced_chaos_replay_fingerprint_bit_identical():
    """Two traced service runs of the same chaos trace at the same seed
    produce byte-identical virtual fingerprints (ids, nesting, names,
    virtual timestamps, args — everything but wall time)."""
    from repro.service import SchedulingService, ServiceConfig, generate_trace

    trace = generate_trace(
        12, seed=3, rate=2.0, families=("stgs", "random", "tpu"),
        chaos={"horizon": 300.0, "failure_rate": 0.03, "outage_mean": 20.0},
    )
    cfg = ServiceConfig(batch_window=0.5, seed=3, max_retries=2,
                        backoff_base=0.5, backoff_cap=8.0)

    def traced_run():
        TRACER.enable()  # resets ids/origin → replayable sequence
        try:
            SchedulingService(trace.system, cfg).run(trace)
            return virtual_fingerprint(TRACER.spans), len(TRACER.spans)
        finally:
            TRACER.disable()

    fp_a, n_a = traced_run()
    fp_b, n_b = traced_run()
    assert n_a == n_b and n_a > 0
    assert fp_a == fp_b
    # and the trace covered the acceptance span families
    names = {s.name for s in TRACER.spans}
    assert "service.run" in names
    assert "service.dispatch" in names
    assert any(n.startswith("event.") for n in names)
    assert "solve.route" in names or "solve.with_fallback" in names


# ---------------------------------------------------------------------------
# logging
# ---------------------------------------------------------------------------

def test_logging_namespaced_and_idempotent():
    log = obs.logger("service")
    assert log.name == "repro.service"
    root = logging.getLogger("repro")
    assert any(isinstance(h, logging.NullHandler) for h in root.handlers)
    n0 = len(root.handlers)
    obs.setup_logging()
    obs.setup_logging()  # second call must not stack handlers
    assert len(root.handlers) == n0 + 1
    stream = [h for h in root.handlers if not isinstance(h, logging.NullHandler)]
    root.removeHandler(stream[0])  # leave global state as found
