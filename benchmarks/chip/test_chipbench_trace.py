"""The reduction from a profiler trace to busy time, idle gaps labelled by
host span, device operations and module time, on a synthetic trace, and the
loader on a trace recorded here on the CPU."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402
from trace_reduce import NO_SPAN  # noqa: E402

#: two chips over a 10 s window; times in seconds on the trace's clock
DEVICES = {
    "/device:TPU:0": {
        "ops": [("fusion.1", 0.0, 2.0), ("fusion.2", 1.5, 3.0),  # overlap: busy 0-3
                ("while.3", 6.0, 7.0), ("fusion.1", 9.5, 12.0)],  # clipped at 10
        "modules": [("jit_one", 0.0, 3.0), ("jit_other", 6.0, 7.0), ("jit_one", 9.5, 12.0)],
    },
    "/device:TPU:1": {
        "ops": [("fusion.1", 0.0, 4.0)],
        "modules": [("jit_one", 0.0, 4.0)],
    },
}
HOST = [  # nested spans: a call containing a pack, then a host solve
    (2.5, 6.5, "mh.ga_sweep"), (3.0, 4.0, "engine.pack"), (7.0, 9.0, "heft"),
]


def test_union_and_complement():
    assert trace_reduce.union([(0, 2), (1.5, 3), (5, 6), (9, 12)], 0, 10) == [
        (0, 3), (5, 6), (9, 10)]
    assert trace_reduce.complement([(0, 3), (5, 6)], 0, 10) == [(3, 5), (6, 10)]


def test_innermost_labels_nested_spans():
    assert trace_reduce.innermost(HOST) == [
        (2.5, 3.0, "mh.ga_sweep"), (3.0, 4.0, "engine.pack"), (4.0, 6.5, "mh.ga_sweep"),
        (7.0, 9.0, "heft")]


def test_self_times_of_nested_operations():
    loop = [("while", 0, 10), ("body", 1, 4), ("inner", 2, 3), ("body", 5, 6), ("while", 11, 12)]
    assert trace_reduce.self_times(loop) == {"while": 7, "body": 3, "inner": 1}


def test_reduce_events():
    r = trace_reduce.reduce_events(DEVICES, (0.0, 10.0), HOST, module_names=("jit_one",))
    # chip 0 busy 3 + 1 + 0.5 = 4.5 s, chip 1 busy 4 s: mean 4.25 s
    assert r["busy_s"] == pytest.approx(4.25)
    assert r["window_s"] == 10.0
    # chip 0 idle: 3-6 and 7-9.5; chip 1 idle: 4-10; halves of each per label
    idle = dict(r["idle_gaps"])
    assert idle["engine.pack"] == pytest.approx(0.5 * 1.0)
    assert idle["mh.ga_sweep"] == pytest.approx(0.5 * (2.0 + 2.5))
    assert idle["heft"] == pytest.approx(0.5 * (2.0 + 2.0))
    assert idle[NO_SPAN] == pytest.approx(0.5 * (0.5 + 0.5 + 1.0))
    assert sum(idle.values()) == pytest.approx(10.0 - 4.25)
    assert r["idle_gaps"][0][0] == "mh.ga_sweep"  # longest first
    ops = dict(r["device_ops"])  # self time: fusion.2 starts inside fusion.1
    assert ops["fusion.1"] == pytest.approx(0.5 * (1.5 + 0.5 + 4.0))
    assert ops["fusion.2"] == pytest.approx(0.5 * 1.5)
    assert ops["while.3"] == pytest.approx(0.5)
    # module time is whole runs that touch the window, over the chips
    assert r["module_s"] == {"jit_one": pytest.approx(0.5 * (3.0 + 2.5 + 4.0))}
    assert r["module_runs"] == {"jit_one": pytest.approx(1.5)}


def test_readers_of_the_reduction():
    import harness

    cell = harness.load_cell("table9-500.sweep8")
    r = trace_reduce.reduce_events(DEVICES, (0.0, 10.0), HOST, module_names=("jit_one",))
    ctx = SimpleNamespace(trace=r, window_s=10.0, device_kind="TPU v5 lite",
                          facts={"fitness_steps_per_call": 21 * 500,
                                 "fitness_work_per_call": [(10**9, 10**8), (10**9, 2 * 10**8)]})
    step = cell.readers["fitness_step_us"].read(ctx)
    per_run = r["module_s"]["jit_one"] / r["module_runs"]["jit_one"]
    assert step == pytest.approx(per_run / (21 * 500) * 1e6)
    share = cell.readers["fitness_roofline"].read(ctx)
    least = (10**8 + 2 * 10**8) / 2 / 8.19e11  # the bytes bound, per call
    assert share == pytest.approx(100 * least / per_run)
    assert cell.readers["device_idle_pct"].read(ctx) == pytest.approx(57.5)
    empty = SimpleNamespace(**vars(ctx))
    empty.trace = dict(r, module_s={}, module_runs={})
    assert cell.readers["fitness_step_us"].read(empty) is None
    assert cell.readers["fitness_roofline"].read(empty) is None
    ctx.device_kind = "TPU v99"
    with pytest.raises(KeyError):
        cell.readers["fitness_roofline"].read(ctx)


def test_loader_finds_the_window_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, window = trace_reduce.load(tmp_path)
    assert window[1] > window[0] >= 0
    with pytest.raises(FileNotFoundError):
        trace_reduce.load(tmp_path / "empty")
