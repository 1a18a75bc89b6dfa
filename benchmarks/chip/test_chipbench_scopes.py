"""The readers of the program's own spans and scope: ``fitness_scope_us``
(operations compiled inside ``jax.named_scope("fitness")``, from a synthetic
profiler trace written in the format a TPU writes), ``host_prepare_ms`` and
``host_finish_ms`` (``repro.obs`` spans), and ``None`` from each where the
program records nothing for it."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import scopes  # noqa: E402

#: two chips; the fitness while loop holds a fused gather of the evaluator
#: (nested: its time is not the loop's), a fusion outside the scope runs
#: after it, and a name whose metadata is both in and out counts as out
XSPACE = """
planes {{
  id: 1 name: "/device:TPU:{chip}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 10 offset_ps: 0 duration_ps: 8000000000 }}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {loop_ps} }}
    events {{ metadata_id: 2 offset_ps: 1000000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 3 offset_ps: 5000000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 4 offset_ps: 6000000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 4 offset_ps: 9000000000 duration_ps: 1000000000 }}
  }}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_one(123)" }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.1 = f32[8] while(%a)"
    stats {{ metadata_id: 9 str_value: "jit(one)/vmap()/while/body/closed_call/{scope}/vmap()/while:" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.2 = f32[8] fusion(%b)"
    stats {{ metadata_id: 9 ref_value: 8 }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.3 = f32[8] fusion(%c)"
    stats {{ metadata_id: 9 str_value: "jit(one)/vmap()/while/body/closed_call/select_n:" }} }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%copy.4 = f32[8] copy(%d)"
    stats {{ metadata_id: 9 str_value: "jit(one)/vmap({scope})/copy:" }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%copy.4 = f32[8] copy(%d)"
    stats {{ metadata_id: 9 str_value: "jit(other)/copy:" }} }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
  stat_metadata {{ key: 8 value {{ id: 8 name: "jit(one)/vmap({scope})/vmap()/gather:" }} }}
}}
"""
HOST = """
planes {
  id: 99 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
}
"""


def _write_trace(trace_dir: Path, scope: str = "fitness") -> Path:
    """A trace of two chips under ``trace_dir`` as the profiler lays it out."""
    from jax.profiler import ProfileData

    planes = [XSPACE.format(chip=0, loop_ps=4_000_000_000, scope=scope),
              XSPACE.format(chip=1, loop_ps=3_000_000_000, scope=scope), HOST]
    out = trace_dir / "plugins" / "profile" / "2026_01_01_00_00_00" / "host.xplane.pb"
    out.parent.mkdir(parents=True)
    out.write_bytes(ProfileData.text_proto_to_serialized_xspace("".join(planes)))
    return out


def test_in_scope_segments():
    assert scopes.in_scope("jit(one)/vmap()/while/body/closed_call/fitness/vmap()/gather:",
                           "fitness")
    assert scopes.in_scope("jit(one)/vmap(fitness)/vmap()/while/body/closed_call/gather:",
                           "fitness")
    assert scopes.in_scope("jit(f)/jvp(vmap(fitness))/mul", "fitness")
    assert not scopes.in_scope("jit(one)/fitness_x/gather:", "fitness")
    assert not scopes.in_scope("jit(population_fitness)/gather", "fitness")
    assert not scopes.in_scope("", "fitness") and not scopes.in_scope(None, "fitness")


def test_scope_seconds_self_time_in_window_over_chips():
    devices = {
        "/device:TPU:0": [("loop", 0.0, 4.0), ("inner", 1.0, 2.0), ("other", 5.0, 6.0),
                          ("late", 7.0, 9.0)],
        "/device:TPU:1": [("loop", 0.0, 3.0), ("inner", 1.0, 2.0)],
    }
    names = {plane: {"loop": {"a/fitness/while:"}, "inner": {"a/vmap(fitness)/gather:"},
                     "other": {"a/select_n:"}, "late": {"a/fitness/copy:"}}
             for plane in devices}
    # chip 0: loop 3 + inner 1 + late clipped to 1; chip 1: loop 2 + inner 1
    assert scopes.scope_seconds(devices, (0.0, 8.0), names, "fitness") == pytest.approx(4.0)
    names["/device:TPU:0"]["inner"].add("a/select_n:")  # ambiguous: out
    assert scopes.scope_seconds(devices, (0.0, 8.0), names, "fitness") == pytest.approx(3.5)


def test_op_names_and_traced_scope_seconds_from_a_trace_file(tmp_path):
    path = _write_trace(tmp_path)
    names = scopes.op_names(path)
    assert sorted(names) == ["/device:TPU:0", "/device:TPU:1"]
    chip0 = names["/device:TPU:0"]
    assert chip0["%fusion.2 = f32[8] fusion(%b)"] == {"jit(one)/vmap(fitness)/vmap()/gather:"}
    assert len(chip0["%copy.4 = f32[8] copy(%d)"]) == 2  # one name, two metadata
    devices, window = scopes.load(path)
    assert window == pytest.approx((0.0, 0.008))
    assert [n for n, _, _ in devices["/device:TPU:1"]][:2] == [
        "%while.1 = f32[8] while(%a)", "%fusion.2 = f32[8] fusion(%b)"]
    # chip 0: loop 3 ms + gather 1 ms, chip 1: 2 ms + 1 ms; the copy is
    # ambiguous and the select is out of the scope
    assert scopes.traced_scope_seconds("fitness", tmp_path) == pytest.approx(0.0035)


def test_no_fitness_operation_reads_none(tmp_path):
    _write_trace(tmp_path, scope="search")
    assert scopes.traced_scope_seconds("fitness", tmp_path) is None
    assert scopes.traced_scope_seconds("fitness", tmp_path / "none") is None


def _ctx(spans=(), module_runs=None):
    return SimpleNamespace(
        spans=list(spans), facts={"fitness_steps_per_call": 10},
        trace={"module_runs": {"jit_one": 2.0} if module_runs is None else module_runs})


def test_fitness_scope_us_reader(tmp_path, monkeypatch):
    reader = harness.load_cell("table9-500.sweep8").readers["fitness_scope_us"]
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    assert reader.read(_ctx()) is None  # no trace
    _write_trace(tmp_path)
    # 3.5 ms per chip over 2 runs of 10 steps
    assert reader.read(_ctx()) == pytest.approx(175.0)
    assert reader.read(_ctx(module_runs={})) is None


def test_fitness_scope_us_reads_none_without_the_scope(tmp_path, monkeypatch):
    reader = harness.load_cell("table9-500.sweep8.4chip").readers["fitness_scope_us"]
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    _write_trace(tmp_path, scope="search")
    assert reader.read(_ctx()) is None


def _span(id, parent, name, ms):
    return SimpleNamespace(id=id, parent=parent, name=name, wall_dur=ms * 1e-3)


#: two calls: prepare (with a pack nested), device, two rescorings each;
#: an ``mh.finish`` of a singleton solve outside any sweep does not count
SPANS = [
    _span(0, None, "mh.ga_sweep", 100), _span(1, 0, "mh.ga_sweep.prepare", 30),
    _span(2, 1, "engine.pack", 10), _span(3, 0, "mh.ga_sweep.device", 50),
    _span(4, 0, "mh.finish", 8), _span(5, 0, "mh.finish", 9),
    _span(6, None, "mh.ga_sweep", 90), _span(7, 6, "mh.ga_sweep.prepare", 20),
    _span(8, 6, "mh.ga_sweep.device", 50), _span(9, 6, "mh.finish", 7),
    _span(10, 6, "mh.finish", 6), _span(11, None, "mh.finish", 500),
]


@pytest.mark.parametrize("workload", ["table9-500.sweep8", "table9-500.sweep8.4chip"])
def test_host_span_readers(workload):
    readers = harness.load_cell(workload).readers
    assert readers["host_prepare_ms"].read(_ctx(SPANS)) == pytest.approx(25.0)
    assert readers["host_finish_ms"].read(_ctx(SPANS)) == pytest.approx(15.0)


def test_host_span_readers_read_none_without_the_spans():
    """A program whose ``ga_sweep`` records only its own span, as before the
    span tree, gives no reading."""
    readers = harness.load_cell("table9-500.sweep8").readers
    only_calls = [_span(0, None, "mh.ga_sweep", 100), _span(1, 0, "engine.pack", 10)]
    for name in ("host_prepare_ms", "host_finish_ms"):
        assert readers[name].read(_ctx(only_calls)) is None
        assert readers[name].read(_ctx()) is None
