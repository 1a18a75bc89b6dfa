"""A rehearsal of the harness on the CPU at tiny size: every cell loads by
name with its readers, ``BENCHMARK.json`` keeps to the benchmark's format,
the run refuses to measure without a TPU, the result line carries the keys
the format asks for, and a new cell needs new files only."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
from testsizes import tiny  # noqa: E402

ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][1:] == ["benchmarks/chip/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith(BENCH["paths"][0] + "/")
        assert (ROOT / c["file"]).is_file()
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["name"] not in names
        assert m["better"] in ("lower", "higher")
        names.add(m["name"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
        reported = [m["name"] for m in BENCH["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in reported and len(reported) >= 2
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(workload):
    cell = harness.load_cell(workload)
    assert cell.cell["name"] == workload
    assert hasattr(cell.driver, "Driver")
    assert cell.readers and all(callable(r.read) for r in cell.readers.values())
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "schedules_per_s"}


def test_refuses_to_measure_on_cpu(tmp_path):
    with pytest.raises(harness.NoDevice):
        harness.devices(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = [sys.executable, "benchmarks/chip/run.py", "--workload", "table9-500.sweep8",
           "--seed", str(2**40 + 3), "--seconds", "1", "--trace", "0"]
    out = subprocess.run(run, cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2 and out.stdout == ""
    # a directory that holds only the benchmark's own files
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(run, cwd=tmp_path, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0 and out.stdout == ""


@pytest.mark.parametrize("workload", ["table9-500.sweep8"])
def test_result_line_keys(workload):
    import time

    cell = tiny(workload)
    for traced in (False, True):
        result = harness.measure(cell, 2**33 + 17, 0.5, traced, t_process=time.time(),
                                 platform="cpu")
        line = json.loads(json.dumps(result))
        keys = ["correct", "attempted", "failed", "metrics", "device"]
        assert list(line) == keys + (["breakdown"] if traced else []) + ["checks"]
        assert line["correct"] is True and line["failed"] == 0
        assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
        wanted = cell.per_layer if traced else cell.end_to_end
        assert set(line["metrics"]) <= {m["name"] for m in wanted}
        if traced:
            assert {"busy_s", "window_s"} <= set(line["device"])
            assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        else:
            assert set(line["metrics"]) == {m["name"] for m in wanted}
        for check in line["checks"].values():
            assert set(check) == {"value", "limit"}


def test_a_new_cell_is_new_files_only(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    traffic = json.loads((chip / "traffic" / "sweep8.json").read_text())
    (chip / "traffic" / "sweep4.json").write_text(json.dumps(dict(traffic, group=4)))
    (chip / "metrics" / "schedules_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx.facts['schedules'])\n")
    bench["workloads"].append({"name": "table9-500.sweep4", "config": "table9-500",
                               "traffic": "sweep4", "chips": 1, "why": "a test cell"})
    bench["per_layer"].append({"name": "schedules_in_window", "unit": "count", "better": "higher",
                               "source": "host_clock", "layer": "engine and search",
                               "moves": "schedules_per_s", "workloads": ["table9-500.sweep4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("table9-500.sweep4", tmp_path)
    assert cell.traffic["group"] == 4
    assert "schedules_in_window" in cell.readers and "device_idle_pct" in cell.readers
    assert "fitness_step_us" not in cell.readers  # listed for other cells only
    assert all(p.read_bytes() == b for p, b in before.items())
