"""The cell-independent machinery of one benchmark run.

``run.py`` parses the command line and calls :func:`main`.  Everything that
belongs to one configuration, traffic mix or per-layer metric is found by
name under this directory:

* ``configs/<config>.json`` — the deployment (``BENCHMARK.json`` names it);
* ``traffic/<traffic>.json`` — the mix; its ``driver`` names
  ``drivers/<driver>.py``, which builds the inputs from the seed, warms up,
  runs one unit of work at a time and checks what the window produced;
* ``metrics/<metric>.py`` — one reader per per-layer metric, ``read(ctx)``;
* ``reference/limits.json`` — the limit of every number compared.

A run loads, warms up, then measures whole units of work until the window's
seconds have passed, and prints one JSON object as its last line.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the persistent compilation cache, at one fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: profiler output of a traced run, replaced by each traced run
TRACE_DIR = ROOT / ".chipbench" / "trace"
#: a traced run profiles the whole units of work that reach this many seconds
TRACE_SECONDS = 1.0
REQUIRED_PLATFORM = "tpu"


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """Everything ``BENCHMARK.json`` and this directory say about one cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; cells {sorted(cells)}")
    cell = cells[workload]
    here = root / bench["paths"][0]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json").read_text())
    driver = load_module(here / "drivers" / f"{traffic['driver']}.py",
                         f"chipbench_driver_{traffic['driver']}")

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"] if applies(m)]
    readers = {m["name"]: load_module(here / "metrics" / f"{m['name']}.py",
                                      f"chipbench_metric_{m['name']}")
               for m in per_layer}
    limits = json.loads((here / "reference" / "limits.json").read_text())
    return SimpleNamespace(name=workload, cell=cell, config=config, traffic=traffic,
                           driver=driver, end_to_end=end_to_end, per_layer=per_layer,
                           readers=readers, limits=limits, run_seconds=bench["run_seconds"])


def devices(chips: int, platform: str = REQUIRED_PLATFORM) -> list:
    import jax

    found = jax.devices()
    if found[0].platform != platform:
        raise NoDevice(f"JAX found no {platform} (platform {found[0].platform!r})")
    if len(found) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found {len(found)}")
    return found


class Compiles:
    """XLA compile requests and persistent-cache hits, from jax.monitoring."""

    def __init__(self) -> None:
        import jax

        self.requests = 0
        self.cache_hits = 0

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.cache_hits


def memory_peak_bytes(used: list) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compare(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """Each number compared beside its limit; ``correct`` when none exceeds it."""
    checks, ok = {}, True
    for name, value in numbers.items():
        finite = value is not None and math.isfinite(value)
        checks[name] = {"value": value if finite else None, "limit": limits[name]}
        ok = ok and finite and value <= limits[name]
    return ok, checks


def measure(cell, seed: int, seconds: float, trace: bool, *, t_process: float,
            platform: str = REQUIRED_PLATFORM) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax

    used = devices(cell.cell["chips"], platform)[: cell.cell["chips"]]
    compiles = Compiles()
    driver = cell.driver.Driver(cell.config, cell.traffic, seed=seed, chips=cell.cell["chips"])
    driver.setup()
    driver.warm_up()

    from repro import obs

    if trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        span_origin = time.perf_counter()
        obs.enable_tracing()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from repro.obs
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    compiles0 = compiles.snapshot()
    t_window = time.perf_counter()
    setup_s = time.time() - t_process
    units, paused = 0, 0.0

    def run_until(seconds_in: float) -> None:
        nonlocal units
        while True:
            with jax.profiler.TraceAnnotation("chipbench.unit"):
                driver.unit(units)
            units += 1
            if time.perf_counter() - t_window - paused >= seconds_in:
                return

    if trace:
        # the profiler records the window's first whole units only: a
        # second of the GA program is hundreds of thousands of device ops;
        # writing the trace out pauses the window, and the pause is left out
        with jax.profiler.TraceAnnotation("chipbench.window"):
            run_until(min(seconds, TRACE_SECONDS))
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        paused = time.perf_counter() - t_stop
        if t_stop - t_window < seconds:
            run_until(seconds)
    else:
        run_until(seconds)
    window_s = time.perf_counter() - t_window - paused
    compiles1 = compiles.snapshot()
    if trace:
        obs.disable_tracing()
    peak = memory_peak_bytes(used)

    facts = driver.window_facts()
    numbers, quality = driver.check()
    correct, checks = compare(numbers, cell.limits)
    result = {
        "correct": correct,
        "attempted": facts["attempted"],
        "failed": facts["failed"],
        "metrics": {},
        "device": {"platform": used[0].platform, "kind": used[0].device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if not trace:
        values = {
            "schedules_per_s": facts["schedules"] / window_s,
            "setup_s": setup_s,
            "makespan_vs_lb": quality,
        }
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from trace_reduce import reduce_profile

        spans = list(obs.TRACER.spans)
        reduced = reduce_profile(TRACE_DIR, spans=spans, span_origin=span_origin,
                                 window_anchor=t_window, module_names=facts.get("modules", ()))
        ctx = SimpleNamespace(
            window_s=window_s, facts=facts, spans=spans, trace=reduced,
            compiles=(compiles1[0] - compiles0[0], compiles1[1] - compiles0[1]),
            device_kind=used[0].device_kind,
        )
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["checks"] = checks
    return result


def main(argv: list[str] | None = None, *, t_process: float | None = None) -> int:
    import argparse

    t_process = time.time() if t_process is None else t_process
    parser = argparse.ArgumentParser(description="Run one benchmark cell once.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    try:
        devices(cell.cell["chips"])
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = measure(cell, args.seed, args.seconds, bool(args.trace), t_process=t_process)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0
