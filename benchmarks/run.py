"""Benchmark harness — one module per paper table/figure plus the kernel
microbenches.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # bounded default set
    PYTHONPATH=src python -m benchmarks.run --full     # + 5000x5000 scale row
    PYTHONPATH=src python -m benchmarks.run --smoke    # small Table IX sizes
                                                       # → BENCH_table9.json
    PYTHONPATH=src python -m benchmarks.run --service  # 200-submission trace
                                                       # → BENCH_service.json
    PYTHONPATH=src python -m benchmarks.run --engine   # per-backend engine
                                                       # throughput
                                                       # → BENCH_engine.json
    PYTHONPATH=src python -m benchmarks.run --campaign smoke
                                                       # any campaign (built-in
                                                       # name or spec file)
                                                       # → BENCH_campaign.json
    PYTHONPATH=src python -m benchmarks.run --campaign chaos
                                                       # robustness lane: seeded
                                                       # failure storms
                                                       # → BENCH_chaos.json
    PYTHONPATH=src python -m benchmarks.run --campaign topology
                                                       # generated continua +
                                                       # twin calibration
                                                       # → BENCH_topology.json
    PYTHONPATH=src python -m benchmarks.run --campaign cycling
                                                       # recurring workflows +
                                                       # hard constraints
                                                       # → BENCH_cycling.json
    PYTHONPATH=src python -m benchmarks.run --scenario f.json  # time one
                                                       # orchestrated Scenario

``--smoke``, ``--service``, ``--engine`` and ``--campaign smoke`` are the CI
modes; each is a thin built-in campaign (:mod:`repro.campaigns.builtin`)
whose export stays byte-compatible with the pre-campaign harness — together
they leave a per-PR perf trajectory (``BENCH_table9.json`` /
``BENCH_service.json`` / ``BENCH_engine.json`` / ``BENCH_campaign.json``).
``--scenario`` times a declarative :class:`repro.core.api.Scenario` end to
end through the Fig. 4 orchestrator.
"""

from __future__ import annotations

import argparse
import time


def _run_scenario(path: str) -> None:
    from repro.core import api

    scenario = api.load_scenario(path)
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    result = api.run_scenario(scenario)
    us = (time.perf_counter() - t0) * 1e6
    summary = result.summary()
    derived = (
        f"rounds={summary['rounds']};adapted={summary['adapted']};"
        f"technique={summary['technique']};"
        f"makespan={summary.get('observed_makespan', summary['predicted_makespan'])}"
    )
    print(f"scenario_{scenario.name},{us:.0f},{derived}")


def _print_suite(name: str, rows_fn) -> None:
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for row in rows_fn():
        print(",".join(str(x) for x in row), flush=True)
    print(f"{name}_suite_total,{(time.perf_counter() - t0) * 1e6:.0f},")


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="paper-table benchmark harness (CSV to stdout, "
        "BENCH_*.json artifacts for the CI lanes)",
    )
    mode = parser.add_mutually_exclusive_group()
    mode.add_argument("--smoke", action="store_true",
                      help="small Table IX sizes → BENCH_table9.json")
    mode.add_argument("--service", action="store_true",
                      help="200-submission service trace → BENCH_service.json")
    mode.add_argument("--engine", action="store_true",
                      help="per-backend engine throughput → BENCH_engine.json")
    mode.add_argument("--campaign", metavar="NAME|SPEC",
                      help="run a campaign (built-in name or spec JSON file) "
                      "→ BENCH_campaign.json")
    mode.add_argument("--scenario", metavar="SPEC",
                      help="time one orchestrated Scenario JSON end to end")
    parser.add_argument("--full", action="store_true",
                        help="default set only: add the 5000x5000 scale row")
    parser.add_argument("--trace", metavar="PATH",
                        help="record a Perfetto trace of the run to PATH "
                        "(plus PATH.metrics.json)")
    args = parser.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.trace:
        from pathlib import Path

        from repro import obs

        out = Path(args.trace)
        obs.enable_tracing()
        try:
            _run_mode(args)
        finally:
            obs.write_trace(out)
            obs.write_metrics(out.with_suffix(".metrics.json"))
        return
    _run_mode(args)


def _run_mode(args: argparse.Namespace) -> None:
    if args.scenario:
        _run_scenario(args.scenario)
        return
    if args.smoke:
        from repro.campaigns import builtin

        _print_suite("table9_smoke", builtin.run_smoke)
        return
    if args.service:
        from repro.campaigns import builtin

        _print_suite("service", builtin.run_service_bench)
        return
    if args.engine:
        from repro.campaigns import builtin

        _print_suite("engine", builtin.run_engine_bench_export)
        return
    if args.campaign:
        from repro.campaigns import builtin

        if args.campaign == "chaos":
            # the robustness lane has its own SLO-centric export
            _print_suite("chaos", builtin.run_chaos_bench)
            return
        if args.campaign == "topology":
            # the continuum lane adds twin-calibration + generator-scale
            # rows beyond the generic campaign export
            _print_suite("topology", builtin.run_topology_bench)
            return
        if args.campaign == "cycling":
            # the cycling lane adds the constraint-satisfaction report and
            # the converging-stream service section
            _print_suite("cycling", builtin.run_cycling_bench)
            return
        run = builtin.run_named_campaign(args.campaign)
        print("name,us_per_call,derived")
        for row in run.rows:
            print(",".join(str(x) for x in row), flush=True)
        print(f"campaign_{run.campaign.name}_suite_total,"
              f"{run.wall_seconds * 1e6:.0f},")
        return

    from benchmarks import (
        bench_fig11_quality,
        bench_kernels,
        bench_table6_mri,
        bench_table9_scale,
    )

    suites = [
        ("table6", lambda: bench_table6_mri.run()),
        ("fig11", lambda: bench_fig11_quality.run(full=args.full)),
        ("table9", lambda: bench_table9_scale.run(full=args.full)),
        ("kernels", lambda: bench_kernels.run()),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures += 1
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
        print(f"{name}_suite_total,{(time.perf_counter() - t0) * 1e6:.0f},", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
