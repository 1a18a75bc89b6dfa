"""Readings for the limits of ``correct``: the program's sound runs, the
control, and the planted faults, over many seeds in one process.

    python3 benchmarks/chip/control.py --workload table9-500.sweep8 \\
        --seeds 12 --units 2 --fault-seeds 3 > readings.jsonl

For every seed the cell's driver runs ``--units`` units of work as a
benchmark window would (no clock), and prints one JSON line with the numbers
compared, the control's readings at the same answers and
``makespan_vs_lb``.  Then each fault of ``faults.py`` that the cell can have
is planted for ``--fault-seeds`` seeds.  The benchmark's own runs never run
this; ``reference/limits.json`` is set from its readings.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import faults  # noqa: E402
import harness  # noqa: E402


def cell_faults(cell) -> tuple[str, ...]:
    return tuple(f for f in faults.SWEEP if f != "lost_exchange" or cell.cell["chips"] > 1)


def readings(cell, seed: int, units: int, *, fault: str | None = None,
             warm: bool = True) -> dict:
    """One seed's numbers: sound (or with ``fault`` planted) and control."""
    driver = cell.driver.Driver(cell.config, cell.traffic, seed=seed, chips=cell.cell["chips"])
    driver.setup()
    if warm:
        driver.warm_up()
    if fault is None:
        for k in range(units):
            driver.unit(k)
        numbers, quality = driver.check()
    else:
        # the fault stays planted through the check, as it would in a run
        with faults.sweep_fault(fault):
            for k in range(units):
                driver.unit(k)
            numbers, quality = driver.check()
    correct, _ = harness.compare(numbers, cell.limits)
    out = {"seed": seed, "fault": fault, "numbers": numbers, "correct": correct,
           "makespan_vs_lb": quality}
    if fault is None:
        out["control"], _ = driver.check(control=True)
        out["control_correct"], _ = harness.compare(out["control"], cell.limits)
    return out


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2**32 + 101)
    parser.add_argument("--units", type=int, default=2)
    parser.add_argument("--fault-seeds", type=int, default=3)
    args = parser.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    try:
        harness.devices(cell.cell["chips"])
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    for k, seed in enumerate(seeds):
        print(json.dumps(readings(cell, seed, args.units, warm=k == 0)), flush=True)
    for fault in cell_faults(cell):
        for seed in seeds[: args.fault_seeds]:
            print(json.dumps(readings(cell, seed, args.units, fault=fault, warm=False)),
                  flush=True)
    return 0


if __name__ == "__main__":
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    sys.exit(main())
