"""Run one cell several times, one process per run, and report each metric's
spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 benchmarks/chip/spread.py --workload table9-500.sweep8 \\
        --seeds 11 12 13 14 15 16 --seconds 40 --out runs.jsonl

This process never imports JAX, so every run gets the chip to itself.  Each
run's result line is appended to ``--out`` with its seed; the summary goes
to standard output.  A second set with the same seeds gives the other half
of the measurement that a bound is set from.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values: list[float]) -> float:
    q1, median, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / median if median else float("nan")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out", type=Path, required=True)
    args = parser.parse_args(argv)
    lines = []
    for seed in args.seeds:
        cmd = [sys.executable, str(ROOT / "benchmarks/chip/run.py"), "--workload",
               args.workload, "--seed", str(seed), "--seconds", str(args.seconds),
               "--trace", str(args.trace)]
        t0 = time.time()
        out = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        last = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
        record = {"seed": seed, "rc": out.returncode, "wall_s": time.time() - t0}
        try:
            record["result"] = json.loads(last)
        except json.JSONDecodeError:
            record["stderr"] = out.stderr[-3000:]
        with args.out.open("a") as f:
            f.write(json.dumps(record) + "\n")
        lines.append(record)
        brief = {k: v["value"] for k, v in record.get("result", {}).get("metrics", {}).items()}
        print(seed, record["rc"], round(record["wall_s"], 1),
              record.get("result", {}).get("correct"), json.dumps(brief), flush=True)
    results = [r["result"] for r in lines if "result" in r]
    for name in sorted({m for r in results for m in r["metrics"]}):
        values = [r["metrics"][name]["value"] for r in results if name in r["metrics"]]
        if len(values) >= 2:
            print(f"{name}: median {statistics.median(values)!r} "
                  f"spread {spread(values)!r} n {len(values)}", flush=True)
    return 0 if all(r.get("rc") == 0 for r in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
