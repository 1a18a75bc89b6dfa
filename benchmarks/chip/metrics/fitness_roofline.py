"""Share of the roofline the GA sweep program reaches: the least time one
call's fitness evaluations need (``roofline.fitness_work`` against the
chip's peaks, averaged over the window's calls) over the program's device
time per run."""

import json
from pathlib import Path

import roofline

PEAKS = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())


def read(ctx):
    seconds = sum(ctx.trace["module_s"].values())
    runs = sum(ctx.trace["module_runs"].values())
    work = ctx.facts.get("fitness_work_per_call")
    if not seconds or not work:
        return None
    if ctx.device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {ctx.device_kind!r} in peaks.json")
    least = [roofline.least_seconds(*w, PEAKS[ctx.device_kind])[0] for w in work]
    return 100.0 * (sum(least) / len(least)) / (seconds / runs)
