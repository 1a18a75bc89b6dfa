"""Share of the roofline the fitness evaluator reaches on a DAG cell: the
least time one call's fitness evaluations need, counted per edge
(``roofline_dag.fitness_work`` from the driver, against the chip's peaks,
averaged over the window's calls), over the ``fitness`` scope's device time
per run of the GA program."""

import json
from pathlib import Path

import roofline
import scopes

PEAKS = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())


def read(ctx):
    runs = sum(ctx.trace["module_runs"].values())
    work = ctx.facts.get("fitness_work_per_call")
    if not runs or not work:
        return None
    seconds = scopes.traced_scope_seconds("fitness")
    if not seconds:
        return None
    if ctx.device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {ctx.device_kind!r} in peaks.json")
    least = [roofline.least_seconds(*w, PEAKS[ctx.device_kind])[0] for w in work]
    return 100.0 * (sum(least) / len(least)) / (seconds / runs)
