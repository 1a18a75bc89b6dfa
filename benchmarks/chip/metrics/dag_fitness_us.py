"""Device time of the fitness evaluator per task step on a DAG cell:
``fitness_scope_us``'s reading (the ``fitness`` scope's self time per chip,
over the GA program's runs times the (generations + 1) x tasks steps of one
call), where the driver counts the mosaics' real tasks."""

from pathlib import Path

import harness

read = harness.load_module(Path(__file__).with_name("fitness_scope_us.py"),
                           "chipbench_metric_fitness_scope_us").read
