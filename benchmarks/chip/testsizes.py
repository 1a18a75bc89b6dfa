"""Cells shrunk to a size the CPU test runs hold: the same drivers, readers
and comparison, on small instances."""

import harness


def tiny(name: str, root=harness.ROOT):
    cell = harness.load_cell(name, root)
    cell.config = dict(
        cell.config, system=dict(cell.config["system"], nodes=16),
        workload=dict(cell.config["workload"], tasks=40), instance_seeds=[1, 2, 3, 4],
        solver=dict(cell.config["solver"], pop_size=16, generations=8))
    cell.traffic = dict(cell.traffic, group=2)
    return cell
