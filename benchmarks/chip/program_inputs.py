"""Plain data from ``generate.py`` turned into the program's own inputs."""

from __future__ import annotations


def nodes_of(nodes: list[dict]):
    from repro.core.system_model import Node, make_system

    return make_system([
        Node(n["name"], {"cores": n["cores"], "memory": n["memory"], "storage": n["storage"]},
             frozenset(n["features"]),
             {"processing_speed": n["speed"], "data_transfer_rate": n["rate"]})
        for n in nodes
    ])


def workflow_of(wf: dict):
    from repro.core.workload_model import Task, Workflow

    return Workflow(
        name=wf["name"], submission=wf["submission"],
        tasks=tuple(Task(name=t["name"], cores=t["cores"], data=t["data"],
                         features=frozenset(t["features"]), work=t["work"],
                         deps=tuple(t["deps"]))
                    for t in wf["tasks"]),
    )


def problem_of(nodes: list[dict], workflows: list[dict]):
    from repro.core.workload_model import Workload, build_problem

    return build_problem(nodes_of(nodes), Workload(tuple(workflow_of(w) for w in workflows)))
