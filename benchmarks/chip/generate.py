"""The benchmark's own generator of Table IX instances, as plain data.

These are copies of the program's seeded generators (``synthetic_system``,
``synthetic_workload`` and the layered DAG of ``random_layered_workflow``),
kept here so that the traffic a cell runs cannot change when the program
changes.  They draw the same random numbers in the same order as the
originals, so the same seed gives the paper's instances
(``test_chipbench_reference.py`` checks this).

Everything returned is plain data — dicts, lists, floats — which the drivers
turn into the program's objects and the reference reads directly:

* a node: ``{"name", "cores", "memory", "storage", "features", "speed",
  "rate"}``;
* a workflow: ``{"name", "submission", "tasks": [{"name", "cores", "data",
  "features", "work", "deps"}]}``.
"""

from __future__ import annotations

import numpy as np

def synthetic_nodes(num_nodes: int, *, seed: int, max_cores: int = 64,
                    hetero_speed: bool = True) -> list[dict]:
    """The Table IX system: cores 4..max_cores, speed 1/2/4x, rate 10/50/100."""
    rng = np.random.default_rng(seed)
    pool = ["F1", "F2", "F3", "F4", "F5", "F6", "F7", "F8"]
    nodes = []
    for i in range(num_nodes):
        cores = int(rng.choice([4, 8, 16, 32, max_cores]))
        feats = {"F1"} | set(rng.choice(pool, size=rng.integers(1, 5), replace=False))
        speed = float(rng.choice([1.0, 2.0, 4.0])) if hetero_speed else 1.0
        rate = float(rng.choice([10.0, 50.0, 100.0]))
        nodes.append({"name": f"n{i}", "cores": cores, "memory": 64.0, "storage": 1000.0,
                      "features": sorted(str(f) for f in feats), "speed": speed, "rate": rate})
    return nodes


def layered_workflow(num_tasks: int, *, name: str, seed: int, max_width: int = 4,
                     density: float = 0.35, comm: bool = True,
                     feature_pool=("F1", "F2"), max_cores: int = 16) -> dict:
    """A layered random DAG: each task depends on the previous one or two
    layers with probability ``density``, and on at least one task of the
    previous layer."""
    rng = np.random.default_rng(seed)
    layers: list[list[int]] = []
    remaining, idx = num_tasks, 0
    while remaining > 0:
        width = int(min(remaining, rng.integers(1, max_width + 1)))
        layers.append(list(range(idx, idx + width)))
        idx += width
        remaining -= width
    tasks = []
    for li, layer in enumerate(layers):
        for t in layer:
            deps: list[str] = []
            if li > 0:
                cands = layers[li - 1] + (layers[li - 2] if li > 1 else [])
                for c in cands:
                    if rng.random() < density:
                        deps.append(f"T{c}")
                if not deps:
                    deps.append(f"T{rng.choice(layers[li - 1])}")
            cores = float(rng.integers(1, max_cores + 1))
            data = float(rng.integers(1, 9)) if comm else 0.0
            feats = rng.choice(list(feature_pool), size=rng.integers(1, len(feature_pool) + 1),
                               replace=False) if feature_pool else []
            work = float(rng.integers(1, 9))
            tasks.append({"name": f"T{t}", "cores": cores, "data": data,
                          "features": sorted(str(f) for f in feats), "work": work,
                          "deps": deps})
    return {"name": name, "submission": 0.0, "tasks": tasks}


def synthetic_workflows(num_tasks: int, *, seed: int, num_workflows: int = 1,
                        comm: bool = True, max_cores: int = 16) -> list[dict]:
    """The Table IX workload: ``num_workflows`` layered DAGs, feature F1 only."""
    rng = np.random.default_rng(seed)
    per = [num_tasks // num_workflows] * num_workflows
    per[-1] += num_tasks - sum(per)
    return [
        layered_workflow(cnt, name=f"W{w}", seed=int(rng.integers(0, 2**31)), comm=comm,
                         max_width=max(2, cnt // 8), max_cores=max_cores, feature_pool=("F1",))
        for w, cnt in enumerate(per)
    ]


def derive_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed from a run seed of any size and a salt (call index...)."""
    return int(np.random.SeedSequence([int(seed) % 2**63, *salt]).generate_state(1)[0] >> 1)
