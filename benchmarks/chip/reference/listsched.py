"""Plain reference of the scheduling semantics, in float64 numpy.

It imports nothing of the program.  It builds its own dense model from the
raw description of the nodes and workflows (``generate.py``'s plain data),
orders the tasks itself, and replays an assignment with capacity-aware,
core-granular list scheduling (paper §IV, Eq. 4, 5 and 12):

* tasks run in one fixed order: workflows one after another, each in Kahn's
  topological order with ties broken by the task's position in its workflow;
* a task of ``R1`` cores on node ``i`` of ``C_i`` cores claims
  ``c = max(1, min(floor(R1), C_i))`` cores;
* it is ready at ``max(release, max over predecessors p of f_p + d_t)``,
  where ``d_t = R3_p / rate(a(p), i)`` when ``a(p) != i`` and 0 otherwise,
  and infinite when that link has no finite positive rate;
* it starts at ``max(ready, the c-th earliest core-free time of node i)``,
  runs for ``d_ij = work_j / speed_i`` (or the task's own per-node duration
  over ``speed_i``), and holds those ``c`` cores until it finishes;
* an assignment is feasible when every task's node provides its features,
  has at least ``R1`` cores and a finite duration.

``population_makespan`` replays many assignments at once; ``dtype`` sets the
arithmetic, so the same code in a lower precision serves as the control of
the comparison that decides ``correct``.
"""

from __future__ import annotations

import heapq

import numpy as np


def task_order(workflows: list[dict]) -> list[tuple[int, int]]:
    """``(workflow index, task index)`` in the order tasks claim cores."""
    order = []
    for w, wf in enumerate(workflows):
        tasks = wf["tasks"]
        index = {t["name"]: k for k, t in enumerate(tasks)}
        indeg = [len(t["deps"]) for t in tasks]
        succs: list[list[int]] = [[] for _ in tasks]
        for k, t in enumerate(tasks):
            for d in t["deps"]:
                succs[index[d]].append(k)
        heap = [k for k, d in enumerate(indeg) if d == 0]
        heapq.heapify(heap)
        while heap:
            k = heapq.heappop(heap)
            order.append((w, k))
            for s in succs[k]:
                indeg[s] -= 1
                if indeg[s] == 0:
                    heapq.heappush(heap, s)
    return order


def default_rates(nodes: list[dict]) -> np.ndarray:
    """Link rates limited by the slower endpoint, infinite on the diagonal."""
    rate = np.array([n["rate"] for n in nodes], dtype=np.float64)
    dtr = np.minimum.outer(rate, rate)
    np.fill_diagonal(dtr, np.inf)
    return dtr


def build_model(nodes: list[dict], workflows: list[dict]) -> dict:
    """Dense arrays in :func:`task_order`."""
    speed = np.array([n["speed"] for n in nodes])
    node_cores = np.array([n["cores"] for n in nodes], dtype=np.float64)
    vocab = sorted({f for n in nodes for f in n["features"]}
                   | {f for wf in workflows for t in wf["tasks"] for f in t["features"]})
    col = {f: k for k, f in enumerate(vocab)}
    provides = np.zeros((len(nodes), len(vocab)), dtype=bool)
    for i, n in enumerate(nodes):
        provides[i, [col[f] for f in n["features"]]] = True

    order = task_order(workflows)
    T, N = len(order), len(nodes)
    position = {}
    names, release, cores, data = [], np.zeros(T), np.zeros(T), np.zeros(T)
    durations = np.zeros((T, N))
    requires = np.zeros((T, len(vocab)), dtype=bool)
    for g, (w, k) in enumerate(order):
        wf, t = workflows[w], workflows[w]["tasks"][k]
        position[(w, t["name"])] = g
        names.append(f"{wf['name']}/{t['name']}")
        release[g], cores[g], data[g] = wf["submission"], t["cores"], t["data"]
        requires[g, [col[f] for f in t["features"]]] = True
        per_node = t.get("durations")
        if per_node is None:
            durations[g] = t["work"] / np.maximum(speed, 1e-30)
        else:
            durations[g] = np.array([per_node.get(n["name"], np.inf) for n in nodes]) \
                / np.maximum(speed, 1e-30)
    preds = [[position[(w, d)] for d in workflows[w]["tasks"][k]["deps"]] for w, k in order]
    feasible = ((requires[:, None, :] & ~provides[None, :, :]).sum(-1) == 0) \
        & (cores[:, None] <= node_cores[None, :]) & np.isfinite(durations)
    return {
        "names": names, "release": release, "cores": cores, "data": data,
        "durations": durations, "feasible": feasible, "preds": preds,
        "node_cores": node_cores, "dtr": default_rates(nodes),
    }


def claims(model: dict, assignment: np.ndarray) -> np.ndarray:
    """Cores each task claims on its node ([P, T])."""
    caps = model["node_cores"][assignment]
    return np.maximum(1, np.minimum(np.floor(model["cores"][None, :]), caps)).astype(np.int64)


def population_makespan(model: dict, population: np.ndarray, dtype=np.float64) -> dict:
    """Replay every row of ``population`` ([P, T] node indices in task order).

    Returns ``start``/``finish`` [P, T], ``makespan`` [P] and ``invalid`` [P]
    (tasks placed where they are infeasible).  Arithmetic is in ``dtype``."""
    pop = np.asarray(population, dtype=np.int64)
    P, T = pop.shape
    one = np.asarray(1, dtype)
    durations = model["durations"].astype(dtype)
    data = model["data"].astype(dtype)
    release = model["release"].astype(dtype)
    with np.errstate(divide="ignore"):
        dtr = model["dtr"]
        usable = np.isfinite(dtr) & (dtr > 0)
        # finite stand-in for an unusable link, like the program's evaluators
        rate = np.where(usable, dtr, one).astype(dtype)
    width = int(model["node_cores"].max())
    rows = np.full((P, len(model["node_cores"]), width), np.inf, dtype=dtype)
    for i, c in enumerate(model["node_cores"]):
        rows[:, i, : max(int(c), 1)] = 0
    claimed = claims(model, pop)
    start = np.zeros((P, T), dtype=dtype)
    finish = np.zeros((P, T), dtype=dtype)
    lanes = np.arange(P)
    for j in range(T):
        node = pop[:, j]
        ready = np.full(P, release[j], dtype=dtype)
        for p in model["preds"][j]:
            src = pop[:, p]
            transfer = np.where(src == node, dtype(0), data[p] / rate[src, node])
            transfer = np.where(usable[src, node] | (src == node), transfer, np.inf)
            ready = np.maximum(ready, finish[:, p] + transfer)
        row = np.sort(rows[lanes, node], axis=1)
        c = claimed[:, j]
        s = np.maximum(ready, row[lanes, c - 1])
        f = (s + durations[j, node]).astype(dtype)
        row = np.where(np.arange(width)[None, :] < c[:, None], f[:, None], row)
        rows[lanes, node] = row
        start[:, j], finish[:, j] = s, f
    invalid = (~model["feasible"][np.arange(T)[None, :], pop]).sum(axis=1)
    return {"start": start, "finish": finish, "makespan": finish.max(axis=1, initial=0),
            "invalid": invalid}


def lower_bound(model: dict) -> float:
    """Critical-path bound: the longest path of each task's fastest feasible
    duration from its release, with no contention and no transfers.  No
    schedule of the problem can finish earlier."""
    fastest = np.where(model["feasible"], model["durations"], np.inf).min(axis=1)
    done = np.zeros(len(fastest))
    for j, preds in enumerate(model["preds"]):
        ready = max([model["release"][j]] + [done[p] for p in preds])
        done[j] = ready + fastest[j]
    return float(done.max(initial=0.0))
