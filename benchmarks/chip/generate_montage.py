"""The benchmark's own generator of Montage mosaic workflows, as plain data.

A copy of the program's ``montage_workflow`` (``repro.core.workload_model``),
kept here so that the traffic a cell runs cannot change when the program
changes.  It draws the same random numbers in the same order as the
original, so the same seed gives the same mosaic
(``test_chipbench_dag.py`` checks this).  A workflow is the plain data of
``generate.py``: ``{"name", "submission", "tasks": [{"name", "cores",
"data", "features", "work", "deps"}]}``.

The structure is the Montage workflow of Bharathi et al. (WORKS 2008) and
Juve et al. (FGCS 29(3), 2013), for ``n`` images and ``m`` overlapping
pairs: ``mProjectPP`` x n; ``mDiffFit`` x m on the pair's projections;
``mConcatFit`` on every fit; ``mBgModel``; ``mBackground`` x n on the model
and the image's projection; ``mImgtbl`` on every corrected image; ``mAdd``
on the table and every corrected image; ``mShrink``; ``mJPEG``.
"""

from __future__ import annotations

import numpy as np

#: per type: work at speed 1 (s) and output size (MB), after the Montage
#: per-job profile of Juve et al. 2013, Table 2, rounded
TYPES = {
    "mProjectPP": (1.73, 8.09),
    "mDiffFit": (0.66, 0.64),
    "mConcatFit": (143.26, 1.18),
    "mBgModel": (384.49, 0.10),
    "mBackground": (1.72, 8.09),
    "mImgtbl": (2.78, 0.12),
    "mAdd": (282.37, 775.45),
    "mShrink": (66.10, 0.49),
    "mJPEG": (0.64, 0.39),
}
#: σ of the seeded lognormal factor on each task's work and output size
JITTER = 0.2


def overlaps(rows: int, cols: int) -> list[tuple[int, int]]:
    """Every 8-neighbour pair of a ``rows`` x ``cols`` grid of images:
    horizontal, vertical, then the two diagonals."""
    def at(r, c):
        return r * cols + c

    pairs = [(at(r, c), at(r, c + 1)) for r in range(rows) for c in range(cols - 1)]
    pairs += [(at(r, c), at(r + 1, c)) for r in range(rows - 1) for c in range(cols)]
    pairs += [(at(r, c), at(r + 1, c + 1)) for r in range(rows - 1) for c in range(cols - 1)]
    pairs += [(at(r, c + 1), at(r + 1, c)) for r in range(rows - 1) for c in range(cols - 1)]
    return pairs


def montage_workflow(rows: int, cols: int, *, seed: int, name: str) -> dict:
    """One mosaic of ``rows`` x ``cols`` images; single-core tasks that need
    feature F1, each type's work and output size times a lognormal factor
    drawn per task in task order (work, then data)."""
    rng = np.random.default_rng(seed)
    n = rows * cols
    pairs = overlaps(rows, cols)
    proj = [f"mProjectPP_{k}" for k in range(n)]
    fits = [f"mDiffFit_{k}" for k in range(len(pairs))]
    back = [f"mBackground_{k}" for k in range(n)]
    layout = [(p, "mProjectPP", []) for p in proj]
    layout += [(f, "mDiffFit", [proj[a], proj[b]]) for f, (a, b) in zip(fits, pairs)]
    layout += [("mConcatFit", "mConcatFit", list(fits)), ("mBgModel", "mBgModel", ["mConcatFit"])]
    layout += [(b, "mBackground", ["mBgModel", p]) for b, p in zip(back, proj)]
    layout += [("mImgtbl", "mImgtbl", list(back)), ("mAdd", "mAdd", ["mImgtbl"] + back),
               ("mShrink", "mShrink", ["mAdd"]), ("mJPEG", "mJPEG", ["mShrink"])]
    tasks = []
    for task, kind, deps in layout:
        work, data = TYPES[kind]
        factor = np.exp(JITTER * rng.standard_normal(2))
        tasks.append({"name": task, "cores": 1.0, "data": float(data * factor[1]),
                      "features": ["F1"], "work": float(work * factor[0]), "deps": deps})
    return {"name": name, "submission": 0.0, "tasks": tasks}
