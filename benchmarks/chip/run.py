"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run.py --workload table9-500.sweep8 --seed 7 \\
        --seconds 40 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The run exits with
code 2 and prints no result when JAX finds no TPU or fewer chips than the
cell asks for.  See ``harness.py`` for how a cell is found and measured.
"""

import time

T_PROCESS = time.time()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import CACHE_DIR, main  # noqa: E402

# the compile cache lives at one fixed path inside the checkout; set before
# JAX is imported, so that the program's own cache set-up takes this one
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
# load from one process with few threads: the host work is single-threaded
# Python and numpy, and idle BLAS pools only add noise on a shared host
for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(var, "1")

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
