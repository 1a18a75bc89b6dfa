"""JAX's persistent compilation cache, switched on by the program's entry
points (``python -m repro``, ``benchmarks/run.py``, ``chip_smoke.py``) and
never by importing the library.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise, on an accelerator, the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache`` — never a
temporary, per-process or timestamped name, since the directory is part of
what a later run must find again.  On the CPU backend this module leaves
JAX's default alone: XLA:CPU cache entries are tied to the host's CPU
features, and compiles there are cheap.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

#: cache compiles down to a tenth of a second, so the second-scale Pallas and
#: fitness compiles hit as well as the long sweep programs
MIN_COMPILE_SECONDS = 0.1


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory it uses (None on
    the CPU backend without ``JAX_COMPILATION_CACHE_DIR``)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECONDS)
    return path
