"""The scheduler's device kernels: the population fitness evaluator.

* ``ref.py`` — the jnp evaluator (``population_makespan_ref``) the compiled
  searches run;
* ``select.py`` — the rank selects it places a task's cores with;
* ``rowdma.py`` — the Pallas row-DMA write of each candidate's core-state
  row (a TPU only; a scatter elsewhere);
* ``makespan.py`` — the Pallas makespan kernel, taken only when asked for;
* ``ops.py`` — the dispatch between the last and the jnp evaluator.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
