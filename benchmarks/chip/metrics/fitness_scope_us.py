"""Device time of the fitness evaluator per population-evaluation step: the
self time of the operations compiled inside the program's
``jax.named_scope("fitness")`` (an ``op_name`` path with a ``fitness``
segment) within the traced window, averaged over the chips, over the GA
program's runs in the window times the (generations + 1) x tasks steps of
one call.  ``None`` when the trace holds no ``fitness`` operation."""

import scopes


def read(ctx):
    runs = sum(ctx.trace["module_runs"].values())
    steps = ctx.facts.get("fitness_steps_per_call")
    if not runs or not steps:
        return None
    seconds = scopes.traced_scope_seconds("fitness")
    if seconds is None:
        return None
    return seconds / runs / steps * 1e6
