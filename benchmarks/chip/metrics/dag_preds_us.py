"""Device time of the evaluator's predecessor terms per task step on a DAG
cell: the self time of the operations inside ``jax.named_scope("preds")``
(the rate select, the transfer and the running maximum of each predecessor
row), per chip, over the GA program's runs times the (generations + 1) x
tasks steps of one call.  ``None`` when the program has no such scope."""

import scopes


def read(ctx):
    runs = sum(ctx.trace["module_runs"].values())
    steps = ctx.facts.get("fitness_steps_per_call")
    if not runs or not steps:
        return None
    seconds = scopes.traced_scope_seconds("preds")
    if seconds is None:
        return None
    return seconds / runs / steps * 1e6
