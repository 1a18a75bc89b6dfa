"""Device time of the GA sweep program per population-evaluation step: the
program's XLA module time per run in the trace, averaged over the chips,
over the (generations + 1) x tasks steps of one call."""


def read(ctx):
    seconds = sum(ctx.trace["module_s"].values())
    runs = sum(ctx.trace["module_runs"].values())
    steps = ctx.facts.get("fitness_steps_per_call")
    if not seconds or not steps:
        return None
    return seconds / runs / steps * 1e6
