"""XLA compilations inside the window: ``jax.monitoring`` backend-compile
events that the persistent compilation cache did not serve."""


def read(ctx):
    requests, cache_hits = ctx.compiles
    return float(requests - cache_hits)
