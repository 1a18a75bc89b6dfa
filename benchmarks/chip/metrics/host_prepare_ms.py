"""Host time a ``ga_sweep`` call spends before its device program: the
``mh.ga_sweep.prepare`` spans (bucket, stack or shard stack, logits, PRNG
keys, copies to the device) summed over the traced run, over its
``mh.ga_sweep`` calls.  ``None`` when the program records no such span."""


def read(ctx):
    calls = sum(1 for s in ctx.spans if s.name == "mh.ga_sweep")
    prepare = [s.wall_dur for s in ctx.spans if s.name == "mh.ga_sweep.prepare"]
    if not calls or not prepare:
        return None
    return sum(prepare) / calls * 1e3
