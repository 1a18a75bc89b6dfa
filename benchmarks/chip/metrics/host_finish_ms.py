"""Host time a ``ga_sweep`` call spends rescoring its returned bests with the
f64 oracle: the ``mh.finish`` spans nested in ``mh.ga_sweep`` spans, summed
over the traced run, over its ``mh.ga_sweep`` calls.  ``None`` when the
program records no such span."""


def read(ctx):
    by_id = {s.id: s for s in ctx.spans}

    def in_sweep(span) -> bool:
        while span.parent is not None:
            span = by_id.get(span.parent)
            if span is None:
                return False
            if span.name == "mh.ga_sweep":
                return True
        return False

    calls = sum(1 for s in ctx.spans if s.name == "mh.ga_sweep")
    finish = [s.wall_dur for s in ctx.spans if s.name == "mh.finish" and in_sweep(s)]
    if not calls or not finish:
        return None
    return sum(finish) / calls * 1e3
