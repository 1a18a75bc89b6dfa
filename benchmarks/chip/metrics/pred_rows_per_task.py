"""Predecessor rows the evaluator scans per task: the ``rows`` (the packed
bucket's row count ``S``) over the ``tasks`` (its ``T``) that each
``mh.ga_sweep`` span records, averaged over the traced calls.  ``None`` when
the program records no rows."""


def read(ctx):
    calls = [s.args for s in ctx.spans
             if s.name == "mh.ga_sweep" and s.args and "rows" in s.args]
    if not calls:
        return None
    return sum(a["rows"] / a["tasks"] for a in calls) / len(calls)
