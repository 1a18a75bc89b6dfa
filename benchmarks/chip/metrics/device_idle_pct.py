"""Share of the traced window in which no operation ran on the chip, averaged
over the chips: 1 - (union of operation intervals) / window."""


def read(ctx):
    if not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
