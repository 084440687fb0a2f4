"""Share of the traced window in which no operation ran, averaged over the
chips the cell uses."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
