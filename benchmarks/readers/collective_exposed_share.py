"""Share of the traced window in which, on the first chip, a collective
operation ran and no other operation did."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.exposed_collective_s() / ctx.trace.window_s
