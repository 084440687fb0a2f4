"""Milliseconds per unit of work: one of the loop's totals over one of its
counts (a train step is ``window_s`` over ``steps``)."""


def read(ctx, *, total: str, count: str):
    n = ctx.run.values.get(count)
    if not n:
        return None
    return 1e3 * ctx.run.values[total] / n
