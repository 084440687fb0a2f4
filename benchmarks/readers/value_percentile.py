"""A percentile of one of the loop's lists (milliseconds per request or per
token gap)."""

from benchmarks.harness.loops import percentile


def read(ctx, *, key: str, q: float):
    xs = ctx.run.values.get(key)
    if not xs:
        return None
    return percentile(xs, q)
