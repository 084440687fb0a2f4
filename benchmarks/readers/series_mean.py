"""Mean of one of the series the benchmark's wrappers note in the window
(``decode_active``: slots decoded per step)."""


def read(ctx, *, series: str):
    xs = ctx.rec.series.get(series)
    if not xs:
        return None
    return sum(xs) / len(xs)
