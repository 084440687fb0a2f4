"""Mean of a span's own time: its duration less the spans named in
``inside``, which only ever run within it."""


def read(ctx, *, span: str, inside: list):
    spans = ctx.rec.spans.get(span)
    if not spans:
        return None
    total = sum(b - a for a, b in spans)
    for name in inside:
        total -= sum(b - a for a, b in ctx.rec.spans.get(name, ()))
    return 1e3 * total / len(spans)
