"""Mean duration of one of the benchmark's spans over the window."""


def read(ctx, *, span: str):
    spans = ctx.rec.spans.get(span)
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
