"""Share of the traced window in which the first chip ran nothing while the
innermost program span was one of the host's phases (``host``: names or
``fnmatch`` patterns, such as ``*.prep``).  What is left of the device's
idle share lies under the spans that launch a program and wait for it, in
gaps under 5 us between operations, or outside every span."""

from fnmatch import fnmatchcase

from benchmarks.harness import reduce
from benchmarks.readers import hetu_spans


def read(ctx, *, host: list):
    sp = hetu_spans.spans(ctx) if ctx.trace is not None else None
    if sp is None:
        return None
    gaps = reduce.complement(ctx.trace.first_chip().busy, *ctx.trace.window)
    by_span = reduce.attribute_gaps(gaps, sp)
    idle = sum(s for name, s in by_span.items()
               if any(fnmatchcase(name, pat) for pat in host))
    return 100.0 * idle / ctx.trace.window_s
