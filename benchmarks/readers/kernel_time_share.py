"""Share of the first chip's busy time spent in operations whose trace name
matches ``pattern``."""

from benchmarks.harness import reduce


def read(ctx, *, pattern: str):
    if ctx.trace is None:
        return None
    busy = reduce.measure(ctx.trace.first_chip().busy)
    if not busy:
        return None
    own = sum(own for _, own in ctx.trace.kernel_events(pattern))
    return 100.0 * own / busy
