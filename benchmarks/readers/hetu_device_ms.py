"""Milliseconds the first chip was busy (union of its ``XLA Ops`` events,
on the host's clock) while the program was inside one of the spans
``spans``, per occurrence of the span ``per``: the device's share of a
decode round is its busy time under ``launch`` and ``fetch``."""

from benchmarks.harness import reduce
from benchmarks.readers import hetu_spans


def read(ctx, *, per: str, spans: list):
    sp = hetu_spans.spans(ctx) if ctx.trace is not None else None
    if sp is None or not sp.get(per):
        return None
    busy = reduce.intersect(hetu_spans.intervals(sp, spans),
                            ctx.trace.first_chip().busy)
    return reduce.measure(busy) / 1e6 / len(sp[per])
