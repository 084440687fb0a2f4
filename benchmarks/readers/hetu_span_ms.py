"""Milliseconds inside the program's spans ``spans`` per occurrence of the
span ``per``, over the traced stretch: the host's share of a decode round
is its ``prep`` and ``post`` per ``serve.decode``."""

from benchmarks.readers import hetu_spans


def read(ctx, *, per: str, spans: list):
    sp = hetu_spans.spans(ctx)
    if sp is None or not sp.get(per):
        return None
    total = sum(b - a for name in spans for a, b in sp.get(name, ()))
    return total / 1e6 / len(sp[per])
