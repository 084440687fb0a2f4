"""A ratio of the ids the program puts on its own spans: the sum of the ids
``over`` divided by the sum of the ids ``under``, times ``scale``, over every
``hetu:<span>`` event of the traced stretch whose span is one of ``spans``.
The profiler keeps a span's ids as the event's stats (``hetu_spans`` reads
names and times only).  The expert layer's counts ride on the ``post`` spans
of a decode round and of a prefill chunk: pairs routed to held experts over
held experts hit is the rows a hit expert computes a call and a layer.  A
trace whose spans carry no such id (a program without the counts, or no
trace at all) gives None, and the metric is left out."""

from functools import lru_cache

from benchmarks.readers.hetu_spans import PREFIX


@lru_cache(maxsize=2)
def _totals(path: str, spans: tuple) -> dict:
    """{id: its sum over the events of ``spans``} from the xplane file."""
    from jax.profiler import ProfileData

    want = {PREFIX + s for s in spans}
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.split("#")[0] in want:
                    for k, v in e.stats:
                        if isinstance(v, (int, float)):
                            out[k] = out.get(k, 0) + v
    return out


def read(ctx, *, spans: list, over: list, under: list, scale: float = 1.0):
    path = getattr(ctx.run, "trace_path", None)
    if not path:
        return None
    totals = _totals(path, tuple(spans))
    if not all(k in totals for k in (*over, *under)):
        return None
    below = sum(totals[k] for k in under)
    if not below:
        return None
    return scale * sum(totals[k] for k in over) / below
