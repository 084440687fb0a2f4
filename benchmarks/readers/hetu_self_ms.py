"""Mean of a program span's own time over the traced stretch, in
milliseconds: its duration less the parts of it covered by the spans named
in ``less`` (a scheduler step less the engine calls inside it)."""

from benchmarks.harness import reduce
from benchmarks.readers import hetu_spans


def read(ctx, *, span: str, less: list):
    sp = hetu_spans.spans(ctx)
    if sp is None or not sp.get(span):
        return None
    own = reduce.subtract(hetu_spans.intervals(sp, [span]),
                          hetu_spans.intervals(sp, less))
    return reduce.measure(own) / 1e6 / len(sp[span])
