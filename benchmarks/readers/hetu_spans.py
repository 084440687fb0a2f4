"""The program's own spans in the profiler's trace of a ``--trace 1`` run:
the host events named ``hetu:<span>`` that ``hetu_tpu.telemetry.trace.span``
writes while a profiler session runs (ids after a ``#`` are cut off), on the
clock ``harness/reduce.py`` shifts the device's times onto.  Shared by the
``hetu_*`` readers; a trace that holds no such event (a program from before
the spans, or no trace at all) gives None, and the metric is left out."""

from collections import defaultdict
from functools import lru_cache

from benchmarks.harness import reduce

PREFIX = "hetu:"


@lru_cache(maxsize=2)
def _load(path: str) -> tuple:
    """(((name, start_ns, end_ns), ...) of every ``hetu:`` event in the
    file, all host threads together; the window the benchmark marked)."""
    planes = reduce.load(path)
    out = []
    for plane in planes:
        if reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out += [(e.name[len(PREFIX):].split("#")[0], e.start, e.end)
                    for e in line.events if e.name.startswith(PREFIX)]
    marks = reduce.host_spans(planes).get(reduce.WINDOW_SPAN)
    window = (min(a for a, _ in marks), max(b for _, b in marks)) \
        if marks else None
    return tuple(out), window


def spans(ctx):
    """{span name: [(start, end)]}, sorted, of the spans that lie wholly
    inside the traced window; None where there is nothing to read.  The
    window is the trace summary's; a trace with no device in it (a
    rehearsal on the CPU) has no summary, and the benchmark's mark in the
    file is used."""
    path = getattr(ctx.run, "trace_path", None)
    if not path:
        return None
    events, window = _load(path)
    if ctx.trace is not None:
        window = ctx.trace.window
    if window is None:
        return None
    out = defaultdict(list)
    for name, a, b in events:
        if a >= window[0] and b <= window[1]:
            out[name].append((a, b))
    return {name: sorted(iv) for name, iv in out.items()} or None


def intervals(sp: dict, names) -> list:
    """Disjoint cover of the spans called any of ``names``."""
    return reduce.union(iv for n in names for iv in sp.get(n, ()))
