"""What the program's own spans say about one traced run: for each
``hetu:`` span its count and mean, the first chip's idle time by the
innermost such span, the benchmark's own spans beside them, and how
``hetu:serve.decode`` sits inside the benchmark's ``bench:engine.decode``.  Reads the newest xplane under
``.bench_out/trace`` (what the last ``--trace 1`` run of this checkout
left) or the file given; prints one JSON object.

    python3 benchmarks/tools/hetu_report.py [file.xplane.pb]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def report(path: str) -> dict:
    from benchmarks.harness import reduce
    from benchmarks.readers import hetu_spans

    planes = reduce.load(path)
    summary = reduce.summarize(planes)
    threads = {want: sorted({(p.name, ln.name) for p in planes
                             for ln in p.lines for e in ln.events
                             if e.name.split("#")[0] == want})
               for want in ("hetu:serve.decode", "bench:engine.decode")}
    ctx = SimpleNamespace(trace=summary,
                          run=SimpleNamespace(trace_path=path))
    sp = hetu_spans.spans(ctx) or {}
    chip = summary.first_chip()
    gaps = reduce.complement(chip.busy, *summary.window)
    out = {"window_s": summary.window_s,
           "idle_share_pct": 100.0 * summary.idle_share,
           "clock_shift_ms": summary.clock_shift_ns / 1e6,
           "spans": {n: {"n": len(iv), "mean_ms": sum(
               b - a for a, b in iv) / 1e6 / len(iv),
               "device_busy_mean_ms": reduce.measure(reduce.intersect(
                   reduce.union(iv), chip.busy)) / 1e6 / len(iv)}
               for n, iv in sorted(sp.items())},
           "idle_s_by_hetu_span": dict(sorted(
               reduce.attribute_gaps(gaps, sp).items(),
               key=lambda kv: -kv[1])),
           "bench_spans": {n: {"n": len(iv), "mean_ms": sum(
               b - a for a, b in iv) / 1e6 / len(iv)}
               for n, iv in sorted(summary.spans.items())},
           "device_ops": summary.breakdown(top=25)["device_ops"]}
    outer = sorted(a_b for a_b in summary.spans.get("engine.decode", ())
                   if a_b[0] >= summary.window[0]
                   and a_b[1] <= summary.window[1])
    inner = sp.get("serve.decode", [])
    if outer and len(outer) == len(inner):
        seams = [sp.get(f"serve.decode.{s}", [])
                 for s in ("prep", "launch", "fetch", "post")]
        mean = [sum(b - a for a, b in iv) / 1e6 / len(iv) for iv in seams]
        out["decode_nesting"] = {
            "same_thread": threads["hetu:serve.decode"]
            == threads["bench:engine.decode"],
            "hetu_inside_bench": all(o[0] <= i[0] and i[1] <= o[1]
                                     for o, i in zip(outer, inner)),
            "seams_in_order": all(
                i[0] <= p[0] and p[1] <= la[0] and la[1] <= f[0]
                and f[1] <= po[0] and po[1] <= i[1]
                for i, p, la, f, po in zip(inner, *seams)),
            "bench_engine_decode_mean_ms": sum(
                b - a for a, b in outer) / 1e6 / len(outer),
            "seams_mean_ms": dict(zip(("prep", "launch", "fetch", "post"),
                                      mean)),
            "seams_sum_mean_ms": sum(mean)}
    return out


if __name__ == "__main__":
    from benchmarks.harness import loops

    print(json.dumps(report(sys.argv[1] if len(sys.argv) > 1
                            else loops.trace_file())))
