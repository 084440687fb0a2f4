"""Where a program launch's time goes, for one traced run: one line a program
kind (decode round, prefill chunk, train step) with its launches, the mean
and 95th percentile of each part (issue, program, runtime, readback:
``benchmarks/readers/hetu_launches.py``), the transfers a fetch waits for and
their bytes, the share of the traced window each part holds, and whether
``prep + issue + program + runtime + readback + post`` tiles the call's own
span; a last line has the window, the device's idle share and the tokens a
second the engine served while it was traced (from the spans' ids).  Reads
the newest xplane under ``.bench_out/trace`` (what the last ``--trace 1`` run
of this checkout left) or the file given; prints one JSON object a line.  A
trace that cannot be paired prints why.

    python3 benchmarks/tools/launch_report.py [file.xplane.pb]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def _p95(values) -> float:
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(0.95 * len(ordered)))]


def report(path: str) -> list:
    from benchmarks.harness import reduce
    from benchmarks.readers import hetu_id_ratio, hetu_launches, hetu_spans

    try:
        summary = reduce.summarize(reduce.load(path))
    except ValueError:          # no device in the trace
        summary = None
    ctx = SimpleNamespace(trace=summary,
                          run=SimpleNamespace(trace_path=path))
    scan = hetu_launches.scan_file(path)
    records, why = hetu_launches.pair_scan(scan)
    if records is None:
        return [{"paired": False, "why": why, "counts": {
            k: len(v) if not isinstance(v, dict)
            else {c: len(runs) for c, runs in v.items()}
            for k, v in scan.items()}}]
    sp = hetu_spans.spans(ctx) or {}
    window = summary.window if summary is not None \
        else hetu_spans._load(path)[1]
    window_ns = window[1] - window[0]
    out = []
    for kind in (*hetu_launches.SERVE, *hetu_launches.TRAIN):
        recs = hetu_launches.inside(records, kind, window)
        if not recs:
            continue
        line = {"kind": kind, "module": recs[0].module.split("(")[0],
                "launches": len(recs)}
        total = 0.0
        for part in hetu_launches.PARTS:
            values = [v for v in (r.part_ns(part) for r in recs)
                      if v is not None]
            if not values:
                continue
            mean = sum(values) / len(values)
            total += mean
            line[part] = {"mean_ms": mean / 1e6,
                          "p95_ms": _p95(values) / 1e6,
                          "window_share_pct": 100.0 * sum(values)
                          / window_ns}
        fetched = [r for r in recs if r.fetch is not None]
        if fetched:
            line["transfers_a_fetch"] = sum(
                r.transfers for r in fetched) / len(fetched)
            line["bytes_a_fetch"] = sum(
                r.transfer_bytes for r in fetched) / len(fetched)
            line["run_ahead"] = sum(
                r.done_prev is not None and r.done_prev > r.x
                for r in recs)
        calls = sp.get(kind)
        if kind in hetu_launches.SERVE and calls:
            host = sum(b - a for s in ("prep", "post")
                       for a, b in sp.get(f"{kind}.{s}", ())) / len(calls)
            call = sum(b - a for a, b in calls) / len(calls)
            line["host_ms"] = host / 1e6
            line["call_ms"] = call / 1e6
            line["parts_over_call"] = (host + total) / call
        out.append(line)
    # what the stretch served while it was traced: a decode round makes one
    # token an active slot, a chunk prefills its tokens (the spans' ids)
    served = sum(r.ids["tokens"] for r in hetu_launches.inside(
        records, "serve.prefill_chunk", window))
    served += hetu_id_ratio._totals(path, ("serve.decode",)).get("active", 0)
    out.append({"window_s": window_ns / 1e9,
                "idle_share_pct": None if summary is None
                else 100.0 * summary.idle_share,
                "tokens_per_s_while_traced": served * 1e9 / window_ns
                if served else None})
    return out


def main(argv) -> int:
    if len(argv) > 1:
        path = argv[1]
    else:
        from benchmarks.harness import loops
        path = loops.trace_file()
    for line in report(path):
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
