"""Where the WINDOW's time went, call by call, from the engine's round log
(``hetu_tpu/serve/metrics.py``: one row an engine call, its five seams on
``time.monotonic_ns()``; ``benchmarks/readers/round_log.py`` cuts it to the
window).  Drives one serving cell's loop as ``run.py`` does, in one process,
and prints one JSON object a line:

* ``window``: the accounting identity.  Every part of every call (``prep``,
  ``launch``, ``fetch``, ``post``) and every gap between two calls, clipped
  to the window, as a share of the window's length by part, and their sum,
  which reads 100.0 when the log covers the window end to end: then nothing
  on the round's path lies outside the parts and the gap.
* ``bucket``: a line for each (kind, batch, pages) the window ran, with its
  calls and p50 / p95 / max of each part and of the gap before the call, ms.
* ``long``: the ten calls that took longest beyond their bucket's usual
  (parts and gap, each less its bucket's median), with ``seq``, kind, bucket,
  seconds since the window opened, the call's own ms, and the part that
  carried the excess.
* with ``--trace 1``, for the traced STRETCH before the window, joined by
  ``seq`` with the launches ``readers/hetu_launches.py`` pairs in the xplane:
  ``clock``, the offset from the log's clock to the profiler's host clock
  (the launch span's opening less the row's ``t_launch``: median, and its
  spread as p5..p95 and range), the log's ``launch`` and ``fetch`` less the
  spans' of the same ``seq`` (median, us); and ``stretch``, a line a bucket
  with the log's ``fetch`` less the program's own run on the device (what
  the turn-round cost that call), beside the same bucket's ``fetch`` over
  the window.
* ``run``: tokens a second, the set-up's phases, whether the check passed.

    python3 benchmarks/tools/round_report.py --workload <cell> --seed <n> \\
        --seconds 40 --trace <0|1> [--rehearse]
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.readers import round_log  # noqa: E402

PARTS = (*round_log.PARTS, "gap")
KIND_NAMES = ("serve.decode", "serve.prefill_chunk")   # by the log's kind


def drive(args):
    """The cell's loop as ``run.py`` runs it; its ``loops.Run``."""
    from benchmarks import run as bench
    from benchmarks.harness import spec

    man = spec.manifest()
    cell = spec.cell(man, args.workload)
    chips = int(cell["chips"])
    if args.rehearse:   # before the first touch of JAX
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    config = spec.config(man, cell["config"], rehearse=args.rehearse)
    traffic = spec.traffic(cell["traffic"], rehearse=args.rehearse)

    from benchmarks.harness import device, loops
    from benchmarks.harness.spans import Recorder

    if not args.rehearse:
        device.enable_compile_cache()
        device.require_chips(chips)
    ctx = bench.Ctx(cell=cell, config=config, traffic=traffic,
                    seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), chips=chips, rec=Recorder(),
                    compiles=loops.CompileCounter())
    return loops.KINDS[traffic["kind"]](ctx)


def _ms(ns) -> dict:
    return {"p50": float(np.percentile(ns, 50)) / 1e6,
            "p95": float(np.percentile(ns, 95)) / 1e6,
            "max": float(ns.max()) / 1e6}


def _buckets(col, mask):
    """[(kind, batch, pages), mask of its rows] of the rows in ``mask``."""
    keys = np.stack([col["kind"], col["batch"], col["pages"]], 1)
    return [(tuple(int(v) for v in key), mask & (keys == key).all(1))
            for key in np.unique(keys[mask], axis=0)]


def window_lines(col, inside, t0: float, t1: float) -> list:
    # the identity: every part and gap of the whole ring, clipped to the
    # window (a call that straddles an edge counts for its part inside)
    seams = [col["t_prep"], col["t_launch"], col["t_fetch"], col["t_post"],
             col["t_close"]]
    clipped = {part: float((np.clip(b, t0, t1) - np.clip(a, t0, t1)).sum())
               for part, a, b in zip(PARTS, seams, seams[1:])}
    clipped["gap"] = float((np.clip(col["t_prep"][1:], t0, t1)
                            - np.clip(col["t_close"][:-1], t0, t1)).sum())
    share = {part: 100.0 * ns / (t1 - t0) for part, ns in clipped.items()}
    out = [{"window": {"seconds": (t1 - t0) / 1e9,
                       "calls": int(inside.sum()),
                       "share_pct": share,
                       "identity_pct": sum(share.values())}}]
    over = np.zeros((len(inside), len(PARTS)))   # each part less its usual
    for (kind, batch, pages), rows in _buckets(col, inside):
        out.append({"bucket": {
            "kind": KIND_NAMES[kind], "batch": batch, "pages": pages,
            "calls": int(rows.sum()),
            **{part: _ms(col[part][rows]) for part in PARTS}}})
        for at, part in enumerate(PARTS):
            over[rows, at] = col[part][rows] - np.median(col[part][rows])
    excess, carried = over.sum(1), over.argmax(1)
    for i in np.argsort(-np.where(inside, excess, -np.inf))[:10]:
        if inside[i]:
            out.append({"long": {
                "seq": int(col["seq"][i]),
                "kind": KIND_NAMES[int(col["kind"][i])],
                "batch": int(col["batch"][i]), "pages": int(col["pages"][i]),
                "at_s": (float(col["t_prep"][i]) - t0) / 1e9,
                "call_ms": float(col["t_close"][i] - col["t_prep"][i]) / 1e6,
                "gap_before_ms": float(col["gap"][i]) / 1e6,
                "excess_ms": float(excess[i]) / 1e6,
                "carried_by": PARTS[carried[i]]}})
    return out


def stretch_lines(col, inside, trace_path: str) -> list:
    from benchmarks.readers import hetu_launches

    records, why = hetu_launches.pair_scan(
        hetu_launches.scan_file(trace_path))
    if records is None:
        return [{"clock": None, "why": why}]
    row_of = {int(seq): i for i, seq in enumerate(col["seq"])}
    joined = [(rec, row_of[rec.ids["seq"]]) for rec in records
              if rec.kind in KIND_NAMES and rec.fetch is not None
              and rec.ids["seq"] in row_of]
    if not joined:
        return [{"clock": None, "why": "no launch of the trace in the log"}]
    at = np.array([i for _, i in joined])
    offset = np.array([rec.l0 for rec, _ in joined]) - col["t_launch"][at]
    launch = col["launch"][at] - np.array(
        [rec.l1 - rec.l0 for rec, _ in joined])
    fetch = col["fetch"][at] - np.array(
        [rec.fetch[1] - rec.fetch[0] for rec, _ in joined])
    program = np.array([rec.program_ns for rec, _ in joined])
    p5, p95 = np.percentile(offset, [5, 95])
    out = [{"clock": {
        "joined": len(joined), "offset_ns": float(np.median(offset)),
        "offset_p5_p95_us": float(p95 - p5) / 1e3,
        "offset_range_us": float(offset.max() - offset.min()) / 1e3,
        "launch_less_span_us": float(np.median(launch)) / 1e3,
        "fetch_less_span_us": float(np.median(fetch)) / 1e3}}]
    traced = np.zeros(len(inside), bool)
    traced[at] = True
    where = np.zeros(len(inside), int)
    where[at] = np.arange(len(at))
    for (kind, batch, pages), rows in _buckets(col, traced):
        mine = where[rows]
        same = inside & (col["kind"] == kind) & (col["batch"] == batch) \
            & (col["pages"] == pages)
        out.append({"stretch": {
            "kind": KIND_NAMES[kind], "batch": batch, "pages": pages,
            "launches": int(rows.sum()),
            "fetch_p50_ms": float(np.median(col["fetch"][rows])) / 1e6,
            "program_p50_ms": float(np.median(program[mine])) / 1e6,
            "fetch_less_program_p50_ms": float(np.median(
                col["fetch"][rows] - program[mine])) / 1e6,
            "window_calls": int(same.sum()),
            "window_fetch_p50_ms": float(np.median(col["fetch"][same])) / 1e6
            if same.any() else None}})
    return out


def main(argv=None) -> int:
    from benchmarks import run as bench

    args = bench.parse(argv)
    run = drive(args)
    found = round_log.window_columns(run)
    if found is None:
        print(json.dumps({"window": None, "why": "no round log covers the "
                          "window (readers/round_log.py says when)"}))
        return 1
    col, inside, _ = found
    t0, t1 = round_log.window_of(run)
    lines = window_lines(col, inside, t0, t1)
    if run.trace_path:
        lines += stretch_lines(col, inside, run.trace_path)
    lines.append({"run": {
        **run.end_to_end, "check_ok": bool(run.check.get("ok")),
        "compiles_in_window": run.compiles_in_window,
        "phases_s": run.extra.get("setup", {})}})
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
