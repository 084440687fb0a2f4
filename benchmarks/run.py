"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: builds the cell named in ``BENCHMARK.json`` from its data files,
makes the weights on the device from ``--seed``, warms the shapes the cell's
traffic can reach, measures for ``--seconds``, reads what the chip holds,
checks the system against the float32 reference and prints one JSON object
as the last line of standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics (a short profiled stretch under the
cell's load comes before the window).  Off a TPU it exits nonzero and prints
no result; there is no CPU fallback.

Two extras, neither of which prints a result line:
``--rehearse`` runs the same code end to end on the CPU at the tiny widths
of the configuration's ``rehearse`` block and prints counts and metric NAMES
only; ``--sweep r1,r2,...`` replays an open-loop cell at several rates in
one process to find its knee.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@dataclass
class Ctx:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    rec: object
    compiles: object


@dataclass
class ReadCtx:
    """What a per-layer metric's reader may read."""
    run: object          # loops.Run
    rec: object          # spans.Recorder (spans and counters of the window)
    trace: object        # reduce.TraceSummary, or None without a trace
    config: dict
    traffic: dict
    cell: dict
    peaks: dict          # the device's published peaks (None off a chip)
    memory: dict         # device.MemoryProbe.fullest(): heap, stack, total


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates (requests/s)")
    return ap.parse_args(argv)


def read_layer_metrics(rctx: ReadCtx, wanted) -> dict:
    """Each per-layer metric the cell reports, by the reader its own file
    names; a reader that finds nothing to read returns None and the metric
    is left out of the line."""
    from benchmarks.harness import spec

    out = {}
    for m in wanted:
        f = spec.layer_metric_file(m["name"])
        reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
        value = reader.read(rctx, **f.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
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

    import jax

    from benchmarks.harness import device, loops, reduce
    from benchmarks.harness.spans import Recorder

    stamp, peaks = None, None
    if not args.rehearse:
        device.enable_compile_cache()
        try:
            stamp = device.require_chips(chips)
        except device.NoChip as e:
            print(f"benchmarks/run.py: {e}", file=sys.stderr)
            return 2
        peaks = device.peaks(stamp["kind"])

    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), chips=chips,
              rec=Recorder(), compiles=loops.CompileCounter())
    if traffic["kind"] not in loops.KINDS:
        raise ValueError(f"traffic {traffic['name']}: unknown kind "
                         f"{traffic['kind']!r}; the harness has "
                         f"{sorted(loops.KINDS)}")
    if args.sweep:
        return sweep(ctx, [float(r) for r in args.sweep.split(",")])
    run = loops.KINDS[traffic["kind"]](ctx)
    setup_s = run.setup_done - _T0

    summary = None
    if run.trace_path:
        try:
            summary = reduce.summarize(reduce.load(run.trace_path))
        except ValueError:
            if not args.rehearse:   # a traced run that never used a device
                raise
    rctx = ReadCtx(run=run, rec=ctx.rec, trace=summary, config=config,
                   traffic=traffic, cell=cell, peaks=peaks,
                   memory=run.memory)
    correct = bool(run.check.get("ok")) and run.compiles_in_window == 0 \
        and run.values.get("engine_new_executables", 0) == 0

    if args.trace:
        metrics = read_layer_metrics(
            rctx, spec.metrics_of(man["per_layer"], cell["name"]))
    else:
        metrics = {}
        for m in spec.metrics_of(man["end_to_end"], cell["name"]):
            value = setup_s if m["name"] == "setup_s" \
                else run.end_to_end.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    detail = {"check": run.check,
              "memory": run.memory,   # at the window's end, before the check
              "compiles_in_window": run.compiles_in_window,
              "programs": {"loaded_or_compiled": ctx.compiles.n,
                           "cache_hits": ctx.compiles.cache_hits,
                           "cache_misses": ctx.compiles.cache_misses},
              "seed": args.seed, "seconds": args.seconds,
              "setup_s": setup_s,
              "phases_s": run.extra.get("setup", {}),
              "counts": {k: v for k, v in run.values.items()
                         if isinstance(v, (int, float))}}
    # each number compared beside its limit: a run's last lines on standard
    # error, which is what the driver's record keeps of a run that failed
    for name, limit in run.check.get("limits", {}).items():
        print(f"check {name} = {run.check.get(name)} (limit {limit})",
              file=sys.stderr)
    print(f"check compiles_in_window = {run.compiles_in_window} (limit 0), "
          f"engine_new_executables = "
          f"{run.values.get('engine_new_executables', 0)} (limit 0), "
          f"ok = {run.check.get('ok')}: {run.check.get('why', '')}",
          file=sys.stderr, flush=True)
    if args.rehearse:
        d = jax.devices()[0]
        print(json.dumps({
            "rehearsal": True, "platform": d.platform,
            "device_count": jax.device_count(), "correct": correct,
            "attempted": run.attempted, "failed": run.failed,
            "metric_names": sorted(metrics), "detail": {
                **detail, "counts": {
                    k: v for k, v in detail["counts"].items()
                    if isinstance(v, int)}}}))
        return 0 if correct and run.failed == 0 else 1

    dev = dict(stamp, memory_peak_bytes=run.memory["total_bytes"])
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": dev,
            "detail": detail}
    if summary is not None:
        detail["device_clock_shift_ms"] = summary.clock_shift_ns / 1e6
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    print(json.dumps(line), flush=True)
    return 0


def sweep(ctx, rates) -> int:
    """One open-loop cell at several offered rates, one process: for each
    rate whether every request finished, and the counted requests still in
    flight at the middle and at the end of the window."""
    from benchmarks.harness import loops, schedule

    if ctx.traffic["kind"] != "open_loop_schedule":
        raise ValueError("--sweep is for open_loop_schedule traffic")
    sv = loops.Serving(ctx)
    sv.warm(schedule.reach(ctx.traffic))
    verdict = sv.check()
    for rate in rates:
        sv.finished.clear()
        run = loops.open_loop(ctx, rate_rps=rate, serving=sv,
                              checked=verdict)
        v = run.values
        print(json.dumps({
            "sweep_rate_rps": rate, "attempted": run.attempted,
            "failed": run.failed, "backlog_mid": v["backlog_mid"],
            "backlog_end": v["backlog_end"],
            "ttft_mean_ms": run.end_to_end.get("ttft_mean_ms"),
            "ttft_p95_ms": (loops.percentile(v["ttft_ms"], 95)
                            if v["ttft_ms"] else None),
            "itl_p95_ms": run.end_to_end.get("itl_p95_ms"),
            "generated_tokens_per_s": v["generated_tokens"] / v["window_s"],
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
