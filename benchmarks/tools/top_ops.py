"""Which device operations took the time in the newest traced run, with
their whole names: what the ``*_time_share`` metrics' patterns are written
against and checked with.  Reads the xplane a ``--trace 1`` run left under
``.bench_out/trace``; touches no device.

    python3 benchmarks/tools/top_ops.py [N] [metric ...]

Prints the N operations with the most own time on the first chip, each
with the share of the chip's busy time and the per-layer metrics (of
those named, by their ``layer_metrics`` file's ``pattern``) that count it,
then each named metric's total.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.harness import loops, reduce, spec  # noqa: E402


def main(argv) -> int:
    top = int(argv[0]) if argv else 40
    patterns = {m: re.compile(spec.layer_metric_file(m)["params"]["pattern"])
                for m in argv[1:]}
    summary = reduce.summarize(reduce.load(loops.trace_file()))
    chip = summary.first_chip()
    busy = reduce.measure(chip.busy)
    own, count = defaultdict(float), defaultdict(int)
    for e, t, _ in chip.ops:
        own[e.name] += t
        count[e.name] += 1
    totals = defaultdict(float)
    for name, t in own.items():
        for m, rx in patterns.items():
            if rx.search(name):
                totals[m] += t
    print(f"busy {busy / 1e9:.4f} s of a window of {summary.window_s:.4f} s")
    for name, t in sorted(own.items(), key=lambda kv: -kv[1])[:top]:
        hit = [m for m, rx in patterns.items() if rx.search(name)]
        print(f"{100 * t / busy:6.2f}% x{count[name]:<5d} {hit} "
              f"{name[:420]}")
    for m in patterns:
        print(f"TOTAL {m} {100 * totals[m] / busy:.3f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
