"""One-shot on-chip cost-model calibration.

Runs `profiler.calibrate.calibrate_simulator` against the REAL device
backend (single-chip: MXU-utilization fit from a measured bf16 matmul) and
writes the fit report to CALIBRATION.json at the repo root.  The
profilers' JSON cost cache persists the raw measurements, so searchers in
later sessions replay the fitted costs without touching the device.

Run by hand on the chip: `python tools/calibrate_chip.py`.  On any other
backend it exits nonzero and writes nothing.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main() -> int:
    import jax

    from hetu_tpu.profiler.calibrate import calibrate_simulator
    from hetu_tpu.utils.platform import device_stamp

    devs = jax.devices()
    if jax.default_backend() != "tpu":
        print(f"calibrate: needs a TPU, found {device_stamp()}",
              file=sys.stderr)
        return 4

    t0 = time.time()
    mesh = None
    if len(devs) > 1:
        # multi-chip: fit per-axis ICI rates too (a 2D factoring when the
        # count allows, so hierarchical layouts price both tiers)
        import numpy as np
        from jax.sharding import Mesh

        n = len(devs)
        # largest PROPER inner factor so both tiers get >= 2 devices
        # (n=4 -> 2x2, n=8 -> 2x4, n=16 -> 2x8); prime/2-device counts
        # fall back to one 'ici' axis
        inner = max((d for d in (8, 4, 2) if n % d == 0 and n // d > 1),
                    default=1)
        if inner > 1:
            mesh = Mesh(np.array(devs).reshape(n // inner, inner),
                        ("outer", "inner"))
        else:
            mesh = Mesh(np.array(devs), ("ici",))
    _, report = calibrate_simulator(mesh)  # mesh=None (1 chip): MXU only
    report.update({
        **device_stamp(),
        "measured_unix": time.time(),
        "measure_seconds": round(time.time() - t0, 2),
    })
    out = REPO / "CALIBRATION.json"
    out.write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
