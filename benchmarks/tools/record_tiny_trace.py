"""Record the small device trace the reduction's tests read
(``benchmarks/tests/data/tiny_v5e.xplane.pb``): on a TPU, a few calls of a
tiny scanned program with the benchmark's spans round them and host gaps
between them.  Run once, on the chip, by the PR that adds the test data.

    python3 benchmarks/tools/record_tiny_trace.py <out.xplane.pb>
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import loops
    from benchmarks.harness.spans import Recorder

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def program(x, w):
        def layer(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(layer, x, w)
        return h.sum()

    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.ones((3, 256, 256), jnp.bfloat16) * 0.01
    program(x, w).block_until_ready()
    rec = Recorder()
    with loops.traced(rec):
        for _ in range(4):
            with rec.span("outer"):
                with rec.span("inner.call"):
                    program(x, w).block_until_ready()
                with rec.span("inner.host"):
                    time.sleep(0.002)
        time.sleep(0.001)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(loops.trace_file(), out)
    print("wrote", out, Path(out).stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
