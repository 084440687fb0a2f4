"""Record the small device trace the ``hetu_*`` readers' tests read
(``benchmarks/tests/data/hetu_v5e.xplane.pb``): on a TPU, four rounds of a
small scanned program inside the spans the program itself opens
(``hetu_tpu.telemetry.trace.span``), laid out as a scheduler step lays them
out, with known host sleeps (2 ms in ``serve.decode.prep``, 1 ms in
``serve.prefill_chunk.prep``), the benchmark's ``engine.decode`` span round
each decode as the harness puts it, and a train step's two spans after each
round.  Run once, on the chip, by the PR that adds the test data.

    python3 benchmarks/tools/record_hetu_trace.py <out.xplane.pb>
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

ROUNDS = 4
SLEEP_S = {"serve.admit": 0.0005, "serve.advance_prefills": 0.0003,
           "serve.prefill_chunk.prep": 0.001,
           "serve.prefill_chunk.post": 0.0002,
           "serve.decode.prep": 0.002, "serve.decode.post": 0.0005,
           "serve.evict": 0.0002, "serve.step": 0.0004,
           "train.host_to_device": 0.0003}


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import loops
    from benchmarks.harness.spans import Recorder
    from hetu_tpu.telemetry import trace

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def program(x, w):
        def layer(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(layer, x, w)
        return h.sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((8, 1024, 1024), jnp.bfloat16) * 0.01
    program(x, w).block_until_ready()

    def host(name):
        with trace.span(name):
            time.sleep(SLEEP_S[name])

    def call(parent, ids):
        """One engine call: prep, launch, fetch, post under ``parent``."""
        with trace.span(parent, ids):
            host(parent + ".prep")
            with trace.span(parent + ".launch", {"bucket": 16}):
                y = program(x, w)
            with trace.span(parent + ".fetch"):
                float(y)
            host(parent + ".post")

    rec = Recorder()
    with loops.traced(rec):
        for i in range(ROUNDS):
            with trace.span("serve.step", {"step": i}):
                host("serve.admit")
                with trace.span("serve.advance_prefills"):
                    time.sleep(SLEEP_S["serve.advance_prefills"])
                    call("serve.prefill_chunk", {"slot": i})
                with rec.span("engine.decode"):
                    call("serve.decode", {"active": 2})
                host("serve.evict")
                time.sleep(SLEEP_S["serve.step"])   # the step's own part
            host("train.host_to_device")
            with trace.span("train.step.train"):
                y = program(x, w)                    # dispatch, no wait
            y.block_until_ready()
        time.sleep(0.001)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(loops.trace_file(), out)
    print("wrote", out, Path(out).stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
