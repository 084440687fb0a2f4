"""Record the three small device traces
``benchmarks/tests/test_hetu_launches.py`` reads
(``benchmarks/tests/data/launch_serial_v5e.xplane.pb``,
``launch_runahead_v5e.xplane.pb`` and ``launch_train_v5e.xplane.pb``): on a
TPU, small programs jitted under the program's own names
(``hetu_serve_decode``, ``hetu_serve_prefill_chunk``, ``_train_step``)
inside the spans the engine and the executor open, with the ids they give
them (``seq`` on a launch and on the fetch that waits for it, ``step`` on a
train step) and known host sleeps.

The SERIAL stretch is the engine as it is: every call launches its program
and fetches that program's result.  The RUN-AHEAD stretch launches round
n + 1 and THEN fetches round n, so from the second round on a program is
handed to the runtime while its predecessor still runs, and its device time
falls under another call's ``launch`` and ``fetch`` spans.  The decode
program runs about 6 ms, longer than a round's host work, so that a queued
program really waits.  The TRAIN stretch dispatches step n + 1 and then
waits for step n, as the benchmark's training loop does.  Run once, on the
chip, by the PR that adds the data.

    python3 benchmarks/tools/record_launch_trace.py <out directory>
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

ROUNDS = 6
CHUNK_EVERY = 3          # a chunk before every third round of the serial run
SLEEP_S = {"serve.decode.prep": 0.001, "serve.decode.post": 0.0005,
           "serve.prefill_chunk.prep": 0.0007,
           "serve.prefill_chunk.post": 0.0002}


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import loops
    from benchmarks.harness.spans import Recorder
    from hetu_tpu.telemetry import trace

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    def layers(x, w):
        def layer(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(layer, x, w)
        return h

    @jax.jit
    def hetu_serve_decode(x, w):
        # a second result beside the tokens, as a model that counts returns
        h = layers(x, w)
        return jnp.argmax(h[:16], -1).astype(jnp.int32), h.sum()

    @jax.jit
    def hetu_serve_prefill_chunk(x, w):
        return jnp.argmax(layers(x, w)[0], -1).astype(jnp.int32)

    x = jnp.ones((8192, 2048), jnp.bfloat16)
    w = jnp.ones((16, 2048, 2048), jnp.bfloat16) * 0.01
    xc = jnp.ones((512, 1024), jnp.bfloat16)
    wc = jnp.ones((4, 1024, 1024), jnp.bfloat16) * 0.01
    jax.block_until_ready(hetu_serve_decode(x, w))
    jax.block_until_ready(hetu_serve_prefill_chunk(xc, wc))

    def host(name):
        with trace.span(name):
            time.sleep(SLEEP_S[name])

    def record(stretch, name):
        rec = Recorder()
        with loops.traced(rec):
            stretch()
            time.sleep(0.001)
        out = Path(out_dir) / name
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(loops.trace_file(), out)
        print("wrote", out, out.stat().st_size, "bytes", flush=True)

    seq = [0]

    def chunk():
        with trace.span("serve.prefill_chunk", {"slot": 0}):
            host("serve.prefill_chunk.prep")
            seq[0] += 1
            with trace.span("serve.prefill_chunk.launch",
                            {"start": 0, "tokens": 16, "bucket": 16,
                             "view_bytes": 4096, "seq": seq[0]}):
                tok = hetu_serve_prefill_chunk(xc, wc)
            with trace.span("serve.prefill_chunk.fetch", {"seq": seq[0]}):
                int(tok)
            host("serve.prefill_chunk.post")

    def serial():
        for i in range(ROUNDS):
            if i % CHUNK_EVERY == 0:
                chunk()
            with trace.span("serve.decode", {"active": 2}):
                host("serve.decode.prep")
                seq[0] += 1
                with trace.span("serve.decode.launch",
                                {"pages": 4, "batch": 2, "seq": seq[0]}):
                    nxt, stats = hetu_serve_decode(x, w)
                with trace.span("serve.decode.fetch", {"seq": seq[0]}):
                    np.asarray(nxt)
                    np.asarray(stats)      # a model that counts: a second
                host("serve.decode.post")  # transfer inside the fetch

    def run_ahead():
        pending = None                     # (seq, results) not fetched yet
        for i in range(ROUNDS + 1):
            with trace.span("serve.decode", {"active": 2}):
                host("serve.decode.prep")
                launched = None
                if i < ROUNDS:
                    seq[0] += 1
                    with trace.span("serve.decode.launch",
                                    {"pages": 4, "batch": 2, "seq": seq[0]}):
                        launched = (seq[0], hetu_serve_decode(x, w))
                if pending is not None:
                    with trace.span("serve.decode.fetch",
                                    {"seq": pending[0]}):
                        np.asarray(pending[1][0])
                        np.asarray(pending[1][1])
                pending = launched
                host("serve.decode.post")

    @jax.jit
    def _train_step(x, w):
        return layers(x, w).sum()

    jax.block_until_ready(_train_step(x, w))

    def train():
        # as the executor issues steps under the benchmark's loop: step
        # n + 1 is dispatched, then the host waits for step n
        waiting = None
        for step in range(1, ROUNDS + 1):
            with trace.span("train.host_to_device"):
                time.sleep(0.0003)
            with trace.span("train.step.train", {"step": step}):
                out = _train_step(x, w)
            if waiting is not None:
                jax.block_until_ready(waiting)
            waiting = out
        jax.block_until_ready(waiting)

    record(serial, "launch_serial_v5e.xplane.pb")
    record(run_ahead, "launch_runahead_v5e.xplane.pb")
    record(train, "launch_train_v5e.xplane.pb")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
