"""Record the small device trace ``benchmarks/tests/test_arch_mellum.py``
reads (``benchmarks/tests/data/masked_flash_v5e.xplane.pb``): on a TPU, a few
calls of a jitted gradient through four flash-attention calls with grouped
heads, three under ``jax.named_scope("hetu.attn.window")`` with a window and
one under ``("hetu.attn.full")`` without, as a period of the ``mellum``
model has them, so that the custom-calls carry the scopes as their names.
Run once, on the chip, by the PR that adds the test data.

    python3 benchmarks/tools/record_masked_flash_trace.py <out.xplane.pb>
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

# what the test's configuration states: one call's shapes
BATCH, HEADS, KV_HEADS, SEQ, HEAD_DIM, WINDOW = 2, 4, 2, 1024, 128, 256
STEPS = 3


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import loops
    from benchmarks.harness.spans import Recorder
    from hetu_tpu.ops.pallas_kernels import flash_attention

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    def period(q, k, v):
        for scope, window in (("hetu.attn.window", WINDOW),) * 3 \
                + (("hetu.attn.full", None),):
            with jax.named_scope(scope):
                q = q + flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(q.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(period, argnums=(0, 1, 2)))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (BATCH, HEADS, SEQ, HEAD_DIM), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (BATCH, KV_HEADS, SEQ, HEAD_DIM),
                              jnp.bfloat16) for kk in ks[1:])
    jax.block_until_ready(grad(q, k, v))
    rec = Recorder()
    with loops.traced(rec):
        for _ in range(STEPS):
            with rec.span("step"):
                jax.block_until_ready(grad(q, k, v))
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(loops.trace_file(), out)
    print("wrote", out, Path(out).stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
