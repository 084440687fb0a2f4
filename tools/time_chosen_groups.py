"""How long a chunk's DSA layer takes to fetch and attend the rows its
queries chose, three ways, at the shapes of ``glm-5.3-flash.batch-context``.

A chunk of 2,048 queries goes 128 at a time; a query reads the 4 rows of each
of its 512 chosen groups and the open group's, 2,052 rows of 512 bfloat16,
under 64 heads, out of a slot's view of 66,624 rows.  Timed, a block of 128
queries (a chunk's 16 blocks in one program, as the model runs them, over
the program's time on the host's clock):

  gathered   the rows gathered out of the ``[T, C]`` view by row index and
             attended (``ops.chosen_rows`` / ``chosen_rows_attention``)
  padded     the same with a query's rows brought up to whole 16-row tiles
  kernel     ``pallas_kernels.chosen_groups.chosen_groups_attention`` over
             the view by group, and the same with the view's relayout to
             groups counted in (once a chunk)

and the kernel's result is held against the gathered form's.  Run by hand on
the chip: ``python tools/time_chosen_groups.py``.  On any other backend it
exits nonzero: a CPU's time says nothing about the device.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# queries a chunk, queries a block, heads, latent width, view rows, groups a
# query chooses: the cell's, and a rehearsal's
CELL = (2048, 128, 64, 512, 66624, 512)
REHEARSAL = (32, 16, 16, 128, 4096, 24)
POOL = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true",
                    help="the same code at tiny shapes on the CPU: the "
                         "differences and the names, never a time")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hetu_tpu import ops
    from hetu_tpu.ops.pallas_kernels import chosen_groups
    from hetu_tpu.utils.platform import device_stamp

    if not args.rehearse and jax.default_backend() != "tpu":
        print(f"time_chosen_groups: needs a TPU, found {device_stamp()}",
              file=sys.stderr)
        return 4
    QUERIES, BLOCK, HEADS, WIDTH, ROWS, TOPK = \
        REHEARSAL if args.rehearse else CELL

    groups = ROWS // POOL
    ks = jax.random.split(jax.random.PRNGKey(59), 3)
    q = jax.random.normal(ks[0], (1, QUERIES, HEADS, WIDTH), jnp.bfloat16)
    view = jax.random.normal(ks[1], (1, ROWS, WIDTH), jnp.bfloat16)
    # a query late in a long prompt: 512 distinct groups in no order, as an
    # exact top-k by score leaves them
    pos = (ROWS - QUERIES + jnp.arange(QUERIES, dtype=jnp.int32))[None]
    complete = groups - QUERIES // POOL - 1      # ... of the first query
    idx = jnp.argsort(jax.random.uniform(ks[2], (1, QUERIES, complete)),
                      axis=-1)[..., :TOPK].astype(jnp.int32)
    n = jnp.full((1, QUERIES), TOPK, jnp.int32)
    scale = 1.0 / 16

    cut = lambda x: jnp.moveaxis(x.reshape(
        (1, QUERIES // BLOCK, BLOCK) + x.shape[2:]), 1, 0)
    join = lambda o: jnp.moveaxis(o, 0, 1).reshape(1, QUERIES, HEADS, WIDTH)

    def gathered(tile):
        def run(q, view, idx, n, pos):
            def block(xs):
                q_, idx_, n_, pos_ = xs
                rows, valid = ops.chosen_rows(idx_, n_, pos_, pool=POOL,
                                              tile=tile)
                latents = jax.vmap(lambda v, r: v[r])(
                    view, jnp.clip(rows, 0, ROWS - 1))
                return ops.chosen_rows_attention(q_, latents, valid,
                                                 scale=scale)

            return join(jax.lax.map(block, (cut(q), cut(idx), cut(n),
                                            cut(pos))))

        return jax.jit(run)

    def by_group(view):
        return view.reshape(1, groups, POOL, WIDTH)

    def kernel(relayout):
        def run(q, view, idx, n, pos):
            by = by_group(view) if relayout else view
            return join(jax.lax.map(
                lambda xs: chosen_groups.chosen_groups_attention(
                    xs[0], by, xs[1], xs[2], xs[3], pool=POOL, scale=scale),
                (cut(q), cut(idx), cut(n), cut(pos))))

        return jax.jit(run)

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            out = fn(*operands)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / args.repeats * 1e3

    blocks = QUERIES // BLOCK
    report = {**device_stamp(), "queries_a_block": BLOCK, "blocks": blocks,
              "rows_a_query": (TOPK + 1) * POOL, "heads": HEADS,
              "width": WIDTH, "view_rows": ROWS, "repeats": args.repeats}
    want, ms = timed(gathered(1), q, view, idx, n, pos)
    report["gathered_block_ms"] = ms / blocks
    got, ms = timed(gathered(16), q, view, idx, n, pos)
    report["padded_block_ms"] = ms / blocks
    report["padded_max_abs_diff"] = float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32))))
    grouped = jax.block_until_ready(jax.jit(by_group)(view))
    got, ms = timed(kernel(False), q, grouped, idx, n, pos)
    report["kernel_block_ms"] = ms / blocks
    report["kernel_copy_ns"] = ms / QUERIES / (TOPK + 1) * 1e6
    report["kernel_max_abs_diff"] = float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32))))
    _, ms = timed(kernel(True), q, view, idx, n, pos)
    report["kernel_with_relayout_block_ms"] = ms / blocks
    report["want_abs_mean"] = float(np.mean(np.abs(
        np.asarray(want, np.float32))))
    if args.rehearse:
        report = {k: v if "diff" in k else None for k, v in report.items()}
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
