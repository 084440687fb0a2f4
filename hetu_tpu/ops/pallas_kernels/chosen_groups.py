"""Pallas TPU kernel for attention over the GROUPS OF ROWS a query chose, the
groups fetched inside the call that attends them.

A row-selecting layer (``ops.attention.select_groups``: a lightning indexer
over a latent cache) reads, a query, the rows of its ``K`` chosen groups of
``pool`` consecutive cached tokens and of the open group it stands in.  As
XLA operations that is a gather of ``(K + 1) * pool`` rows a query out of the
sequence's view, a relayed copy of what was gathered and float32 scores, all
through HBM (``ops.attention.chosen_rows`` / ``chosen_rows_attention``: 269
MB + 269 MB + 67 MB a block of 128 queries at GLM-5.3-Flash's widths; PERF.md
section 5, PR 58: 103 GB/s, an eighth of the memory's rate).  Here the view
stays where it lies in HBM, BY GROUP (``[groups, pool, C]``: a group is one
contiguous slab, 4 KB at ``pool`` 4 and 512 bfloat16), the choice is
scalar-prefetched, and a query's ``K + 1`` slabs are copied HBM -> VMEM, one
DMA a group, into one of two buffers, so the next query's copies fly while
this one's two products and its float32 softmax run out of the other.  Which
rows count is worked out from ``n`` and ``pos`` in the kernel (group ``j <
n``; the open group's row ``<= pos``), never read from an array; nothing
gathered, no score and no probability is written to HBM.

Arithmetic as ``chosen_rows_attention`` has it: the absorbed form (the rows
are keys AND values), bfloat16 operands, float32 scores and softmax, the
probabilities normalised before they are rounded; a query with no valid row
reads zeros.

Interpret mode runs the same kernel on the CPU for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.utils.platform import auto_interpret

NEG_INF = -1e30
# two buffers of (K + 1) slabs, padded to whole 128-row runs and each slab's
# 4 rows to the tile they take in VMEM, and the float32 scores: over the 16
# MiB a program is lent by default, well under the 128 MiB a v5e core has
_VMEM_LIMIT = 64 << 20


def _kernel(idx_ref, n_ref, pos_ref, q_ref, view_hbm, o_ref, buf0, buf1, sems,
            *, scale: float, pool: int, k: int):
    """A grid step is TWO queries, each with a buffer of its own: query ``2
    i`` is attended out of ``buf0`` while query ``2 i + 1``'s copies land in
    ``buf1``, then the other way round.  Two scratch arrays and not two
    halves of one picked by ``i % 2``: the compiler then SEES that the
    copies it starts and the rows it reads never meet, and packs a copy's
    scalar work (an index out of SMEM, two address sums, a descriptor) into
    the products' bundles; and the copies are started in straight-line code,
    not a loop, so they stand in one block with the products.  A block of
    128 queries at GLM-5.3-Flash's widths: 0.98 ms so; 1.34 with one array
    and ``i % 2``; 1.54 from a loop unrolled by 8 (the copies alone 1.08-1.16
    there, the products and softmax alone 0.43: PERF.md section 6, PR 59)."""
    i, steps = pl.program_id(0), pl.num_programs(0)
    bufs = (buf0, buf1)
    slabs = buf0.shape[0]

    def start(query, half):
        def one(j, carry):
            pltpu.make_async_copy(view_hbm.at[idx_ref[query, j]],
                                  bufs[half].at[j], sems.at[half]).start()
            return carry

        # traced ONCE and laid out K + 1 times where the kernel is lowered
        # (a Python loop of K + 1 copies here is the same code, and took
        # 10.5 s to trace a shape on the chip's host: 42 s of set-up)
        jax.lax.fori_loop(0, k + 1, one, 0, unroll=True)

    def wait(half):
        # one wait for the K + 1 copies: a DMA semaphore counts what
        # arrived, and this descriptor (never started) is as large as all
        pltpu.make_async_copy(view_hbm.at[pl.ds(0, k + 1)],
                              bufs[half].at[pl.ds(0, k + 1)],
                              sems.at[half]).wait()

    def attend(half):
        query = 2 * i + half
        rows = bufs[half][...].reshape(slabs * pool, buf0.shape[-1])
        s = jax.lax.dot_general(
            q_ref[half], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [heads, rows]
        r = jax.lax.broadcasted_iota(jnp.int32, (1, slabs * pool), 1)
        valid = (r < n_ref[query] * pool) | (
            (r >= k * pool) & (r - k * pool < (pos_ref[query] + 1) % pool))
        s = jnp.where(valid, s, NEG_INF)
        e = jnp.where(valid,
                      jnp.exp(s - jnp.max(s, axis=1, keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
        o_ref[half] = jnp.dot(p.astype(rows.dtype), rows,
                              preferred_element_type=jnp.float32).astype(
                                  o_ref.dtype)

    @pl.when(i == 0)
    def _():
        # the slabs past the open group's are never copied into: masked out
        # of the scores, they still meet a zero probability in the weighted
        # sum, so they have to be finite
        buf0[...] = jnp.zeros_like(buf0)
        buf1[...] = jnp.zeros_like(buf1)
        start(0, 0)

    start(2 * i + 1, 1)
    wait(0)
    attend(0)
    # the next step's first query; the last step fetches its own second
    # once more (no branch cuts the block in two) and awaits it below
    start(jnp.minimum(2 * i + 2, 2 * steps - 1), 0)
    wait(1)
    attend(1)

    @pl.when(i == steps - 1)
    def _():
        wait(0)             # every copy is awaited before the call ends


def chosen_groups_attention(q, view, idx, n, pos, *, pool: int, scale: float,
                            interpret=None):
    """Attention of every query over the groups it chose, the absorbed form.

    q [B, S, heads, C] (each head's query already through its key
    up-projection); view [B, G, pool, C] the sequence's rows BY GROUP (keys
    AND values: one array); idx [B, S, K] int32, n [B, S], pos [B, S] as
    ``ops.attention.select_groups`` returns them: the first ``n`` of a
    query's ``K`` are groups, and it reads their rows and the open group's
    (``(pos + 1) // pool``) up to ``pos``.  ``softmax(q . c * scale)`` over
    those rows in float32; returns [B, S, heads, C] in ``view``'s dtype (a
    head's value up-projection is the caller's).  A query with no valid row
    reads zeros.  What ``idx`` holds past ``n`` is fetched (from inside the
    view: the indices are clipped) and masked."""
    b, s, nh, c = q.shape
    g = view.shape[1]
    k = idx.shape[-1]
    if view.shape[2] != pool:
        raise ValueError(f"a view by groups of {view.shape[2]} rows, "
                         f"chosen by groups of {pool}")
    # inside the view whatever the operands hold: a copy from a wild address
    # takes the chip down; a sequence's groups lie behind those before it
    groups = jnp.concatenate(
        [idx.astype(jnp.int32), ((pos + 1) // pool)[..., None]], -1)
    groups = (jnp.clip(groups, 0, g - 1)
              + g * jnp.arange(b, dtype=jnp.int32)[:, None, None])
    # two queries a grid step: an odd count takes one more (group 0's rows),
    # whose result is dropped
    flat = lambda x: jnp.pad(x.reshape((b * s,) + x.shape[2:]),
                             ((0, b * s % 2),) + ((0, 0),) * (x.ndim - 2))
    return _attend(flat(q.astype(view.dtype)), view.reshape(b * g, pool, c),
                   flat(groups), flat(jnp.minimum(n, k).astype(jnp.int32)),
                   flat(pos.astype(jnp.int32)), scale=float(scale),
                   interpret=auto_interpret(interpret)
                   )[:b * s].reshape(b, s, nh, c)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _attend(q, view, groups, n, pos, *, scale, interpret):
    """The call itself, jitted on its own so that one trace of it serves
    every program whose operands have these shapes."""
    queries, nh, c = q.shape
    pool = view.shape[1]
    k = groups.shape[1] - 1
    # the rows a query holds in VMEM, in whole 128-row runs
    slabs = -(-(k + 1) * pool // 128) * 128 // pool
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(queries // 2,),
        in_specs=[
            pl.BlockSpec((2, nh, c), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((2, nh, c), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((slabs, pool, c), view.dtype),
            pltpu.VMEM((slabs, pool, c), view.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, pool=pool, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((queries, nh, c), view.dtype),
        # a query's copies are started by the query before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(groups, n, pos, q, view)
