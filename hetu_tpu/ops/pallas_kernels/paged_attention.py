"""Pallas TPU kernel for one-query attention over a PAGED cache, in place.

The serving engine's decode round used to gather one cache layer's pages
into a dense ``[B, n_pg * page_size, g * D]`` view (``PagedLayers.read``),
write the new row into the view and attend over it: the view was as wide as
the page BUCKET for every sequence, written once and read two or three times
a layer (PERF.md section 5, PRs 31 and 32: 52% and 35% of the chip's busy
time in the two serving cells).  This kernel walks each sequence's page
table in the pool as it lies in HBM: the pools stay where they are
(``memory_space=pl.ANY``), the tables and lengths are scalar-prefetched, and
a sequence's LIVE pages are copied a block at a time into double-buffered
VMEM.  A page of one layer is one contiguous ``[page_size, g * D]`` block of
the pool, so a copy is one DMA; pages past a sequence's length are neither
copied nor computed.

Arithmetic as ``ops.attention._attend_one_query`` has it: the rows stay FLAT,
the queries are laid block-diagonally (head ``h``'s query in the columns of
the KV head it reads, zeros elsewhere), scores and weighted sum are two plain
matmuls over the rows in bfloat16 with float32 scores and accumulators, under
a running maximum and sum across the blocks; the head's own columns are
picked outside.  The zeros cost ``g`` times the multiplications, which one
query a sequence can afford (the kernel is bound by the rows it reads).

Interpret mode runs the same kernel on the CPU for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.telemetry import trace
from hetu_tpu.utils.platform import auto_interpret

NEG_INF = -1e30
# rows of one pool that one block of pages may hold in VMEM, in bytes: two
# pools, each double-buffered, stay under 4 MiB of the 16 MiB a program is
# lent by default
_BLOCK_BYTES = 1 << 20
# a table is handed to the kernel padded to a multiple of this many columns:
# the walk reads a sequence's live pages and no column past them, so the
# width costs nothing but SMEM, and decode programs whose page buckets pad
# to one width share ONE traced kernel a slot bucket: 4 traces for
# gpt2-large's 28 decode programs, 20 for K-EXAONE's 50 (tracing it was
# 0.14 s of each program's first call on the chip's host, 3.8 s of
# gpt2-large's warm-up; at 512 columns, 5 traces for K-EXAONE, the warm-up
# read no shorter: PERF.md, PR 33)
_TABLE_COLUMNS = 64


def pages_per_step(page_size: int, width: int, itemsize: int) -> int:
    """Pages a block holds, from the rows' shape alone: the largest power
    of two whose rows fit ``_BLOCK_BYTES``, between 128 rows (the scores'
    lanes) and 1024: 256 rows of gpt2-large's 1280-wide rows, 512 of
    K-EXAONE's 1024; at least one page."""
    rows = min(max(_BLOCK_BYTES // (width * itemsize), 128), 1024)
    pages = max(rows // page_size, 1)
    return 1 << (pages.bit_length() - 1)


def plan(q, kv_heads: int, *, kernel: bool, why: str = "", rows: int = 0,
         page_size: int = 0, pages: int = 0, n_pg: int = 0):
    """Whether a decode program's one-query attention walks the pages in
    place is fixed when the program is traced: one instant per attention
    built says so (and, if not, why: ``window``, a ring is read as its view;
    ``dense_cache``, no pages; ``sharded``, a pool laid over a mesh;
    ``backend``, not a TPU) in a JSONL trace or
    the xplane of a profiled compile, as ``flash.plan`` does.  q: [B, heads,
    1, D]; ``rows``: the view's length where one is read."""
    b, nh, _, d = q.shape
    trace.instant("paged_attn.plan", {
        "kernel": int(kernel), "why": why, "g": int(kv_heads), "d": d,
        "heads": nh, "page_size": page_size, "pages_per_step": pages,
        "n_pg": n_pg, "batch": b, "rows": rows})


def _kernel(layer_ref, lengths_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            scale: float, pages: int, page_size: int):
    b, n_seq = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    t_blk = pages * page_size

    def pages_of(seq, block, slot, each, go=True):
        """``each(K copy, V copy)`` for every LIVE page of ``block`` of
        ``seq`` (one that holds a row at or before the newest token's), into
        buffer ``slot``.  A loop, not ``pages`` copies of its body: a decode
        program's first call traces and lowers this kernel, and the engine
        builds one program a slot and page bucket."""
        first = block * pages
        live = jnp.clip(lengths_ref[seq] // page_size + 1 - first, 0, pages)

        def page(i, carry):
            at = tables_ref[seq, first + i]
            rows = pl.ds(pl.multiple_of(i * page_size, page_size), page_size)
            each(pltpu.make_async_copy(k_hbm.at[layer, at],
                                       k_buf.at[slot, rows], sems.at[0, slot]),
                 pltpu.make_async_copy(v_hbm.at[layer, at],
                                       v_buf.at[slot, rows], sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.where(go, live, 0), page, 0)

    def start(seq, block, slot, go=True):
        pages_of(seq, block, slot, lambda k, v: (k.start(), v.start()), go)

    def wait(seq, block, slot):
        pages_of(seq, block, slot, lambda k, v: (k.wait(), v.wait()))

    @pl.when(b == 0)
    def _():
        # rows of a block that no copy ever filled are masked out of the
        # scores, but they meet a zero probability in the weighted sum:
        # they have to be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    first = slot_ref[0]          # where this sequence's block 0 was sent
    length = lengths_ref[b]
    n_blk = length // t_blk + 1  # blocks that hold a row up to the newest
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]

    def block(j, carry):
        slot = (first + j) % 2
        # the block after this one: the sequence's next, or the next
        # sequence's first, is on its way while this one is computed
        more = j + 1 < n_blk
        nxt = jnp.minimum(jnp.where(more, b, b + 1), n_seq - 1)
        start(nxt, jnp.where(more, j + 1, 0), 1 - slot,
              more | (b + 1 < n_seq))
        wait(b, j, slot)
        k = k_buf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [heads, t_blk]
        pos = j * t_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = pos <= length
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_buf.dtype), v_buf[slot],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)
    slot_ref[0] = (first + n_blk) % 2
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           kv_heads: int, scale=None, interpret=None):
    """Attention of ONE query a sequence over cache layer ``layer`` of a
    paged pool pair, read in place.

    q: [B, heads, 1, D]; k_pool / v_pool: [L, num_pages, page_size,
    kv_heads * D] / [.., kv_heads * Dv], a token's row flat; layer: int32
    scalar; tables: [B, n_pg] int32, each sequence's pages in order (padded
    with any valid page: the scratch page 0); lengths: [B] int32, the index
    of the newest token, whose row is already in the pool: positions
    ``<= lengths[b]`` are seen.  Rows of a padded batch give finite
    results.  A block holds :func:`pages_per_step` pages, from the rows'
    shape.  Returns [B, heads, 1, Dv]."""
    n_layers, num_pages, page_size, width = k_pool.shape
    n_pg = tables.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pages = pages_per_step(page_size, max(width, v_pool.shape[-1]),
                           k_pool.dtype.itemsize)
    plan(q, kv_heads, kernel=True, page_size=page_size, pages=pages,
         n_pg=n_pg)
    # inside the pool and the table whatever the operands hold: a copy from
    # a wild address takes the chip down
    tables = jnp.pad(jnp.clip(tables.astype(jnp.int32), 0, num_pages - 1),
                     ((0, 0), (0, -n_pg % _TABLE_COLUMNS)))
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, n_pg * page_size - 1)
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0,
                     n_layers - 1).reshape(1)
    return _attend(q, k_pool, v_pool, layer, tables, lengths,
                   kv_heads=int(kv_heads), scale=float(scale), pages=pages,
                   interpret=auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "pages",
                                             "interpret"))
def _attend(q, k_pool, v_pool, layer, tables, lengths, *, kv_heads, scale,
            pages, interpret):
    """The call itself, jitted on its own so that one trace of it serves
    every decode program whose operands have these shapes."""
    b, nh, _, d = q.shape
    g = kv_heads
    page_size, width = k_pool.shape[2:]
    v_width = v_pool.shape[-1]
    # the queries block-diagonally, [B, heads (padded to whole tiles),
    # g * D]: head h's query in the columns of the KV head it reads
    eye = jnp.eye(g, dtype=q.dtype)
    q_blocks = jnp.einsum("bgrd,gh->bgrhd", q.reshape(b, g, nh // g, d),
                          eye).reshape(b, nh, width).astype(k_pool.dtype)
    nh_pad = -(-nh // 16) * 16
    q_blocks = jnp.pad(q_blocks, ((0, 0), (0, nh_pad - nh), (0, 0)))
    t_blk = pages * page_size

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, nh_pad, width), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, nh_pad, v_width),
                               lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, t_blk, width), k_pool.dtype),
            pltpu.VMEM((2, t_blk, v_width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((nh_pad, 1), jnp.float32),
            pltpu.VMEM((nh_pad, 1), jnp.float32),
            pltpu.VMEM((nh_pad, v_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, pages=pages,
                          page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh_pad, v_width), v_pool.dtype),
        # a sequence's first block is sent by the sequence before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, lengths, tables, q_blocks, k_pool, v_pool)
    # head h's own KV head's columns of its row
    dv = v_width // g
    out = jnp.einsum("bgrhd,gh->bgrd",
                     out[:, :nh].reshape(b, g, nh // g, g, dv),
                     eye.astype(out.dtype))
    return out.reshape(b, nh, 1, dv)
