"""Scaled-dot-product attention cores.

The reference has no fused attention op — its MultiHeadAttention layer
(python/hetu/layers/attention.py) composes batch_matmul/softmax ops.  On TPU
we provide (a) an XLA composition that the compiler fuses well at moderate
sequence lengths, and (b) a Pallas flash-attention kernel for long sequences
(hetu_tpu/ops/pallas_kernels/flash_attention.py), plus ring attention for the
sequence-parallel axis (hetu_tpu/parallel/ring_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hetu_tpu.ops.pallas_kernels import paged_attention
from hetu_tpu.ops.pallas_kernels.flash_attention import (
    SAVED_LSE, SAVED_OUT, flash_chunk_attention,
    flash_sparse_chunk_attention,
)
from hetu_tpu.parallel.mesh import AXIS_TP
from hetu_tpu.telemetry import trace
from hetu_tpu.utils.platform import (
    default_backend_is_tpu as _default_backend_is_tpu,
)

# The name (``jax.ad_checkpoint.checkpoint_name``) of a row-parallel
# attention out-projection's result: under a mesh that splits 'tp' it is a
# sum over chips (Megatron's all-reduce), and :func:`remat` keeps it there.
SAVED_REDUCED = "hetu.tp.reduced"


def attention(q, k, v, *, mask=None, scale=None):
    """q,k,v: [..., heads, seq, head_dim] (or [B,H,S,D]).

    mask: broadcastable to [..., heads, q_len, kv_len]; True/1 = keep.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("...qd,...kd->...qk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("...qk,...kd->...qd", probs, v)


def causal_attention(q, k, v, *, scale=None, window=None):
    """``window``: a query sees the last ``window`` keys only, itself
    included (key ``j`` for a query at ``i`` when ``0 <= i - j < window``);
    None: every key up to itself."""
    s_q, s_k = q.shape[-2], k.shape[-2]
    mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                          k=s_k - s_q - int(window))
    return attention(q, k, v, mask=mask, scale=scale)


def remat(layer, policy: str = "full"):
    """``layer`` recomputed in the backward pass (``jax.checkpoint``), less
    the flash kernel's forward: its output and LSE rows, all its backward
    needs beside q, k and v, are kept by name, so dK/dV and dQ run against
    the saved pair.  ``policy`` 'full' keeps nothing else but the layer's
    inputs; 'dots' keeps the matmul results too.  A layer whose attention
    is an XLA composition carries no such names and keeps what it kept.

    Where the mesh in context (``jax.set_mesh``) splits 'tp', the attention
    out-projection's result (``SAVED_REDUCED``) is kept too: the backward
    needs it (the second norm's input) and recomputing it there means the
    matmul and its all-reduce again, a value crossing chips twice.  With no
    mesh, or 'tp' of one, it is a local matmul and dearer to hold than to
    redo, so the policy does not ask for it.  The mesh stands in for the
    split, which is the strategy's (``MegatronLM.ROW``) and cannot be read
    off a traced weight: a 'tp' mesh whose strategy leaves ``out_weight``
    whole (``DataParallel``, a hidden size 'tp' does not divide) keeps one
    activation a layer for a matmul alone.  One ``remat.plan`` instant a
    call says which it was.

    Every call with the same answer hands ``jax.checkpoint`` the SAME policy
    object (:func:`_keep`): JAX splits a jitted function inside a
    checkpointed layer into what is kept and what is recomputed once a
    (function, policy object) pair, so the layers of one scan body, each
    under its own ``remat``, share that split, and a Pallas kernel inside
    such a function is lowered once for all of them
    (``ops.moe_ops.held_expert_ffn``)."""
    tp = jax.sharding.get_abstract_mesh().shape.get(AXIS_TP, 1)
    trace.instant("remat.plan", {"tp": tp, "reduced": int(tp > 1)})
    return jax.checkpoint(layer, policy=_keep(tp > 1, policy == "dots"))


@functools.cache
def _keep(reduced: bool, dots: bool):
    """:func:`remat`'s checkpoint policy, one object an answer."""
    names = (SAVED_OUT, SAVED_LSE) + ((SAVED_REDUCED,) if reduced else ())
    keep = jax.checkpoint_policies.save_only_these_names(*names)
    if dots:
        keep = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable, keep)
    return keep


# ---- serving decode: attention over a preallocated cache ----
# (hetu_tpu/serve) — the cache is TIME-major ([B, T, kv_heads, D]) because
# every write is a per-sequence update at one time index; attention
# transposes to head-major internally.

def cache_update(k_cache, v_cache, k_new, v_new, lengths):
    """Write one new token's K/V into each sequence's cache slot.

    k_cache/v_cache: [B, T, kv_heads, D]; k_new/v_new: [B, 1, kv_heads, D]
    (or both without the heads axis: ``[B, T, D]`` and ``[B, 1, D]``);
    lengths: [B] int32 — tokens already cached per sequence, i.e. the index
    the new token lands at.  Returns the updated caches.
    """
    write = jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n, (i,) + (0,) * (c.ndim - 1)))
    return write(k_cache, k_new, lengths), write(v_cache, v_new, lengths)


def read_cache_layer(cache, layer):
    """Layer ``layer`` of an all-layer cache, as the dense ``[B, T, kv_heads,
    D]`` the attention steps below work on.  A cache that is held some other
    way reads its own layer (``cache.read(layer)``: the serving engine's
    ``serve.kv_cache.PagedLayers`` gathers that layer's pages, and only
    them); a plain ``[L, B, T, kv_heads, D]`` array is indexed.  No engine
    holds plain arrays: they are the tests' oracle for a cached but unpaged
    run (``tests/paged_programs.py`` ``dense_greedy``) and what
    ``benchmarks/tools/compile_v5e.py`` hands the entry points."""
    read = getattr(cache, "read", None)
    if read is not None:
        return read(layer)
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def write_cache_layer(cache, layer, view, at, n: int):
    """Put back what a layer's step wrote into its ``view`` (from
    :func:`read_cache_layer`, or a reshape of it that keeps ``[B, T]`` in
    front): the ``n`` rows from position ``at[b]`` on, ``at`` [B] int32.  A
    cache that writes its own rows (``cache.write(layer, rows)``) is given
    just those rows; a plain array (:func:`read_cache_layer` says who
    passes one) takes the whole view back as its layer.  Returns the
    cache."""
    write = getattr(cache, "write", None)
    if write is not None:
        rows = jax.vmap(
            lambda v, i: jax.lax.dynamic_slice_in_dim(v, i, n, 0))(view, at)
        return write(layer, rows)
    return jax.lax.dynamic_update_index_in_dim(
        cache, view.reshape(cache.shape[1:]), layer, 0)


def scan_cached_layers(step, blocks, h, k_cache, v_cache, at, n: int):
    """A cache entry point's layer scan with the two all-layer caches
    CARRIED: layer ``l`` reads its own layer of each
    (:func:`read_cache_layer`), runs ``step(p_l, h, k_l, v_l) -> (h, k_l,
    v_l)`` (a block's decode or chunk step, which writes its new rows into
    the views it is handed) and puts back the ``n`` rows a sequence that it
    wrote from position ``at[b]`` on (:func:`write_cache_layer`).  As scan
    inputs and outputs the caches would be held twice and rewritten whole.
    ``blocks`` are the layers' stacked parameters.  Returns (h, k_cache,
    v_cache)."""
    def on_views(p_l, h, k_all, v_all, l):
        h, k_l, v_l = step(p_l, h, read_cache_layer(k_all, l),
                           read_cache_layer(v_all, l))
        return (h, write_cache_layer(k_all, l, k_l, at, n),
                write_cache_layer(v_all, l, v_l, at, n))

    return scan_layers_over_caches(on_views, blocks, h, k_cache, v_cache)


def scan_layers_over_caches(step, blocks, h, k_cache, v_cache):
    """The layer scan of every cache entry point: the two all-layer caches
    are CARRIED, and layer ``l`` is handed them whole with its index,
    ``step(p_l, h, k_cache, v_cache, l) -> (h, k_cache, v_cache)``.  A step
    that reads its layer's views goes through :func:`scan_cached_layers`; a
    decode entry point whose attention is the one-query step
    (:func:`decode_layer_attention`) calls this, and no view of the layer is
    made for it.  Returns (h, k_cache, v_cache)."""
    def layer(carry, xs):
        return step(xs[0], *carry, xs[1]), None

    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    carry, _ = jax.lax.scan(layer, (h, k_cache, v_cache),
                            (blocks, jnp.arange(n_layers)))
    return carry


KEY_BLOCK = 1024     # keys walked at a time over a view longer than this


def ring_update(k_ring, v_ring, k_new, v_new, starts):
    """Write a step's new K/V rows into each sequence's RING view (a window
    group's gathered pages: row ``r`` of the ``R`` holds the position ``p =
    r (mod R)`` written last).  k_ring/v_ring: [B, R, kv_heads, D];
    k_new/v_new: [B, S, kv_heads, D], row ``i`` at position ``starts[b] +
    i``; starts: [B] int32.  ``R >= window - 1 + S``, so the rows written
    over are behind every query's window.  Returns the updated views."""
    r = k_ring.shape[1]
    at = (starts[:, None] + jnp.arange(k_new.shape[1])) % r      # [B, S]
    write = jax.vmap(lambda c, n, i: c.at[i].set(n))
    return write(k_ring, k_new, at), write(v_ring, v_new, at)


def _ring_positions(r: int, last):
    """The position each row of a ring of ``r`` rows holds once position
    ``last`` [B] has been written: the newest ``p <= last`` with ``p = row
    (mod r)``; negative where no such position exists yet.  [B, r]."""
    rows = jnp.arange(r)[None, :]
    return last[:, None] - (last[:, None] - rows) % r


def _grouped(q, n_kv: int):
    """q [B, heads, S, D] -> [B, kv_heads, heads / kv_heads, S, D]: query
    head ``h`` reads KV head ``h // rep``, so the view is never repeated."""
    b, nh, s, d = q.shape
    return q.reshape(b, n_kv, nh // n_kv, s, d)


def _attend_seen(q, k_cache, v_cache, seen, scale):
    """Softmax attention of q [B, heads, S, D] over a whole time-major view
    [B, T, kv_heads, D] under the mask ``seen`` [B, S, T], heads grouped.
    Returns [B, heads, S, D]."""
    b, nh, s, d = q.shape
    scores = jnp.einsum("bgrsd,btgd->bgrst", _grouped(q, k_cache.shape[2]),
                        k_cache, preferred_element_type=jnp.float32) * scale
    scores = jnp.where(seen[:, None, None], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bgrst,btgd->bgrsd", probs, v_cache)
    return out.reshape(b, nh, s, v_cache.shape[-1])


def _attend_one_query(q, k_rows, v_rows, g: int, seen, scale):
    """Softmax attention of ONE query a sequence, q [B, heads, 1, D], over a
    time-major view of ``g`` KV heads under the mask ``seen`` [B, T], heads
    grouped, with the view read as the FLAT rows it is gathered in, k_rows /
    v_rows [B, T, g * D]: the queries are laid out block-diagonally ([g * D,
    heads], head ``h``'s query in the rows of the KV head it reads, zeros
    elsewhere), so scores and weighted sum are two plain matmuls over the
    rows and the view is neither split by head nor relaid (as ``[B, T, g,
    D]`` the view is tiled another way than its pages, and as ``[B, g, T,
    D]`` operands it is transposed: either is a copy of the whole view, a
    quarter of a long decode round; my chip run, PERF.md PR 32).  The zeros
    cost ``g`` times the multiplications, which one query a sequence can
    afford.  Returns [B, heads, 1, D]."""
    b, nh, _, d = q.shape
    eye = jnp.eye(g, dtype=q.dtype)
    q_blocks = jnp.einsum("bgrd,gh->bgdhr", q.reshape(b, g, nh // g, d),
                          eye).reshape(b, g * d, nh)
    scores = jnp.einsum("btk,bkh->bht", k_rows, q_blocks,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(seen[:, None, :], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_rows.dtype)
    dv = v_rows.shape[-1] // g
    out = jnp.einsum("bht,btk->bhk", probs, v_rows)
    # head h's own KV head's columns of its row
    out = jnp.einsum("bgrhd,gh->bgrd", out.reshape(b, g, nh // g, g, dv),
                     eye.astype(out.dtype))
    return out.reshape(b, nh, 1, dv)


def _attend_blocks(q, k_cache, v_cache, pos, scale, block: int, *,
                   chosen=None, block_size: int = 0):
    """Causal attention of q [B, heads, S, D] at positions ``pos`` [B, S]
    over a time-major view [B, T, kv_heads, D] with ``T > block``: the keys
    are walked ``block`` at a time under a running maximum and sum, heads
    grouped, so neither the [heads, S, T] scores nor a repeated copy of the
    view is ever whole; and the walk ends at the last key any query can see,
    so a chunk's cost follows its history and not the width of the table it
    was handed.  ``chosen`` [B, S, kv_heads, T / block_size] bool: a query
    reads a key only in a block of ``block_size`` positions it chose
    (:func:`masked_block_attention`; ``block_size`` divides ``block`` and
    ``T``).  Returns [B, heads, S, D]."""
    b, nh, s, d = q.shape
    t, n_kv = k_cache.shape[1], k_cache.shape[2]
    q5 = _grouped(q, n_kv)
    rep = nh // n_kv
    blocks = -(-t // block)

    def step(j, carry):
        m, l, acc = carry
        # the last block is moved back to end with the view; the keys it
        # then shares with the block before are masked out of it
        at = jnp.minimum(j * block, t - block)
        k_blk = jax.lax.dynamic_slice_in_dim(k_cache, at, block, 1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_cache, at, block, 1)
        key = at + jnp.arange(block)
        seen = ((key[None, None, :] <= pos[:, :, None])
                & (key >= j * block)[None, None, :])[:, None, None]
        if chosen is not None:
            mine = jax.lax.dynamic_slice_in_dim(
                chosen, at // block_size, block // block_size, 3)
            seen = seen & jnp.moveaxis(
                jnp.repeat(mine, block_size, axis=-1), 2, 1)[:, :, None]
        scores = jnp.einsum("bgrsd,btgd->bgrst", q5, k_blk,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(seen, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
        l = l * alpha + probs.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrst,btgd->bgrsd", probs.astype(v_cache.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (jnp.full((b, n_kv, rep, s), -1e30, jnp.float32),
             jnp.zeros((b, n_kv, rep, s), jnp.float32),
             jnp.zeros((b, n_kv, rep, s, v_cache.shape[-1]), jnp.float32))
    trips = jnp.minimum(jnp.max(pos) // block + 1, blocks)
    _, l, acc = jax.lax.fori_loop(0, trips, step, carry)
    return (acc / l[..., None]).astype(v_cache.dtype).reshape(
        b, nh, s, v_cache.shape[-1])


def chunk_kernel_why() -> str:
    """Why a chunk's attention over a long view can NOT run in the flash
    forward kernel (``pallas_kernels.flash_attention.flash_chunk_attention``)
    where this is traced, "" when it can: ``backend``, not a TPU (the
    interpreter would run); ``sharded``, a mesh is in context (the serving
    engine traces its chunk program under the mesh it laid its pools over),
    and the partitioner cannot split a Mosaic call."""
    if not _default_backend_is_tpu():
        return "backend"
    if not jax.sharding.get_abstract_mesh().empty:
        return "sharded"
    return ""


def chunk_plan(q, rows: int, kv_heads: int, d_v: int, why: str):
    """Which way a chunk's attention went is fixed when the program is
    traced: one instant per attention built says whether the flash forward
    kernel runs it (``kernel`` 1, ``why`` "") or an XLA composition does,
    and then why (:func:`chunk_kernel_why`'s reasons; ``short``, a view of
    at most ``KEY_BLOCK`` rows; ``window``, a ring; ``static_trip``, a walk
    that must be reverse-differentiable, and ``few_queries``, a chunk too
    short to pay for rebuilding the keys, both of
    ``LatentAttention.expanded``), as ``paged_attn.plan`` does for a decode
    round.  q: [B, heads, S_c, D]; ``rows``: the view's length."""
    b, nh, s_c, d = q.shape
    trace.instant("chunk_attn.plan", {
        "kernel": int(not why), "why": why, "heads": nh,
        "kv_heads": int(kv_heads), "d": d, "d_v": int(d_v), "s_c": s_c,
        "rows": int(rows), "batch": b})


def chunk_attention(q, k_cache, v_cache, starts, *, scale=None, window=None):
    """Multi-token chunk attention against a cache (the chunked-prefill /
    prefix-sharing core, GQA-aware).

    q: [B, heads, S_c, D] — a CHUNK of queries whose token ``i`` sits at
    absolute position ``starts[b] + i``; its K/V must already be written
    into the cache (:func:`cache_update` handles multi-row writes).
    k_cache/v_cache: [B, T, kv_heads, D] holding the tokens BEFORE the
    chunk (a shared prefix, earlier chunks) plus the chunk itself.
    starts: [B] int32 — the chunk's first absolute position.  Query ``i``
    attends to cache positions ``<= starts[b] + i`` (history + the
    chunk's own causal triangle in one mask); later positions (unwritten,
    or stale from a previous page occupant) are masked out.

    With ``starts == 0`` and S_c == T this reduces to causal attention —
    the property the engine's token parity with the training forward
    rides on.

    kv_heads < heads (GQA): the query heads are grouped by the KV head they
    read and the view is used as it is, never repeated.

    **A view longer than** ``KEY_BLOCK`` is walked in key blocks under a
    running softmax, as far as the chunk's last position, and the rule for
    who walks it is on what is observed here (:func:`chunk_kernel_why`).  On
    a TPU backend with no mesh in context it is a CHUNK CALL of the flash
    forward kernel: the score and probability tiles kept in VMEM, the
    causal offset each sequence's own ``starts[b]``, and the view read where
    it lies at head widths that are whole lane tiles (128, 256; others from
    a head-major copy).  Anywhere else :func:`_attend_blocks`
    walks it, the same mathematics as a ``fori_loop`` of XLA operations
    whose float32 score blocks go through HBM: the portable path and the
    tests' oracle.  A ``chunk_attn.plan`` instant (:func:`chunk_plan`) says
    which, and why, when the program is traced.

    ``window``: the layer sees the last ``window`` positions only, and the
    cache is the window group's RING of ``T`` rows (:func:`ring_update`
    wrote the chunk into it): row ``r`` holds the newest position ``p = r
    (mod T)``, and query ``i`` sees it when ``0 <= starts[b] + i - p <
    window``.  A window layer reads its ring, never the full table.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nh, nkv = q.shape[1], k_cache.shape[2]
    t, s_c = k_cache.shape[1], q.shape[-2]
    why = "window" if window is not None else "short" if t <= KEY_BLOCK \
        else chunk_kernel_why()
    chunk_plan(q, t, nkv, v_cache.shape[-1], why)
    if not why:
        return flash_chunk_attention(q, k_cache, v_cache, starts,
                                     scale=scale)
    if why != "short" or nkv != nh:
        pos = starts[:, None] + jnp.arange(s_c)              # [B, S_c]
        if window is not None:
            held = _ring_positions(t, starts + s_c - 1)      # [B, T]
            back = pos[:, :, None] - held[:, None, :]
            seen = (held >= 0)[:, None, :] & (back >= 0) \
                & (back < int(window))
            return _attend_seen(q, k_cache, v_cache, seen, scale)
        if t > KEY_BLOCK:
            return _attend_blocks(q, k_cache, v_cache, pos, scale,
                                  KEY_BLOCK)
        return _attend_seen(q, k_cache, v_cache,
                            jnp.arange(t)[None, None, :] <= pos[:, :, None],
                            scale)
    # as many KV heads as query heads over a short view: as it always was
    k = jnp.moveaxis(k_cache, 1, 2)  # [B, kv_heads, T, D]
    v = jnp.moveaxis(v_cache, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    pos = starts[:, None] + jnp.arange(s_c)                  # [B, S_c]
    valid = jnp.arange(t)[None, None, :] <= pos[:, :, None]  # [B, S_c, T]
    scores = jnp.where(valid[:, None], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None,
                     window=None, kv_heads=None):
    """Single-token attention against one cache layer's VIEW (GQA-aware).

    q: [B, heads, 1, D] — the newest token's query, already positioned at
    index ``lengths[b]`` in its sequence (so its K/V must have been written
    via :func:`cache_update` first).  k_cache/v_cache: [B, T, kv_heads, D]
    with kv_heads dividing heads.  Whatever the grouping, the query heads
    are grouped by the KV head they read and the view is read as the flat
    rows it is, never repeated nor relaid (:func:`_attend_one_query`).
    lengths: [B] int32 index of the newest token; positions > lengths[b]
    (unwritten or stale from a previous occupant) are masked out.

    ``window``: as :func:`chunk_attention`'s — the cache is the window
    group's ring, written by :func:`ring_update`.  A ring is never walked
    by the paged kernel (:func:`decode_layer_attention`): a
    ``paged_attn.plan`` instant says so when the program is traced.

    ``kv_heads``: the caches are given as the FLAT rows their pages hold,
    [B, T, kv_heads * D] (written by :func:`cache_update` / :func:`ring_update`
    with flat new rows): what is read anyway, and from a paged view without
    the copy that splitting the rows by head costs.
    """
    if q.shape[-2] != 1:
        raise ValueError(
            f"decode_attention takes one query token, got {q.shape[-2]} "
            "(prefill goes through causal_attention over the chunk)")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nkv = kv_heads or k_cache.shape[2]
    b, t = k_cache.shape[:2]
    seen = jnp.arange(t)[None, :] <= lengths[:, None]       # [B, T]
    if window is not None:
        paged_attention.plan(q, nkv, kernel=False, why="window", rows=t)
        held = _ring_positions(t, lengths)                   # [B, T]
        seen = (held >= 0) & (lengths[:, None] - held < int(window))
    return _attend_one_query(q, k_cache.reshape(b, t, -1),
                             v_cache.reshape(b, t, -1), nkv, seen, scale)


def decode_layer_attention(q, k_new, v_new, k_cache, v_cache, layer,
                           lengths, *, scale=None):
    """The one-query step of a decode round over cache layer ``layer`` (one
    that keeps every position) of the two all-layer caches: each sequence's
    new K/V row is written at position ``lengths[b]`` and its query attends
    over every position up to it.

    q: [B, heads, 1, D]; k_new / v_new: [B, 1, kv_heads, D]; k_cache /
    v_cache: the all-layer caches as a cache entry point is handed them;
    lengths: [B] int32 tokens already cached.  Returns (out [B, heads, 1,
    Dv], k_cache, v_cache).

    **The rule is on what is observed here.**  A paged cache (one with a
    one-query step of its own, ``k_cache.attend``: the serving engine's
    ``serve.kv_cache.PagedLayers``) on a TPU backend: the new rows go into
    the pool through the write map, and ONE Pallas kernel walks each
    sequence's page table in the pool where it lies, as far as the sequence
    is long (``pallas_kernels.paged_attention``); no view of the layer is
    gathered.  A paged cache on another backend, and a plain ``[L, B, T,
    kv_heads, D]`` array (:func:`read_cache_layer` says who passes one),
    take the XLA composition over that layer's flat rows
    (:func:`decode_attention`); a ``paged_attn.plan`` instant says which
    and why when the program is traced."""
    b, _, g, _ = k_new.shape
    why = ("dense_cache" if not hasattr(k_cache, "attend")
           else "sharded" if k_cache.sharded
           else "" if _default_backend_is_tpu() else "backend")
    if not why:
        k_cache = k_cache.write(layer, k_new)
        v_cache = v_cache.write(layer, v_new)
        out = k_cache.attend(v_cache, layer, q, lengths, scale=scale)
        return out, k_cache, v_cache
    k_l = read_cache_layer(k_cache, layer)
    t = k_l.shape[1]
    paged_attention.plan(q, g, kernel=False, rows=t, why=why)
    k_l, v_l = cache_update(
        k_l.reshape(b, t, -1), read_cache_layer(v_cache, layer).reshape(
            b, t, -1), k_new.reshape(b, 1, -1), v_new.reshape(b, 1, -1),
        lengths)
    out = decode_attention(q, k_l, v_l, lengths, scale=scale, kv_heads=g)
    return (out, write_cache_layer(k_cache, layer, k_l, lengths, 1),
            write_cache_layer(v_cache, layer, v_l, lengths, 1))


# ---------------------------------------------------------------------------
# Block-sparse attention chosen by compressed keys (InfLLM-V2, arXiv
# 2509.24663; the ``minicpm4`` layers of ``models/minicpm_sala.py``).  A KV
# head keeps one COMPRESSED key every ``stride`` positions, the mean of the
# ``kernel`` K rows from there on; a query scores them, the scores of a KV
# head's query heads are summed, a BLOCK of ``block`` positions takes the
# largest score of the windows that overlap it, and the query attends over
# the ``topk`` best blocks only, the first ``init_blocks`` and those of its
# last ``local`` positions always among them.  Plain ``jax.numpy``, float32
# scores.
# ---------------------------------------------------------------------------

SELECT_WINDOWS = 512   # compressed keys a chunk's queries score at a time


def compress_keys(rows, *, stride: int, kernel: int):
    """The compressed keys of a span of K rows: rows [B, R, ...] (``R`` a
    multiple of ``stride``, the span's first row at a multiple of ``stride``
    in its sequence) -> float32 [B, R / stride - kernel / stride + 1, ...],
    entry ``i`` the mean of rows ``stride * i .. stride * i + kernel - 1``:
    every window that lies inside the span.  A caller that writes new rows
    hands over the ``kernel - stride`` rows (or more) before them too, so
    that the windows the new rows COMPLETE are among the result, whichever
    page, chunk or round their first rows came in."""
    b, r = rows.shape[:2]
    m = kernel // stride
    if kernel % stride or r % stride or r < kernel:
        raise ValueError(f"a span of {r} rows holds no whole window of "
                         f"{kernel} at stride {stride}")
    parts = rows.astype(jnp.float32).reshape(
        (b, r // stride, stride) + rows.shape[2:]).sum(2)
    n = r // stride - m + 1
    return sum(parts[:, j:j + n] for j in range(m)) / kernel


def _block_scores(q, comp, pos, *, stride: int, kernel: int, block: int,
                  scale: float):
    """:func:`select_blocks`' ``score`` [B, kv_heads, S, blocks] float32
    before any block is forced or hidden: the max, over the windows that
    overlap a block, of the query heads' summed softmax probabilities.

    One query a sequence (a decode round, whose compressed keys are as wide
    as the round's page bucket) and a short sequence score every window at
    once.  A chunk's queries WALK the windows ``SELECT_WINDOWS`` at a time,
    twice (the softmax's maximum and sum, then the probabilities pooled into
    their blocks), as far as the chunk's last position sees and no further:
    the scores of 2,048 queries against a 66,624-position table's 4,164
    windows are a gigabyte in float32, most of it behind the mask (the
    whole-table form took 58% of the cell's busy time: ``PERF.md`` section
    6, PR 56)."""
    b, nh, s, d = q.shape
    n_w, g = comp.shape[1:3]
    r, m = block // stride, kernel // stride
    q5 = _grouped(q, g)
    comp = comp.astype(q.dtype)

    def scored(keys, w):
        """Scores [B, g, rep, S, W] of the windows of indices ``w`` [W] and
        which of them each query sees [B, 1, 1, S, W]."""
        sc = jnp.einsum("bgrqd,bwgd->bgrqw", q5, keys,
                        preferred_element_type=jnp.float32) * scale
        seen = ((w >= 0) & (w < n_w))[None, None] & (
            (stride * w + kernel)[None, None] <= pos[:, :, None] + 1)
        return sc, seen[:, None, None]

    def pooled(p):
        """Summed probabilities [B, g, S, W + m - 1] (from ``m - 1`` windows
        before a block's first) -> the blocks' maxima [B, g, S, W / r]."""
        return jax.lax.reduce_window(
            p, -jnp.inf, jax.lax.max, (1, 1, 1, r + m - 1), (1, 1, 1, r),
            "VALID")

    wb = SELECT_WINDOWS // r * r
    if s == 1 or n_w <= wb:
        sc, seen = scored(comp, jnp.arange(n_w))
        sc = jnp.where(seen, sc, -1e30)
        p = jnp.where(seen, jnp.exp(sc - sc.max(-1, keepdims=True)), 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return pooled(jnp.pad(p.sum(2), ((0, 0),) * 3 + ((m - 1, 0),)))
    n_wb = -(-n_w // wb)
    held = jnp.pad(comp, ((0, 0), (m - 1, n_wb * wb - n_w), (0, 0), (0, 0)))
    # window i is seen from position stride * i + kernel - 1 on
    trips = jnp.minimum(jnp.max(pos) // (stride * wb) + 1, n_wb)
    own = (jnp.arange(wb + m - 1) >= m - 1)[None, None, None, None]

    def some(j):
        return scored(jax.lax.dynamic_slice_in_dim(held, wb * j,
                                                   wb + m - 1, 1),
                      wb * j - (m - 1) + jnp.arange(wb + m - 1))

    def sums(j, carry):
        top, total = carry
        sc, seen = some(j)
        mine = seen & own          # the windows before are the last block's
        sc = jnp.where(mine, sc, -1e30)
        new = jnp.maximum(top, sc.max(-1))
        total = total * jnp.exp(top - new) + jnp.where(
            mine, jnp.exp(sc - new[..., None]), 0.0).sum(-1)
        return new, total

    rep = nh // g
    top, total = jax.lax.fori_loop(0, trips, sums, (
        jnp.full((b, g, rep, s), -1e30, jnp.float32),
        jnp.zeros((b, g, rep, s), jnp.float32)))
    total = jnp.maximum(total, 1e-30)

    def pools(j, score):
        sc, seen = some(j)
        p = jnp.where(seen, jnp.exp(jnp.minimum(sc - top[..., None], 0.0)),
                      0.0) / total[..., None]
        return jax.lax.dynamic_update_slice_in_dim(
            score, pooled(p.sum(2)), (wb // r) * j, 3)

    score = jax.lax.fori_loop(
        0, trips, pools, jnp.zeros((b, g, s, n_wb * wb // r), jnp.float32))
    return score[..., :n_w // r]


def select_blocks(q, comp, pos, *, stride: int, kernel: int, block: int,
                  topk: int, init_blocks: int, local: int, scale=None):
    """The blocks each query reads.  q [B, heads, S, D]; comp [B, n_w,
    kv_heads, D], a sequence's compressed keys in order (rows no window has
    filled yet hold anything: a query sees window ``i`` only when ``stride *
    i + kernel <= pos + 1``); pos [B, S] the queries' absolute positions.
    Blocks are ``n_w * stride / block`` of ``block`` positions::

        p_h    = softmax_i(q_h . c_i * scale) over the windows the query sees
        P      = the sum of p_h over the query heads of a KV head
        score_j = max of P over the windows that OVERLAP block j: those with
                 a position in it, ``block/stride * j - (kernel/stride - 1)
                 .. block/stride * j + block/stride - 1``
        forced = the first ``init_blocks`` and the blocks that hold
                 positions ``pos - local + 1 .. pos``
        chosen = the ``topk`` best among the blocks with a position <= pos,
                 the forced ones first

    Returns (idx [B, S, kv_heads, min(topk, blocks)] int32, the chosen
    blocks ASCENDING, behind them ``blocks`` where fewer exist; n [B, S] how
    many are real: ``min(topk, pos // block + 1)``).  :func:`_block_scores`
    says how the scores are walked."""
    b, nh, s, d = q.shape
    n_w = comp.shape[1]
    r = block // stride
    n_blocks = n_w // r
    if block % stride or kernel % stride or n_w % r:
        raise ValueError(f"{n_w} windows at stride {stride} are no whole "
                         f"blocks of {block}")
    k = min(int(topk), n_blocks)
    score = _block_scores(q, comp, pos, stride=stride, kernel=kernel,
                          block=block,
                          scale=d ** -0.5 if scale is None else scale)
    first = block * jnp.arange(n_blocks)
    t = pos[:, None, :, None]
    forced = (first < init_blocks * block) | (
        (first + block > t - local + 1) & (first <= t))
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(first <= t, score, -jnp.inf)
    top, idx = jax.lax.top_k(score, k)
    idx = jnp.sort(jnp.where(top > -jnp.inf, idx, n_blocks), -1)
    return jnp.moveaxis(idx, 1, 2).astype(jnp.int32), \
        jnp.minimum(k, pos // block + 1).astype(jnp.int32)


def chosen_mask(idx, n_blocks: int):
    """:func:`select_blocks`' idx [B, S, g, k] as a mask [B, S, g, n_blocks]:
    True at the blocks a query chose."""
    b, s, g, _ = idx.shape
    at = jnp.ix_(jnp.arange(b), jnp.arange(s), jnp.arange(g))
    return jnp.zeros((b, s, g, n_blocks + 1), bool).at[
        at[0][..., None], at[1][..., None], at[2][..., None], idx].set(
        True)[..., :n_blocks]


def sparse_plan(form: str, q, rows: int, **ids):
    """Which way a block-sparse layer's attention went is fixed when the
    program is traced: one instant per attention built says so (``form``
    ``kernel``: a chunk's or the dense forward's queries walk the whole view
    under their blocks' mask in the flash forward kernel; ``masked``: the
    same walk as XLA operations, and ``why``, as ``chunk_attn.plan`` says it
    (:func:`sparse_kernel_why`); ``paged``: a decode round's walk the pages
    they chose, in the pool where they lie; ``gathered``: the same from a
    view of those pages, off a TPU)."""
    b, nh, s, d = q.shape
    trace.instant("sparse.plan", {"form": form, "heads": nh, "d": d,
                                  "queries": s, "batch": b,
                                  "rows": int(rows), **ids})


def sparse_kernel_why(queries: int, rows: int, block: int) -> str:
    """Why a block-sparse layer's masked attention of ``queries`` queries a
    sequence over a view of ``rows`` rows, chosen in blocks of ``block``
    positions, can NOT run in the flash forward kernel where this is traced,
    "" when it can: ``short``, a view of at most ``KEY_BLOCK`` rows (one
    softmax); ``blocks``, a block that does not divide ``KEY_BLOCK`` or the
    view; ``ragged``, queries no tile of the kernel divides (more than one
    tile of them and no multiple of 8: a dense forward's odd length, never a
    chunk bucket); else :func:`chunk_kernel_why`'s reasons, the rule of a
    dense chunk."""
    if rows <= KEY_BLOCK:
        return "short"
    if KEY_BLOCK % block or rows % block:
        return "blocks"
    if queries > 512 and queries % 8:
        return "ragged"
    return chunk_kernel_why()


def masked_block_attention(q, k_cache, v_cache, pos, chosen, *, block: int,
                           scale=None):
    """Causal attention of q [B, heads, S, D] at positions ``pos`` [B, S]
    (a sequence's are consecutive: a chunk's, the dense forward's) over a
    time-major view [B, T, kv_heads, D], each query over the blocks (of
    ``block`` positions) it CHOSE: ``chosen`` [B, S, kv_heads, T / block]
    bool.  The masked form: the walk of a dense chunk over a long view with
    the mask beside the causal one, so it costs what dense attention costs
    and is exact; and who walks is decided as a dense chunk's walker is
    (:func:`sparse_kernel_why`): the flash forward kernel's SPARSE chunk
    call, score and probability tiles in VMEM, or :func:`_attend_blocks`
    under the mask, the portable path and the tests' oracle.  A
    ``sparse.plan`` instant says which, and why."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    t = k_cache.shape[1]
    why = sparse_kernel_why(q.shape[2], t, block)
    sparse_plan("masked" if why else "kernel", q, t, block=block,
                kv_heads=k_cache.shape[2], why=why)
    if not why:
        return flash_sparse_chunk_attention(
            q, k_cache, v_cache, pos[:, 0], chosen, block=block, scale=scale)
    if why not in ("short", "blocks"):
        return _attend_blocks(q, k_cache, v_cache, pos, scale, KEY_BLOCK,
                              chosen=chosen, block_size=block)
    b, nh, s, d = q.shape
    seen = (jnp.arange(t)[None, None, :] <= pos[:, :, None])[:, None] \
        & jnp.moveaxis(jnp.repeat(chosen, block, axis=-1), 2, 1)[..., :t]
    scores = jnp.einsum("bgrsd,btgd->bgrst", _grouped(q, k_cache.shape[2]),
                        k_cache, preferred_element_type=jnp.float32) * scale
    scores = jnp.where(seen[:, :, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bgrst,btgd->bgrsd", probs, v_cache)
    return out.reshape(b, nh, s, v_cache.shape[-1])


def chosen_pages_attention(q, k_cache, v_cache, layer, idx, n, lengths,
                           sparse, *, dense_blocks: int, scale=None):
    """The one-query step of a decode round over cache layer ``layer`` of a
    PAGED pool pair whose pages are the blocks (``page_size == block``),
    each (sequence, KV head) over the pages it chose; the new rows are
    already written.

    q [B, heads, 1, D]; idx [B, kv_heads, k], n [B] :func:`select_blocks`'
    choice for the one query; lengths [B] the newest token's position;
    sparse [B] bool: the sequence reads its choice, else (a sequence still
    under the model's dense length, at most ``dense_blocks`` pages long)
    every page it holds.  A (sequence, KV head) is one walk of the paged
    kernel over a SHORT table, ``max(k, dense_blocks)`` wide and no wider
    than the round's: the chosen pages ascending, the newest token's page
    last, so the walk's own causal mask (positions ``<= length`` ALONG THE
    WALK, ``(n - 1) * page + lengths % page``) is the layer's.  The kernel
    reads whole pages, every KV head's columns, and a walk's queries are
    its own KV head's (the others' rows of the result are dropped), so a
    chosen page is read once a KV head that chose it, never a page nobody
    chose.  Off a TPU the same tables gather a view (:func:`decode_attention`
    over it).  Returns [B, heads, 1, Dv]."""
    b, nh, _, d = q.shape
    g = idx.shape[1]
    n_pg = k_cache.tables.shape[1]
    ps = k_cache.pool.shape[2]
    width = min(max(idx.shape[2], int(dense_blocks)), n_pg)
    walk = jnp.arange(width)[None, None, :]
    blocks = jnp.where(
        sparse[:, None, None],
        jnp.pad(idx, ((0, 0), (0, 0), (0, width - idx.shape[2])),
                constant_values=n_pg), walk)
    tables = jnp.take_along_axis(
        k_cache.tables[:, None, :], jnp.clip(blocks, 0, n_pg - 1), 2)
    along = jnp.where(sparse, (n - 1) * ps + lengths % ps, lengths)
    tables = tables.reshape(b * g, width)
    along = jnp.repeat(along, g)
    q_each = jnp.repeat(q, g, axis=0)                 # [B * g, heads, 1, D]
    kernel = not k_cache.sharded and _default_backend_is_tpu()
    sparse_plan("paged" if kernel else "gathered", q, width * ps,
                block=ps, kv_heads=g, pages=width)
    if kernel:
        o = k_cache.attend(v_cache, layer, q_each, along, scale=scale,
                           tables=tables)
    else:
        o = decode_attention(
            q_each, k_cache.read(layer, tables).reshape(b * g, width * ps, -1),
            v_cache.read(layer, tables).reshape(b * g, width * ps, -1),
            along, scale=scale, kv_heads=g)
    # walk (b, j) holds KV head j's query heads' results
    dv = o.shape[-1]
    o = jnp.einsum("bjgrd,jg->bgrd", o.reshape(b, g, g, nh // g, dv),
                   jnp.eye(g, dtype=o.dtype))
    return o.reshape(b, nh, 1, dv)


# ---------------------------------------------------------------------------
# selection by ROWS: a lightning indexer over a latent cache (DeepSeek
# Sparse Attention with pooled indexer keys).  A second, small network scores
# GROUPS of ``pool`` consecutive cached tokens by the MEAN of their indexer
# keys; a query reads the rows of its ``topk`` best complete groups and of the
# open group it stands in, and nothing else.  A group is neither a page nor a
# flash block: its rows are GATHERED (a caller's, out of a view or out of the
# page pool through a slot's table) and attended in the absorbed latent form,
# or, a chunk's on a TPU, FETCHED BY GROUP inside the call that attends them
# (``pallas_kernels.chosen_groups``; :func:`index_kernel_why`).  The rest is
# plain ``jax.numpy``, float32 scores, exact ``lax.top_k``.
# ---------------------------------------------------------------------------

INDEX_KEY_BLOCK = 2048   # pooled keys a call's queries score at a time
INDEX_LANES = 128        # the lanes of a row tile: what a slab's rows fill
# rows of a tile of the narrowest cached type (bfloat16: 16): a query's
# gathered rows are brought up to a multiple, so a block's are whole tiles
INDEX_ROW_TILE = 16


def pool_index_keys(keys, open_sum, at, *, pool: int, last=None):
    """The pooled indexer keys a call's new rows complete, and the open
    group it leaves.  keys [B, S, D]: the call's new keys, row ``i`` of
    sequence ``b`` at position ``at[b] + i``; open_sum [B, D] float32: the
    sum of the ``at % pool`` keys of the group that was open when the call
    began (not read where ``at % pool == 0``); last: the index of the last
    real row (None: ``S - 1``).  Returns (means [B, n, D] float32, entry
    ``j`` the mean of group ``at // pool + j``; done [B, n] bool, the group's
    last position is a real row of this call; the new open sum [B, D]
    float32, zeros where the last real row closes a group).  Whichever page,
    chunk or round a group's first keys came in, its mean is the same sum."""
    b, s, d = keys.shape
    n = -(-s // pool) + 1
    shift = (at % pool)[:, None]                            # [B, 1]
    src = jnp.arange(n * pool)[None] - shift                # [B, n * pool]
    upto = (s - 1 if last is None else last)
    ok = (src >= 0) & (src <= upto)
    rows = jnp.take_along_axis(
        keys.astype(jnp.float32), jnp.clip(src, 0, s - 1)[..., None], 1)
    sums = jnp.where(ok[..., None], rows, 0.0).reshape(b, n, pool, d).sum(2)
    sums = sums.at[:, 0].add(jnp.where(shift > 0, open_sum, 0.0))
    end = at + upto                                         # [B]
    first = at // pool
    done = pool * (first[:, None] + jnp.arange(n)[None]) + pool - 1 \
        <= jnp.reshape(end, (-1, 1))
    still = jnp.reshape((end + 1) % pool != 0, (-1, 1))
    at_open = jnp.clip((end + 1) // pool - first, 0, n - 1)
    new_open = jnp.where(still, jnp.take_along_axis(
        sums, jnp.reshape(at_open, (-1, 1, 1)), 1)[:, 0], 0.0)
    return sums / pool, done, new_open


def select_groups(qi, w, kbar, pos, *, topk: int, pool: int):
    """The groups each query reads.  qi [B, S, J, D] the indexer's queries,
    w [B, S, J] float32 its head weights, kbar [B, G, D] the sequence's
    pooled keys in order (rows past the complete groups: anything), pos [B,
    S] the queries' positions.  ``I(t, g) = sum_j w_j relu(qi_j . kbar_g)``
    in float32 over the ``(pos + 1) // pool`` COMPLETE groups; the ``topk``
    largest, ties to the lower group (``lax.top_k``).  The scores are walked
    ``INDEX_KEY_BLOCK`` groups at a time, so ``[J, S, G]`` is never whole.
    Returns (idx [B, S, topk] int32 by falling score, n [B, S] int32: the
    first ``n = min(topk, complete groups)`` of them are groups, the rest
    padding)."""
    b, s, j, d = qi.shape
    g = kbar.shape[1]
    kb = min(INDEX_KEY_BLOCK, g)
    pad = -g % kb
    if pad:
        kbar = jnp.pad(kbar, ((0, 0), (0, pad), (0, 0)))
    blocks = jnp.moveaxis(kbar.reshape(b, (g + pad) // kb, kb, d), 1, 0)

    def one(block):
        dots = jnp.einsum("bsjd,bgd->bsjg", qi, block,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jnp.maximum(dots, 0.0) * w[..., None], axis=2)

    scores = jnp.moveaxis(jax.lax.map(one, blocks), 0, 2).reshape(
        b, s, g + pad)
    complete = (pos + 1) // pool
    scores = jnp.where(jnp.arange(g + pad)[None, None] < complete[..., None],
                       scores, -jnp.inf)
    if g + pad < topk:
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, topk - g - pad)),
                         constant_values=-jnp.inf)
    _, idx = jax.lax.top_k(scores, topk)
    return idx.astype(jnp.int32), jnp.minimum(complete, topk).astype(
        jnp.int32)


def chosen_rows(idx, n, pos, *, pool: int, tile: int = 1):
    """The positions a query reads: idx [..., K], n [...] (:func:`select_groups`)
    and pos [...] -> (rows [..., M] int32, valid bool the same shape), ``M``
    = ``(K + 1) * pool``: the ``pool`` rows of each of the first ``n``
    groups, then the OPEN group's (``(pos + 1) // pool``) up to ``pos``: 0 to
    ``pool - 1`` of them, the query's own among them unless it closes a
    group.  ``tile``: ``M`` is brought up to a multiple of it by rows that
    are not valid, behind the others: what is gathered by a query's rows is
    then a whole number of the memory's row tiles a query, and a block of
    queries' rows ``[queries * M, C]`` read as ``[queries, M, C]`` is the
    same bytes (2,052 rows of bfloat16, no multiple of 16, were a copy of
    269 MB a block of 128 queries: PERF.md section 6, PR 59)."""
    k = idx.shape[-1]
    groups = jnp.concatenate([idx, ((pos + 1) // pool)[..., None]], -1)
    rows = groups[..., None] * pool + jnp.arange(pool)
    valid = jnp.concatenate([
        jnp.broadcast_to((jnp.arange(k) < n[..., None])[..., None],
                         idx.shape + (pool,)),
        rows[..., k:, :] <= pos[..., None, None]], -2)
    flat = idx.shape[:-1] + ((k + 1) * pool,)
    rows, valid = rows.reshape(flat).astype(jnp.int32), valid.reshape(flat)
    pad = ((0, 0),) * (rows.ndim - 1) + ((0, -flat[-1] % tile),)
    return jnp.pad(rows, pad), jnp.pad(valid, pad)


def chosen_rows_attention(q, latents, valid, *, scale: float):
    """Attention over GATHERED latent rows, the absorbed form: q [B, S,
    heads, C] (each head's query already through its key up-projection),
    latents [B, S, M, C] the rows query (b, s) reads (keys AND values: one
    array), valid [B, S, M].  ``softmax(q . c * scale)`` over the valid rows
    in float32; returns [B, S, heads, C] in ``latents``' dtype (a head's
    value up-projection is the caller's).  A query with no valid row reads
    zeros."""
    scores = jnp.einsum("bshc,bsmc->bshm", q, latents,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, :, None], scores, -jnp.inf)
    top = jnp.max(scores, -1, keepdims=True)
    e = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    return jnp.einsum("bshm,bsmc->bshc", p.astype(latents.dtype), latents,
                      preferred_element_type=jnp.float32).astype(
                          latents.dtype)


def index_kernel_why(width: int) -> str:
    """Why a chunk's row-selecting layer can NOT fetch its chosen groups
    inside the call that attends them where this is traced
    (``pallas_kernels.chosen_groups.chosen_groups_attention``), "" when it
    can: ``width``, rows that are no whole number of ``INDEX_LANES`` lanes (a
    group's slab is copied as it lies); else :func:`chunk_kernel_why`'s
    reasons, the rule of a dense chunk."""
    if width % INDEX_LANES:
        return "width"
    return chunk_kernel_why()


def index_plan(form: str, queries: int, batch: int, groups: int, **ids):
    """Which way a row-selecting layer's attention went is fixed when the
    program is traced: one instant per layer built says so (``form``
    ``kernel``: a chunk's or the dense forward's queries fetch the groups
    they chose inside the call that attends them, ``query_block`` queries a
    call; ``gathered``: the chosen groups' rows gathered by XLA and attended
    in the absorbed form, and ``why``: :func:`index_kernel_why`'s reasons,
    ``round`` for a decode round, which gathers out of the page pool through
    the tables, ``forward`` for the dense forward, which is differentiated
    where the kernel has no backward)."""
    trace.instant("index.plan", {"form": form, "queries": int(queries),
                                 "batch": int(batch), "groups": int(groups),
                                 **ids})
