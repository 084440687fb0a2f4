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


def _attend_blocks(q, k_cache, v_cache, pos, scale, block: int):
    """Causal attention of q [B, heads, S, D] at positions ``pos`` [B, S]
    over a time-major view [B, T, kv_heads, D] with ``T > block``: the keys
    are walked ``block`` at a time under a running maximum and sum, heads
    grouped, so neither the [heads, S, T] scores nor a repeated copy of the
    view is ever whole; and the walk ends at the last key any query can see,
    so a chunk's cost follows its history and not the width of the table it
    was handed.  Returns [B, heads, S, D]."""
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
