"""Scaled-dot-product attention cores.

The reference has no fused attention op — its MultiHeadAttention layer
(python/hetu/layers/attention.py) composes batch_matmul/softmax ops.  On TPU
we provide (a) an XLA composition that the compiler fuses well at moderate
sequence lengths, and (b) a Pallas flash-attention kernel for long sequences
(hetu_tpu/ops/pallas_kernels/flash_attention.py), plus ring attention for the
sequence-parallel axis (hetu_tpu/parallel/ring_attention.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def causal_attention(q, k, v, *, scale=None):
    s_q, s_k = q.shape[-2], k.shape[-2]
    mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
    return attention(q, k, v, mask=mask, scale=scale)


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
    def layer(carry, xs):
        h, k_all, v_all = carry
        p_l, l = xs
        h, k_l, v_l = step(p_l, h, read_cache_layer(k_all, l),
                           read_cache_layer(v_all, l))
        return (h, write_cache_layer(k_all, l, k_l, at, n),
                write_cache_layer(v_all, l, v_l, at, n)), None

    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    carry, _ = jax.lax.scan(layer, (h, k_cache, v_cache),
                            (blocks, jnp.arange(n_layers)))
    return carry


def chunk_attention(q, k_cache, v_cache, starts, *, scale=None):
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
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nh, nkv = q.shape[1], k_cache.shape[2]
    k = jnp.moveaxis(k_cache, 1, 2)  # [B, kv_heads, T, D]
    v = jnp.moveaxis(v_cache, 1, 2)
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    t = k_cache.shape[1]
    s_c = q.shape[-2]
    pos = starts[:, None] + jnp.arange(s_c)                  # [B, S_c]
    valid = jnp.arange(t)[None, None, :] <= pos[:, :, None]  # [B, S_c, T]
    scores = jnp.where(valid[:, None], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None):
    """Single-token attention against a cache (GQA-aware).

    q: [B, heads, 1, D] — the newest token's query, already positioned at
    index ``lengths[b]`` in its sequence (so its K/V must have been written
    via :func:`cache_update` first).  k_cache/v_cache: [B, T, kv_heads, D]
    with kv_heads dividing heads (kv_heads < heads = GQA; repeats serve
    each kv head to heads/kv_heads query heads).  lengths: [B] int32 index
    of the newest token; positions > lengths[b] (unwritten or stale from a
    previous occupant) are masked out.
    """
    if q.shape[-2] != 1:
        raise ValueError(
            f"decode_attention takes one query token, got {q.shape[-2]} "
            "(prefill goes through causal_attention over the chunk)")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nh, nkv = q.shape[1], k_cache.shape[2]
    k = jnp.moveaxis(k_cache, 1, 2)  # [B, kv_heads, T, D]
    v = jnp.moveaxis(v_cache, 1, 2)
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    t = k_cache.shape[1]
    valid = jnp.arange(t)[None, :] <= lengths[:, None]      # [B, T]
    scores = jnp.where(valid[:, None, None, :], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
