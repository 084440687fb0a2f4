"""Rotary position embedding (RoPE, Su et al. '21).

Reference analog: the Llama/Baichuan models under tools/Galvatron
(galvatron/models/llama_hf) position-encode q/k with HF's rotary embedding
inside the attention kernel.  TPU form: precompute the [S, D/2] cos/sin
tables once per call (XLA hoists them out of the layer scan) and rotate
pairs with two fused multiplies — no gather, no complex dtype.

Convention: HALF-ROTATION layout (the HF/Llama one) — the head dim is
split [x1 | x2] and rotated as (x1*cos - x2*sin, x2*cos + x1*sin).
:func:`apply_rope_interleaved` is the other published layout: neighbouring
pairs (x[2i], x[2i+1]) rotate together (latent-attention models).
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_tables(seq_len: int, head_dim: int, *, theta: float = 10000.0,
                dtype=jnp.float32):
    """cos/sin tables ``[S, D/2]`` for :func:`apply_rope`."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, jnp.float32) / head_dim)
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv_freq)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """Rotate ``x [..., S, D]`` by position; cos/sin are ``[S, D/2]``.

    Works for any leading batch/head dims (tables broadcast over them).
    Computation in the input dtype — the tables should be f32 for long
    sequences (angles lose precision in bf16) and are cast here.
    """
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope_at(x, cos, sin, positions):
    """Rotate ``x [B, H, S, D]`` at per-sequence ABSOLUTE positions.

    The serving decode/prefill form of :func:`apply_rope`: sequence ``b``'s
    chunk starts at ``positions[b]`` (its token ``i`` sits at absolute
    position ``positions[b] + i``), so each sequence gathers its own rows
    from the full-length ``cos``/``sin`` tables ``[T_max, D/2]``.  With
    ``positions == zeros`` this matches ``apply_rope`` exactly.
    """
    s = x.shape[-2]
    pos = positions[:, None] + jnp.arange(s)        # [B, S]
    # clamp: a padded chunk's tail can run past the table (chunked
    # prefill near max_len), and the default out-of-range gather FILLS
    # NaN — which would poison real lanes through 0 * NaN in masked
    # attention.  Clamping only ever touches pad positions.
    pos = jnp.clip(pos, 0, cos.shape[0] - 1)
    c = cos[pos][:, None].astype(x.dtype)           # [B, 1, S, D/2]
    sn = sin[pos][:, None].astype(x.dtype)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def apply_rope_interleaved(x, cos, sin):
    """Rotate ``x [..., D]`` pairwise: (x[2i], x[2i+1]) by angle ``i`` of its
    position.  cos/sin ``[..., D/2]`` are already gathered at each token's
    position and broadcast against ``x``'s leading dims; float32 inside,
    result in ``x``'s dtype."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
