"""The selective state-space layer (Mamba-2, "SSD") as a server runs it.

One head keeps a MATRIX of a sequence, ``S`` [d_head, d_state], whatever the
sequence's length::

    S_t = a_t S_(t-1) + dt_t * x_t B_t^T        a_t = exp(dt_t * A),  A < 0
    y_t = S_t C_t + D * x_t

``x_t`` [d_head] is the head's input, ``B_t`` and ``C_t`` [d_state] are
shared by the heads of a GROUP (head ``i`` of ``h`` reads group ``i // (h /
g)``), ``dt_t > 0``, ``A`` and ``D`` are one number a head.  The recurrence
in its two serving forms, both from a state handed in and both handing the
new one back in float32:

* :func:`ssd_chunk_scan`: many rows of one sequence.  Within a chunk of
  ``chunk`` rows the quadratic form (row ``i`` reads row ``j <= i`` through
  ``C_i . B_j`` and the decay between them: two matmuls a chunk), between
  chunks the carried state (a ``lax.scan`` over the chunks).  Rows past
  ``last`` get ``dt`` = 0: they neither decay the state nor feed it, so the
  state left is the one after row ``last``, which is what a prefill chunk
  padded to its bucket needs.
* :func:`ssm_step`: one row a sequence, a decode round.

Beside them what the layer puts round the recurrence: the causal depth-wise
convolution over ``[state | new rows]`` (:func:`causal_conv`) and the gated
RMS norm over groups of channels (:func:`gated_group_rms_norm`).  The
convolution also serves the Gated DeltaNet mixer, whose recurrence is NOT
this one (its update reads the state before it writes it: ``ops/delta_rule.py``).  Plain
``jax.numpy`` / ``lax``; decays, their cumulative sums and the state in
float32 whatever the rows' dtype, the matmuls over the rows' dtype with
float32 sums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _grouped(a, groups: int, axis: int):
    """Split the head axis ``axis`` of ``a`` into (group, head in group)."""
    shape = a.shape
    return a.reshape(shape[:axis] + (groups, shape[axis] // groups)
                     + shape[axis + 1:])


def ssd_chunk_scan(x, dt, A, B, C, D, state, *, chunk: int, last=None):
    """``x`` [b, s, h, p]; ``dt`` [b, s, h] (positive: the softplus is the
    caller's); ``A``, ``D`` [h]; ``B``, ``C`` [b, s, g, n]; ``state`` [b, h,
    p, n] or None (zeros); ``last`` the index of the last real row (None:
    ``s - 1``).  Returns (y [b, s, h, p] in ``x``'s dtype, the state after
    row ``last`` [b, h, p, n] float32).  A call of fewer rows than ``chunk``
    is one short chunk; a row count that ``chunk`` does not divide is padded
    with rows that, like those past ``last``, change nothing."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    dt = dt.astype(F32)
    if last is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] <= last, dt, 0.0)
    q = min(int(chunk), s)
    pad = -s % q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (s + pad) // q
    # [b, chunks, rows of a chunk, ...], the heads by group
    xc = _grouped(x.reshape(b, nc, q, h, p), g, 3)         # b c q g r p
    Bc, Cc = B.reshape(b, nc, q, g, n), C.reshape(b, nc, q, g, n)
    dtc = dt.reshape(b, nc, q, h)
    cum = jnp.cumsum(dtc * A.astype(F32), axis=2)          # log decay to row i
    if state is None:
        state = jnp.zeros((b, h, p, n), F32)

    # within a chunk: row i reads row j <= i
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=F32)
    gap = cum[:, :, :, None] - cum[:, :, None, :]          # b c i j h
    seen = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    w = jnp.exp(jnp.where(seen, gap, -jnp.inf)) * dtc[:, :, None]
    w = _grouped(jnp.moveaxis(w, 4, 2), g, 2)              # b c g r i j
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   (w * scores[:, :, :, None]).astype(x.dtype), xc,
                   preferred_element_type=F32)

    # what a chunk's rows add to the state by the chunk's end
    end = cum[:, :, -1]                                    # b c h
    fed = (jnp.exp(end[:, :, None] - cum) * dtc)           # b c q h
    fed = xc * _grouped(fed, g, 3)[..., None].astype(x.dtype)
    added = jnp.einsum("bcjgrp,bcjgn->bcgrpn", fed, Bc,
                       preferred_element_type=F32).reshape(b, nc, h, p, n)

    # between chunks: the state each chunk starts from, and the last
    def carry(st, chunk_c):
        decay, add = chunk_c
        return st * decay[:, :, None, None] + add, st

    state, before = jax.lax.scan(
        carry, state.astype(F32),
        (jnp.moveaxis(jnp.exp(end), 1, 0), jnp.moveaxis(added, 1, 0)))
    before = _grouped(jnp.moveaxis(before, 0, 1), g, 2)    # b c g r p n
    carried = jnp.einsum("bcgrpn,bcign->bcigrp", before.astype(x.dtype), Cc,
                         preferred_element_type=F32)
    y = y + carried * _grouped(jnp.exp(cum), g, 3)[..., None]
    y = y.reshape(b, nc * q, h, p)[:, :s]
    x = x[:, :s]
    return (y + D.astype(F32)[:, None] * x.astype(F32)).astype(x.dtype), state


def ssm_step(x, dt, A, B, C, D, state):
    """One row a sequence: ``x`` [b, h, p], ``dt`` [b, h], ``B``, ``C`` [b,
    g, n], ``state`` [b, h, p, n].  Returns (y [b, h, p] in ``x``'s dtype,
    the new state float32)."""
    h, g = x.shape[1], B.shape[1]
    xf, dt = x.astype(F32), dt.astype(F32)
    Bh, Ch = (jnp.repeat(a.astype(F32), h // g, axis=1) for a in (B, C))
    decay = jnp.exp(dt * A.astype(F32))
    state = state.astype(F32) * decay[:, :, None, None] \
        + (dt[..., None] * xf)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(state * Ch[:, :, None, :], axis=-1)
    return (y + D.astype(F32)[:, None] * xf).astype(x.dtype), state


def causal_conv(rows, taps, bias=None, state=None, last=None):
    """A depth-wise causal convolution of ``taps`` [k, channels] (``taps[j]``
    weighs the row ``k - 1 - j`` before) over ``rows`` [b, s, channels],
    which follow the ``k - 1`` rows of ``state`` [b, k - 1, channels] (None:
    zeros, a sequence's start): one sum of ``k`` shifted products over
    ``[state | rows]`` in float32, ``bias`` [channels] added.  Returns (the
    sum [b, s, channels] float32, the ``k - 1`` rows ending at row ``last``
    (None: the call's last) in ``rows``' dtype: the state the next call
    starts from)."""
    b, s, ch = rows.shape
    k = taps.shape[0]
    if state is None:
        state = jnp.zeros((b, k - 1, ch), rows.dtype)
    both = jnp.concatenate([state.astype(rows.dtype), rows], axis=1)
    keep = jax.lax.dynamic_slice_in_dim(
        both, (s - 1 if last is None else last) + 1, k - 1, axis=1)
    w = taps.astype(F32)
    out = sum(w[j] * both[:, j:j + s].astype(F32) for j in range(k))
    if bias is not None:
        out = out + bias.astype(F32)
    return out, keep


def gated_group_rms_norm(y, gate, weight, *, groups: int, eps: float):
    """``y * silu(gate)`` normalised by its root mean square over each of
    ``groups`` equal runs of the last axis, times ``weight`` [channels]: the
    gate first, then the norm.  Float32 inside, ``y``'s dtype out."""
    v = y.astype(F32) * jax.nn.silu(gate.astype(F32))
    vg = v.reshape(v.shape[:-1] + (groups, v.shape[-1] // groups))
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, -1, keepdims=True) + eps)
    return (vg.reshape(v.shape) * weight.astype(F32)).astype(y.dtype)
