"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
residual step of a decoder whose stream is ``n`` rows a token, ``X`` [n, H].

A sublayer ``F`` does not read the stream and add to it; it reads a learned
MIX of the ``n`` rows, writes its result back through a learned vector, and
the rows themselves are mixed by a doubly stochastic matrix made a token::

    x~     = N_flat(vec(X))                       # one RMSNorm over n H
    a_pre  = alpha_pre  (x~ Phi_pre)  + b_pre     # [n]
    a_post = alpha_post (x~ Phi_post) + b_post    # [n]
    A_res  = alpha_res  mat(x~ Phi_res) + B_res   # [n, n]
    H_pre  = sigmoid(a_pre);  H_post = 2 sigmoid(a_post)
    H_res  = SK(exp(A_res)): ``iters`` times { rows / (row sums + eps);
                                               columns / (column sums + eps) }
    u = sum_i H_pre[i] X_i;   y = F(N(u))
    X_i <- sum_j H_res[i, j] X_j + H_post[i] y

:func:`coefficients` makes the three, :func:`stream_read` the sublayer's
input, :func:`stream_write` the new stream.  The coefficients are float32
throughout (the projection at the highest precision: ``n H`` x ``2 n + n^2``,
a sliver of a layer's work) and the two mixes multiply and add in float32,
rounding once to the stream's type.  At ONE row with ``H_pre = H_post = 1``
and ``H_res = I`` the pair is ``X + y``, the plain residual add, bit for bit
in float32 (``tests/test_hyper_connections.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sinkhorn(logits, *, iters: int, eps: float):
    """``exp(logits)`` [..., n, n] brought to (nearly) doubly stochastic:
    ``iters`` passes of rows over their sums then columns over theirs, each
    sum with ``eps`` added.  float32.  The passes run with the two matrix
    axes LEADING, ``[n, n, ...]``: a sum over a row or a column is then a
    sum of ``n`` slabs whose minor axis is the tokens', where a reduction
    over a minor axis of 4 would tile every token's 4 x 4 to a whole
    register tile."""
    def one_pass(_, m):
        m = m / (jnp.sum(m, 1, keepdims=True) + eps)
        return m / (jnp.sum(m, 0, keepdims=True) + eps)

    # a LOOP, not ``iters`` copies of the pass: unrolled, ten sublayers' 20
    # passes were three quarters of a serving program's 15 MB of HLO, 11.5 s
    # to compile each of a cell's 141 programs and too much for the compile
    # cache to keep (PERF.md section 6, PR 58)
    m = jnp.moveaxis(jnp.exp(logits.astype(F32)), (-2, -1), (0, 1))
    m = jax.lax.fori_loop(0, int(iters), one_pass, m)
    return jnp.moveaxis(m, (0, 1), (-2, -1))


def coefficients(x, p, *, iters: int, eps: float, rms_eps: float):
    """x [..., n, H] the stream; ``p`` one sublayer's leaves: ``norm`` [n H],
    ``phi`` [n H, 2 n + n n] float32 (``[pre | post | res]`` side by side),
    ``alpha`` [3] and ``bias`` [2 n + n n] float32.  Returns (H_pre [...,
    n], H_post [..., n], H_res [..., n, n]) float32."""
    n = x.shape[-2]
    flat = x.astype(F32).reshape(x.shape[:-2] + (-1,))
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + rms_eps) * p["norm"].astype(F32)
    a = jnp.dot(flat, p["phi"].astype(F32),
                precision=jax.lax.Precision.HIGHEST)
    alpha = jnp.concatenate([
        jnp.broadcast_to(p["alpha"].astype(F32)[i], (width,))
        for i, width in enumerate((n, n, n * n))])
    a = a * alpha + p["bias"].astype(F32)
    res = a[..., 2 * n:].reshape(a.shape[:-1] + (n, n))
    return jax.nn.sigmoid(a[..., :n]), 2.0 * jax.nn.sigmoid(a[..., n:2 * n]), \
        sinkhorn(res, iters=iters, eps=eps)


def stream_read(x, h_pre):
    """``sum_i H_pre[i] X_i``: x [..., n, H], h_pre [..., n] -> [..., H] in
    ``x``'s dtype."""
    return jnp.sum(x.astype(F32) * h_pre[..., None], -2).astype(x.dtype)


def stream_write(x, h_res, h_post, y):
    """``X_i <- sum_j H_res[i, j] X_j + H_post[i] y``: x [..., n, H], h_res
    [..., n, n], h_post [..., n], y [..., H] -> [..., n, H] in ``x``'s
    dtype."""
    n = x.shape[-2]
    xf = x.astype(F32)
    # n rows a token: a sum of n scaled rows, no matmul
    mixed = sum(h_res[..., :, j, None] * xf[..., j:j + 1, :]
                for j in range(n))
    return (mixed + h_post[..., None] * y.astype(F32)[..., None, :]).astype(
        x.dtype)
