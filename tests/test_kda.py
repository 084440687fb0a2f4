"""The delta rule with a decay a key CHANNEL (``ops/delta_rule.py``:
``kda_chunk_scan``, ``kda_step``) against a naive row-by-row loop: across
chunk and sub-block edges, ragged row counts, padded rows, a carried state,
and ``g`` at its lower bound for a whole call (no overflow, no NaN)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import delta_rule as dr

LOWER = -5.0


@jax.jit
def naive(q, k, v, g, beta, state):
    """S <- Diag(exp(g)) S; d = beta (v - S^T k); S <- S + k d^T; o = S^T q /
    sqrt(d_k), one row at a time."""
    dk = q.shape[-1]
    qf, kf = dr.unit_rows(q) * dk ** -0.5, dr.unit_rows(k)

    def row(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
        d = bt[..., None] * (vt - jnp.einsum("bhkd,bhk->bhd", state, kt))
        state = state + kt[..., None] * d[..., None, :]
        return state, jnp.einsum("bhkd,bhk->bhd", state, qt)

    state, outs = jax.lax.scan(row, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (qf, kf, v, g, beta)))
    return jnp.moveaxis(outs, 0, 1), state


# one compile a call shape, not one an operation
chunk_scan = jax.jit(dr.kda_chunk_scan, static_argnames=("chunk", "sub"))
step = jax.jit(dr.kda_step)


def rows(s, seed=0, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(kk, (b, s, h, dk)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = LOWER * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


@pytest.mark.parametrize("s,chunk,sub", [
    (50, 16, 4),      # ragged: three chunks and two rows, four sub-blocks
    (64, 64, 16),     # the published sizes: one chunk, four sub-blocks
    (40, 8, 8),       # a sub-block a chunk
    (5, 64, 16),      # fewer rows than a sub-block
    (33, 32, 16),     # one row into the second chunk
])
def test_chunk_scan_is_the_naive_loop(s, chunk, sub):
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, s0 = rows(s)
        want, end = naive(q, k, v, g, beta, s0)
        got, left = chunk_scan(q, k, v, g, beta, s0, chunk=chunk,
                                      sub=sub)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(left, end, atol=2e-5)


def test_step_row_by_row_is_the_chunk_scan():
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, s0 = rows(21, seed=1)
        want, end = chunk_scan(q, k, v, g, beta, s0, chunk=8, sub=4)
        state, outs = s0, []
        for t in range(21):
            o, state = step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], state)
            outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=2e-5)
    np.testing.assert_allclose(state, end, atol=2e-5)


def test_rows_past_last_leave_the_state_after_last():
    """A chunk padded to its bucket: the state handed back is the one after
    the last REAL row, and the real rows' results are untouched."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, s0 = rows(32, seed=2)
        got, left = chunk_scan(q, k, v, g, beta, s0, chunk=16, sub=4,
                                      last=20)
        want, end = naive(*(a[:, :21] for a in (q, k, v, g, beta)), s0)
    np.testing.assert_allclose(got[:, :21], want, atol=2e-5)
    np.testing.assert_allclose(left, end, atol=2e-5)


def test_a_padded_row_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, s0 = rows(4, seed=3)
    zero = jnp.zeros_like
    _, left = chunk_scan(q, k, v, zero(g), zero(beta), s0)
    assert np.array_equal(np.asarray(left), np.asarray(s0))
    _, left = step(q[:, 0], k[:, 0], v[:, 0], zero(g[:, 0]),
                          zero(beta[:, 0]), s0)
    assert np.array_equal(np.asarray(left), np.asarray(s0))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_bound_for_a_whole_chunk_stays_finite(dtype):
    """Every channel at ``gate_lower_bound`` for 64 rows: a sub-block's
    growing factor reaches e^75 and no further, the chunk's e^-320 is a
    clean zero; nothing overflows and the rule is still the loop's."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, s0 = rows(64, seed=4)
        g = jnp.full_like(g, LOWER)
        want, end = naive(q, k, v, g, beta, s0)
        got, left = chunk_scan(
            q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta, s0)
    assert bool(jnp.all(jnp.isfinite(got))) and bool(
        jnp.all(jnp.isfinite(left)))
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol)
    np.testing.assert_allclose(left, end, atol=tol)


def test_sub_blocks_must_divide_the_chunk():
    q, k, v, g, beta, s0 = rows(8)
    with pytest.raises(ValueError, match="do not divide"):
        dr.kda_chunk_scan(q, k, v, g, beta, s0, chunk=24, sub=16)
