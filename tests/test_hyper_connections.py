"""Hyper-connections (``ops/hyper.py``) and the decoder's residual seam
(``models/block.py`` ``_read`` / ``_write``): Sinkhorn's projection, the
mix against its einsum, and the plain add as the seam's one-stream case."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models.block import BlockDecoder
from hetu_tpu.ops import hyper


@pytest.mark.parametrize("n,scale,diagonal", [
    (4, 0.5, 1.0),     # the model's: alpha_res 0.5 round a bias of I
    (4, 0.5, 0.0), (4, 0.3, 2.0), (8, 0.5, 1.0)])
def test_sinkhorn_rows_and_columns_sum_to_one(n, scale, diagonal):
    """20 passes, as ``hc_sinkhorn_iters`` states: rows and columns within
    1e-4 of one at logits of the order the model's coefficients have."""
    logits = scale * jax.random.normal(jax.random.PRNGKey(n), (64, 64, n, n)) \
        + diagonal * jnp.eye(n)
    m = hyper.sinkhorn(logits, iters=20, eps=1e-6)
    assert bool(jnp.all(m > 0))
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-4)


def leaves(key, n, h):
    ks = jax.random.split(key, 3)
    wide = 2 * n + n * n
    return {"norm": 1.0 + 0.1 * jax.random.normal(ks[0], (n * h,)),
            "phi": jax.random.normal(ks[1], (n * h, wide)) / (n * h) ** 0.5,
            "alpha": jnp.array([1.0, 0.5, 2.0]),
            "bias": 0.3 * jax.random.normal(ks[2], (wide,))}


def test_coefficients_and_mix_are_the_equations():
    n, h = 4, 16
    key = jax.random.PRNGKey(0)
    p = leaves(key, n, h)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 5, n, h))
    y = jax.random.normal(jax.random.fold_in(key, 2), (2, 5, h))
    pre, post, res = hyper.coefficients(x, p, iters=20, eps=1e-6,
                                        rms_eps=1e-5)
    flat = x.reshape(2, 5, -1)
    flat = flat / jnp.sqrt(jnp.mean(flat ** 2, -1, keepdims=True) + 1e-5) \
        * p["norm"]
    a = flat @ p["phi"]
    np.testing.assert_allclose(
        pre, jax.nn.sigmoid(a[..., :n] + p["bias"][:n]), atol=1e-5)
    np.testing.assert_allclose(
        post, 2 * jax.nn.sigmoid(0.5 * a[..., n:2 * n] + p["bias"][n:2 * n]),
        atol=1e-5)
    want = hyper.sinkhorn((2.0 * a[..., 2 * n:] + p["bias"][2 * n:]).reshape(
        2, 5, n, n), iters=20, eps=1e-6)
    np.testing.assert_allclose(res, want, atol=1e-5)
    np.testing.assert_allclose(hyper.stream_read(x, pre),
                               jnp.einsum("bsn,bsnh->bsh", pre, x), atol=1e-5)
    np.testing.assert_allclose(
        hyper.stream_write(x, res, post, y),
        jnp.einsum("bsij,bsjh->bsih", res, x) + post[..., None]
        * y[:, :, None], atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_stream_at_unit_coefficients_is_the_plain_add(dtype):
    """The seam handed ``H_pre = H_post = 1`` and ``H_res = I`` at ONE stream
    is ``BlockDecoder``'s own: the read hands the stream on, the write adds,
    bit for bit."""
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (2, 6, 32)).astype(dtype)
    y = jax.random.normal(jax.random.fold_in(key, 1), (2, 6, 32)).astype(
        dtype)
    one = jnp.ones((2, 6, 1), jnp.float32)
    u = hyper.stream_read(h[:, :, None], one)
    out = hyper.stream_write(h[:, :, None], one[..., None], one, y)[:, :, 0]
    plain = BlockDecoder.__new__(BlockDecoder)
    read, mix = plain._read(None, 0, 0, h)
    assert mix is None and read is h
    assert np.array_equal(np.asarray(u, np.float32),
                          np.asarray(read, np.float32))
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(plain._write(h, y, mix), np.float32))
