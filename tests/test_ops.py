"""Op-library oracle tests vs numpy.

Mirrors the reference's tests/test_ops.py pattern (HetuTester: same op on two
backends, allclose) with numpy as the oracle.
"""

import numpy as np
import jax.numpy as jnp
import jax
import pytest

import hetu_tpu as ht
from hetu_tpu import ops


def rnd(*shape, seed=0, pos=False):
    g = np.random.default_rng(seed)
    x = g.standard_normal(shape).astype(np.float32)
    return np.abs(x) + 0.1 if pos else x


def close(a, b, tol=1e-5):
    np.testing.assert_allclose(np.asarray(a), b, rtol=tol, atol=tol)


def test_elementwise():
    x, y = rnd(4, 5), rnd(4, 5, seed=1)
    close(ops.add(x, y), x + y)
    close(ops.minus(x, y), x - y)
    close(ops.multiply(x, y), x * y)
    close(ops.divide(x, np.abs(y) + 1), x / (np.abs(y) + 1))
    close(ops.opposite(x), -x)
    close(ops.abs_(x), np.abs(x))
    close(ops.exp(x), np.exp(x), tol=1e-4)
    close(ops.log(np.abs(x) + 1), np.log(np.abs(x) + 1))
    close(ops.sqrt(np.abs(x)), np.sqrt(np.abs(x)))
    close(ops.sin(x), np.sin(x))
    close(ops.floor(x), np.floor(x))
    close(ops.clamp(x, -0.5, 0.5), np.clip(x, -0.5, 0.5))
    close(ops.sign(x), np.sign(x))
    close(ops.where(x > 0, x, y), np.where(x > 0, x, y))
    close(ops.masked_fill(x, x > 0, -1.0), np.where(x > 0, -1.0, x))


def test_matmul_family():
    a, b = rnd(4, 6), rnd(6, 3, seed=1)
    close(ops.matmul(a, b), a @ b)
    close(ops.matmul(a.T, b, trans_a=True), a @ b)
    close(ops.matmul(a, b.T, trans_b=True), a @ b)
    bias = rnd(3, seed=2)
    close(ops.linear(a, b, bias), a @ b + bias)
    ba, bb = rnd(2, 4, 6, seed=3), rnd(2, 6, 3, seed=4)
    close(ops.batch_matmul(ba, bb), ba @ bb)
    inp = rnd(4, 3, seed=5)
    close(ops.addmm(inp, a, b, alpha=2.0, beta=0.5), 0.5 * inp + 2.0 * (a @ b))
    close(ops.matrix_dot(a, a), np.sum(a * a, axis=-1))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_minor_is_linear_over_a_weight_stored_the_other_way(dtype):
    """``linear_minor`` over ``[*out, K]`` is ``linear`` over ``[K, N]``, the
    result and the bias split as the weight's output axes are: bit for bit
    under bfloat16 operands (float32 accumulation in both), to rounding in
    float32, where the CPU's kernel sums the other operand order its own
    way."""
    def same(a, b):
        return np.array_equal(a, b) if dtype == jnp.bfloat16 \
            else np.allclose(a, b, rtol=1e-5, atol=1e-5)

    x = jnp.asarray(rnd(2, 5, 16), dtype)
    w = jnp.asarray(rnd(16, 24, seed=1), dtype)
    bias = jnp.asarray(rnd(24, seed=2), dtype)
    want = ops.linear(x, w, bias)
    got = ops.linear_minor(x, w.T, bias)
    assert got.dtype == want.dtype == dtype and same(got, want)
    split = ops.linear_minor(x, w.T.reshape(4, 3, 2, 16),
                             bias.reshape(4, 3, 2))
    assert split.shape == (2, 5, 4, 3, 2)
    assert same(split.reshape(2, 5, 24), want)
    assert same(ops.linear_minor(x, w.T), ops.linear(x, w))


def test_conv_pool():
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F
    x, w = rnd(2, 3, 8, 8), rnd(4, 3, 3, 3, seed=1)
    ref = F.conv2d(torch.tensor(x), torch.tensor(w), stride=1, padding=1).numpy()
    close(ops.conv2d(x, w, stride=1, padding=1), ref, tol=1e-4)
    bias = rnd(4, seed=2)
    ref_b = F.conv2d(torch.tensor(x), torch.tensor(w),
                     torch.tensor(bias), stride=2, padding=0).numpy()
    close(ops.conv2d_add_bias(x, w, bias, stride=2, padding=0), ref_b, tol=1e-4)
    ref_mp = F.max_pool2d(torch.tensor(x), 2, 2).numpy()
    close(ops.max_pool2d(x, 2, 2), ref_mp)
    ref_ap = F.avg_pool2d(torch.tensor(x), 2, 2).numpy()
    close(ops.avg_pool2d(x, 2, 2), ref_ap)


def test_norms():
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F
    x = rnd(4, 3, 5, 5)
    scale, bias = rnd(3, seed=1), rnd(3, seed=2)
    y, rm, rv = ops.batch_norm(x, scale, bias, np.zeros(3, np.float32),
                               np.ones(3, np.float32), train=True)
    ref = F.batch_norm(torch.tensor(x), None, None, torch.tensor(scale),
                       torch.tensor(bias), training=True).numpy()
    close(y, ref, tol=1e-4)
    x2 = rnd(4, 6, seed=3)
    s2, b2 = rnd(6, seed=4), rnd(6, seed=5)
    ref_ln = F.layer_norm(torch.tensor(x2), (6,), torch.tensor(s2),
                          torch.tensor(b2)).numpy()
    close(ops.layer_norm(x2, s2, b2), ref_ln, tol=1e-4)
    ref_in = F.instance_norm(torch.tensor(x)).numpy()
    close(ops.instance_norm2d(x), ref_in, tol=1e-3)


def test_activations_losses():
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F
    x = rnd(4, 7)
    close(ops.relu(x), np.maximum(x, 0))
    close(ops.leaky_relu(x, 0.1), np.where(x >= 0, x, 0.1 * x))
    close(ops.sigmoid(x), 1 / (1 + np.exp(-x)), tol=1e-5)
    close(ops.softmax(x), F.softmax(torch.tensor(x), dim=-1).numpy(), tol=1e-5)
    close(ops.log_softmax(x),
          F.log_softmax(torch.tensor(x), dim=-1).numpy(), tol=1e-5)
    labels = np.random.default_rng(0).integers(0, 7, size=(4,))
    ref_ce = F.cross_entropy(torch.tensor(x), torch.tensor(labels),
                             reduction="none").numpy()
    close(ops.softmax_cross_entropy_sparse(x, labels), ref_ce, tol=1e-5)
    onehot = np.eye(7, dtype=np.float32)[labels]
    close(ops.softmax_cross_entropy(x, onehot), ref_ce, tol=1e-5)
    logits = rnd(4, seed=9)
    tgt = (rnd(4, seed=10) > 0).astype(np.float32)
    ref_bce = F.binary_cross_entropy_with_logits(
        torch.tensor(logits), torch.tensor(tgt), reduction="none").numpy()
    close(ops.binary_cross_entropy_with_logits(logits, tgt), ref_bce, tol=1e-5)


def test_shape_ops():
    x = rnd(4, 6)
    close(ops.reshape(x, (2, 12)), x.reshape(2, 12))
    close(ops.transpose(x), x.T)
    close(ops.concat(x, x, axis=1), np.concatenate([x, x], 1))
    parts = ops.split(x, 2, axis=0)
    close(parts[0], x[:2])
    close(ops.slice_(x, (1, 2), (2, 3)), x[1:3, 2:5])
    y = rnd(2, 3, seed=1)
    sa = ops.slice_assign(x.copy(), y, (1, 2))
    ref = x.copy(); ref[1:3, 2:5] = y
    close(sa, ref)
    close(ops.pad(x, ((1, 1), (0, 2))), np.pad(x, ((1, 1), (0, 2))))
    close(ops.tile(x, (2, 1)), np.tile(x, (2, 1)))
    close(ops.roll(x, 2, axis=0), np.roll(x, 2, 0))
    close(ops.broadcast_shape(x[:, :1], (4, 6)), np.broadcast_to(x[:, :1], (4, 6)))
    idx = np.array([2, 0, 1])
    close(ops.gather(x, idx, axis=1), x[:, idx])
    close(ops.one_hot(idx, 4), np.eye(4, dtype=np.float32)[idx])
    close(ops.cumsum(x, axis=1), np.cumsum(x, 1))
    close(ops.tril(x), np.tril(x))
    tl = ops.tril_lookup(np.arange(9).reshape(3, 3).astype(np.float32))
    close(tl, np.array([0, 3, 4, 6, 7, 8], np.float32))


def test_scatter_gather_elements():
    x = rnd(3, 5)
    idx = np.random.default_rng(1).integers(0, 5, size=(3, 5))
    close(ops.gather_elements(x, idx, axis=1),
          np.take_along_axis(x, idx, axis=1))
    upd = rnd(3, 5, seed=2)
    ref = x.copy()
    np.put_along_axis(ref, idx, upd, axis=1)
    # duplicate indices: numpy keeps last write; our scatter uses .set which
    # also keeps one write — compare only where indices are unique per row
    out = np.asarray(ops.scatter(x, idx, upd, axis=1))
    for r in range(3):
        uniq, cnt = np.unique(idx[r], return_counts=True)
        for c in uniq[cnt == 1]:
            cols = np.where(idx[r] == c)[0]
            assert np.allclose(out[r, c], upd[r, cols[-1]])


def test_reductions_topk_unique():
    x = rnd(4, 6)
    close(ops.reduce_sum(x, 1), x.sum(1))
    close(ops.reduce_mean(x, (0, 1)), x.mean())
    close(ops.reduce_max(x, 0), x.max(0))
    close(ops.reduce_norm2(x, 1), np.sqrt((x * x).sum(1)))
    close(ops.reduce_sum_axis_zero(x), x.sum(0))
    close(ops.argmax(x, 1), x.argmax(1))
    v, i = ops.topk(x, 3)
    ref_i = np.argsort(-x, 1)[:, :3]
    close(i, ref_i)
    close(v, np.take_along_axis(x, ref_i, 1))
    ints = np.array([3, 1, 3, 2, 1, 9])
    u, inv = ops.unique(ints, size=6, fill_value=0)
    assert set(np.asarray(u)[:4].tolist()) >= {1, 2, 3, 9}
    close(np.asarray(u)[inv], ints)


def test_embedding_and_indexed_slices():
    table = rnd(10, 4)
    idx = np.array([[1, 3], [9, 1]])
    close(ops.embedding_lookup(table, idx), table[idx])
    # out-of-range → zeros (reference bounds-check behavior)
    oob = np.array([0, 100, -1])
    out = np.asarray(ops.embedding_lookup(table, oob))
    close(out[0], table[0])
    assert np.all(out[1] == 0) and np.all(out[2] == 0)

    g = rnd(2, 2, 4, seed=3)
    sl = ops.take_grad_indexed(jnp.asarray(idx), jnp.asarray(g), 10)
    dense = np.zeros((10, 4), np.float32)
    np.add.at(dense, idx.reshape(-1), g.reshape(-1, 4))
    close(sl.to_dense(), dense, tol=1e-5)
    ded = sl.deduplicate()
    close(ded.to_dense(), dense, tol=1e-5)
    close(ops.assign_with_indexed_slices(jnp.zeros((10, 4)), sl, add=True),
          dense, tol=1e-5)


def test_quantize_roundtrip():
    x = rnd(6, 8)
    q, scale = ops.quantize(x, bits=8)
    deq = np.asarray(ops.dequantize(q, scale))
    assert np.max(np.abs(deq - x)) < float(scale) * 1.01
    qt, s = ops.quantize(x, bits=8)
    idx = np.array([0, 3, 5])
    close(ops.quantize_embedding_lookup(qt, s, idx),
          np.asarray(ops.dequantize(qt, s))[idx], tol=1e-6)


def test_interpolate():
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F
    x = rnd(1, 2, 4, 4)
    ref = F.interpolate(torch.tensor(x), size=(8, 8), mode="bilinear",
                        align_corners=False).numpy()
    close(ops.interpolate(x, size=(8, 8)), ref, tol=1e-4)


def test_dropout():
    x = np.ones((1000,), np.float32)
    key = jax.random.PRNGKey(0)
    y = np.asarray(ops.dropout(x, 0.5, key, train=True))
    assert 0.3 < (y == 0).mean() < 0.7
    kept = y[y != 0]
    close(kept, np.full_like(kept, 2.0))
    close(ops.dropout(x, 0.5, key, train=False), x)
    # same key → same mask (reproducible)
    y2 = np.asarray(ops.dropout(x, 0.5, key, train=True))
    close(y, y2)
