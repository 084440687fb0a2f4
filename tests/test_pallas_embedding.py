"""Pallas embedding gather / scatter-add / top-k gating kernels vs the XLA
oracles (interpret mode on CPU; chip_smoke.py runs them compiled).

Reference kernels replaced: src/ops/EmbeddingLookUp.cu (+ its scatter-add
gradient) and src/ops/TopKIdx.cu — SURVEY §2.2 row 28's named Pallas gaps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import ops
from hetu_tpu.ops.pallas_kernels.embedding import (
    embedding_gather, embedding_scatter_add, topk_gating,
)


def test_gather_matches_oracle():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 64, 37), jnp.int32)
    got = embedding_gather(table, ids, interpret=True)
    want = ops.embedding_lookup(table, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_gather_out_of_range_gives_zero_rows():
    table = jnp.ones((8, 128), jnp.float32)
    ids = jnp.asarray([-1, 0, 7, 8, 100], jnp.int32)
    got = np.asarray(embedding_gather(table, ids, interpret=True))
    np.testing.assert_allclose(got[[0, 3, 4]], 0.0)
    np.testing.assert_allclose(got[[1, 2]], 1.0)


def test_scatter_add_accumulates_duplicates():
    rng = np.random.default_rng(1)
    # nonconsecutive duplicates on purpose (the pipeline-hazard case)
    ids = jnp.asarray([3, 7, 3, 0, 7, 3, -1, 9], jnp.int32)
    grads = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    got = embedding_scatter_add(grads, ids, 12, interpret=True)
    want = np.zeros((12, 128), np.float32)
    for i, r in enumerate(np.asarray(ids)):
        if 0 <= r < 12:
            want[r] += np.asarray(grads)[i]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_scatter_is_gather_transpose():
    """<scatter(g, ids), table> == <g, gather(table, ids)> — the vjp
    contract that makes these a forward/backward pair."""
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 32, 16), jnp.int32)
    g = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
    lhs = jnp.vdot(embedding_scatter_add(g, ids, 32, interpret=True), table)
    rhs = jnp.vdot(g, embedding_gather(table, ids, interpret=True))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_topk_gating_matches_lax(k):
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((512, 16)), jnp.float32)
    gates, idx = topk_gating(logits, k, kernel=True)
    want_g, want_i = ops.top_k_idx_gate(logits, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_g),
                               rtol=1e-5)
    # the XLA form (kernel=False) must agree with the kernel
    xg, xi = topk_gating(logits, k, kernel=False)
    np.testing.assert_array_equal(np.asarray(xi), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(xg), np.asarray(gates), rtol=1e-5)


def test_topk_gating_ties_resolve_low_index():
    logits = jnp.asarray([[1.0, 5.0, 5.0, 0.0]], jnp.float32)
    _, idx = topk_gating(logits, 2, block_tokens=1, kernel=True)
    assert idx.tolist() == [[1, 2]]


def test_topk_rejects_indivisible_block():
    with pytest.raises(ValueError, match="divisible"):
        topk_gating(jnp.zeros((10, 8)), 2, block_tokens=4, kernel=False)


def test_topk_gating_grad_matches_lax():
    """custom-vjp of the fused gate == autodiff through lax.top_k+softmax."""
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    g_out = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)

    def f_pallas(x):
        gates, _ = topk_gating(x, 3, kernel=True)
        return jnp.sum(gates * g_out)

    def f_lax(x):
        gates, _ = ops.top_k_idx_gate(x, 3)
        return jnp.sum(gates * g_out)

    np.testing.assert_allclose(np.asarray(jax.grad(f_pallas)(logits)),
                               np.asarray(jax.grad(f_lax)(logits)),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("kernel", [True, False])
def test_routed_gather_vjp_and_invalid_ids(kernel):
    """routed_gather: fwd zero-rows for -1/oob, bwd scatter-adds dups and
    drops invalid — matches a dense one-hot oracle, through the Pallas
    kernels (interpret mode here) and through the XLA form alike."""
    rng = np.random.default_rng(8)
    table = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    ids = jnp.asarray([3, 3, -1, 15, 0, 99, 7, 3], jnp.int32)
    from hetu_tpu.ops.pallas_kernels import routed_gather

    out = routed_gather(table, ids, kernel=kernel)
    valid = (np.asarray(ids) >= 0) & (np.asarray(ids) < 16)
    want = np.where(valid[:, None],
                    np.asarray(table)[np.clip(np.asarray(ids), 0, 15)], 0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)

    g = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    dt = jax.grad(lambda t: jnp.sum(routed_gather(t, ids, kernel=kernel)
                                    * g))(table)
    want_dt = np.zeros((16, 8), np.float32)
    for i, r in enumerate(np.asarray(ids)):
        if 0 <= r < 16:
            want_dt[r] += np.asarray(g)[i]
    np.testing.assert_allclose(np.asarray(dt), want_dt, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_group_kernels_off_the_tile_grid(dtype):
    """Row counts, id counts and ids that do not line up with the 8/16-row
    tile groups the kernels move: a table whose last group is partial, an
    id count that is not a multiple of the group, negative and out-of-range
    ids (which sort to both ends of the scatter's sentinel runs)."""
    rng = np.random.default_rng(11)
    V, D, N = 37, 24, 21
    table = jnp.asarray(rng.standard_normal((V, D)), dtype)
    ids = jnp.asarray(rng.integers(-3, V + 3, N), jnp.int32).at[0].set(V - 1)
    valid = np.asarray((ids >= 0) & (ids < V))
    safe = np.clip(np.asarray(ids), 0, V - 1)
    got = embedding_gather(table, ids, interpret=True)
    want = np.where(valid[:, None], np.asarray(table, np.float32)[safe], 0)
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)

    grads = jnp.asarray(rng.standard_normal((N, D)), dtype)
    got = embedding_scatter_add(grads, ids, V, interpret=True)
    want = np.zeros((V, D), np.float32)
    np.add.at(want, safe[valid], np.asarray(grads, np.float32)[valid])
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=tol,
                               atol=tol)
