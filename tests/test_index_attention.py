"""Selection by ROWS (``ops/attention.py``: ``pool_index_keys``,
``select_groups``, ``chosen_rows``, ``chosen_rows_attention``): pooled keys
and the open group across call edges, the choice against a dense stable
argsort with ties, and the gathered attention against masked dense
attention."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import ops

POOL = 4


def pooled_whole(keys):
    """The means of the complete groups of keys [B, S, D], and the open
    group's sum."""
    b, s, d = keys.shape
    full = s // POOL
    return keys[:, :full * POOL].reshape(b, full, POOL, d).mean(2), \
        keys[:, full * POOL:].sum(1)


@pytest.mark.parametrize("cuts", [
    (23,),                    # one call, an open group of 3 left
    (10, 13),                 # an edge inside a group
    (8, 8, 7),                # edges ON group boundaries
    (1, 1, 1, 1, 1, 18),      # rounds of one row, then a chunk
    (5, 1, 1, 1, 15),         # a chunk, rounds that close a group, a chunk
])
def test_pooled_keys_across_call_edges(cuts):
    """However a sequence's keys are cut into calls (chunks, rounds), the
    groups' means and the open group's sum are the whole sequence's."""
    keys = jax.random.normal(jax.random.PRNGKey(0), (2, sum(cuts), 8))
    want, want_open = pooled_whole(keys)
    got = np.zeros_like(np.asarray(want))
    open_sum, at = jnp.zeros((2, 8)), 0
    for n in cuts:
        means, done, open_sum = ops.pool_index_keys(
            keys[:, at:at + n], open_sum, jnp.full((2,), at), pool=POOL)
        for j in np.flatnonzero(np.asarray(done[0])):
            got[:, at // POOL + j] = np.asarray(means[:, j])
        at += n
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(open_sum, want_open, atol=1e-6)


def test_pooled_keys_of_a_padded_chunk_stop_at_last():
    """A chunk padded to its bucket: rows past ``last`` complete nothing
    and are in no sum; sequences at different offsets in one call."""
    keys = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
    at = jnp.array([0, 6])
    carried = jnp.stack([jnp.zeros(8), jnp.arange(8.0)])    # 6 % 4 = 2 keys
    means, done, open_sum = ops.pool_index_keys(keys, carried, at, pool=POOL,
                                                last=9)
    # sequence 0: positions 0..9 -> groups 0, 1 done, 2 keys open
    assert done[0].tolist() == [True, True, False, False, False]
    np.testing.assert_allclose(means[0, 1], keys[0, 4:8].mean(0), atol=1e-6)
    np.testing.assert_allclose(open_sum[0], keys[0, 8:10].sum(0), atol=1e-6)
    # sequence 1: positions 6..15 -> group 1 (carried + rows 0, 1), 2, 3 done
    assert done[1].tolist() == [True, True, True, False, False]
    np.testing.assert_allclose(
        means[1, 0], (carried[1] + keys[1, :2].sum(0)) / 4, atol=1e-6)
    np.testing.assert_allclose(means[1, 2], keys[1, 6:10].mean(0), atol=1e-6)
    np.testing.assert_allclose(open_sum[1], 0.0)


def dense_choice(qi, w, kbar, pos, topk):
    """Every score at once, a stable argsort: ties to the lower group."""
    scores = jnp.einsum("bsj,bsjg->bsg", w, jax.nn.relu(
        jnp.einsum("bsjd,bgd->bsjg", qi, kbar, precision="highest")))
    complete = (pos + 1) // POOL
    scores = jnp.where(jnp.arange(kbar.shape[1]) < complete[..., None],
                       scores, -jnp.inf)
    order = jnp.argsort(-scores, axis=-1, stable=True)[..., :topk]
    return np.asarray(order), np.asarray(jnp.minimum(complete, topk))


@pytest.mark.parametrize("groups,topk,key_block", [
    (40, 8, 16),       # three key blocks, the last ragged
    (40, 8, 2048),     # one block
    (6, 8, 4),         # fewer groups than topk: padded
])
def test_select_groups_is_a_dense_argsort(groups, topk, key_block,
                                          monkeypatch):
    monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"],
                        "INDEX_KEY_BLOCK", key_block)
    key = jax.random.PRNGKey(2)
    s = groups * POOL
    qi = jax.random.normal(key, (2, s, 3, 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (2, s, 3))
    kbar = jax.random.normal(jax.random.fold_in(key, 2), (2, groups, 8))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (2, s))
    with jax.default_matmul_precision("highest"):
        idx, n = ops.select_groups(qi, w, kbar, pos, topk=topk, pool=POOL)
    want, want_n = dense_choice(qi, w, kbar, pos, topk)
    assert np.array_equal(np.asarray(n), want_n)
    idx = np.asarray(idx)
    for b in range(2):
        for t in range(s):
            m = int(want_n[b, t])
            assert idx[b, t, :m].tolist() == want[b, t, :m].tolist()


def test_select_groups_breaks_ties_to_the_lower_group():
    """Equal keys score equally to the last bit: of six tied groups the
    first four are read, in order."""
    qi = jnp.ones((1, 1, 2, 4))
    w = jnp.ones((1, 1, 2))
    kbar = jnp.concatenate([jnp.full((1, 2, 4), 2.0), jnp.ones((1, 6, 4)),
                            jnp.full((1, 2, 4), -1.0)], 1)
    idx, n = ops.select_groups(qi, w, kbar, jnp.array([[39]]), topk=6,
                               pool=POOL)
    assert int(n[0, 0]) == 6 and idx[0, 0].tolist() == [0, 1, 2, 3, 4, 5]


def test_chosen_rows_are_the_groups_and_the_tail():
    idx = jnp.array([[[3, 0, 9, 9]]])
    rows, valid = ops.chosen_rows(idx, jnp.array([[2]]), jnp.array([[21]]),
                                  pool=POOL)
    read = sorted(np.asarray(rows)[np.asarray(valid)].tolist())
    # groups 3 and 0, then the open group 5 (= 22 // 4) up to position 21
    assert read == [0, 1, 2, 3, 12, 13, 14, 15, 20, 21]
    # a query that closes a group reads no tail: its own row only if chosen
    rows, valid = ops.chosen_rows(idx, jnp.array([[2]]), jnp.array([[23]]),
                                  pool=POOL)
    assert sorted(np.asarray(rows)[np.asarray(valid)].tolist()) \
        == [0, 1, 2, 3, 12, 13, 14, 15]


def test_chosen_rows_attention_is_masked_dense_attention():
    """Gathered rows in the absorbed form against a masked softmax in the
    expanded form (every head's keys and values made from the latents)."""
    key = jax.random.PRNGKey(3)
    b, s, nh, c, d, topk = 2, 48, 3, 16, 8, 4
    lat = jax.random.normal(key, (b, s, c))
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, s, nh, d))
    kb = jax.random.normal(jax.random.fold_in(key, 2), (nh, c, d)) / 4
    vb = jax.random.normal(jax.random.fold_in(key, 3), (nh, c, d)) / 4
    idx = jax.random.randint(jax.random.fold_in(key, 4), (b, s, topk), 0, 12)
    # distinct groups a query: a choice never repeats one
    idx = (idx[..., :1] + jnp.arange(topk)) % 12
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    n = jnp.minimum((pos + 1) // POOL, topk)
    idx = jnp.where(jnp.arange(topk) < n[..., None],
                    idx % jnp.maximum((pos + 1) // POOL, 1)[..., None], 0)
    # ... and distinct after the modulo too: keep the first occurrence
    first = jnp.argmax(idx[..., None] == idx[..., None, :], -1) \
        == jnp.arange(topk)
    with jax.default_matmul_precision("highest"):
        rows, valid = ops.chosen_rows(idx, n, pos, pool=POOL)
        valid = valid & jnp.concatenate(
            [jnp.repeat(first, POOL, -1), jnp.ones((b, s, POOL), bool)], -1)
        got = ops.chosen_rows_attention(
            jnp.einsum("bshd,hcd->bshc", q, kb),
            jax.vmap(lambda v, r: v[r])(lat, jnp.clip(rows, 0, s - 1)),
            valid, scale=d ** -0.5)
        got = jnp.einsum("bshc,hcd->bshd", got, vb)
        mask = jnp.zeros((b, s, s + POOL), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
            jnp.where(valid, rows, s)].set(True)[..., :s]
        k = jnp.einsum("bsc,hcd->bshd", lat, kb)
        v = jnp.einsum("bsc,hcd->bshd", lat, vb)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k) * d ** -0.5
        want = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(
            jnp.where(mask[:, None], scores, -jnp.inf), -1), v)
    # position 0..2 of a sequence read their open group alone (never empty)
    np.testing.assert_allclose(got, want, atol=2e-5)
