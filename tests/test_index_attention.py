"""Selection by ROWS (``ops/attention.py``: ``pool_index_keys``,
``select_groups``, ``chosen_rows``, ``chosen_rows_attention``): pooled keys
and the open group across call edges, the choice against a dense stable
argsort with ties, and the gathered attention against masked dense
attention; the rows brought up to whole tiles against the rows as they are;
and the kernel that fetches the chosen groups itself
(``ops/pallas_kernels/chosen_groups.py``, interpret mode) against the
gathered form."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import ops
from hetu_tpu.ops.pallas_kernels.chosen_groups import chosen_groups_attention

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


# ---- the chosen groups fetched inside the call that attends them ----

def gathered(q, view, idx, n, pos, *, scale, tile=1):
    """The oracle: XLA gathers the rows out of view [B, T, C]."""
    rows, valid = ops.chosen_rows(idx, n, pos, pool=POOL, tile=tile)
    latents = jax.vmap(lambda v, r: v[r])(
        view, jnp.clip(rows, 0, view.shape[1] - 1))
    return ops.chosen_rows_attention(q, latents, valid, scale=scale)


def a_choice(key, pos, topk, groups):
    """``select_groups``' result for queries at ``pos`` [B, S]: distinct
    complete groups in no order, anything behind the first ``n``."""
    b, s = pos.shape
    order = jnp.argsort(jax.random.uniform(key, (b, s, groups)), -1)
    complete = (pos + 1) // POOL
    # a complete group first: the ranks of the groups under ``complete``
    rank = jnp.argsort(jnp.argsort(
        jnp.where(order < complete[..., None], 0, 1), -1, stable=True), -1)
    idx = jnp.zeros_like(order).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        rank].set(order)[..., :topk]
    return idx.astype(jnp.int32), jnp.minimum(complete, topk).astype(
        jnp.int32)


KERNEL_CASES = {
    # positions of one sequence's queries; view rows; topk
    "fewer complete groups than topk": ([5, 9, 14, 18], 64, 8),
    "an open group of 0, 1 and pool - 1 rows": ([47, 48, 50, 43], 64, 8),
    "a query that closes a group": ([39, 43, 63, 59], 64, 8),
    "a query with no valid row": ([-1, 40, -1, 2], 64, 8),
    "a view no multiple of pool": ([61, 44, 30, 57], 62, 8),
    "more groups chosen than a 128-row run holds": ([200, 150, 255, 131],
                                                    256, 40),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernel_reads_what_the_gather_reads(case, dtype):
    """Two sequences a call (the second's positions the first's reversed),
    the view by group padded to whole groups as the model lays it."""
    at, t, topk = KERNEL_CASES[case]
    key = jax.random.PRNGKey(len(case))
    nh, c = 4, 32
    pos = jnp.array([at, at[::-1]], jnp.int32)
    b, s = pos.shape
    q = jax.random.normal(key, (b, s, nh, c)).astype(dtype)
    view = jax.random.normal(jax.random.fold_in(key, 1),
                             (b, t, c)).astype(dtype)
    idx, n = a_choice(jax.random.fold_in(key, 2), pos, topk, -(-t // POOL))
    by_group = jnp.pad(view, ((0, 0), (0, -t % POOL), (0, 0))).reshape(
        b, -1, POOL, c)
    with jax.default_matmul_precision("highest"):
        want = gathered(q, view, idx, n, pos, scale=c ** -0.5)
        got = chosen_groups_attention(q, by_group, idx, n, pos, pool=POOL,
                                      scale=c ** -0.5)
    assert got.shape == want.shape and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if dtype == jnp.float32 else 2e-2)
    if case == "a query with no valid row":
        assert not np.asarray(got, np.float32)[0, 0].any()
        assert not np.asarray(got, np.float32)[1, 3].any()


def test_the_kernel_takes_an_odd_count_of_queries():
    """A grid step is two queries: five take a sixth, which is dropped."""
    key = jax.random.PRNGKey(5)
    pos = jnp.array([[7, 20, 33, 46, 63]], jnp.int32)
    q = jax.random.normal(key, (1, 5, 4, 32))
    view = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 32))
    idx, n = a_choice(jax.random.fold_in(key, 2), pos, 8, 16)
    with jax.default_matmul_precision("highest"):
        want = gathered(q, view, idx, n, pos, scale=0.2)
        got = chosen_groups_attention(q, view.reshape(1, 16, POOL, 32), idx,
                                      n, pos, pool=POOL, scale=0.2)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_kernel_refuses_a_view_by_other_groups():
    with pytest.raises(ValueError, match="groups of 8"):
        chosen_groups_attention(
            jnp.zeros((1, 2, 4, 32)), jnp.zeros((1, 8, 8, 32)),
            jnp.zeros((1, 2, 4), jnp.int32), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 2), jnp.int32), pool=POOL, scale=1.0)


@pytest.mark.parametrize("topk", [3, 5, 8, 10])
def test_rows_brought_up_to_whole_tiles_read_the_same(topk):
    """``chosen_rows(tile=16)``: the rows and their validity are the
    unpadded ones to the bit, those behind them are not valid, and the
    attention over them is the unpadded one in float32 but for the order of
    its sums (a zero probability a padded row: where the padding is none,
    ``topk`` 3, to the bit; XLA's CPU sums 36 and 48 terms in different
    runs of lanes, so 4e-7 of a value of order one elsewhere)."""
    key = jax.random.PRNGKey(topk)
    b, s, nh, c, t = 2, 24, 3, 16, 96
    pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    q = jax.random.normal(key, (b, s, nh, c))
    view = jax.random.normal(jax.random.fold_in(key, 1), (b, t, c))
    idx, n = a_choice(jax.random.fold_in(key, 2), pos, topk, t // POOL)
    rows, valid = ops.chosen_rows(idx, n, pos, pool=POOL)
    padded, padded_valid = ops.chosen_rows(idx, n, pos, pool=POOL, tile=16)
    m = (topk + 1) * POOL
    assert padded.shape[-1] == -(-m // 16) * 16
    np.testing.assert_array_equal(padded[..., :m], rows)
    np.testing.assert_array_equal(padded_valid[..., :m], valid)
    assert not np.asarray(padded_valid[..., m:]).any()
    got = gathered(q, view, idx, n, pos, scale=0.25, tile=16)
    want = gathered(q, view, idx, n, pos, scale=0.25)
    if m % 16 == 0:
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
