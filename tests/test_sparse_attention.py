"""Block-sparse attention chosen by compressed keys (``ops/attention.py``:
``compress_keys``, ``select_blocks``, ``masked_block_attention``,
``chosen_pages_attention``) against the plain reference's statement
(``benchmarks/reference/minicpm_sala.py``) and against each other: the
compressed keys are the windows' means wherever a span starts; the chosen
blocks are the reference's, forced blocks, visibility and the short
sequences' "every block" included; the masked walk over a long view is the
masked softmax; a decode round's walk over the chosen PAGES (the paged
kernel in interpret mode, and the gathered view) reads what the mask
reads."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import minicpm_sala as ref  # noqa: E402
from hetu_tpu import ops  # noqa: E402
from hetu_tpu.serve.kv_cache import PagedLayers  # noqa: E402

HOW = dict(stride=2, kernel=4, block=8, topk=6, init_blocks=1, local=8)
DIMS = dict(HOW, head_dim=8, dense_len=48)
HEADS, G, D = 4, 2, 8


def drawn(t: int, seed: int = 0, b: int = 2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = 2.0 * jax.random.normal(ks[0], (b, HEADS, t, D), jnp.float32)
    k = 2.0 * jax.random.normal(ks[1], (b, t, G, D), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, G, D), jnp.float32)
    return q, k, v


def all_windows(k):
    """Every window of a whole sequence, padded as the dense forward does."""
    pad = HOW["kernel"] - HOW["stride"]
    return ops.compress_keys(jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                             stride=HOW["stride"], kernel=HOW["kernel"])


@pytest.mark.parametrize("first,rows", [(0, 16), (6, 12), (14, 4), (10, 22)])
def test_compress_keys_is_the_windows_mean_wherever_the_span_starts(first,
                                                                    rows):
    """A span that starts inside a page (8) or a chunk (16): its windows are
    the sequence's own."""
    _, k, _ = drawn(40, seed=first)
    got = ops.compress_keys(k[:, first:first + rows], stride=2, kernel=4)
    want = np.stack([np.asarray(k[:, first + 2 * i:first + 2 * i + 4]).mean(1)
                     for i in range(rows // 2 - 1)], 1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        ops.compress_keys(k[:, :7], stride=2, kernel=4)


@pytest.mark.parametrize("t,seed", [(96, 0), (64, 1), (200, 2)])
def test_select_blocks_is_the_references_choice(t, seed):
    q, k, _ = drawn(t, seed)
    comp = all_windows(k)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    idx, n = ops.select_blocks(q, comp, pos, **HOW)
    got = np.asarray(ops.chosen_mask(idx, t // 8))          # [B, S, g, nb]
    whole = (t - 4) // 2 + 1           # the reference holds whole windows
    want = np.asarray(ref.chosen_blocks(
        jnp.moveaxis(q, 1, 2), comp[:, :whole], jnp.arange(t), DIMS, t // 8))
    np.testing.assert_array_equal(got, np.moveaxis(want, 1, 2))
    np.testing.assert_array_equal(n, np.minimum(6, np.arange(t) // 8 + 1)
                                  [None].repeat(2, 0))
    # the forced blocks are among the chosen, a hidden block never is
    for s in (0, 7, 8, 40, t - 1):
        mine = got[0, s, 0]
        assert mine[0] and mine[s // 8] and mine[max(s - 7, 0) // 8]
        assert not mine[s // 8 + 1:].any() and mine.sum() == n[0, s]
    # ascending, the missing ones behind
    assert (np.diff(np.asarray(idx), axis=-1) >= 0).all()
    assert (np.asarray(idx)[0, 3, 0] == [0] + [t // 8] * 5).all()


@pytest.mark.parametrize("windows,first", [(8, 0), (16, 0), (12, 40),
                                           (20, 90)])
def test_a_chunk_walks_the_windows_it_can_see(monkeypatch, windows, first):
    """A chunk's queries walk the compressed keys ``SELECT_WINDOWS`` at a
    time (8, 12, 16 and 20 of 64: a last block that is short, blocks of 2,
    3, 4 and 5 score blocks, a window before each block read twice), twice,
    and no further than its last position sees: the choice is the one-pass
    form's, for a chunk at the sequence's start, in its middle and one that
    ends short of the table."""
    q, k, _ = drawn(128, seed=4)
    comp = all_windows(k)
    last = min(first + 38, 128)
    pos = jnp.broadcast_to(jnp.arange(first, last)[None], (2, last - first))
    q = q[:, :, first:last]
    whole, n = ops.select_blocks(q, comp, pos, **HOW)
    monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"],
                        "SELECT_WINDOWS", windows)
    walked, n2 = ops.select_blocks(q, comp, pos, **HOW)
    np.testing.assert_array_equal(walked, whole)
    np.testing.assert_array_equal(n, n2)


def masked_softmax(q, k, v, pos, chosen):
    """The statement, whole: softmax over the chosen blocks' positions."""
    t = k.shape[1]
    seen = (np.arange(t)[None, None] <= np.asarray(pos)[:, :, None])[
        :, None] & np.moveaxis(np.repeat(np.asarray(chosen), 8, -1), 2, 1)
    s = np.einsum("bgrsd,btgd->bgrst", np.asarray(q).reshape(
        q.shape[0], G, HEADS // G, -1, D), np.asarray(k)) * D ** -0.5
    s = np.where(seen[:, :, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bgrst,btgd->bgrsd", p, np.asarray(v)).reshape(
        q.shape[0], HEADS, -1, D)


@pytest.mark.parametrize("key_block", [None, 16, 32])
def test_the_masked_walk_is_the_masked_softmax(monkeypatch, key_block):
    """Over a view longer than ``KEY_BLOCK`` the keys are walked in blocks,
    the last one moved back to end with the view (72 = 4.5 x 16)."""
    t = 72
    q, k, v = drawn(t, seed=5)
    q, pos = q[:, :, 40:], jnp.broadcast_to(jnp.arange(40, t)[None], (2, 32))
    idx, _ = ops.select_blocks(q, all_windows(k), pos, **HOW)
    chosen = ops.chosen_mask(idx, t // 8)
    if key_block:
        monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"],
                            "KEY_BLOCK", key_block)
    got = ops.masked_block_attention(q, k, v, pos, chosen, block=8)
    np.testing.assert_allclose(got, masked_softmax(q, k, v, pos, chosen),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True])
def test_a_round_walks_the_chosen_pages_and_reads_what_the_mask_reads(
        monkeypatch, kernel):
    """Three sequences of one round over a paged pool whose pages are the
    blocks, pages in a scrambled order: one long (reads its 6 chosen pages),
    one under the dense length (reads every page it holds), one long whose
    newest token opens a page.  The paged kernel (interpret mode) and the
    gathered view agree with the masked softmax over the whole sequence."""
    if kernel:
        monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"],
                            "_default_backend_is_tpu", lambda: True)
    lens = np.array([93, 29, 64])                  # newest token's position
    n_pg, n_pages = 16, 64
    rng = np.random.default_rng(0)
    tables = rng.permutation(np.arange(1, n_pages))[:3 * n_pg].reshape(
        3, n_pg).astype(np.int32)
    t = n_pg * 8
    q, k, v = drawn(t, seed=6, b=3)
    k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    one = jnp.stack([q[i, :, n] for i, n in enumerate(lens)])[:, :, None] \
        .astype(jnp.bfloat16)
    pool = lambda rows: jnp.zeros(                          # noqa: E731
        (2, n_pages, 8, G * D), jnp.bfloat16).at[1, tables].set(
        rows.reshape(3, n_pg, 8, G * D))
    z = jnp.zeros((3, 1), jnp.int32)
    kc = PagedLayers(pool(k), jnp.asarray(tables), z, z, (G, D))
    vc = PagedLayers(pool(v), jnp.asarray(tables), z, z, (G, D))
    pos = jnp.asarray(lens)[:, None]
    comp = all_windows(k.astype(jnp.float32))
    idx, n = ops.select_blocks(one, comp, pos, **HOW)
    sparse = jnp.asarray(lens + 1 >= 48)
    got = ops.chosen_pages_attention(
        one, kc, vc, 1, idx[:, 0], n[:, 0], jnp.asarray(lens), sparse,
        dense_blocks=6)
    chosen = ops.chosen_mask(idx, n_pg) | ~sparse[:, None, None, None]
    want = masked_softmax(one.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), pos, chosen)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0.03,
                               atol=0.03)
    assert np.asarray(n)[:, 0].tolist() == [6, 4, 6]
