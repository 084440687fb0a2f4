"""Block-sparse attention chosen by compressed keys (``ops/attention.py``:
``compress_keys``, ``select_blocks``, ``masked_block_attention``,
``chosen_pages_attention``; the flash forward kernel's SPARSE CHUNK call,
``flash_sparse_chunk_attention``) against the plain reference's statement
(``benchmarks/reference/minicpm_sala.py``) and against each other: the
compressed keys are the windows' means wherever a span starts; the chosen
blocks are the reference's, forced blocks, visibility and the short
sequences' "every block" included; the masked walk over a long view is the
masked softmax, and the kernel's sparse chunk call (interpret mode) is that
walk; a decode round's walk over the chosen PAGES (the paged kernel in
interpret mode, and the gathered view) reads what the mask reads."""

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


# ---- the flash forward kernel's sparse chunk call (interpret mode) ----

def _sparse_chunk_case(heads=8, kv_heads=2, d=16, s_c=32, rows=128,
                       starts=(0, 40), block=8, tile_q=16, tile_k=32,
                       dtype=jnp.float32, seed=0, empty=(), every=(),
                       every_rows=0):
    """q [B, heads, S_c, d], a time-major view of ``rows`` rows whose rows
    past each sequence's chunk hold garbage, ``starts`` and a choice [B, S_c,
    kv_heads, rows / block]: a third of the blocks, a query's own among
    them; none of the K TILES ``empty`` (the own block apart); every block
    for the sequences ``every`` and a sequence's first ``every_rows``
    queries."""
    b = len(starts)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, heads, s_c, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, rows, kv_heads, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, rows, kv_heads, d)).astype(dtype)
    starts = jnp.asarray(starts, jnp.int32)
    pos = starts[:, None] + jnp.arange(s_c)[None]
    unseen = (jnp.arange(rows)[None] >= (starts + s_c)[:, None])[
        :, :, None, None]
    blocks = jnp.arange(rows // block)
    chosen = jax.random.bernoulli(ks[3], 0.35,
                                  (b, s_c, kv_heads, rows // block))
    for tile in empty:
        chosen &= (blocks // (tile_k // block) != tile)
    chosen |= (pos // block)[:, :, None, None] == blocks
    for i in every:
        chosen = chosen.at[i].set(True)
    chosen = chosen.at[:, :every_rows].set(True)
    return (q, jnp.where(unseen, 3e3, k), jnp.where(unseen, -7e3, v),
            starts, pos, chosen)


SPARSE_CHUNK_CASES = {
    # two KV heads x four query heads each, two sequences: one from position
    # 0, one whose chunk starts inside a K tile of 32
    "start0_and_inside_a_tile": dict(),
    "past_several_tiles": dict(starts=(96, 70), rows=160),
    "one_query_head_a_kv_head": dict(heads=2, starts=(13,)),
    # 136 rows = 4.25 K tiles: the view is padded to 5, its choice with it
    "view_no_whole_tiles": dict(rows=136, starts=(100, 5)),
    # nobody chose a block of K tile 1 (tile 0, the forced block's, lives)
    "a_tile_nobody_chose": dict(starts=(64, 90), empty=(1,)),
    # init_blocks 0: the FIRST tile of every walk is empty, and m still
    # holds its initial value when the first live score arrives
    "the_first_tile_empty": dict(starts=(64, 40), empty=(0,)),
    "the_first_two_tiles_empty": dict(starts=(96,), rows=160, empty=(0, 1)),
    # a prompt under the dense length beside one over it, and queries that
    # read everything beside queries that choose within one Q tile
    "every_beside_choosing": dict(starts=(40, 8), every=(1,)),
    "every_rows_in_a_tile": dict(starts=(50,), every_rows=5, empty=(0,)),
    # the cell's pair: blocks of 64 under K tiles of 512 (8 bits a word)
    "b64_under_512": dict(heads=4, s_c=64, rows=1600, starts=(700, 1500),
                          block=64, tile_q=32, tile_k=512, empty=(1,)),
    # one Q tile, 32 blocks a K tile: every bit of a word
    "32_blocks_a_tile": dict(heads=4, s_c=16, rows=512, starts=(300,),
                             block=8, tile_q=16, tile_k=256),
    "bf16": dict(starts=(45, 64), dtype=jnp.bfloat16, empty=(1,)),
}


@pytest.mark.parametrize("case", list(SPARSE_CHUNK_CASES))
def test_the_sparse_chunk_call_is_the_masked_walk(case):
    """The kernel fed a chunk and its queries' choice against the XLA walk
    under the same mask (``_attend_blocks(chosen=...)``, what runs off a
    TPU)."""
    from hetu_tpu.ops.attention import _attend_blocks
    from hetu_tpu.ops.pallas_kernels.flash_attention import (
        flash_sparse_chunk_attention,
    )

    kw = dict(SPARSE_CHUNK_CASES[case])
    q, k, v, starts, pos, chosen = _sparse_chunk_case(**kw)
    block = kw.get("block", 8)
    got = flash_sparse_chunk_attention(
        q, k, v, starts, chosen, block=block, block_q=kw.get("tile_q", 16),
        block_k=kw.get("tile_k", 32))
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _attend_blocks(q, k, v, pos, q.shape[-1] ** -0.5, 4 * block,
                          chosen=chosen, block_size=block)
    tol = dict(rtol=2e-2, atol=2e-2) if q.dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_a_query_that_chose_nothing_it_sees_reads_zero():
    """No block is forced in the kernel: a query whose choice holds no
    visible block reads 0, not an average of what its row's m and l held."""
    from hetu_tpu.ops.pallas_kernels.flash_attention import (
        flash_sparse_chunk_attention,
    )

    q, k, v, starts, pos, chosen = _sparse_chunk_case(starts=(40,))
    chosen = chosen.at[0, 7].set(False).at[0, 7, :, -1].set(True)
    got = flash_sparse_chunk_attention(q, k, v, starts, chosen, block=8,
                                       block_q=16, block_k=32)
    assert not np.asarray(got[0, :, 7]).any()
    assert np.abs(np.asarray(got[0, :, 6])).max() > 0
    with pytest.raises(ValueError):        # 12 positions divide no K tile
        flash_sparse_chunk_attention(q, k[:, :120], v[:, :120], starts,
                                     chosen[..., :10], block=12,
                                     block_q=16, block_k=32)


def test_who_walks_a_sparse_layers_view_is_decided_on_what_is_observed(
        monkeypatch):
    """``ops.sparse_kernel_why``: a long view whose blocks divide
    ``KEY_BLOCK``, on a TPU backend with no mesh in context, takes the
    kernel's sparse chunk call; ``sparse.plan`` says ``kernel`` or
    ``masked`` and why; both give the masked softmax."""
    att = sys.modules["hetu_tpu.ops.attention"]
    monkeypatch.setattr(att, "KEY_BLOCK", 32)
    seen = []
    monkeypatch.setattr(att.trace, "instant",
                        lambda name, attrs=None, cat="hetu":
                        seen.append((name, attrs)))
    q, k, v, _, pos, chosen = _sparse_chunk_case(
        heads=HEADS, d=D, starts=(40, 96), rows=128)
    assert ops.sparse_kernel_why(32, 128, 8) == "backend"
    walked = ops.masked_block_attention(q, k, v, pos, chosen, block=8)
    monkeypatch.setattr(att, "_default_backend_is_tpu", lambda: True)
    assert ops.sparse_kernel_why(32, 128, 8) == ""
    assert ops.sparse_kernel_why(32, 32, 8) == "short"
    assert ops.sparse_kernel_why(32, 128, 12) == "blocks"
    assert ops.sparse_kernel_why(32, 100, 8) == "blocks"
    assert ops.sparse_kernel_why(1001, 2048, 8) == "ragged"
    assert ops.sparse_kernel_why(100, 2048, 8) == ""
    from hetu_tpu.parallel.mesh import make_mesh
    with jax.set_mesh(make_mesh(tp=2)):
        assert ops.sparse_kernel_why(32, 128, 8) == "sharded"
    kernel = ops.masked_block_attention(q, k, v, pos, chosen, block=8)
    np.testing.assert_allclose(kernel, walked, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        kernel, masked_softmax(q, k, v, pos, chosen), rtol=2e-4, atol=2e-5)
    plans = [a for n, a in seen if n == "sparse.plan"]
    assert [(p["form"], p["why"]) for p in plans] == [
        ("masked", "backend"), ("kernel", "")]
    assert any(n == "flash.plan" and a["kernel"] == "fwd_sparse_chunk"
               and a["block"] == 8 for n, a in seen)


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
