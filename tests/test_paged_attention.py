"""The paged decode kernel (``ops/pallas_kernels/paged_attention.py``) in
interpret mode on the CPU: parity with the XLA composition it replaces
(``ops.attention._attend_one_query`` over the gathered view) for both cells'
head groupings at cut sizes, over ragged lengths, padded tables, duplicated
pad rows and shared pages; through a tiny ``PagedServeEngine`` with the
kernel chosen as a TPU backend chooses it: the dense oracle's tokens; and the
``paged_attn.plan`` instants that say which attention a decode program got.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import ops
from hetu_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeModel
from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.models.llama import LlamaConfig, LlamaModel
from hetu_tpu.ops.pallas_kernels.paged_attention import (
    paged_decode_attention, pages_per_step,
)
from hetu_tpu.serve import PagedServeEngine
from hetu_tpu.serve.kv_cache import PagedLayers
from paged_programs import dense_greedy, engine_greedy

ATT = sys.modules["hetu_tpu.ops.attention"]
PAGED = sys.modules["hetu_tpu.ops.pallas_kernels.paged_attention"]

# (kv heads, heads, head width, page size): gpt2-large's grouping (every head
# its own KV head, 16-row pages) and K-EXAONE's (four query heads a KV head,
# 128-row pages), cut in heads
GROUPINGS = {"mha_page16": (4, 4, 64, 16), "gqa_page128": (2, 8, 128, 128)}
N_PG, LAYERS, NUM_PAGES = 4, 3, 24


def _pools(grouping, dtype, seed=0):
    g, nh, d, ps = GROUPINGS[grouping]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (LAYERS, NUM_PAGES, ps, g * d)
    return (jax.random.normal(ks[0], shape).astype(dtype),
            jax.random.normal(ks[1], shape).astype(dtype), ks[2])


def _view_attention(q, k_pool, v_pool, layer, tables, lengths, g):
    """What the decode program did before the kernel: gather the layer's
    pages into a view, attend over its flat rows."""
    b = q.shape[0]
    k = k_pool[layer][tables].reshape(b, -1, k_pool.shape[-1])
    v = v_pool[layer][tables].reshape(b, -1, v_pool.shape[-1])
    seen = jnp.arange(k.shape[1])[None] <= lengths[:, None]
    return ATT._attend_one_query(q, k, v, g, seen, q.shape[-1] ** -0.5)


def _check(grouping, tables, lengths, *, dtype=jnp.float32, tol=2e-5):
    g, nh, d, ps = GROUPINGS[grouping]
    k_pool, v_pool, key = _pools(grouping, dtype)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q = jax.random.normal(key, (len(lengths), nh, 1, d)).astype(dtype)
    got = paged_decode_attention(q, k_pool, v_pool, 1, tables, lengths,
                                 kv_heads=g, interpret=True)
    want = _view_attention(q, k_pool, v_pool, 1, tables, lengths, g)
    assert got.shape == want.shape == (len(lengths), nh, 1, d)
    assert got.dtype == dtype
    err = np.max(np.abs(np.asarray(got, np.float32)
                        - np.asarray(want, np.float32)))
    assert err < tol, err
    return np.asarray(got, np.float32)


def _tables(rows, seed=1):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(np.arange(1, NUM_PAGES))[:N_PG]
                     for _ in range(rows)])


@pytest.mark.parametrize("grouping", GROUPINGS)
@pytest.mark.parametrize("length", ["first", "second", "page_edge",
                                    "page_edge_plus_1", "full_table"])
def test_one_sequence_at_a_ragged_length(grouping, length):
    """``lengths`` is the newest token's index: 0 sees one row, a page's
    last row ends a page, the next starts one, the last fills the table."""
    ps = GROUPINGS[grouping][3]
    n = {"first": 0, "second": 1, "page_edge": ps - 1,
         "page_edge_plus_1": ps, "full_table": N_PG * ps - 1}[length]
    _check(grouping, _tables(1), [n])


@pytest.mark.parametrize("grouping", GROUPINGS)
@pytest.mark.parametrize("pages", [1, 2, 4])
def test_a_ragged_batch_at_every_block_size(grouping, pages, monkeypatch):
    """Sequences of every length in one call, blocks of one page, of two
    and of the whole table (the cut table fits the block the rows' shape
    gives, so the block is set from here): a block's unfilled pages are
    masked, the next sequence's first block is sent while the last of this
    one is computed, whichever buffer that is."""
    monkeypatch.setattr(PAGED, "pages_per_step", lambda *_: pages)
    ps = GROUPINGS[grouping][3]
    lengths = [0, 1, ps - 1, ps, 2 * ps + 3, N_PG * ps - 1, 3 * ps, 5]
    _check(grouping, _tables(len(lengths)), lengths)


@pytest.mark.parametrize("grouping", GROUPINGS)
def test_bfloat16_rows_float32_accumulation(grouping):
    ps = GROUPINGS[grouping][3]
    _check(grouping, _tables(3), [ps + 2, 3 * ps - 1, 7],
           dtype=jnp.bfloat16, tol=0.04)


@pytest.mark.parametrize("grouping", GROUPINGS)
def test_scratch_padded_tables_read_nothing_past_the_length(grouping):
    """A table holds a sequence's pages and then scratch page 0; what lies
    in the scratch page and in the pages past the length never shows: the
    pool's other pages set to NaN change nothing."""
    g, nh, d, ps = GROUPINGS[grouping]
    lengths = [ps + 1, 2, 3 * ps - 1]
    tables = _tables(3)
    for row, n in zip(tables, lengths):
        row[n // ps + 1:] = 0
    want = _check(grouping, tables, lengths)
    k_pool, v_pool, key = _pools(grouping, jnp.float32)
    live = {int(p) for row, n in zip(tables, lengths)
            for p in row[:n // ps + 1]}
    dead = jnp.asarray([p for p in range(NUM_PAGES) if p not in live])
    q = jax.random.normal(key, (3, nh, 1, d))
    got = paged_decode_attention(
        q, k_pool.at[:, dead].set(jnp.nan), v_pool.at[:, dead].set(jnp.nan),
        1, jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
        kv_heads=g, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("grouping", GROUPINGS)
def test_pad_rows_of_a_slot_bucket_and_a_shared_page(grouping):
    """Pad rows of a slot bucket duplicate slot 0's table and length: they
    give slot 0's (finite) result again.  Two sequences that share a prefix
    page read the one page."""
    ps = GROUPINGS[grouping][3]
    tables = _tables(4)
    tables[1, 0] = tables[0, 0]                  # a shared first page
    tables[2], tables[3] = tables[0], tables[0]  # the bucket's pad rows
    out = _check(grouping, tables, [2 * ps + 1, ps + 4, 2 * ps + 1,
                                    2 * ps + 1])
    assert np.isfinite(out).all()


def test_operands_outside_the_pool_are_held_inside_it():
    """A page index past the pool, a length past the table, a layer past the
    last: clipped, never an address to copy from."""
    g, nh, d, ps = GROUPINGS["mha_page16"]
    k_pool, v_pool, key = _pools("mha_page16", jnp.float32)
    q = jax.random.normal(key, (2, nh, 1, d))
    tables = jnp.asarray(_tables(2), jnp.int32)
    got = paged_decode_attention(
        q, k_pool, v_pool, LAYERS + 5, tables.at[1, 1].set(10 ** 6),
        jnp.asarray([3, 10 ** 6], jnp.int32), kv_heads=g, interpret=True)
    want = _view_attention(
        q, k_pool, v_pool, LAYERS - 1, tables.at[1, 1].set(NUM_PAGES - 1),
        jnp.asarray([3, N_PG * ps - 1], jnp.int32), g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_pages_a_block_follow_the_rows_shape():
    """256 rows a block at gpt2-large's 1280-wide rows, 512 at K-EXAONE's
    1024, at most 1024 of narrow ones, at least one page: from the rows'
    shape alone, so that every page bucket's program holds the same
    kernel."""
    assert pages_per_step(16, 1280, 2) == 16
    assert pages_per_step(128, 1024, 2) == 4
    assert pages_per_step(4, 24, 4) == 256
    assert pages_per_step(2048, 1024, 2) == 1


# ------------------------------------------------ through the serving engine

@pytest.fixture
def on_a_tpu(monkeypatch):
    """The rule reads the backend: say TPU, and the kernel is chosen (and, on
    this CPU, interpreted)."""
    monkeypatch.setattr(ATT, "_default_backend_is_tpu", lambda: True)


@pytest.fixture
def plans(monkeypatch):
    """The ``paged_attn.plan`` instants of the programs traced from here."""
    seen = []
    monkeypatch.setattr(
        ATT.paged_attention.trace, "instant",
        lambda name, attrs=None, cat="hetu": seen.append(attrs)
        if name == "paged_attn.plan" else None)
    return seen


def _gpt():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


def _llama():
    m = LlamaModel(LlamaConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=128, max_position=64))
    return m, m.init(jax.random.PRNGKey(0))


def _exaone():
    m = ExaoneMoeModel(ExaoneMoeConfig(
        vocab_size=97, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=48, expert_ffn_size=16,
        n_routed_experts=8, moe_topk=2, held=(0, 4), window=8,
        max_position=128, dtype=jnp.float32, param_dtype=jnp.float32))
    return m, jax.jit(m.init)(jax.random.PRNGKey(0))


MODELS = {"gpt": _gpt, "llama_gqa": _llama, "exaone_two_groups": _exaone}


def _engine(model, variables, num_slots, **kw):
    return PagedServeEngine(model, variables, num_slots=num_slots,
                            max_len=64, page_size=8, prefill_chunk=8,
                            min_bucket=8, **kw)


def _served(model, variables, prompts, rounds=7):
    """Three requests in flight together (a slot bucket of four: one pad
    row) through the engine's own steps."""
    engine = _engine(model, variables, 4, prefix_sharing=False)
    slots = [engine.alloc_slot() for _ in prompts]
    toks = [[engine.prefill(s, p)] for s, p in zip(slots, prompts)]
    for _ in range(rounds):
        out = engine.decode()
        for s, t in zip(slots, toks):
            t.append(out[s])
    return toks


@pytest.mark.parametrize("name", MODELS)
def test_engine_tokens_with_the_kernel_equal_the_dense_oracle(
        name, monkeypatch, plans):
    """Prompts that end inside a page, on a page's edge and past two pages:
    the tokens of the engine whose decode rounds walk the pages in place are
    those of a cached run with no pages in it (``dense_greedy``; a cache of
    two groups has no dense form, so there the oracle is the same engine on
    the XLA side, which ``test_exaone_moe.py`` holds to the reference)."""
    model, variables = MODELS[name]()
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 97, n)] for n in (5, 16, 21)]
    if name == "exaone_two_groups":
        want = _served(model, variables, prompts)
        assert not any(p["kernel"] for p in plans)
    else:
        want = [dense_greedy(model, variables, p, 8, 64) for p in prompts]
    monkeypatch.setattr(ATT, "_default_backend_is_tpu", lambda: True)
    assert _served(model, variables, prompts) == want
    assert any(p["kernel"] for p in plans)


def test_the_decode_program_holds_no_view_and_no_second_pool(monkeypatch):
    """With the kernel, the decode program holds the pool once (carried,
    updated by the row scatters, read by the kernel) and no gathered view of
    a layer: nothing shaped ``[B, n_pg, page_size, width]`` or ``[B, n_pg *
    page_size, ..]``.  On the XLA side the same program holds both."""
    from paged_programs import CARRIERS, _results

    model, variables = _gpt()

    def views():
        engine = _engine(model, variables, 4)
        cache = engine.cache
        n_pg, ps = cache.pages_per_slot, cache.page_size
        closed = jax.make_jaxpr(engine._build_decode())(
            engine.params, cache.k, cache.v,
            jax.ShapeDtypeStruct((4, n_pg + 4), np.int32))
        made = list(_results(closed.jaxpr))
        # the pool's shape: the carried pool and its in-place row scatters
        assert {p for p, s in made if s == tuple(cache.k.shape)} <= CARRIERS
        return "pallas_call" in str(closed), [
            (p, s) for p, s in made if s[:3] == (4, n_pg, ps)
            or (len(s) > 2 and s[:2] == (4, n_pg * ps))]

    kernel, found = views()
    assert not kernel and {p for p, _ in found} >= {"gather"}
    monkeypatch.setattr(ATT, "_default_backend_is_tpu", lambda: True)
    assert views() == (True, [])


# ------------------------------------------------------ the plan's instants

def _decode_once(model, variables):
    engine = _engine(model, variables, 2)
    engine_greedy(engine, [3, 5, 7, 11, 13], 3)
    return engine


def test_one_plan_a_decode_program_built(on_a_tpu, plans):
    """The layer scan is traced once a program: ONE instant for each decode
    program the engine built (slot bucket x page bucket), none for a chunk
    program, with the shapes the kernel took from its operands."""
    model, variables = _gpt()
    engine = _decode_once(model, variables)
    assert len(plans) == engine.metrics.count("decode_compiles") == 1
    assert plans[0] == {
        "kernel": 1, "why": "", "g": 4, "d": 16, "heads": 4, "page_size": 8,
        "pages_per_step": 128, "n_pg": 1, "batch": 1, "rows": 0}


def test_a_cpu_backend_takes_the_view_and_says_so(plans):
    model, variables = _gpt()
    _decode_once(model, variables)
    assert [(p["kernel"], p["why"], p["rows"]) for p in plans] \
        == [(0, "backend", 8)]


def test_a_ring_group_and_a_plain_array_are_never_walked(on_a_tpu, plans):
    """K-EXAONE's tiny sibling: the ONE full layer is walked in place, each
    of the four window layers' rings is read as its view (``window``); the
    dense oracle's plain arrays have no pages (``dense_cache``), with a TPU
    backend too."""
    model, variables = _exaone()
    _decode_once(model, variables)
    assert sorted((p["kernel"], p["why"]) for p in plans) \
        == [(0, "window")] * 4 + [(1, "")]
    del plans[:]
    model, variables = _llama()
    dense_greedy(model, variables, [3, 5, 7], 2, 32)
    assert [(p["kernel"], p["why"], p["g"], p["heads"]) for p in plans] \
        == [(0, "dense_cache", 2, 4)]


def test_the_one_query_step_over_a_paged_cache_on_both_sides(monkeypatch):
    """``ops.decode_layer_attention`` over the same ``PagedLayers`` pair with
    and without the kernel: the same rows written, the same attention."""
    g, nh, d, ps = GROUPINGS["mha_page16"]
    k_pool, v_pool, key = _pools("mha_page16", jnp.float32)
    ks = jax.random.split(key, 3)
    lengths = jnp.asarray([ps + 3, 5], jnp.int32)
    tables = jnp.asarray(_tables(2), jnp.int32)
    wpage = jnp.take_along_axis(tables, lengths[:, None] // ps, 1)
    woff = lengths[:, None] % ps
    q = jax.random.normal(ks[0], (2, nh, 1, d))
    k_new, v_new = (jax.random.normal(k, (2, 1, g, d)) for k in ks[1:])
    sides = {}
    for tpu in (False, True):
        monkeypatch.setattr(ATT, "_default_backend_is_tpu", lambda: tpu)
        k = PagedLayers(k_pool, tables, wpage, woff, (g, d))
        v = PagedLayers(v_pool, tables, wpage, woff, (g, d))
        sides[tpu] = ops.decode_layer_attention(
            q, k_new, v_new, k, v, 2, lengths)
    (o0, k0, v0), (o1, k1, v1) = sides[False], sides[True]
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(k0.pool), np.asarray(k1.pool))
    np.testing.assert_array_equal(np.asarray(v0.pool), np.asarray(v1.pool))
    assert not np.array_equal(np.asarray(k1.pool), np.asarray(k_pool))


def test_a_pool_laid_over_a_mesh_keeps_the_view(on_a_tpu, plans):
    """Under a tensor-parallel mesh the pool's rows are split by KV head
    and no partitioner splits a Mosaic call: the engine says so on its
    ``PagedLayers`` and the one-query step reads the view (``sharded``), on
    a TPU backend too; the tokens are the dense oracle's."""
    import hetu_tpu as ht

    model, variables = _llama()
    engine = _engine(model, variables, 2, mesh=ht.make_mesh(tp=2))
    prompt = [3, 5, 7, 11, 13]
    got = engine_greedy(engine, prompt, 6)
    assert plans and {(p["kernel"], p["why"]) for p in plans} \
        == {(0, "sharded")}
    assert got == dense_greedy(model, variables, prompt, 6, 64)
