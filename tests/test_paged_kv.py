"""Paged KV cache + prefix sharing + chunked prefill (ISSUE 13).

The contract under test: greedy decode through the paged engine is
TOKEN-FOR-TOKEN identical to the training forward (prompt by prompt in
tests/test_serve.py) and to the models' cache entry points over dense
caches — for GPT, for GQA-Llama, under a tp mesh, across chunked
prefills of any chunk split, and through live migration — while prefix
sharing dedups identical prefixes to one physical copy with
copy-on-write isolation and exact refcount release, and the whole
engine compiles a BOUNDED number of executables.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.models.llama import LlamaConfig, LlamaModel
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.serve.kv_cache import (
    KVCacheSpec, PagedKVCache, PagedLayers, SlotStates,
)
from hetu_tpu.telemetry import trace
from paged_programs import engine_greedy as _engine_greedy
from paged_programs import (
    dense_greedy, engine_logits, oversized, pad_writes, param_converts,
    ref_greedy, tiny_served,
)

pytestmark = pytest.mark.paged


def _gpt():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


def _llama_gqa():
    m = LlamaModel(LlamaConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=96, max_position=64))
    return m, m.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def gpt():
    return _gpt()


@pytest.fixture(scope="module")
def llama():
    return _llama_gqa()



# ---- chunked prefill (prompt-by-prompt parity: tests/test_serve.py) ----

def test_parity_independent_of_chunk_split(gpt):
    """The same prompt prefilled in one chunk vs many page-aligned
    chunks must generate identical tokens — chunk boundaries never leak
    into the numerics."""
    model, variables = gpt
    g = np.random.default_rng(42)
    prompt = [int(t) for t in g.integers(0, 97, 37)]
    one = PagedServeEngine(model, variables, num_slots=1, max_len=64,
                           page_size=8, prefill_chunk=64)
    many = PagedServeEngine(model, variables, num_slots=1, max_len=64,
                            page_size=8, prefill_chunk=8)
    assert _engine_greedy(one, prompt, 10) == _engine_greedy(many, prompt, 10)


# ---- prefix sharing ----

def test_shared_prefix_divergent_suffixes_token_exact(llama):
    """System-prompt traffic: one shared prefix, divergent suffixes.
    The paged engine dedups the prefix (hits counted) and every request
    still decodes token-for-token like the unshared full forward."""
    model, variables = llama
    g = np.random.default_rng(3)
    prefix = [int(t) for t in g.integers(0, 97, 17)]
    suffixes = [[int(t) for t in g.integers(0, 97, k)] for k in (5, 9, 3)]

    paged = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                             page_size=8)
    reqs = [Request(prompt=prefix + s, max_tokens=8) for s in suffixes]
    ContinuousBatchingScheduler(paged).run(reqs)
    assert [r.tokens for r in reqs] == _oracle(
        model, variables, [prefix + s for s in suffixes], 8)
    snap = paged.metrics.snapshot()
    # the 2nd and 3rd requests share the prefix's full pages (17 tokens
    # → two 8-token pages each)
    assert snap["prefix_hits"] >= 2
    assert snap["prefix_hit_tokens"] >= 2 * 16
    assert 0.0 < snap["prefix_hit_rate"] < 1.0


def test_identical_prompts_full_dedup_and_cow(gpt):
    """Two identical prompts: the second shares everything except one
    recomputed token (the logits source), which copy-on-writes the
    shared tail page — and both decode the same tokens as an unshared
    run."""
    model, variables = gpt
    g = np.random.default_rng(5)
    prompt = [int(t) for t in g.integers(0, 97, 21)]
    want = ref_greedy(model, variables, prompt, 8)
    paged = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                             page_size=8)
    sch = ContinuousBatchingScheduler(paged)
    r1 = Request(prompt=list(prompt), max_tokens=8)
    r2 = Request(prompt=list(prompt), max_tokens=8)
    sch.run([r1, r2])
    assert r1.tokens == want and r2.tokens == want
    assert paged.cache.cow_copies >= 1
    # full dedup: the sharer covered every full page of the prompt
    assert paged.cache.prefix_hit_tokens >= len(prompt) - 1


def test_cow_isolation_between_forks(gpt):
    """Requests forked off one shared prefix must not corrupt each
    other: interleaved decode of divergent suffixes equals each
    sequence decoded alone."""
    model, variables = gpt
    g = np.random.default_rng(9)
    prefix = [int(t) for t in g.integers(0, 97, 16)]  # page-aligned
    sufa = [int(t) for t in g.integers(0, 97, 3)]
    sufb = [int(t) for t in g.integers(0, 97, 3)]

    def alone(suffix):
        e = PagedServeEngine(model, variables, num_slots=1, max_len=64,
                             page_size=8)
        return _engine_greedy(e, prefix + suffix, 10)

    want_a, want_b = alone(sufa), alone(sufb)
    e = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                         page_size=8)
    sa = e.alloc_slot()
    ta = [e.prefill(sa, prefix + sufa)]
    sb = e.alloc_slot()
    tb = [e.prefill(sb, prefix + sufb)]  # shares the prefix pages
    for _ in range(9):
        out = e.decode()
        ta.append(out[sa])
        tb.append(out[sb])
    assert ta == want_a and tb == want_b


def test_refcount_release_on_free(gpt):
    """Freeing every slot leaves only index-held (reclaimable) pages;
    evicting the index returns the pool to empty — no leaked pages, no
    double frees."""
    model, variables = gpt
    e = PagedServeEngine(model, variables, num_slots=3, max_len=64,
                         page_size=8)
    g = np.random.default_rng(11)
    prefix = [int(t) for t in g.integers(0, 97, 16)]
    slots = []
    for k in (3, 5, 7):
        s = e.alloc_slot()
        e.prefill(s, prefix + [int(t) for t in g.integers(0, 97, k)])
        slots.append(s)
    for _ in range(4):
        e.decode()
    assert e.cache.pages_in_use > 0
    for s in slots:
        e.release(s)
    c = e.cache
    assert c.pages_in_use == c.reclaimable_pages  # only the index holds on
    while c._evict_one_entry():
        pass
    assert c.pages_in_use == 0 and c.prefix_entries == 0
    assert not np.any(c.ref_table) and not np.any(c.ref_index)


def test_double_free_raises(gpt):
    model, variables = gpt
    e = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                         page_size=8)
    s = e.alloc_slot()
    e.release(s)
    with pytest.raises(ValueError, match="double-freed"):
        e.cache.free(s)


# ---- compilation discipline + backpressure ----

def test_bounded_executables_varied_paged_traffic(gpt):
    model, variables = gpt
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=8)
    sch = ContinuousBatchingScheduler(engine)
    g = np.random.default_rng(0)
    reqs = [Request(prompt=[int(t) for t in
                            g.integers(0, 97, int(g.integers(1, 40)))],
                    max_tokens=int(g.integers(2, 12)))
            for _ in range(24)]
    out = sch.run(reqs)
    assert all(len(r.tokens) >= 1 for r in reqs)
    assert len(out) == 24
    assert engine.compiled_executables() <= engine.max_executables


def test_page_budget_backpressure_queues_not_fails(gpt):
    """A page pool far smaller than the workload's total footprint must
    QUEUE admissions (page-budget backpressure), not fail them — every
    request still completes."""
    model, variables = gpt
    # 17 pages of 8 tokens ≈ two concurrent 40-token working sets
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=8, num_pages=17,
                              prefix_sharing=False)
    sch = ContinuousBatchingScheduler(engine)
    g = np.random.default_rng(1)
    reqs = [Request(prompt=[int(t) for t in g.integers(0, 97, 20)],
                    max_tokens=8) for _ in range(8)]
    sch.run(reqs)
    assert all(r.status == "ok" and len(r.tokens) == 8 for r in reqs)


def test_chunked_prefill_interleaves_with_decode(gpt):
    """While a long prompt prefills in chunks, in-flight requests keep
    decoding: the long request's admission must not stall them for its
    whole prompt."""
    model, variables = gpt
    engine = PagedServeEngine(model, variables, num_slots=3, max_len=64,
                              page_size=8, prefill_chunk=8)
    sch = ContinuousBatchingScheduler(engine, prefill_chunks_per_step=1)
    short = Request(prompt=[1, 2, 3], max_tokens=30)
    sch.submit(short)
    sch.step()  # short is decoding
    tokens_before = len(short.tokens)
    g = np.random.default_rng(2)
    long_req = Request(prompt=[int(t) for t in g.integers(0, 97, 40)],
                       max_tokens=4)
    sch.submit(long_req)
    # 40 tokens / 8-token chunks = 5 chunked steps; the short request
    # must gain a token on EVERY one of them
    for i in range(4):
        sch.step()
        assert len(short.tokens) == tokens_before + i + 1
        assert len(long_req.tokens) == 0  # still prefilling
    sch.step()
    assert len(long_req.tokens) >= 1  # final chunk emitted its token
    while sch.has_work():
        sch.step()
    assert long_req.status == "ok" and short.status == "ok"


# ---- migration: live pages only, codec-compatible ----

def _oracle(model, variables, prompts, n):
    return [ref_greedy(model, variables, p, n) for p in prompts]


@pytest.mark.migrate
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_paged_to_paged_migration_token_parity(kind, gpt, llama):
    from hetu_tpu.serve import migrate as mg
    model, variables = gpt if kind == "gpt" else llama
    g = np.random.default_rng(7)
    prompts = [[int(t) for t in g.integers(0, 97, k)] for k in (11, 23, 6)]
    want = _oracle(model, variables, prompts, 10)
    src = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=4, max_len=64, page_size=8))
    dst = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=4, max_len=64, page_size=8))
    reqs = [Request(prompt=list(p), max_tokens=10) for p in prompts]
    for r in reqs:
        src.submit(r)
    for _ in range(5):
        src.step()  # mid-decode
    mg.migrate_inflight(src, dst)
    for _ in range(80):
        if not dst.has_work():
            break
        dst.step()
    assert [r.tokens for r in reqs] == want
    # zero re-prefill on the adopter: adopted mid-decode slots continue
    assert dst.engine.metrics.count("slots_adopted") >= 1


@pytest.mark.migrate
def test_paged_payload_roundtrip_with_codec(gpt):
    """export_payload/adopt_payload between paged schedulers through the
    self-describing packed payload (live pages only on the wire), with
    the int8 block-scaled codec accepted by the same unpack path."""
    from hetu_tpu.serve import migrate as mg
    model, variables = gpt
    g = np.random.default_rng(13)
    prompts = [[int(t) for t in g.integers(0, 97, k)] for k in (10, 19)]
    src = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=2, max_len=64, page_size=8))
    reqs = [Request(prompt=list(p), max_tokens=12) for p in prompts]
    for r in reqs:
        src.submit(r)
    for _ in range(3):
        src.step()
    payload, pairs = mg.export_payload(src, codec="none")
    # payload ships LIVE tokens only: far below the whole-slot footprint
    spec = src.engine.cache.spec
    per_tok = 2 * spec.num_layers * spec.num_kv_heads * spec.head_dim * 4
    live = sum(int(n) for n in src.engine.cache.lengths)
    assert len(payload) < live * per_tok + 4096
    dst = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=2, max_len=64, page_size=8))
    adopted, slot_map = mg.adopt_payload(dst, payload)
    mg.release_exported(src, pairs)
    assert len(adopted) == 2 and len(slot_map) == 2
    for _ in range(80):
        if not dst.has_work():
            break
        dst.step()
    want = _oracle(model, variables, prompts, 12)
    assert [sorted_r.tokens for sorted_r in adopted] == want


# ---- scheduler-state coverage for the chunked path ----

def test_requeue_mid_chunked_prefill_re_prefills(gpt):
    """Engine failover while a chunked prefill is in flight: the request
    requeues and re-prefills on the replacement engine, token-exact."""
    model, variables = gpt
    g = np.random.default_rng(21)
    prompt = [int(t) for t in g.integers(0, 97, 30)]
    want = _engine_greedy(PagedServeEngine(
        model, variables, num_slots=1, max_len=64, page_size=8), prompt, 6)
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                              page_size=8, prefill_chunk=8)
    sch = ContinuousBatchingScheduler(engine)
    req = Request(prompt=list(prompt), max_tokens=6)
    sch.submit(req)
    sch.step()  # admitted; first chunk ran, prefill NOT complete
    assert len(req.tokens) == 0 and sch._prefilling
    fresh = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                             page_size=8, prefill_chunk=8)
    sch.replace_engine(fresh)
    while sch.has_work():
        sch.step()
    assert req.tokens == want and req.status == "ok"


def test_cancel_mid_chunked_prefill_frees_pages(gpt):
    model, variables = gpt
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                              page_size=8, prefill_chunk=8,
                              prefix_sharing=False)
    sch = ContinuousBatchingScheduler(engine)
    g = np.random.default_rng(22)
    req = Request(prompt=[int(t) for t in g.integers(0, 97, 30)],
                  max_tokens=6)
    sch.submit(req)
    sch.step()
    assert engine.cache.pages_in_use > 0
    sch.cancel(req)
    assert req.status == "cancelled"
    assert engine.cache.pages_in_use == 0
    assert engine.cache.num_free == engine.cache.num_slots


def test_full_dedup_near_max_len_no_clamp_corruption(gpt):
    """Review regression: a near-max_len prompt resubmitted (full prefix
    hit → one recomputed token at start = n-1) pads its chunk bucket
    past the slot's own page window.  The extended gather view must
    absorb the padding — a clamped window would smear pad junk over
    real history and silently change the token."""
    model, variables = gpt
    g = np.random.default_rng(31)
    # max_len 64, page 8: prompt 58 → full-hit resubmit runs one chunk
    # at start=57 padded to bucket 16 → 73 > 64 without the extension
    prompt = [int(t) for t in g.integers(0, 97, 58)]
    want = dense_greedy(model, variables, prompt, 4, 64)
    paged = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                             page_size=8)
    first = _engine_greedy(paged, prompt, 4)
    assert first == want
    again = _engine_greedy(paged, prompt, 4)  # the full-dedup resubmit
    assert again == want
    assert paged.cache.prefix_hit_tokens >= len(prompt) - 1


def test_import_respects_outstanding_reservations(gpt):
    """Review regression: a migration adoption must not consume pages
    an in-flight chunked prefill's admission reserved."""
    from hetu_tpu.serve.kv_cache import KVSlotSnapshot
    model, variables = gpt
    e = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                         page_size=8, num_pages=9, prefix_sharing=False)
    slot = e.alloc_slot()
    e.begin_prefill(slot, list(range(1, 30)), max_tokens=8)  # reserves
    reserved = int(e.cache._reserve[slot])
    assert reserved > 0
    spec = e.cache.spec
    n = 17
    snap = KVSlotSnapshot(
        slot=0, length=n,
        k=np.zeros((spec.num_layers, n, spec.num_kv_heads,
                    spec.head_dim), np.dtype(spec.dtype)),
        v=np.zeros((spec.num_layers, n, spec.num_kv_heads,
                    spec.head_dim), np.dtype(spec.dtype)),
        meta={"last_token": 1})
    # 8 usable pages, reservation holds `reserved`; adopting 3 more must
    # refuse rather than eat the reserved headroom
    if 3 > e.cache.available_pages():
        with pytest.raises(RuntimeError, match="available"):
            e.adopt_slots([snap])
    # and the reserved prefill still completes
    while e.prefill_step(slot) is None:
        pass
    assert e.active[slot]


def test_prefill_timeout_resolves_behind_slower_prefills(gpt):
    """Review regression: a deadline-blown mid-prefill request resolves
    the same step even when older prefills consume the chunk budget."""
    import time as _time
    model, variables = gpt
    engine = PagedServeEngine(model, variables, num_slots=3, max_len=64,
                              page_size=8, prefill_chunk=8)
    sch = ContinuousBatchingScheduler(engine, prefill_chunks_per_step=1)
    g = np.random.default_rng(33)
    slow = Request(prompt=[int(t) for t in g.integers(0, 97, 40)],
                   max_tokens=4)
    doomed = Request(prompt=[int(t) for t in g.integers(0, 97, 40)],
                     max_tokens=4, timeout_s=0.01)
    sch.submit(slow)
    sch.submit(doomed)
    sch.step()  # both admitted, budget goes to `slow`
    _time.sleep(0.02)
    sch.step()  # doomed's deadline has passed; budget still goes to slow
    assert doomed.status == "timeout" and doomed.done.is_set()
    while sch.has_work():
        sch.step()
    assert slow.status == "ok"


def test_llama_full_dedup_near_max_len(llama):
    """Same near-boundary clamp/NaN regression on the RoPE path: the
    chunk's pad positions gather past the rope tables and must clamp,
    not NaN-fill."""
    model, variables = llama
    g = np.random.default_rng(37)
    prompt = [int(t) for t in g.integers(0, 97, 58)]
    want = dense_greedy(model, variables, prompt, 4, 64)
    paged = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                             page_size=8)
    assert _engine_greedy(paged, prompt, 4) == want
    assert _engine_greedy(paged, prompt, 4) == want  # full-dedup resubmit


# ---- one layer's pages at a time (ISSUE 29) ----

def _wide(kind):
    """The two models with four layers and 256 positions: a view of every
    layer then outweighs one layer's (GQA-repeated) and any weight."""
    if kind == "gpt":
        m = GPTModel(GPTConfig(
            vocab_size=97, hidden_size=64, num_layers=4, num_heads=4,
            ffn_size=128, max_position=256, dropout_rate=0.0))
    else:
        m = LlamaModel(LlamaConfig(
            vocab_size=97, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, ffn_size=96, max_position=256))
    return m, m.init(jax.random.PRNGKey(2))


@pytest.mark.parametrize("program", ["decode", "chunk", "chunk_ext"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_no_program_holds_a_pool_or_a_view_of_every_layer(kind, program):
    """The jaxpr of each paged program: nothing as large as the K pool or
    as ``L x b x T x row`` is made, other than the carried pool the row
    scatter writes into."""
    model, variables = _wide(kind)
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=256,
                              page_size=8, prefill_chunk=16)
    floor, found = oversized(engine, program, batch=2, chunk=16)
    assert floor >= 4 * 256 * 32 and found == []


@pytest.mark.parametrize("case", ["boundary", "cow", "tp2"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_paged_tokens_equal_the_dense_caches(kind, case, gpt, llama):
    """Float32, token for token, against the same two entry points over
    dense caches (and, for the first token, the full forward): a prompt
    whose padded final chunk runs past the slot's pages (the boundary
    program), two requests sharing a prefix with a copy-on-write page, and
    a ``tp=2`` mesh."""
    model, variables = gpt if kind == "gpt" else llama
    g = np.random.default_rng(29)
    # max_len 60 = 15 pages of 4: the chunk [48, 57) pads to 16 -> 64 > 60
    prompt = [int(t) for t in g.integers(0, 97, 57 if case == "boundary"
                                         else 21)]
    n = 3 if case == "boundary" else 8
    want = dense_greedy(model, variables, prompt, n, 60)
    assert want[:1] == ref_greedy(model, variables, prompt, 1)
    paged = PagedServeEngine(
        model, variables, num_slots=2, max_len=60, page_size=4,
        prefill_chunk=16, mesh=ht.make_mesh(tp=2) if case == "tp2" else None)
    assert _engine_greedy(paged, prompt, n) == want
    if case == "boundary":
        assert paged._chunk_fn_ext is not None
    if case == "cow":
        # the same prompt again: all but its last token adopted, that one
        # recomputed into the shared tail page, which is copied first
        assert _engine_greedy(paged, prompt, n) == want
        assert paged.cache.cow_copies >= 1
        assert paged.cache.prefix_hit_tokens == len(prompt) - 1


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_pad_positions_are_written_to_scratch_only(kind, gpt, llama):
    """Chunks padded to their bucket and a decode round with a pad row:
    every row the programs wrote is a live position's or scratch (0, 0)."""
    model, variables = gpt if kind == "gpt" else llama
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=8, prefill_chunk=16,
                              prefix_sharing=False)
    g = np.random.default_rng(5)
    prompts = [[int(t) for t in g.integers(0, 97, n)] for n in (5, 19, 9)]
    assert pad_writes(engine, prompts) == {
        "stray": [], "missed": [], "scratch_written": True}


# ---- each weight held in the dtype its programs read it in (ISSUE 31) ----

def _bf16(kind, dtype=jnp.bfloat16):
    """The two models computing in ``dtype`` over the float32 leaves their
    ``init`` yields."""
    if kind == "gpt":
        m = GPTModel(GPTConfig(
            vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            ffn_size=128, max_position=64, dropout_rate=0.0, dtype=dtype))
    else:
        m = LlamaModel(LlamaConfig(
            vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, ffn_size=96, max_position=64, dtype=dtype))
    v = m.init(jax.random.PRNGKey(3))
    assert all(a.dtype == jnp.float32 for a in jax.tree_util.tree_leaves(v))
    return m, v


def _leaves(tree) -> dict:
    return {jax.tree_util.keystr(path): a for path, a
            in jax.tree_util.tree_leaves_with_path(tree)}


# the leaves a program reads in float32: norms, positions, the lookup's table
READ_IN_FLOAT32 = ("ln", "rms", "pos_emb", "tok_emb")


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_engine_holds_each_leaf_in_the_dtype_it_is_read_in(kind):
    """bfloat16 compute over float32 leaves: the engine's matmul leaves
    (and the biases cast beside them) are bfloat16, its norm, position and
    lookup leaves the very float32 arrays it was given, the tied head a
    second, bfloat16 leaf; the build instant and the metrics count them."""
    model, variables = _bf16(kind)
    given = _leaves(variables["params"])
    tracer = trace.enable()
    try:
        engine = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                                  page_size=8, prefill_chunk=16)
    finally:
        trace.disable()
    held = _leaves(engine.params)
    retyped = 0
    for path_, a in held.items():
        if any(k in path_ for k in READ_IN_FLOAT32):
            assert a is given[path_], path_
        else:
            assert a.dtype == jnp.bfloat16, path_
            retyped += 1
    # GPT-2's tied table is read both ways and held both ways, and its fused
    # projection's leaf is held with its contracting axis minor (ISSUE 45)
    qkv, qkv_t = (f"['blocks']['attn']['qkv_weight{t}']" for t in ("", "_t"))
    assert set(held) - set(given) == ({"['lm_head']", qkv_t}
                                      if kind == "gpt" else set())
    assert set(given) - set(held) == ({qkv} if kind == "gpt" else set())
    if kind == "gpt":      # [L, heads, 3, width, H]
        assert np.array_equal(held[qkv_t], jnp.swapaxes(
            given[qkv].astype(jnp.bfloat16), 1, 2).reshape(2, 4, 3, 16, 64))
    assert np.array_equal(
        held["['lm_head']"],
        given["['tok_emb']" if kind == "gpt" else "['lm_head']"]
        .astype(jnp.bfloat16))
    want = {"leaves": len(given), "retyped": retyped,
            "relaid": int(kind == "gpt"),
            "bytes_given": sum(a.nbytes for a in given.values()),
            "bytes_held": sum(a.nbytes for a in held.values())}
    assert retyped == (9 if kind == "gpt" else 6)
    assert want["bytes_held"] < 0.7 * want["bytes_given"]
    snap = engine.metrics.snapshot()
    assert {k: snap[f"params_{k}"] for k in want} == want
    (instant,) = [e for e in tracer.events
                  if e["name"] == "serve.params_held"]
    assert instant["args"] == want


@pytest.mark.parametrize("program", ["decode", "chunk", "chunk_ext"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_no_program_converts_a_parameter_leaf(kind, program):
    """The jaxpr of each paged program over the engine's leaves holds no
    ``convert_element_type`` of a parameter leaf of two or more dimensions,
    the scan's body included; over the float32 leaves the engine was given
    the same walk finds one for every matmul weight."""
    model, variables = _bf16(kind)
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                              page_size=8, prefill_chunk=16)
    assert param_converts(engine, program, batch=2, chunk=16) == []
    before = param_converts(engine, program, batch=2, chunk=16,
                            params=variables["params"])
    assert len(before) == (5 if kind == "gpt" else 6)
    assert {(a, b) for _, a, b in before} == {("float32", "bfloat16")}


@pytest.mark.parametrize("case", ["hot", "boundary"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_logits_over_held_leaves_equal_those_over_given_leaves(kind, case):
    """Rounding a weight once at build and once a call are the same
    mathematics: every chunk's and every decode round's logits, out of the
    engine's own programs, are equal bit for bit over the leaves the engine
    holds and over the float32 leaves it was given."""
    model, variables = _bf16(kind)
    g = np.random.default_rng(31)
    # max_len 60 = 15 pages of 4: the chunk [48, 57) pads to 16 -> 64 > 60
    prompt = [int(t) for t in g.integers(0, 97, 57 if case == "boundary"
                                         else 21)]
    kw = dict(num_slots=2, max_len=60, page_size=4, prefill_chunk=16)
    held, engine = engine_logits(model, variables, prompt, 4, **kw)
    given, _ = engine_logits(model, variables, prompt, 4, as_given=True, **kw)
    assert (engine._chunk_fn_ext is not None) == (case == "boundary")
    assert len(held) == len(given) == -(-len(prompt) // 16) + 3
    for a, b in zip(held, given):
        assert a.shape[-1] == 97 and np.array_equal(a, b)


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_float32_compute_is_served_from_the_arrays_given(kind):
    """Every leaf is already in the dtype it is read in: the engine holds
    the very arrays it was given, no copy, no second head, nothing counted
    as re-typed; but for the one leaf GPT-2's programs read the other way
    round (ISSUE 45), which is held transposed whatever its dtype."""
    model, variables = _bf16(kind, jnp.float32)
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                              page_size=8, prefill_chunk=16)
    given, held = _leaves(variables["params"]), _leaves(engine.params)
    relaid = {"['blocks']['attn']['qkv_weight']"} if kind == "gpt" else set()
    assert set(given) - set(held) == relaid
    assert {p.replace("_t']", "']") for p in held} == set(given)
    assert all(held[p] is given[p] for p in set(given) - relaid)
    snap = engine.metrics.snapshot()
    assert snap["params_retyped"] == 0
    assert snap["params_relaid"] == len(relaid)
    assert snap["params_bytes_held"] == snap["params_bytes_given"]


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_an_engine_built_from_shapes_holds_the_same_types(kind):
    """``jax.eval_shape``'s tree in place of the arrays (an engine built
    only for its programs to be compiled, ``benchmarks/tools``): the same
    leaves in the same types, counted the same."""
    model, variables = _bf16(kind)
    kw = dict(num_slots=2, max_len=64, page_size=8, prefill_chunk=16)
    real = PagedServeEngine(model, variables, **kw)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(3))
    described = PagedServeEngine(model, shapes, **kw)
    assert {p: (a.shape, a.dtype) for p, a in _leaves(real.params).items()} \
        == {p: (a.shape, a.dtype)
            for p, a in _leaves(described.params).items()}
    want, got = real.metrics.snapshot(), described.metrics.snapshot()
    assert all(got[k] == want[k] for k in want if k.startswith("params_"))


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_retyped_leaves_keep_the_megatron_placement(kind):
    """Under a ``tp=2`` mesh a re-typed leaf has the sharding its float32
    leaf was placed with, and the tokens are those of the leaves as given."""
    model, variables = _bf16(kind)
    mesh = ht.make_mesh(tp=2)
    kw = dict(num_slots=2, max_len=64, page_size=8, prefill_chunk=16,
              mesh=mesh)
    engine = PagedServeEngine(model, variables, **kw)
    from hetu_tpu.serve.engine import _DecodeTP
    want = _leaves(_DecodeTP().shardings(variables["params"], mesh))
    split = 0
    for path, a in _leaves(engine.params).items():
        if path in want:
            assert a.sharding.is_equivalent_to(want[path], a.ndim), path
            split += not a.sharding.is_fully_replicated
    assert split >= (3 if kind == "gpt" else 4)
    if kind == "gpt":
        # the leaf held transposed, its columns split by head, is split
        # over the heads: whole heads a device still (ISSUE 45)
        from jax.sharding import NamedSharding, PartitionSpec as P
        qkv_t = engine.params["blocks"]["attn"]["qkv_weight_t"]
        assert qkv_t.shape == (2, 4, 3, 16, 64)
        assert qkv_t.sharding.is_equivalent_to(
            NamedSharding(mesh, P(None, "tp", None, None, None)), 5)
    g = np.random.default_rng(7)
    prompt = [int(t) for t in g.integers(0, 97, 21)]
    as_given = PagedServeEngine(model, variables, **kw)
    as_given.params = _DecodeTP().place(variables["params"], mesh)
    assert _engine_greedy(engine, prompt, 6) == \
        _engine_greedy(as_given, prompt, 6)


# ---- each projection weight held where its programs read it (ISSUE 45) ----

SERVED = ["gpt", "exaone", "lfm2", "longcat"]


@pytest.mark.parametrize("kind", SERVED + ["falcon"])
def test_logits_over_the_held_layout_equal_those_over_given_leaves(kind):
    """Where the bytes of a projection weight lie changes no logit: every
    chunk's and every decode round's logits, out of the engine's own
    programs, are equal bit for bit over the leaves the engine holds (a leaf
    a layer; the two minor axes exchanged) and over the tree it was given.
    The contraction and its operands' order are the same in both."""
    model, variables, kw = tiny_served(kind)
    g = np.random.default_rng(45)
    prompt = [int(t) for t in g.integers(0, 96, 21)]
    held, engine = engine_logits(model, variables, prompt, 4, **kw)
    given, _ = engine_logits(model, variables, prompt, 4, as_given=True, **kw)
    assert engine.metrics.snapshot()["params_relaid"] == \
        {"gpt": 1, "exaone": 4, "lfm2": 4, "longcat": 2, "falcon": 0}[kind]
    assert len(held) == len(given) == 3 + 3
    for a, b in zip(held, given):
        assert a.shape[-1] == 96 and np.array_equal(a, b)


@pytest.mark.parametrize("kind", SERVED)
def test_an_engine_built_from_shapes_holds_the_same_layout(kind):
    """``jax.eval_shape``'s tree in place of the arrays: the same leaves in
    the same shapes and structure, counted the same."""
    model, variables, kw = tiny_served(kind)
    real = PagedServeEngine(model, variables, **kw)
    described = PagedServeEngine(
        model, jax.eval_shape(model.init, jax.random.PRNGKey(0)), **kw)
    assert {p: (a.shape, a.dtype) for p, a in _leaves(real.params).items()} \
        == {p: (a.shape, a.dtype)
            for p, a in _leaves(described.params).items()}
    want, got = real.metrics.snapshot(), described.metrics.snapshot()
    assert want["params_relaid"] > 0
    assert all(got[k] == want[k] for k in want if k.startswith("params_"))


@pytest.mark.parametrize("kind", ["exaone", "lfm2", "longcat"])
def test_the_held_layout_pins_no_leaf_it_was_given(kind):
    """A caller that drops its tree after the build gets the stacked
    originals' bytes back: what the engine holds of a leaf it relaid is
    arrays of its own, and of every other leaf the very array given."""
    import gc
    model, variables, kw = tiny_served(kind)
    given = _leaves(variables["params"])
    engine = PagedServeEngine(model, variables, **kw)
    held = _leaves(engine.params)
    same = [p for p in held if p in given]
    assert all(held[p] is given[p] for p in same)
    relaid = set(given) - set(held)
    assert len(relaid) == engine.metrics.snapshot()["params_relaid"]
    ids = {p: id(given[p]) for p in relaid}
    del given, variables
    gc.collect()
    assert not any(id(a) in ids.values() for a in jax.live_arrays())
    assert sum(a.nbytes for a in _leaves(engine.params).values()) \
        == engine.metrics.snapshot()["params_bytes_given"]


def test_a_model_that_yields_its_matrices_a_layer_an_array_is_held_as_given():
    """Falcon-H1's ``init`` yields each matrix of a layer as a tuple of the
    layers' arrays: the engine re-holds none, every leaf it serves from IS
    the array given, so a caller that keeps its tree holds nothing twice."""
    model, variables, kw = tiny_served("falcon")
    engine = PagedServeEngine(model, variables, **kw)
    given, held = _leaves(variables["params"]), _leaves(engine.params)
    assert set(given) == set(held) and all(held[p] is given[p] for p in held)
    assert isinstance(engine.params["layers"]["ffn"]["gate"], tuple)
    snap = engine.metrics.snapshot()
    assert snap["params_relaid"] == 0
    assert snap["params_bytes_held"] == snap["params_bytes_given"]


# ---- a state layer of several parts (ISSUE 47) ----

def _two_part_states(fresh=None):
    """Two layers, four slots; a part a tuple of its layers' arrays."""
    conv = jnp.arange(2 * 4 * 3, dtype=jnp.bfloat16).reshape(2, 4, 3) + 1
    ssm = jnp.arange(2 * 4 * 2 * 5, dtype=jnp.float32).reshape(2, 4, 2, 5) + 1
    return conv, ssm, SlotStates((tuple(conv), tuple(ssm)),
                                 jnp.asarray([2, 0]), fresh)


def test_slot_states_of_two_parts_read_zeros_where_fresh_in_both():
    conv, ssm, st = _two_part_states(jnp.asarray([True, False]))
    got_conv, got_ssm = st.read(1)
    assert got_conv.dtype == jnp.bfloat16 and got_ssm.dtype == jnp.float32
    for got, held in ((got_conv, conv), (got_ssm, ssm)):
        np.testing.assert_array_equal(np.asarray(got[0], np.float32), 0.0)
        np.testing.assert_array_equal(got[1], held[1, 0])
    # one part alone
    np.testing.assert_array_equal(st.read(1, 1), got_ssm)


@pytest.mark.parametrize("part", [None, 0, 1])
def test_slot_states_of_two_parts_write_each_part_in_its_own_dtype(part):
    conv, ssm, st = _two_part_states()
    new = (jnp.full((2, 3), -1.0), jnp.full((2, 2, 5), -2.0))
    out = st.write(0, new if part is None else new[part], part)
    for i, (held, was) in enumerate(zip(out.rows, (conv, ssm))):
        assert isinstance(held, tuple) and held[0].dtype == was.dtype
        value = -1.0 - i
        for slot in range(4):
            row = np.asarray(held[0][slot], np.float32)
            if part in (None, i) and slot in (0, 2):
                assert (row == value).all()
            else:
                np.testing.assert_array_equal(
                    row, np.asarray(was[0, slot], np.float32))
        assert held[1] is st.rows[i][1]       # the other layer: untouched


def test_slot_states_hand_a_large_part_over_a_layer_whole():
    """A decode round's way to a part too large to gather: every slot's row
    of a layer, the step's small inputs laid out by slot with zeros where no
    sequence is, its results read back by sequence, and the layer's array
    put back whole."""
    conv, ssm, st = _two_part_states()
    rows = st.whole(1, 1)
    np.testing.assert_array_equal(rows, ssm[1])
    spread = st.spread(jnp.asarray([[5.0, 6.0], [7.0, 8.0]]), rows)
    np.testing.assert_array_equal(
        spread, [[7.0, 8.0], [0.0, 0.0], [5.0, 6.0], [0.0, 0.0]])
    np.testing.assert_array_equal(st.pick(jnp.arange(4.0) * 10), [20.0, 0.0])
    out = st.put_whole(1, 1, rows * 2)
    np.testing.assert_array_equal(out.rows[1][1], ssm[1] * 2)
    assert out.rows[1][0] is st.rows[1][0] and out.rows[0] is st.rows[0]


def test_a_cache_of_a_two_part_spec_holds_an_array_a_part_a_layer():
    spec = KVCacheSpec(2, 2, 8, state_layers=3, state_parts=(
        ("conv", (18,), jnp.bfloat16), ("ssm", (2, 4, 5), jnp.float32)))
    assert spec.part_bytes_per_slot == {"conv": 3 * 18 * 2,
                                        "ssm": 3 * 40 * 4}
    assert spec.bytes_per_slot == 3 * (36 + 160)
    cache = PagedKVCache(spec, 4, 64, page_size=4)
    conv, ssm = cache.state
    assert [(a.shape, a.dtype) for a in conv] == 3 * [((5, 18), jnp.bfloat16)]
    assert [(a.shape, a.dtype) for a in ssm] \
        == 3 * [((5, 2, 4, 5), jnp.float32)]
    assert cache.state_bytes == 5 * spec.bytes_per_slot
    assert cache.max_prefix_entries == 0
    # one array over the layers, as before, for a spec of one shape
    one = KVCacheSpec(2, 2, 8, state_layers=3, state_shape=(2, 6))
    assert one.parts == (("state", (2, 6), np.dtype("float32")),)
    assert PagedKVCache(one, 4, 64, page_size=4).state.shape == (3, 5, 2, 6)
    assert KVCacheSpec(2, 2, 8).parts == () \
        and KVCacheSpec(2, 2, 8).bytes_per_slot == 0


def test_two_part_state_layers_beside_fewer_cache_layers_than_layers():
    """A model of eight layers, two of them cache layers of ONE page group
    and six state layers of two parts (ISSUE 51): the pools hold the two,
    the state an array a part a state layer, a slot's pages and its state
    are counted apart, and a slot handed on reads as zeros in both parts
    while its pages are its own."""
    spec = KVCacheSpec(2, 2, 16, dtype=jnp.bfloat16, state_layers=6,
                       state_parts=(("conv", (3 * 64,), jnp.bfloat16),
                                    ("delta", (4, 8, 8), jnp.float32)))
    assert len(spec.groups) == 1 and spec.num_layers == 2
    assert spec.bytes_per_token == 2 * 2 * (16 + 16) * 2
    assert spec.part_bytes_per_slot == {"conv": 6 * 192 * 2,
                                        "delta": 6 * 256 * 4}
    cache = PagedKVCache(spec, 4, 64, page_size=4)
    assert cache.k.shape[0] == cache.v.shape[0] == 2
    conv, delta = cache.state
    assert [(a.shape, a.dtype) for a in conv] == 6 * [((5, 192), jnp.bfloat16)]
    assert [(a.shape, a.dtype) for a in delta] \
        == 6 * [((5, 4, 8, 8), jnp.float32)]
    assert cache.state_bytes == 5 * spec.bytes_per_slot
    slot = cache.alloc()
    cache.prepare_write(slot, 0, 9)                 # three pages of four
    assert cache.pages_in_use == 3
    # the slot's last owner left something in both parts of state layer 4
    cache.state = tuple(tuple(a.at[slot].set(3.0) for a in part)
                        for part in cache.state)
    fresh = SlotStates(cache.state, jnp.asarray([slot]),
                       jnp.asarray([True]))
    assert all(float(jnp.abs(r).max()) == 0.0 for r in fresh.read(4))
    again = SlotStates(cache.state, jnp.asarray([slot]),
                       jnp.asarray([False]))
    assert all(float(r.min()) == 3.0 for r in again.read(4))
    # a round's whole-layer update of the large part leaves the small one
    rows = again.whole(4, 1)
    out = again.put_whole(4, 1, rows + 1.0)
    assert float(out.rows[1][4].min()) == 1.0       # the idle rows' too
    assert out.rows[0] is again.rows[0]
    assert all(out.rows[1][l] is again.rows[1][l] for l in (0, 1, 2, 3, 5))


def test_state_layers_over_a_mesh_stay_refused_whatever_their_parts():
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = KVCacheSpec(2, 2, 8, state_layers=1, state_parts=(
        ("conv", (3, 6), jnp.float32), ("ssm", (2, 4, 5), jnp.float32)))
    mesh = ht.make_mesh(tp=2)
    with pytest.raises(ValueError, match="state layers over a mesh"):
        PagedKVCache(spec, 4, 64, page_size=4,
                     sharding=NamedSharding(mesh, P()))


def test_a_leaf_held_by_layer_or_transposed_keeps_its_other_axes_split():
    """Under a ``tp`` mesh a stacked leaf split over one of its two minor
    axes is held, a layer at a time, split over that axis, and transposed,
    over the axis it became (the major one of those it is split into)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from hetu_tpu.layers.base import held_by_layer, held_transposed
    mesh = ht.make_mesh(tp=2)
    leaf = jax.device_put(jnp.arange(3 * 8 * 4, dtype=jnp.float32).reshape(
        3, 8, 4), NamedSharding(mesh, P(None, "tp", None)))
    layers = held_by_layer({"w": leaf}, "w")["w"]
    assert len(layers) == 3
    for l, a in enumerate(layers):
        assert a.sharding.is_equivalent_to(
            NamedSharding(mesh, P("tp", None)), 2)
        assert np.array_equal(a, leaf[l])
    t = held_transposed({"w": leaf}, w=None)["w_t"]
    assert t.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, None, "tp")), 3)
    assert np.array_equal(t, np.swapaxes(np.asarray(leaf), 1, 2))
    leaf = jax.device_put(leaf, NamedSharding(mesh, P(None, None, "tp")))
    t = held_transposed({"w": leaf}, w=(2, 2))["w_t"]        # [3, 2, 2, 8]
    assert t.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "tp", None, None)), 4)
    assert np.array_equal(t, np.swapaxes(np.asarray(leaf), 1, 2).reshape(
        3, 2, 2, 8))


# ---- groups of cache layers: window layers beside full ones (ISSUE 32) ----

def _grouped_cache(*, window=8, slots=3, max_len=64, page=4, step_rows=8,
                   prefix=256, layers=(1, 2)):
    from hetu_tpu.serve.kv_cache import KVCacheSpec, PagedKVCache
    spec = KVCacheSpec(
        num_layers=layers[0], num_kv_heads=2, head_dim=4, dtype=jnp.float32,
        also=(KVCacheSpec(num_layers=layers[1], num_kv_heads=2, head_dim=4,
                          dtype=jnp.float32, window=window),))
    return PagedKVCache(spec, slots, max_len, page_size=page,
                        max_prefix_entries=prefix, step_rows=step_rows)


def _write(cache, slot, start, n):
    """What an engine step does to the books: the write map, then the
    length."""
    pages, offs = cache.prepare_write(slot, start, n)
    cache.lengths[slot] = start + n
    return pages, offs


def test_a_window_group_drops_the_pages_behind_the_window():
    """Window 8 over pages of 4, chunks of 8: the full group's table grows
    with the sequence, the window group's holds the pages a query at the
    next position or later can still see, and a dropped page is free
    again."""
    cache = _grouped_cache()
    full, win = cache.groups
    assert (full.window, win.window) == (None, 8)
    assert cache.spec.groups[1].ring_pages(8, 4) == 5     # 7 + 8 rows, + 1
    assert cache.spec.groups[1].ring_pages(1, 4) == 3
    assert win.num_pages == 1 + 3 * (5 + 1)
    slot = cache.alloc()
    for start in range(0, 40, 8):
        (wp_full, wp_win), offs = _write(cache, slot, start, 8)
        assert list(offs) == [0, 1, 2, 3] * 2
        assert len(full.tables[slot]) == (start + 8) // 4
        # positions > start - 8 are kept while the chunk is written
        first = max(start - 8 + 1, 0) // 4
        assert win.base[slot] == first
        assert len(win.tables[slot]) == (start + 8) // 4 - first <= 5
        assert list(wp_win[:4]) == [win.tables[slot][start // 4 - first]] * 4
        assert list(wp_full[4:]) == [full.tables[slot][start // 4 + 1]] * 4
    assert cache.window_released == win.released == 6
    assert win.pages_in_use == 4 and full.pages_in_use == 10
    # decoding on: a page is dropped each time the window leaves it
    for pos in range(40, 52):
        _write(cache, slot, pos, 1)
    assert win.base[slot] == (51 - 8 + 1) // 4 and len(win.tables[slot]) == 2
    assert cache.held_layer_pages() == (13 * 1, 2 * 2, 13 * 3)
    cache.free(slot)
    assert cache.pages_in_use == 0 and not np.any(win.ref_table)


def test_a_window_groups_table_is_a_ring_in_the_program():
    cache = _grouped_cache()
    win = cache.groups[1]
    slot = cache.alloc()
    _write(cache, slot, 0, 8)
    _write(cache, slot, 8, 8)
    _write(cache, slot, 16, 8)          # holds logical pages 2..5
    assert win.base[slot] == 2 and len(win.tables[slot]) == 4
    ring = win.device_table(slot, 5)
    for i, page in enumerate(win.tables[slot]):
        assert ring[(2 + i) % 5] == page
    assert ring[1] == 0                 # the one column not held: scratch
    with pytest.raises(AssertionError, match="ring"):
        win.device_table(slot, 3)
    full = cache.groups[0]
    assert list(full.device_table(slot, 8)[:6]) == full.tables[slot]


def test_a_prefix_entry_needs_every_groups_pages():
    """A hit is taken only where every group holds what the continuation
    will read: entries are made for the prefixes whose window pages the
    slot still holds, and a match of one hands back pages of both
    groups."""
    cache = _grouped_cache()
    full, win = cache.groups
    tokens = list(range(100, 130))           # 30 tokens: 7 pages and a tail
    slot = cache.alloc()
    for start in (0, 8, 16, 24):
        _write(cache, slot, start, min(8, 30 - start))
    cache.register_prefix(slot, tokens)
    # the window group holds pages 4.. (positions > 24 - 8): entries exist
    # for the prefixes whose last 8 positions lie in them
    held = {e.n_tokens for e in cache._prefix.values()}
    assert held == {24, 28, 30}
    n, pages = cache.match_prefix(tokens + [7, 8])     # a longer prompt
    assert n == 28 and [len(p) for _, p in pages] == [7, 2]
    n, pages = cache.match_prefix(tokens)              # the same prompt
    assert n == 29                                     # one token prefills
    (f0, f_pages), (w0, w_pages) = pages
    assert (f0, w0) == (0, (30 - 8) // 4) and len(f_pages) == 8
    assert list(w_pages) == win.tables[slot][w0 - int(win.base[slot]):]
    # a prompt that shares only 20 tokens finds no entry: the pages a
    # continuation from there would read are gone
    assert cache.match_prefix(tokens[:20] + [1, 2, 3]) == (0, [])
    other = cache.alloc()
    cache.adopt_prefix(other, n, pages)
    assert win.base[other] == w0 and cache.lengths[other] == 29
    # the adopter writes on: a shared page is copied first, in both groups
    cow0 = cache.cow_copies
    _write(cache, other, 29, 3)
    assert cache.cow_copies == cow0 + 2
    assert win.tables[other][-1] != win.tables[slot][-1]
    for s in (slot, other):
        cache.free(s)
    assert cache.pages_in_use == cache.reclaimable_pages > 0
    while cache._evict_one_entry():
        pass
    assert cache.pages_in_use == 0
    assert not np.any(win.ref_index) and not np.any(full.ref_index)


def _books(cache):
    return [(g.tables, g.base.tolist(), g.free_pages, g.ref_table.tolist(),
             g.ref_index.tolist(), g.released) for g in cache.groups] \
        + [cache.cow_copies, cache.lengths.tolist()]


@pytest.mark.parametrize("window", [8, None])
def test_a_rounds_write_map_is_each_slots_own_in_turn(window):
    """``prepare_round`` over the active slots leaves the books, and hands
    back the pages and offsets, that ``prepare_write`` of one position does
    slot by slot: pages claimed at a boundary, a shared tail copied first,
    a window's pages dropped, twice over without a step between."""
    def grown():
        cache = _grouped_cache(window=window or 8, slots=4) if window \
            else _grouped_cache(slots=4, layers=(1, 1), window=64)
        tokens = list(range(100, 122))
        first = cache.alloc()
        for start in (0, 8, 16):
            _write(cache, first, start, min(8, 22 - start))
        cache.register_prefix(first, tokens)
        second = cache.alloc()
        n, pages = cache.match_prefix(tokens)
        cache.adopt_prefix(second, n, pages)       # shares a partial tail
        third = cache.alloc()
        _write(cache, third, 0, 3)
        return cache, np.array([first, second, third])

    one, act = grown()
    many, _ = grown()
    assert _books(one) == _books(many)
    for _ in range(14):
        each = [one.prepare_write(int(s), int(one.lengths[s]), 1)
                for s in act]
        pages, offs = many.prepare_round(act)
        again = many.prepare_round(act)             # safe to repeat
        assert pages.dtype == offs.dtype == np.int32
        assert pages.shape == (2, 3) and offs.shape == (3,)
        assert pages.tolist() == again[0].tolist() == [
            [int(p[g][0]) for p, _ in each] for g in range(2)]
        assert offs.tolist() == [int(o[0]) for _, o in each]
        for cache in (one, many):
            cache.lengths[act] += 1
        assert _books(one) == _books(many)
    assert many.cow_copies >= 2
    many.lengths[act[0]] = many.max_len
    with pytest.raises(ValueError, match="overruns max_len"):
        many.prepare_round(act)


def test_a_grouped_slot_freed_twice_raises():
    cache = _grouped_cache()
    slot = cache.alloc()
    _write(cache, slot, 0, 8)
    cache.free(slot)
    with pytest.raises(ValueError, match="double-freed"):
        cache.free(slot)
    assert cache.pages_in_use == 0


def test_two_full_groups_leave_the_first_groups_books_as_one_groups():
    """A cache of one group is the cache it was before groups, and a second
    group changes nothing of the first's books: the same writes, prefix
    entries and frees give the same tables, refcounts, free list and
    reservations."""
    from hetu_tpu.serve.kv_cache import KVCacheSpec, PagedKVCache
    one = KVCacheSpec(num_layers=2, num_kv_heads=2, head_dim=4,
                      dtype=jnp.float32)
    two = KVCacheSpec(num_layers=2, num_kv_heads=2, head_dim=4,
                      dtype=jnp.float32, also=(one,))
    assert one.groups == (one,) and two.groups == (one, one)
    caches = [PagedKVCache(s, 3, 64, page_size=4) for s in (one, two)]
    tokens = list(range(50, 71))
    for cache in caches:
        a = cache.alloc()
        cache.reserve(a, 9)
        _write(cache, a, 0, 16)
        _write(cache, a, 16, 5)
        cache.register_prefix(a, tokens)
        b = cache.alloc()
        n, pages = cache.match_prefix(tokens + [1])
        cache.adopt_prefix(b, n, pages)
        _write(cache, b, n, 3)
        cache.free(a)
    first, second = caches
    assert first.k is first.groups[0].k and len(first.groups) == 1
    assert first.tables == second.tables
    assert first.groups[0].free_pages == second.groups[0].free_pages
    for name in ("ref_table", "ref_index", "_reserve", "lengths"):
        np.testing.assert_array_equal(getattr(first, name),
                                      getattr(second, name))
    assert first.available_pages() == second.available_pages(0) \
        == second.available_pages(1)
    assert first.cow_copies * 2 == second.cow_copies
    np.testing.assert_array_equal(np.asarray(first.k),
                                  np.asarray(second.groups[1].k))


def test_admission_is_by_group():
    """A request is admitted when EVERY group can hold its worst case: the
    window group's claim is its ring plus one page whatever the prompt's
    length, and a window pool with nothing left refuses what the full
    group could hold."""
    from hetu_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeModel
    model = ExaoneMoeModel(ExaoneMoeConfig(
        vocab_size=97, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=48, expert_ffn_size=16,
        n_routed_experts=8, moe_topk=2, held=(0, 4), window=8,
        max_position=128, dtype=jnp.float32, param_dtype=jnp.float32))
    e = PagedServeEngine(model, jax.jit(model.init)(jax.random.PRNGKey(0)),
                         num_slots=3, max_len=128, page_size=4,
                         prefill_chunk=8, min_bucket=4)
    full, win = e.cache.groups
    assert e.admission_pages(100, 20) == 31 + 1          # the first group's
    assert e.admission_pages(100, 20, group=1) == 5 + 1  # ring + one COW
    assert e.admission_pages(6, 1, group=1) == 2 + 1     # never past its need
    prompt = list(range(1, 41))
    assert e.admission_ok(prompt, 8)
    slot = e.alloc_slot()
    e.begin_prefill(slot, prompt, max_tokens=8)
    assert (full.reserve[slot], win.reserve[slot]) == (14, 6)
    while e.prefill_step(slot) is None:
        pass
    # the ring's claim is of pages held at once: dropped pages give it back
    assert win.reserve[slot] + len(win.tables[slot]) <= 6
    assert e.metrics.count("kv_window_released") == win.released > 0
    # nothing left in the window pool: refused, though the full group fits
    other = e.alloc_slot()
    e.cache.reserve(other, (0, win.available_pages()))
    assert full.available_pages() > 40 and not e.admission_ok(prompt, 8)
    e.release(other)
    assert e.admission_ok(prompt, 8)


# ---- state layers cost a model without them nothing (ISSUE 43) ----

def _programs_before_state_layers(engine):
    """Both programs of a cache of one group as they were written before
    state layers existed (under the names they had, which a jaxpr shows):
    four arguments, nothing of a state."""
    model = engine.model
    k_row, v_row = engine.cache.spec.row_shapes()
    n_table = engine.cache.pages_per_slot

    def hetu_serve_decode(params, k_pool, v_pool, aux):
        n_pg = aux.shape[1] - 4
        tables = aux[:, :n_pg]
        lengths = aux[:, n_pg]
        tokens = aux[:, n_pg + 1]
        wpage = aux[:, n_pg + 2:n_pg + 3]
        woff = aux[:, n_pg + 3:n_pg + 4]
        k = PagedLayers(k_pool, tables, wpage, woff, k_row, False)
        v = PagedLayers(v_pool, tables, wpage, woff, v_row, False)
        logits, k, v, *stats = model.decode_with_cache(
            {"params": params, "state": {}}, tokens, k, v, lengths)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return k.pool, v.pool, nxt, tuple(stats)

    def hetu_serve_prefill_chunk(params, k_pool, v_pool, aux):
        sc = (aux.shape[0] - n_table - 2) // 3
        ids = aux[:sc][None]
        wpage = aux[sc:2 * sc][None]
        woff = aux[2 * sc:3 * sc][None]
        table = aux[3 * sc:3 * sc + n_table][None]
        start = aux[3 * sc + n_table]
        last = aux[3 * sc + n_table + 1]
        k = PagedLayers(k_pool, table, wpage, woff, k_row)
        v = PagedLayers(v_pool, table, wpage, woff, v_row)
        logits, k, v, *stats = model.prefill_chunk_with_cache(
            {"params": params, "state": {}}, ids, k, v, start,
            last_index=last)
        tok = jnp.argmax(logits[0], -1).astype(jnp.int32)
        return k.pool, v.pool, tok, tuple(stats)

    return {"decode": jax.jit(hetu_serve_decode, donate_argnums=(1, 2)),
            "chunk": jax.jit(hetu_serve_prefill_chunk,
                             donate_argnums=(1, 2))}


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_a_model_without_state_layers_keeps_its_programs(kind, program, gpt,
                                                         llama):
    """The engine's program of a model with no state layers takes four
    arguments and traces to the jaxpr of the program as it was written
    before state layers: no argument, operand column or equation more."""
    model, variables = gpt if kind == "gpt" else llama
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=8, prefill_chunk=16)
    assert engine.cache.state is None and not engine._states
    n_pg = engine.cache.pages_per_slot
    if program == "decode":
        fn, aux = engine._build_decode(), (4, n_pg + 4)
    else:
        fn, aux = engine._build_chunk(n_pg), (3 * 16 + n_pg + 2,)
    args = (engine.params, engine.cache.k, engine.cache.v,
            jax.ShapeDtypeStruct(aux, np.int32))
    now = jax.make_jaxpr(fn)(*args)
    before = jax.make_jaxpr(
        _programs_before_state_layers(engine)[program])(*args)
    assert len(now.jaxpr.invars) == len(before.jaxpr.invars)
    assert str(now) == str(before)


# ------------------------------ compressed rows beside the pages (ISSUE 56)

def _comp_spec(**kw):
    return KVCacheSpec(num_layers=2, num_kv_heads=2, head_dim=8,
                       dtype=jnp.float32, comp_stride=2, **kw)


def test_compressed_rows_are_a_part_of_the_page_group():
    """A spec with ``comp_stride`` counts a token's share of a compressed
    row, and the cache holds a third pool of ``page_size // stride`` rows a
    page under the group's own tables; the programs take K as the pair."""
    spec = _comp_spec()
    assert spec.bytes_per_token == 2 * 2 * (8 + 8) * 4 + 2 * 2 * 8 * 4 // 2
    assert KVCacheSpec(num_layers=2, num_kv_heads=2, head_dim=8,
                       dtype=jnp.float32).bytes_per_token == 2 * 2 * 16 * 4
    cache = PagedKVCache(spec, 2, 32, page_size=8,
                         max_prefix_entries=0)
    group = cache.groups[0]
    assert group.comp.shape == (2, cache.num_pages, 4, 16)
    k, v = cache.pool_args()
    assert k[0] is group.k and k[1] is group.comp and v is group.v
    cache.update((k[0] + 1, k[1] + 2), v + 3)
    assert float(cache.k[0, 0, 0, 0]) == 1 and float(group.comp[0, 0, 0, 0]) == 2
    with pytest.raises(ValueError, match="multiple"):
        PagedKVCache(spec, 2, 32, page_size=7, max_prefix_entries=0)


@pytest.mark.parametrize("index,valid", [([0, 3, 4, 9], [1, 1, 1, 1]),
                                         ([2, 5, 7, 11], [1, 0, 1, 0])])
def test_a_compressed_row_lands_in_its_page_by_the_tables(index, valid):
    """Row ``i`` of a sequence is row ``i % 4`` of its ``i // 4``-th page;
    one no real token completed lands in the scratch page; what is read back
    by the tables is the sequence's rows in order."""
    tables = jnp.asarray([[5, 2, 7], [3, 6, 1]], jnp.int32)
    z = jnp.zeros((2, 1), jnp.int32)
    layers = PagedLayers(jnp.zeros((2, 8, 8, 16)), tables, z, z, (2, 8),
                         comp=jnp.zeros((2, 8, 4, 16)))
    rows = jnp.arange(2 * 4 * 16, dtype=jnp.float32).reshape(2, 4, 16) + 1
    idx = jnp.asarray([index, index], jnp.int32)
    ok = jnp.asarray([valid, valid], bool)
    out = layers.write_comp(1, rows, idx, ok)
    got = np.asarray(out.read_comp(1))                   # [2, 12, 2, 8]
    assert got.shape == (2, 12, 2, 8)
    for b in range(2):
        for j, (i, real) in enumerate(zip(index, valid)):
            want = np.asarray(rows[b, j]).reshape(2, 8) if real else 0.0
            np.testing.assert_array_equal(got[b, i], want)
    assert not np.asarray(out.comp[0]).any()             # layer 0 untouched
    assert np.asarray(out.comp[1, 0]).any() == (not all(valid))
    assert out.held()[1] is out.comp and layers.pool is out.pool


def test_copy_on_write_carries_a_pages_compressed_rows():
    cache = PagedKVCache(_comp_spec(), 2, 32, page_size=8)
    g = cache.groups[0]
    a = cache.alloc()
    pages, _ = cache.prepare_write(a, 0, 8)
    page = int(pages[0][0])
    g.k = g.k.at[:, page].set(1.0)
    g.comp = g.comp.at[:, page].set(2.0)
    g.ref_index[page] += 1              # an index entry shares the page
    new = cache._cow(a, 0)
    assert new != page and cache.cow_copies == 1
    assert float(g.k[1, new, 3, 0]) == 1.0
    assert float(g.comp[1, new, 3, 0]) == 2.0


def test_slots_with_compressed_rows_are_not_exported():
    from hetu_tpu.serve.kv_cache import GroupedCacheNotPortable

    cache = PagedKVCache(_comp_spec(), 2, 32, page_size=8,
                         max_prefix_entries=0)
    with pytest.raises(GroupedCacheNotPortable, match="compressed"):
        cache.export_slots([cache.alloc()])


def test_padding_rows_of_a_round_are_not_sequences():
    rows = ((jnp.zeros((4, 2)), jnp.zeros((4, 2))),)
    st = SlotStates(rows, jnp.asarray([0, 2, 3, 3], jnp.int32))
    assert np.asarray(st.real).tolist() == [True, True, False, False]
    one = SlotStates(jnp.zeros((2, 4, 2)), jnp.asarray([3, 1], jnp.int32))
    assert np.asarray(one.real).tolist() == [False, True]


@pytest.mark.parametrize("comp_dim,v_head_dim", [(6, 0), (6, None),
                                                  (None, 0)])
def test_compressed_rows_of_their_own_width_and_a_pool_of_no_width(
        comp_dim, v_head_dim):
    """A latent layer keeps ONE array a token (``v_head_dim`` 0: the V pool
    is of no width and the programs carry it empty) and compressed rows of
    a width of their own (``comp_dim``: a lightning indexer's pooled keys),
    read back flat; the bytes a token count both; a page's copy-on-write
    carries them."""
    spec = KVCacheSpec(num_layers=2, num_kv_heads=1, head_dim=16,
                       v_head_dim=v_head_dim, dtype=jnp.float32,
                       comp_stride=4, comp_dim=comp_dim)
    wide = 16 if comp_dim is None else comp_dim
    v_wide = 16 if v_head_dim is None else 0
    assert (spec.comp_width, spec.v_dim) == (wide, v_wide)
    assert spec.bytes_per_token == 2 * 4 * (16 + v_wide) + 2 * wide * 4 // 4
    cache = PagedKVCache(spec, 2, 32, page_size=8, max_prefix_entries=0)
    group = cache.groups[0]
    assert group.comp.shape == (2, cache.num_pages, 2, wide)
    assert group.v.shape == (2, cache.num_pages, 8, v_wide)
    tables = jnp.asarray([[3, 1], [2, 4]], jnp.int32)
    z = jnp.zeros((2, 1), jnp.int32)
    layers = PagedLayers.over((group.k, group.comp), tables, z, z, (1, 16))
    rows = jnp.arange(2 * 3 * wide, dtype=jnp.float32).reshape(2, 3, wide) + 1
    layers = layers.write_comp(1, rows, jnp.asarray([[0, 1, 3]] * 2),
                               jnp.ones((2, 3), bool))
    back = layers.read_comp(1, row=None if comp_dim is None else (wide,))
    assert back.shape == ((2, 4, 1, 16) if comp_dim is None else (2, 4, wide))
    back = np.asarray(back).reshape(2, 4, wide)
    np.testing.assert_array_equal(back[:, [0, 1, 3]], np.asarray(rows))
    assert not back[:, 2].any()


def test_a_state_part_may_state_how_many_layers_keep_it():
    """``state_parts``' fourth value: the part is kept by that many layers,
    not by every state layer (the open group of a row-selecting layer's
    pooled keys lives on the layers that hold paged rows); the bytes a slot
    and the cache's arrays follow."""
    spec = KVCacheSpec(
        num_layers=1, num_kv_heads=1, head_dim=8, dtype=jnp.float32,
        state_layers=3, state_parts=(("conv", (6,), jnp.float32),
                                     ("delta", (2, 4, 4), jnp.float32),
                                     ("open", (5,), jnp.float32, 1)))
    assert spec.part_layers == (3, 3, 1)
    assert [n for n, _, _ in spec.parts] == ["conv", "delta", "open"]
    assert spec.part_bytes_per_slot == {"conv": 3 * 6 * 4,
                                        "delta": 3 * 32 * 4, "open": 5 * 4}
    assert spec.bytes_per_slot == 72 + 384 + 20
    cache = PagedKVCache(spec, 2, 16, page_size=8)
    assert [len(part) for part in cache.state] == [3, 3, 1]
    assert cache.state[2][0].shape == (3, 5)
    assert cache.state_bytes == 3 * (72 + 384 + 20)
    # three-value parts read as before
    plain = KVCacheSpec(num_layers=1, num_kv_heads=1, head_dim=8,
                        state_layers=2,
                        state_parts=(("a", (3,), jnp.float32),))
    assert plain.part_layers == (2,)
