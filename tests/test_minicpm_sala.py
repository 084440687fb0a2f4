"""MiniCPM-SALA against its plain reference
(``benchmarks/reference/minicpm_sala.py``) at small widths on the CPU, seeded
weights at the configuration's own rule of stds, every comparison one of
LOGITS: (a) the dense forward, float32 and bfloat16, with the sparse branch
LIVE (a toy ``dense_len`` and ``topk`` below the blocks a query sees), and a
CONTROL that fails for each thing the comparison must see; (b) chunked
prefill (several chunks, a padded last one, compressed windows that straddle
pages, chunks and rounds) then rounds through ``PagedServeEngine`` over
compressed rows beside the pages and state layers, prompts over and under
the dense length and one that crosses it while decoding; (c) the compressed
rows the engine holds are the sequence's, and are freed and reused with
their pages; (d) a decode round's sparse layer walks at most ``topk`` pages
a (slot, KV head, layer); (e) a long decode with the rule's state in
float32 and, failing, in bfloat16."""

import math
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
from hetu_tpu.models.block import (  # noqa: E402
    SPARSE_STATS, ChosenBlocks, GroupedHeads,
)
from hetu_tpu.models.minicpm_sala import (  # noqa: E402
    LIGHTNING, SPARSE, MiniCPMSALAConfig, MiniCPMSALAModel,
)
from hetu_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from paged_programs import LogitsOut  # noqa: E402

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 97
TOY = ChosenBlocks(stride=2, kernel=4, block=8, topk=6, init_blocks=1,
                   local=8, dense_len=48)


def tiny(**kw) -> MiniCPMSALAConfig:
    """Small widths in the published ratios: a sparse layer of grouped heads
    (4 | 2) at both ends of two Lightning layers (4 | 4), cut from the middle
    of a deeper published model; blocks of 8 that are the pages, 6 chosen,
    dense under 48."""
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=4,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE), num_heads=4,
        num_kv_heads=2, head_dim=8, lightning_heads=4, lightning_kv_heads=4,
        lightning_head_dim=8, lightning_chunk=8, ffn_size=64, sparse=TOY,
        published_layers=8, first_layer=2, max_position=512,
        dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return MiniCPMSALAConfig(**base)


def dims_of(c: MiniCPMSALAConfig) -> dict:
    sp = c.sparse
    return dict(
        head_dim=c.head_dim, theta=c.rope_theta, eps=c.rms_eps,
        lightning_heads=c.lightning_heads, scale_emb=c.scale_emb,
        branch=c.scale_depth / math.sqrt(c.published_layers),
        dim_model_base=c.dim_model_base,
        published_layers=c.published_layers, first_layer=c.first_layer,
        mixer_types=c.mixer_types, stride=sp.stride, kernel=sp.kernel,
        block=sp.block, topk=sp.topk, init_blocks=sp.init_blocks,
        local=sp.local, dense_len=sp.dense_len)


def make(seed=1, **kw):
    model = MiniCPMSALAModel(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def sala():
    return make()


def ref_logits(model, params, ids, prompt_len=None):
    dims = dims_of(model.c)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, x: ref.logits(p, x, dims, prompt_len))(
                params, np.asarray(ids)))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(got - want)) / (want.max() - want.min()))


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


def engine_of(model, variables, **kw):
    """An engine whose two programs hand their logits on as their counts,
    and the list they land in, one entry a call: [B, V]."""
    kw = {"num_slots": 3, "max_len": 160, "page_size": 8,
          "prefill_chunk": 16, "min_bucket": 4, **kw}
    engine = PagedServeEngine(LogitsOut(model), variables, **kw)
    calls = []
    engine._count = lambda stats: calls.append(np.asarray(stats[0]))
    return engine, calls


def served_logits(engine, calls, prompt, n: int):
    """The logits behind the first token and ``n - 1`` decoded ones of one
    request with the engine to itself ([n, V]), its tokens and its slot
    (still held)."""
    slot = engine.alloc_slot()
    toks = [engine.prefill(slot, prompt)]
    rows = [calls[-1][0]]
    for _ in range(n - 1):
        toks.append(engine.decode()[slot])
        rows.append(calls[-1][0])
    return np.stack(rows), toks, slot


# ---- (a) the dense forward, and what the comparison must see ----

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, 0.2)])
def test_dense_forward_equals_the_reference(dtype, tol):
    """100 tokens over a dense length of 48: every token chooses 6 of up to
    13 blocks.  bfloat16 against the float32 reference over the same
    (bfloat16) weights: at these widths a block exchanged at a near tie
    moves a row, so the limit is wide; the chip's readings at the published
    widths set the cell's (PERF.md)."""
    model, variables = make(dtype=dtype, param_dtype=dtype)
    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 100))
    got = np.asarray(model.apply(variables, jnp.asarray(ids))[0]
                     .astype(jnp.float32))
    err = rel_err(got, ref_logits(model, variables["params"], ids))
    assert err < tol
    assert dtype == jnp.float32 or err > 1e-4


@pytest.mark.parametrize("n,prompt_len", [(30, None), (100, 40), (100, 47),
                                          (60, 48)])
def test_the_dense_switch_is_by_the_prompts_length(sala, n, prompt_len):
    """A short sequence whole (dense), a short prompt with generated tokens
    that cross the dense length, one at its edge, and a prompt just over it
    (sparse from its first token)."""
    model, variables = sala
    ids = np.random.default_rng(n).integers(0, VOCAB, (1, n))
    got = np.asarray(model.apply(
        variables, jnp.asarray(ids), prompt_len=None if prompt_len is None
        else jnp.asarray([prompt_len]))[0])
    assert rel_err(got, ref_logits(model, variables["params"], ids,
                                   prompt_len)) < F32_TOL


def _forced_only(q, comp, pos, **how):
    return _SELECT(q, comp, pos, **{**how, "topk": 3})


_SELECT = ops.select_blocks


def _wrong(monkeypatch, what: str):
    """One thing of the forward made wrong; returns the model's keywords."""
    if what == "forced blocks only":
        monkeypatch.setattr(ops, "select_blocks", _forced_only)
    elif what == "rotation on a sparse layer":
        init = MiniCPMSALAModel.__init__

        def rotated(self, config):
            init(self, config)
            self.rotated = frozenset(self.sparse_layers)
        monkeypatch.setattr(MiniCPMSALAModel, "__init__", rotated)
    elif what == "no decay":
        monkeypatch.setattr(MiniCPMSALAConfig, "decay_rates",
                            lambda self, l: jnp.zeros(
                                (self.lightning_heads,), jnp.float32))
    elif what == "a missing gate":
        out = GroupedHeads._out
        monkeypatch.setattr(GroupedHeads, "_out",
                            lambda self, p, l, o, gate=None: out(self, p, l,
                                                                 o))
    elif what == "a wrong constant factor":
        return {"scale_depth": 1.0}
    elif what == "the layer factor by the held index":
        return {"first_layer": 0}
    return {}



@pytest.mark.parametrize("what", [
    "forced blocks only", "rotation on a sparse layer", "no decay",
    "a missing gate", "a wrong constant factor",
    "the layer factor by the held index"])
def test_the_comparison_fails_when_the_forward_is_wrong(monkeypatch, what):
    """Each is far over the float32 tolerance AND over the bfloat16 reading,
    so the comparison sees it in either precision."""
    kw = _wrong(monkeypatch, what)
    model, variables = make(**kw)
    right, _ = make()
    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 100))
    got = np.asarray(model.apply(variables, jnp.asarray(ids))[0])
    err = rel_err(got, ref_logits(right, variables["params"], ids))
    assert err > 0.02, (what, err)


# ---- (b) the engine: chunks, rounds, compressed rows, state ----

@pytest.mark.parametrize("n_prompt,n", [(100, 9), (30, 6), (61, 7),
                                        (44, 9), (48, 5)])
def test_prefill_in_chunks_then_decode_equals_the_reference(sala, n_prompt,
                                                            n):
    """Chunks of 16 over pages of 8 and compressed windows of 4 at stride 2
    (every third window straddles a page, every eighth a chunk), a padded
    last chunk, then rounds that complete windows one at a time; 44 tokens
    cross the dense length of 48 while decoding, 48 start at it."""
    model, variables = sala
    engine, calls = engine_of(model, variables)
    prompt = prompt_of(n_prompt, seed=n_prompt)
    got, toks, slot = served_logits(engine, calls, prompt, n)
    ids = np.asarray([prompt + toks])
    want = ref_logits(model, variables["params"], ids, n_prompt)[0]
    assert rel_err(got, want[n_prompt - 1:n_prompt - 1 + n]) < F32_TOL
    # (c) the compressed rows the cache holds are the sequence's own
    cache = engine.cache
    kc = cache.groups[0]
    table = np.asarray(cache.tables[slot])
    held = len(prompt) + n - 1                  # rows written
    for layer in range(2):
        rows = np.asarray(kc.k)[layer, table].reshape(-1, 2, 8)[:held]
        comp = np.asarray(kc.comp)[layer, table].reshape(-1, 2, 8)
        whole = (held - 4) // 2 + 1
        want_c = np.stack([rows[2 * i:2 * i + 4].mean(0)
                           for i in range(whole)])
        np.testing.assert_allclose(comp[:whole], want_c, rtol=1e-5,
                                   atol=1e-5)


def test_slots_in_flight_together_and_handed_on(sala):
    """Four requests over three slots through the scheduler: long and short
    together in one round (a mixed round hands the short ones their whole
    tables), a slot handed on with its pages, compressed rows and state."""
    model, variables = sala
    engine = PagedServeEngine(model, variables, num_slots=3, max_len=160,
                              page_size=8, prefill_chunk=16)
    reqs = [Request(prompt=prompt_of(n, seed=n), max_tokens=9)
            for n in (100, 30, 61, 44, 90)]
    ContinuousBatchingScheduler(engine).run(reqs)
    for r in reqs:
        assert r.status == "ok"
        ids = np.asarray([list(r.prompt) + list(r.tokens)])
        want = ref_logits(model, variables["params"], ids, len(r.prompt))[0]
        n = len(r.prompt)
        gaps = [float(want[n - 1 + j].max() - want[n - 1 + j][t])
                for j, t in enumerate(r.tokens)]
        assert max(gaps) < 1e-3 * (want.max() - want.min())
    # nothing is held once every request is done
    assert engine.cache.pages_in_use == 0


def test_compressed_rows_are_freed_and_reused_with_their_pages(sala):
    """A second request in the pages (and the slot) a first one left reads
    none of its compressed rows: its logits are those of an engine that
    never saw the first."""
    model, variables = sala
    engine, calls = engine_of(model, variables, num_slots=1)
    first, _, slot = served_logits(engine, calls, prompt_of(100, 1), 4)
    pages = set(engine.cache.tables[slot])
    engine.release(slot)
    again, toks, slot = served_logits(engine, calls, prompt_of(70, 2), 6)
    assert set(engine.cache.tables[slot]) <= pages
    fresh, calls2 = engine_of(model, variables, num_slots=1)
    alone, toks2, _ = served_logits(fresh, calls2, prompt_of(70, 2), 6)
    np.testing.assert_allclose(again, alone, rtol=1e-5, atol=1e-5)
    assert toks == toks2


# ---- (d) what a round reads ----

def test_a_round_past_the_dense_length_walks_topk_pages_at_most(sala):
    """``sparse_pages_read`` on a decode round: at most ``topk`` pages a
    (slot, KV head, sparse layer) whatever the slots hold, under
    ``pages_held``; a round of short sequences counts dense queries and
    reads no chosen pages."""
    model, variables = sala
    engine = PagedServeEngine(model, variables, num_slots=3, max_len=160,
                              page_size=8, prefill_chunk=16)
    seen = []
    count = engine._count
    engine._count = lambda stats: seen.append(count(stats)) or seen[-1]
    slots = [engine.alloc_slot() for _ in range(3)]
    for s, n in zip(slots, (130, 99, 20)):
        engine.prefill(s, prompt_of(n, seed=n))
    seen.clear()
    engine.decode()
    ids = seen[-1]
    assert set(ids) == set(SPARSE_STATS)
    layers, g, topk = 2, 2, 6
    assert ids["sparse_queries"] == 2 * layers
    assert ids["dense_queries"] == 1 * layers
    assert ids["sparse_pages_read"] == 2 * layers * g * topk
    assert ids["pages_held"] == layers * g * (130 // 8 + 1 + 99 // 8 + 1)
    assert ids["blocks_chosen"] == ids["sparse_pages_read"]
    assert ids["blocks_visible"] == ids["pages_held"]
    engine.release(slots[0])
    engine.release(slots[1])
    seen.clear()
    engine.decode()
    assert seen[-1]["sparse_pages_read"] == 0
    assert seen[-1]["dense_queries"] == layers


def test_a_chunk_counts_what_its_queries_chose(sala):
    model, variables = sala
    engine = PagedServeEngine(model, variables, num_slots=1, max_len=160,
                              page_size=8, prefill_chunk=16)
    slot = engine.alloc_slot()
    engine.prefill(slot, prompt_of(70, 3))
    got = engine.metrics.snapshot()
    visible = sum(t // 8 + 1 for t in range(70)) * 2 * 2
    chosen = sum(min(6, t // 8 + 1) for t in range(70)) * 2 * 2
    assert got["sparse_queries"] == 70 * 2 and got["dense_queries"] == 0
    assert got["blocks_visible"] == visible
    assert got["blocks_chosen"] == chosen
    assert got["sparse_pages_read"] == 0


def test_a_chunks_sparse_layers_take_the_kernel_where_the_rule_says(
        sala, monkeypatch):
    """A chunk program's ``sparse.plan`` says ``kernel`` when the rule's
    conditions hold (``ops.sparse_kernel_why``: a view longer than
    ``KEY_BLOCK``, a TPU backend; forced here, the kernels in interpret
    mode) and ``masked`` otherwise, and ``sparse_kernel_queries`` counts the
    chunks' choosing queries then, none otherwise; the first tokens are the
    same either way."""
    model, variables = sala
    att = sys.modules["hetu_tpu.ops.attention"]
    plans = []
    monkeypatch.setattr(att.trace, "instant",
                        lambda name, attrs=None, cat="hetu":
                        plans.append((name, attrs)))
    first = {}
    for forced in (False, True):
        if forced:
            monkeypatch.setattr(att, "KEY_BLOCK", 32)
            monkeypatch.setattr(att, "_default_backend_is_tpu", lambda: True)
        plans.clear()
        engine = PagedServeEngine(model, variables, num_slots=2, max_len=160,
                                  page_size=8, prefill_chunk=16)
        slots = [engine.alloc_slot(), engine.alloc_slot()]
        first[forced] = [engine.prefill(s, prompt_of(n, 3))
                         for s, n in zip(slots, (70, 30))]
        forms = {(a["form"], a["why"]) for n, a in plans
                 if n == "sparse.plan" and a["queries"] > 1}
        assert forms == ({("kernel", "")} if forced
                         else {("masked", "short")})
        got = engine.metrics.snapshot()
        # the prompt of 70 chooses (dense under 48), two sparse layers
        assert got["sparse_queries"] == 70 * 2
        assert got["dense_queries"] == 30 * 2
        assert got["sparse_kernel_queries"] == (70 * 2 if forced else 0)
        engine.decode()                        # a round never takes it
        assert engine.metrics.snapshot()["sparse_kernel_queries"] \
            == got["sparse_kernel_queries"]
    assert first[True] == first[False]


# ---- (e) the state's precision ----

def test_a_bfloat16_state_loses_a_long_decode():
    """256 rounds after a 40-token prompt, the rule's matrix float32 as
    stated and bfloat16: the slow heads of the late layers (a decay of a
    thousandth a row) are fed increments bfloat16 drops.  The sparse layer
    comes LAST here: a greedy decode of random weights falls into a loop of
    tokens, and an unrotated sparse layer fed by the embedding alone then
    sees the same keys at several blocks, whose scores tie to the last bit
    and whose order is the implementation's (``PERF.md`` section 7); behind
    Lightning layers a block's keys carry their history and nothing
    ties."""
    errs = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        model, variables = make(state_dtype=dtype, first_layer=4,
                                mixer_types=(LIGHTNING,) * 3 + (SPARSE,))
        engine, calls = engine_of(model, variables, num_slots=1,
                                  max_len=320)
        prompt = prompt_of(40, seed=9)
        got, toks, _ = served_logits(engine, calls, prompt, 256)
        want = ref_logits(model, variables["params"],
                          np.asarray([prompt + toks]), 40)[0][39:39 + 256]
        errs[name] = rel_err(got[-64:], want[-64:])
    assert errs["float32"] < 5e-4, errs
    assert errs["bfloat16"] > 10 * errs["float32"], errs


def test_an_engine_is_freed_when_dropped_not_when_the_collector_runs(sala):
    """The two programs close over values, never over the engine: an engine
    that is dropped frees its pools at once (a tool that builds one engine a
    seed at 15 GB each has no room for two), with the collector off."""
    import gc
    import weakref

    model, variables = sala
    gc.collect()
    gc.disable()
    try:
        engine = PagedServeEngine(model, variables, num_slots=2, max_len=64,
                                  page_size=8, prefill_chunk=16)
        slot = engine.alloc_slot()
        engine.prefill(slot, prompt_of(20))
        engine.decode()
        held = weakref.ref(engine)
        del engine
        assert held() is None
    finally:
        gc.enable()
