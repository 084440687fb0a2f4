"""LFM2's expert model against its plain reference
(``benchmarks/reference/lfm2_moe.py``) at small widths on the CPU, seeded
weights, every comparison one of LOGITS: the dense forward (float32 and
bfloat16), chunked prefill and decode through ``PagedServeEngine`` over a
cache of one page group and STATE LAYERS (chunks that do and do not divide
the prompt, so a padded chunk must leave the state after its last real
token), requests in flight together with a slot handed on mid-run, a decode
bucket wider than the active slots, the prefix index that such an engine
does not have, and a preempted request that prefills again."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from hetu_tpu.layers.moe import MOE_STATS  # noqa: E402
from hetu_tpu.models.lfm2_moe import (  # noqa: E402
    CONV, FULL, Lfm2MoeConfig, Lfm2MoeModel,
)
from hetu_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.serve.kv_cache import (  # noqa: E402
    GroupedCacheNotPortable, KVCacheSpec, PagedKVCache, SlotStates,
)
from paged_programs import LogitsOut  # noqa: E402

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 97
# the cut's own order: a leading dense conv layer, then attention, conv, conv
KINDS = (CONV, FULL, CONV, CONV, FULL, CONV)


def tiny(**kw) -> Lfm2MoeConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=len(KINDS), num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=64, expert_ffn_size=16,
        first_dense=1, n_routed_experts=8, moe_topk=2, layer_types=KINDS,
        max_position=64, dtype=jnp.float32, param_dtype=jnp.float32,
        init_std=0.2, router_init_std=0.5, router_bias_std=0.05,
        expert_block_rows=4)
    base.update(kw)
    return Lfm2MoeConfig(**base)


def dims_of(c: Lfm2MoeConfig) -> dict:
    return dict(heads=c.num_heads, kv_heads=c.num_kv_heads,
                head_dim=c.head_dim, layer_types=c.layer_types,
                first_dense=c.first_dense, topk=c.moe_topk,
                scaling=c.routed_scaling_factor, held=c.held,
                theta=c.rope_theta, eps=c.rms_eps)


def make(seed=1, **kw):
    model = Lfm2MoeModel(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def lfm2():
    return make()


def ref_logits(model, params, ids):
    dims = dims_of(model.c)
    return np.asarray(jax.jit(lambda p, x: ref.logits(p, x, dims))(
        params, np.asarray(ids)))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(got - want)) / (want.max() - want.min()))


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


def engine_of(model, variables, **kw):
    """An engine whose two programs hand their logits on as their counts,
    and the list they land in, one entry a call: [B, V]."""
    kw = {"num_slots": 4, "max_len": 64, "page_size": 4, "prefill_chunk": 8,
          "min_bucket": 4, **kw}
    engine = PagedServeEngine(LogitsOut(model), variables, **kw)
    calls = []
    engine._count = lambda stats: calls.append(np.asarray(stats[0]))
    return engine, calls


def served_logits(engine, calls, prompt, n: int):
    """The logits behind the first token and ``n - 1`` decoded ones of one
    request with the engine to itself ([n, V]), and its tokens."""
    slot = engine.alloc_slot()
    toks = [engine.prefill(slot, prompt)]
    rows = [calls[-1][0]]
    for _ in range(n - 1):
        toks.append(engine.decode()[slot])
        rows.append(calls[-1][0])
    engine.release(slot)
    return np.stack(rows), toks


# ---- (a) the dense forward ----

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, 0.15)])
def test_dense_forward_equals_the_reference(dtype, tol):
    """bfloat16 against the float32 reference over the same (bfloat16)
    weights: at these widths a choice flipped by rounding moves a logit by
    several percent of the range, so the limit is wide; the chip's readings
    at the published widths set the cell's (PERF.md)."""
    model, variables = make(dtype=dtype, param_dtype=dtype)
    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 21))
    got = np.asarray(model.apply(variables, jnp.asarray(ids))[0]
                     .astype(jnp.float32))
    assert rel_err(got, ref_logits(model, variables["params"], ids)) < tol


def test_the_model_states_one_page_group_and_its_state_layers(lfm2):
    model, _ = lfm2
    spec = model.kv_cache_spec()
    assert len(spec.groups) == 1 and spec.num_layers == KINDS.count(FULL)
    assert (spec.state_layers, spec.state_shape) == (KINDS.count(CONV),
                                                     (2, 32))
    assert spec.bytes_per_slot == 4 * 2 * 32 * 4
    assert KVCacheSpec(2, 2, 8).bytes_per_slot == 0
    assert model.step_stats == MOE_STATS + ("moe_experts", "moe_grouped")


# ---- (b) chunks, padded and not, then decode ----

@pytest.mark.parametrize("n,chunk", [(13, 8), (16, 8), (5, 8), (13, 16),
                                     (21, 4)])
def test_chunked_prefill_and_decode_equal_the_reference(lfm2, n, chunk):
    """13 by 8: the second chunk is 5 real tokens in a bucket of 8, and the
    state it leaves must be the one after the fifth."""
    model, variables = lfm2
    engine, calls = engine_of(model, variables, prefill_chunk=chunk)
    prompt = prompt_of(n, seed=n)
    got, toks = served_logits(engine, calls, prompt, 6)
    want = ref_logits(model, variables["params"], [prompt + toks])[0]
    assert rel_err(got, want[n - 1:n + 5]) < F32_TOL


def test_bfloat16_serving_stays_near_the_reference():
    model, variables = make(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    engine, calls = engine_of(model, variables)
    prompt = prompt_of(13, seed=5)
    got, toks = served_logits(engine, calls, prompt, 5)
    want = ref_logits(model, variables["params"], [prompt + toks])[0]
    assert 1e-4 < rel_err(got.astype(np.float32), want[12:17]) < 0.15


# ---- (c) requests in flight together, a slot handed on mid-run ----

def test_a_reused_slot_starts_from_nothing(lfm2):
    model, variables = lfm2
    engine, calls = engine_of(model, variables)
    prompts = [prompt_of(n, seed=10 + n) for n in (5, 13, 9, 21)]
    slots = [engine.alloc_slot() for _ in prompts]
    for s, p in zip(slots, prompts):
        engine.prefill(s, p)
    for _ in range(3):
        engine.decode()
    # one finishes; its slot goes to a newcomer while three still decode
    engine.release(slots[1])
    new = engine.alloc_slot()
    assert new == slots[1]
    late = prompt_of(11, seed=99)
    toks = [engine.prefill(new, late)]
    rows = [calls[-1][0]]
    for _ in range(4):
        out = engine.decode()
        toks.append(out[new])
        # a round's rows are the active slots in order
        rows.append(calls[-1][sorted(out).index(new)])
    alone_engine, alone_calls = engine_of(model, variables)
    alone, alone_toks = served_logits(alone_engine, alone_calls, late, 5)
    assert toks == alone_toks
    np.testing.assert_allclose(np.stack(rows), alone, atol=1e-5)
    want = ref_logits(model, variables["params"], [late + toks])[0]
    assert rel_err(np.stack(rows), want[10:15]) < F32_TOL


def test_requests_in_flight_together_each_equal_the_reference(lfm2):
    model, variables = lfm2
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=4, prefill_chunk=8, min_bucket=4)
    reqs = [Request(prompt=prompt_of(n, seed=n), max_tokens=5)
            for n in (5, 13, 9, 21, 7, 16)]       # six over four slots
    ContinuousBatchingScheduler(engine).run(reqs)
    for r in reqs:
        assert r.status == "ok"
        want = ref_logits(model, variables["params"],
                          [list(r.prompt) + r.tokens])[0]
        n = len(r.prompt)
        assert r.tokens == np.argmax(want[n - 1:n + 4], -1).tolist()
    assert engine.metrics.count("state_resets") == 6
    assert engine.metrics.count("moe_absent") == 0
    assert engine.metrics.count("moe_experts") % (8 * 5) == 0


# ---- (d) a decode bucket wider than the active slots ----

def test_a_decode_round_leaves_idle_slots_state_alone(lfm2):
    model, variables = lfm2
    engine, _ = engine_of(model, variables, num_slots=8)
    slots = [engine.alloc_slot() for _ in range(3)]
    for s, n in zip(slots, (5, 9, 13)):
        engine.prefill(s, prompt_of(n, seed=n))
    cache = engine.cache
    idle = [s for s in range(8) if s not in slots]
    cache.state = cache.state.at[:, np.asarray(idle)].set(7.0)
    before = np.asarray(cache.state)
    engine.decode()              # three rows in a bucket of four: one pad
    after = np.asarray(cache.state)
    np.testing.assert_array_equal(after[:, idle], before[:, idle])
    assert not np.array_equal(after[:, slots], before[:, slots])
    # the padding row went to the scratch slot, and nowhere else
    assert cache.state.shape[1] == 8 + 1


def test_slot_states_read_zeros_where_fresh_and_pad_to_scratch():
    rows = jnp.arange(2 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 3)
    st = SlotStates(rows, jnp.asarray([2, 0]), jnp.asarray([True, False]))
    got = np.asarray(st.read(1))
    np.testing.assert_array_equal(got[0], 0.0)
    np.testing.assert_array_equal(got[1], np.asarray(rows[1, 0]))
    out = st.write(0, jnp.ones((2, 3)))
    assert np.asarray(out.rows[0, [0, 2]]).min() == 1.0
    np.testing.assert_array_equal(np.asarray(out.rows[0, [1, 3]]),
                                  np.asarray(rows[0, [1, 3]]))
    np.testing.assert_array_equal(np.asarray(out.rows[1]),
                                  np.asarray(rows[1]))


# ---- (e) no prefix match over state layers ----

def test_the_same_prompt_twice_takes_no_prefix_match(lfm2):
    model, variables = lfm2
    engine, calls = engine_of(model, variables)
    prompt = prompt_of(21, seed=4)          # five whole pages and a tail
    once, toks = served_logits(engine, calls, prompt, 4)
    n_calls = len(calls)
    twice, toks2 = served_logits(engine, calls, prompt, 4)
    assert toks2 == toks
    np.testing.assert_allclose(twice, once, atol=1e-5)
    assert len(calls) == 2 * n_calls        # every chunk ran again
    m = engine.metrics
    assert m.count("prefix_hits") == 0 and engine.cache.prefix_entries == 0
    assert m.count("prefix_state_refusals") == 2
    assert m.count("prefix_miss_tokens") == 2 * len(prompt)
    want = ref_logits(model, variables["params"], [prompt + toks])[0]
    assert rel_err(twice, want[20:24]) < F32_TOL


# ---- (f) live slots are not exported without their state ----

def test_export_and_import_refuse_a_cache_with_state_layers(lfm2):
    model, variables = lfm2
    engine, _ = engine_of(model, variables)
    slot = engine.alloc_slot()
    engine.prefill(slot, prompt_of(9))
    with pytest.raises(GroupedCacheNotPortable, match="state layers"):
        engine.export_slots([slot])
    assert engine.active[slot]              # nothing was suspended
    with pytest.raises(GroupedCacheNotPortable, match="state layers"):
        engine.cache.import_slots([])


def test_a_drained_request_moves_folded_and_prefills_again(lfm2):
    """The scheduler's hand-over catches the refusal and moves the request
    without rows: the adopter prefills prompt + tokens so far from position
    0, which rebuilds the state by construction."""
    model, variables = lfm2
    prompt = prompt_of(13, seed=8)
    alone_engine, alone_calls = engine_of(model, variables)
    _, want = served_logits(alone_engine, alone_calls, prompt, 6)

    def build():
        return PagedServeEngine(model, variables, num_slots=2, max_len=64,
                                page_size=4, prefill_chunk=8, min_bucket=4)

    src = ContinuousBatchingScheduler(build())
    req = Request(prompt=list(prompt), max_tokens=6)
    src.submit(req)
    for _ in range(4):          # two chunks, then two decode rounds
        src.step()
    assert 0 < len(req.tokens) < 6
    src.replace_engine(build())             # preempted: prefill again
    while src.has_work():
        src.step()
    assert req.status == "ok" and req.tokens == want


# ---- the cache's books ----

def test_the_post_spans_ids_count_state_beside_pages(lfm2):
    model, variables = lfm2
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=4, prefill_chunk=8, min_bucket=4)
    slots = [engine.alloc_slot() for _ in range(2)]
    for s, n in zip(slots, (5, 13)):
        engine.prefill(s, prompt_of(n, seed=n))
    ids = engine._held({"moe_hit": 3}, slots, [5, 13])
    per_slot = engine.cache.spec.bytes_per_slot
    assert ids == {"moe_hit": 3, "state_slots_held": 2,
                   "state_bytes": 2 * per_slot,
                   # 2 + 4 pages of 4 tokens, 2 attention layers, K and V of
                   # 2 heads of 8 in float32
                   "kv_bytes_held": 6 * 4 * 2 * 2 * 2 * 8 * 4,
                   "kv_pages_full": 6 * 2}
    assert engine.metrics.count("state_resets") == 2



def test_the_cache_counts_its_state_and_builds_no_index(lfm2):
    model, _ = lfm2
    spec = model.kv_cache_spec()
    cache = PagedKVCache(spec, 4, 64, page_size=4)
    assert cache.state.shape == (4, 4 + 1, 2, 32)
    assert cache.state_bytes == 5 * spec.bytes_per_slot
    assert cache.max_prefix_entries == 0
    plain = PagedKVCache(KVCacheSpec(2, 2, 8), 4, 64, page_size=4)
    assert plain.state is None and plain.state_bytes == 0
    assert plain.max_prefix_entries == 256
