"""hetu_tpu.serve: KV-cache decode parity, bounded compilation, and
continuous batching.

The contract under test (ISSUE 1 acceptance): greedy decode through the
serving engine is TOKEN-FOR-TOKEN identical to re-running the full
sequence through the training forward and taking argmax — for GPT, for
Llama (incl. GQA), and under a tp mesh — while a serving run over many
requests of varied prompt lengths compiles a BOUNDED number of
executables (power-of-two chunk buckets, and power-of-two batch by page
buckets of the decode step).
"""

import jax
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.models.llama import LlamaConfig, LlamaModel
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, PagedServeEngine, Request, ServeMetrics,
)
from paged_programs import engine_greedy as _engine_greedy
from paged_programs import ref_greedy as _ref_greedy


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


def _engine(model, variables, **kw):
    """Pages of 8: a 16-position engine then has two pages a slot."""
    kw.setdefault("page_size", 8)
    kw.setdefault("min_bucket", 8)
    return PagedServeEngine(model, variables, **kw)


# ---- decode parity ----

# (max_len, tokens) of the two geometries these cases came from: this file's
# and test_paged_kv.py's, which held the same engine to a second engine
_SHORT, _LONG = (40, 10), (64, 12)


@pytest.mark.parametrize("geometry,prompt_len", [
    (_SHORT, 1), (_SHORT, 5), (_SHORT, 9), (_SHORT, 17),
    (_LONG, 1), (_LONG, 5), (_LONG, 9), (_LONG, 17), (_LONG, 33)])
def test_gpt_decode_parity(gpt, geometry, prompt_len):
    model, variables = gpt
    max_len, n = geometry
    g = np.random.default_rng(prompt_len)
    prompt = [int(t) for t in g.integers(0, 97, prompt_len)]
    engine = _engine(model, variables, num_slots=2, max_len=max_len)
    assert _engine_greedy(engine, prompt, n) == \
        _ref_greedy(model, variables, prompt, n)


@pytest.mark.parametrize("geometry,prompt_len", [
    (_SHORT, 3), (_SHORT, 11), (_LONG, 1), (_LONG, 7), (_LONG, 19)])
def test_llama_gqa_decode_parity(llama, geometry, prompt_len):
    model, variables = llama
    assert model.c.num_kv_heads < model.c.num_heads  # really GQA
    max_len, n = geometry
    g = np.random.default_rng(100 + prompt_len)
    prompt = [int(t) for t in g.integers(0, 97, prompt_len)]
    engine = _engine(model, variables, num_slots=2, max_len=max_len)
    assert _engine_greedy(engine, prompt, n) == \
        _ref_greedy(model, variables, prompt, n)


def test_llama_mha_decode_parity():
    """num_kv_heads == num_heads (MHA) through the same cache path."""
    m = LlamaModel(LlamaConfig(
        vocab_size=53, hidden_size=32, num_layers=2, num_heads=4,
        ffn_size=64, max_position=32))
    v = m.init(jax.random.PRNGKey(2))
    engine = _engine(m, v, num_slots=1, max_len=24)
    prompt = [5, 1, 9]
    assert _engine_greedy(engine, prompt, 8) == _ref_greedy(m, v, prompt, 8)


def test_parity_independent_of_bucket_padding(gpt):
    """The same prompt through two different chunk buckets (forced by
    engine min_bucket) must generate identical tokens — pad K/V never
    leaks."""
    model, variables = gpt
    prompt = [3, 14, 15, 9, 2]
    small = _engine(model, variables, num_slots=1, max_len=40)  # bucket 8
    big = _engine(model, variables, num_slots=1, max_len=40,
                  min_bucket=32)                                # bucket 32
    assert small.chunk_bucket_for(5) == 8 and big.chunk_bucket_for(5) == 32
    assert _engine_greedy(small, prompt, 8) == _engine_greedy(big, prompt, 8)


# ---- tp mesh: sharded decode on the 8-virtual-device platform ----

def test_tp_sharded_decode_matches_unsharded(llama):
    model, variables = llama
    prompt = [3, 14, 15, 9, 2, 6]
    mesh = ht.make_mesh(tp=2)  # nkv=2 → kv-head-sharded page pool
    tp = _engine(model, variables, num_slots=2, max_len=32, mesh=mesh)
    assert _engine_greedy(tp, prompt, 8) == \
        _ref_greedy(model, variables, prompt, 8)


def test_tp8_graceful_when_kv_heads_do_not_divide(llama):
    """tp=8 over 2 kv heads: the cache falls back to replicated and the
    weight splits degrade per-dim (Strategy._fit); numerics unchanged."""
    model, variables = llama
    prompt = [7, 3, 1]
    tp = _engine(model, variables, num_slots=1, max_len=24,
                 mesh=ht.make_mesh(tp=8))
    assert _engine_greedy(tp, prompt, 6) == \
        _ref_greedy(model, variables, prompt, 6)


# ---- bounded compilation under real traffic ----

def test_bounded_executables_serving_32_varied_requests(gpt):
    """>= 32 requests of varied prompt lengths through the
    continuous-batching scheduler compile at most one executable per
    chunk bucket plus one per decode bucket, and a second wave stays
    inside the same ceiling."""
    model, variables = gpt
    engine = _engine(model, variables, num_slots=4, max_len=48)
    g = np.random.default_rng(7)
    reqs = [Request(prompt=[int(t) for t in g.integers(0, 97,
                                                       int(g.integers(1, 40)))],
                    max_tokens=int(g.integers(1, 6)))
            for _ in range(32)]
    sched = ContinuousBatchingScheduler(engine)
    out = sched.run(reqs)
    assert len(out) == 32
    assert all(r.status == "ok" for r in reqs)
    assert engine.compiled_executables() <= engine.max_executables
    assert engine.metrics.count("decode_steps") > 0
    reqs2 = [Request(prompt=[int(t) for t in g.integers(0, 97,
                                                        int(g.integers(1, 40)))],
                     max_tokens=2) for _ in range(8)]
    sched.run(reqs2)
    assert engine.compiled_executables() <= engine.max_executables


# ---- continuous batching semantics ----

def test_admission_into_freed_slots_midstream(gpt):
    """More requests than slots: later requests must start while earlier
    ones are still decoding (continuous batching, not batch-at-once)."""
    model, variables = gpt
    engine = _engine(model, variables, num_slots=2, max_len=32)
    sched = ContinuousBatchingScheduler(engine)
    short_a = Request(prompt=[1], max_tokens=2)
    long_b = Request(prompt=[11, 12], max_tokens=14)
    short_c = Request(prompt=[2], max_tokens=2)
    for r in (short_a, long_b, short_c):  # a+b fill both slots; c queues
        sched.submit(r)
    # step until c (admitted into a's freed slot) finishes; b — admitted
    # BEFORE c — must still be decoding: iteration-level admission, not
    # batch-at-once
    for _ in range(50):
        sched.step()
        if short_c.done.is_set():
            break
    assert short_a.done.is_set() and short_c.done.is_set()
    assert not long_b.done.is_set()
    sched.run([])  # drain
    assert all(r.status == "ok" for r in (short_a, long_b, short_c))


def test_eos_evicts_and_frees_slot(gpt):
    model, variables = gpt
    engine = _engine(model, variables, num_slots=1, max_len=32)
    prompt = [3, 14, 15]
    ref = _ref_greedy(model, variables, prompt, 10)
    eos = ref[3]
    sched = ContinuousBatchingScheduler(engine)
    req = Request(prompt=prompt, max_tokens=10, eos_id=eos)
    out = sched.run([req])
    # stopped AT the eos token's FIRST occurrence (greedy streams repeat)
    assert out[req.rid] == ref[:ref.index(eos) + 1]
    assert engine.cache.num_free == 1       # slot reclaimed


def test_page_budget_of_one_working_set_serializes_admission(gpt):
    """With a page pool that fits one request's worst case, concurrency
    collapses to sequential admission even though slots are free."""
    model, variables = gpt
    # 8 + 3 + 1 tokens = 2 pages + 1 of copy-on-write headroom, of 3
    engine = _engine(model, variables, num_slots=4, max_len=32,
                     num_pages=4, prefix_sharing=False)
    sched = ContinuousBatchingScheduler(engine)
    reqs = [Request(prompt=[1, 2, 3, 4, 5, 6, 7, 8], max_tokens=3)
            for _ in range(3)]
    for r in reqs:
        sched.submit(r)
    max_occupied = 0
    for _ in range(100):
        sched.step()
        max_occupied = max(max_occupied,
                           engine.cache.num_slots - engine.cache.num_free)
        if all(r.done.is_set() for r in reqs):
            break
    assert all(r.status == "ok" for r in reqs)
    assert max_occupied == 1, "a pool of one working set must serialize"


def test_request_the_page_pool_can_never_hold_rejected_not_wedged(gpt):
    """A request whose worst case could NEVER fit the page pool must fail
    as overflow — not deadlock the queue head while the engine loop
    hot-spins."""
    model, variables = gpt
    engine = _engine(model, variables, num_slots=2, max_len=32,
                     num_pages=4)
    sched = ContinuousBatchingScheduler(engine)
    # 10 + 9 + 1 tokens = 3 pages + 1 of headroom > the pool's 3
    too_big = Request(prompt=list(range(1, 11)), max_tokens=9)
    fits = Request(prompt=[1, 2, 3], max_tokens=2)
    sched.submit(too_big)
    sched.submit(fits)
    for _ in range(20):
        sched.step()
        if fits.done.is_set():
            break
    assert too_big.status == "overflow" and too_big.tokens == []
    assert fits.status == "ok"          # the queue kept moving behind it
    assert engine.cache.num_free == 2


def test_submit_after_shutdown_drain_fails_fast(gpt):
    """A listener racing close() must get an immediate 'shutdown'
    completion, not a request parked forever with no engine loop."""
    model, variables = gpt
    engine = _engine(model, variables, num_slots=1, max_len=16)
    sched = ContinuousBatchingScheduler(engine)
    sched.drain("shutdown", stop_accepting=True)
    late = sched.submit(Request(prompt=[1, 2], max_tokens=4))
    assert late.done.is_set() and late.status == "shutdown"
    # an ERROR drain keeps accepting (the loop recovers per-request)
    sched2 = ContinuousBatchingScheduler(
        _engine(model, variables, num_slots=1, max_len=16))
    sched2.drain("error")
    req = sched2.submit(Request(prompt=[1, 2], max_tokens=2))
    sched2.run([])
    assert req.status == "ok"


def test_prompt_overflow_rejected(gpt):
    model, variables = gpt
    engine = _engine(model, variables, num_slots=1, max_len=16)
    sched = ContinuousBatchingScheduler(engine)
    req = Request(prompt=list(range(1, 20)), max_tokens=4)
    sched.run([req])
    assert req.status == "overflow" and req.tokens == []


def test_generation_capped_by_cache_capacity(gpt):
    """A request whose max_tokens exceeds the slot's remaining room ends
    cleanly at capacity instead of writing past max_len."""
    model, variables = gpt
    # two pages of the sequence and one of copy-on-write headroom
    engine = _engine(model, variables, num_slots=1, max_len=16, num_pages=4)
    sched = ContinuousBatchingScheduler(engine)
    req = Request(prompt=list(range(1, 12)), max_tokens=50)
    out = sched.run([req])
    assert req.status == "ok"
    assert len(out[req.rid]) == 16 - 11  # prompt 11 + 5 generated = max_len
    assert engine.cache.num_free == 1


def test_expired_request_times_out_in_queue(gpt):
    model, variables = gpt
    engine = _engine(model, variables, num_slots=1, max_len=16)
    sched = ContinuousBatchingScheduler(engine)
    req = Request(prompt=[1, 2], max_tokens=4, timeout_s=0.0)
    sched.submit(req)
    sched.step()
    assert req.done.is_set() and req.status == "timeout"


# ---- metrics through the repo logger ----

def test_metrics_report_through_metric_logger(gpt, tmp_path):
    import json

    from hetu_tpu.utils.logger import MetricLogger

    model, variables = gpt
    metrics = ServeMetrics()
    engine = _engine(model, variables, num_slots=2, max_len=32,
                     metrics=metrics)
    sched = ContinuousBatchingScheduler(engine)
    sched.run([Request(prompt=[1, 2, 3], max_tokens=4),
               Request(prompt=[4, 5], max_tokens=3)])
    log_path = tmp_path / "serve.jsonl"
    logger = MetricLogger(str(log_path))
    snap = metrics.report(logger)
    logger.close()
    for key in ("ttft_avg_s", "tokens_per_sec", "queue_depth",
                "slot_occupancy", "prefill_compiles", "decode_compiles",
                "requests_ok", "generated_tokens", "rounds_kept",
                "decode_launch_p50_ms", "decode_fetch_p95_ms",
                "chunk_fetch_p50_ms", "engine_gap_p50_ms"):
        assert key in snap, key
    assert snap["requests_ok"] == 2
    # from the round log: a row a decode round and a prefill chunk, and the
    # rounds' tokens over the time from the first opening to the last close
    rows = metrics.rounds()
    assert snap["rounds_kept"] == len(rows) \
        == snap["decode_steps"] + snap["prefill_chunks"]
    rounds = rows[rows[:, 1] == 0]
    assert snap["tokens_per_sec"] == pytest.approx(
        rounds[:, -1].sum() * 1e9 / (rounds[-1, 6] - rounds[0, 2]))
    assert snap["ttft_avg_s"] > 0
    rec = json.loads(log_path.read_text().strip().splitlines()[-1])
    assert rec["requests_ok"] == 2 and "ttft_avg_s" in rec


# ---- state layers behind the same scheduler (ISSUE 43) ----

@pytest.fixture(scope="module")
def lfm2():
    import jax.numpy as jnp

    from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
    m = Lfm2MoeModel(Lfm2MoeConfig(
        vocab_size=97, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=64, expert_ffn_size=16,
        first_dense=1, n_routed_experts=8, moe_topk=2,
        layer_types=("conv", "full_attention", "conv", "conv",
                     "full_attention"),
        max_position=64, dtype=jnp.float32, param_dtype=jnp.float32,
        init_std=0.2, router_init_std=0.5, expert_block_rows=4))
    return m, jax.jit(m.init)(jax.random.PRNGKey(3))


def _is_greedy(model, variables, prompt, tokens) -> bool:
    """Whether ``tokens`` are the greedy continuation of ``prompt`` by ONE
    jitted full forward over prompt + tokens (each token the argmax after
    the tokens before it, which is what greedy means)."""
    ids = np.asarray([list(prompt) + list(tokens)], np.int32)
    logits = jax.jit(lambda v, x: model.apply(v, x)[0])(variables, ids)[0]
    n = len(prompt)
    return list(tokens) == np.argmax(
        np.asarray(logits[n - 1:n - 1 + len(tokens)]), -1).tolist()


@pytest.mark.parametrize("prompt_len", [1, 8, 13])
def test_state_layers_decode_parity(lfm2, prompt_len):
    """A model whose layers keep a state a slot and not rows a token, by the
    entry points every other model is served by: token-exact against the
    full forward, chunks of 8 that pad (13 = 8 + 5) and that do not."""
    model, variables = lfm2
    prompt = list(np.random.default_rng(prompt_len).integers(0, 97,
                                                             prompt_len))
    engine = _engine(model, variables, num_slots=2, max_len=32,
                     prefill_chunk=8)
    toks = _engine_greedy(engine, prompt, 6)
    assert _is_greedy(model, variables, prompt, toks)
    # the slot is handed out again: the same request, nothing left behind
    assert _engine_greedy(engine, prompt, 6) == toks


def test_state_layers_preempted_request_prefills_again(lfm2):
    """Preemption by re-prefill rebuilds the state by construction: the
    preempted request's prompt and tokens so far are prefilled from position
    0 into whatever slot it is given."""
    model, variables = lfm2
    prompts = [[3, 14, 15, 9, 2, 6], [5, 3, 5, 8, 9, 7, 9, 3, 2]]
    engine = _engine(model, variables, num_slots=2, max_len=32,
                     prefill_chunk=8)
    sched = ContinuousBatchingScheduler(engine)
    reqs = [Request(prompt=p, max_tokens=8) for p in prompts]
    for r in reqs:
        sched.submit(r)
    for _ in range(4):
        sched.step()
    assert all(0 < len(r.tokens) < 8 for r in reqs)
    sched.replace_engine(_engine(model, variables, num_slots=2, max_len=32,
                                 prefill_chunk=8))
    sched.run([])
    for p, r in zip(prompts, reqs):     # a requeued request's own prompt
        assert r.status == "ok" and len(r.tokens) == 8      # is folded
        assert _is_greedy(model, variables, p, r.tokens)
