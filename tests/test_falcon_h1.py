"""Falcon-H1 against its plain reference (``benchmarks/reference/falcon_h1.py``)
at small widths on the CPU, seeded weights at the configuration's own rule of
stds, every comparison one of LOGITS: (a) the dense forward, float32 and
bfloat16; (b) chunked prefill (chunks under, at and over the scan's chunk,
padded buckets with the last real token inside a chunk) and decode through
``PagedServeEngine`` over a cache whose every layer is a cache layer AND a
state layer of two parts; (c) a long decode with the recurrence's state in
float32 and, failing, in bfloat16; (e) a slot handed on, a decode bucket wider
than the active slots, a preempted request; (f) each branch, the key's
rotation and the gate's factor visible to the comparison; (g) the programs of
the models that share the edited code, equation for equation the parent's."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.arch import falcon_h1 as arch  # noqa: E402
from benchmarks.reference import falcon_h1 as ref  # noqa: E402
from hetu_tpu.models.falcon_h1 import (  # noqa: E402
    CONV, SSM, FalconH1Config, FalconH1Model,
)
from hetu_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.serve.kv_cache import (  # noqa: E402
    GroupedCacheNotPortable, PagedKVCache,
)
from paged_programs import (  # noqa: E402
    LogitsOut, program_digest, tiny_served, traced,
)

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 97
LAYERS = 3


def tiny(**kw) -> FalconH1Config:
    """The published multipliers over small widths; a scan chunk of 8."""
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=LAYERS, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=64, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_groups=2, conv_taps=4, ssm_chunk=8,
        max_position=512, dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return FalconH1Config(**base)


def dims_of(c: FalconH1Config) -> dict:
    return dict(
        heads=c.num_heads, kv_heads=c.num_kv_heads, head_dim=c.head_dim,
        theta=c.rope_theta, eps=c.rms_eps, ssm_heads=c.ssm_heads,
        ssm_head_dim=c.ssm_head_dim, d_state=c.ssm_state,
        groups=c.ssm_groups,
        mult=dict(embedding=c.embedding_multiplier,
                  attention_in=c.attention_in_multiplier,
                  attention_out=c.attention_out_multiplier,
                  key=c.key_multiplier, ssm_in=c.ssm_in_multiplier,
                  ssm=c.ssm_multipliers, ssm_out=c.ssm_out_multiplier,
                  mlp=c.mlp_multipliers, lm_head=c.lm_head_multiplier))


def make(seed=1, **kw):
    model = FalconH1Model(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def falcon():
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
                                       (jnp.bfloat16, 0.05)])
def test_dense_forward_equals_the_reference(dtype, tol):
    """bfloat16 against the float32 reference over the same (bfloat16)
    weights: no router exchanges a near tie here, so the limit is a third of
    LFM2's at these widths; the chip's readings at the published widths set
    the cell's (PERF.md)."""
    model, variables = make(dtype=dtype, param_dtype=dtype)
    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 21))
    got = np.asarray(model.apply(variables, jnp.asarray(ids))[0]
                     .astype(jnp.float32))
    err = rel_err(got, ref_logits(model, variables["params"], ids))
    assert err < tol
    assert dtype == jnp.float32 or err > 1e-4


def test_the_weights_are_drawn_to_the_rule_and_the_logits_are_of_order_one(
        falcon):
    model, variables = falcon
    c, p = model.c, variables["params"]
    assert float(jnp.std(p["tok_emb"])) == pytest.approx(
        1 / c.embedding_multiplier, rel=0.05)
    assert float(jnp.std(p["layers"]["attn"]["k"][0])) == pytest.approx(
        c.hidden_size ** -0.5 / c.key_multiplier, rel=0.1)
    # a matrix a layer is a tuple of the layers' arrays, as init yields it
    for leaves, names in ((p["layers"]["attn"], "qkvo"),
                          (p["layers"]["ssm"], ("in", "out")),
                          (p["layers"]["ffn"], ("gate", "up", "down"))):
        assert all(isinstance(leaves[n], tuple) and len(leaves[n]) == LAYERS
                   for n in names)
    ssm = p["layers"]["ssm"]
    assert ssm["A_log"].dtype == ssm["dt_bias"].dtype == jnp.float32
    assert 0.0 <= float(ssm["A_log"].min()) and \
        float(ssm["A_log"].max()) <= np.log(16.0)
    dt = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, 33))
    logits = ref_logits(model, p, ids)
    assert 0.3 < logits.std() < 3.0


def test_the_model_states_a_cache_layer_and_a_two_part_state_layer_each(
        falcon):
    model, _ = falcon
    spec = model.kv_cache_spec()
    assert len(spec.groups) == 1
    assert spec.num_layers == spec.state_layers == LAYERS
    conv_ch = 4 * 8 + 2 * 2 * 16
    assert [(n, sh, str(dt)) for n, sh, dt in spec.parts] == [
        ("conv", (3 * conv_ch,), "float32"), ("ssm", (4, 8, 16), "float32")]
    assert spec.part_bytes_per_slot == {
        "conv": LAYERS * 3 * conv_ch * 4, "ssm": LAYERS * 4 * 8 * 16 * 4}
    assert spec.bytes_per_slot == sum(spec.part_bytes_per_slot.values())
    # bfloat16 compute keeps the recurrence's matrix in float32
    low = FalconH1Model(tiny(dtype=jnp.bfloat16)).kv_cache_spec()
    assert [str(dt) for _, _, dt in low.parts] == ["bfloat16", "float32"]
    assert model.multipliers == {
        "embed": model.c.embedding_multiplier, "key": model.c.key_multiplier,
        "gate": model.c.mlp_multipliers[0],
        "down": model.c.mlp_multipliers[1],
        "head": model.c.lm_head_multiplier}


# ---- (b) chunks, padded and not, then decode ----

@pytest.mark.parametrize("n,chunk", [
    (13, 8),    # the second chunk: 5 real rows in a bucket of 8 = one scan chunk
    (16, 8),    # two whole chunks
    (5, 8),     # one padded chunk, under the scan's chunk
    (3, 4),     # a bucket under the scan's chunk: one short scan chunk
    (21, 4),    # six chunks of half a scan chunk each
    (13, 16),   # a bucket of two scan chunks, the last real row in the second
    (7, 16),    # ... and in the first: the second scan chunk is all padding
    (40, 32),   # four scan chunks, then one real scan chunk in a bucket of 8
])
def test_chunked_prefill_and_decode_equal_the_reference(falcon, n, chunk):
    model, variables = falcon
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
    assert 1e-4 < rel_err(got.astype(np.float32), want[12:17]) < 0.05
    assert all(a.dtype == jnp.float32 for a in engine.cache.state[SSM])
    assert all(a.dtype == jnp.bfloat16 for a in engine.cache.state[CONV])


# ---- (c) a long decode: the state's precision shows only here ----

def long_decode_err(state_dtype, rounds: int = 256) -> float:
    model, variables = make(seed=2, state_dtype=state_dtype)
    engine, calls = engine_of(model, variables, num_slots=2, max_len=288)
    prompt = prompt_of(9, seed=21)
    got, toks = served_logits(engine, calls, prompt, rounds + 1)
    want = ref_logits(model, variables["params"], [prompt + toks])[0]
    return rel_err(got, want[8:9 + rounds])


def test_a_long_decode_keeps_to_the_reference_with_a_float32_state():
    assert long_decode_err(jnp.float32) < F32_TOL


def test_a_long_decode_with_a_bfloat16_state_fails_the_tolerance():
    """Everything float32 but the recurrence's matrix, rounded to bfloat16
    once a round: the control.  The benchmark's check decodes eight tokens
    and cannot see this."""
    assert long_decode_err(jnp.bfloat16) > 2 * F32_TOL
    # eight decoded tokens, the benchmark's check, stay inside it
    assert long_decode_err(jnp.bfloat16, rounds=8) < F32_TOL


# ---- (e) slots handed on, padding rows, a preempted request ----

def test_a_reused_slot_starts_from_nothing_in_both_parts(falcon):
    model, variables = falcon
    engine, calls = engine_of(model, variables)
    prompts = [prompt_of(n, seed=10 + n) for n in (5, 13, 9, 21)]
    slots = [engine.alloc_slot() for _ in prompts]
    for s, p in zip(slots, prompts):
        engine.prefill(s, p)
    for _ in range(3):
        engine.decode()
    # one finishes; its slot goes to a newcomer while three still decode
    engine.release(slots[1])
    for part in engine.cache.state:         # its last owner's state is there
        assert all(float(jnp.abs(rows[slots[1]]).max()) > 0 for rows in part)
    new = engine.alloc_slot()
    assert new == slots[1]
    late = prompt_of(11, seed=99)
    toks = [engine.prefill(new, late)]
    rows = [calls[-1][0]]
    for _ in range(4):
        out = engine.decode()
        toks.append(out[new])
        rows.append(calls[-1][sorted(out).index(new)])
    alone_engine, alone_calls = engine_of(model, variables)
    alone, alone_toks = served_logits(alone_engine, alone_calls, late, 5)
    assert toks == alone_toks
    np.testing.assert_allclose(np.stack(rows), alone, atol=1e-5)
    want = ref_logits(model, variables["params"], [late + toks])[0]
    assert rel_err(np.stack(rows), want[10:15]) < F32_TOL


def test_requests_in_flight_together_each_equal_the_reference(falcon):
    model, variables = falcon
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
    assert engine.metrics.count("prefix_state_refusals") == 6


def test_a_decode_round_leaves_idle_slots_state_alone_in_both_parts(falcon):
    """Three rows in a bucket of four: the padding row goes to the scratch
    slot, and the recurrence's matrix of a slot with no sequence in the
    round, which the round passes over with ``dt`` = 0, stays bit for bit."""
    model, variables = falcon
    engine, _ = engine_of(model, variables, num_slots=8)
    slots = [engine.alloc_slot() for _ in range(3)]
    for s, n in zip(slots, (5, 9, 13)):
        engine.prefill(s, prompt_of(n, seed=n))
    cache = engine.cache
    idle = np.asarray([s for s in range(8) if s not in slots])
    cache.state = tuple(tuple(rows.at[idle].set(7.0) for rows in part)
                        for part in cache.state)
    before = [np.asarray(rows) for part in cache.state for rows in part]
    engine.decode()
    after = [np.asarray(rows) for part in cache.state for rows in part]
    assert len(after) == 2 * LAYERS
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a[idle], b[idle])
        assert not np.array_equal(a[slots], b[slots])
        assert a.shape[0] == 8 + 1          # the scratch slot


def test_a_preempted_request_prefills_again_to_the_same_tokens(falcon):
    model, variables = falcon
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


def test_export_refuses_and_the_same_prompt_takes_no_prefix_match(falcon):
    model, variables = falcon
    engine, calls = engine_of(model, variables)
    prompt = prompt_of(21, seed=4)
    once, toks = served_logits(engine, calls, prompt, 4)
    twice, toks2 = served_logits(engine, calls, prompt, 4)
    assert toks2 == toks
    np.testing.assert_allclose(twice, once, atol=1e-5)
    assert engine.metrics.count("prefix_hits") == 0
    assert engine.metrics.count("prefix_state_refusals") == 2
    slot = engine.alloc_slot()
    engine.prefill(slot, prompt_of(9))
    with pytest.raises(GroupedCacheNotPortable, match="state layers"):
        engine.export_slots([slot])


# ---- the cache's books ----

def test_the_post_spans_ids_and_gauges_state_the_state_by_part(falcon):
    model, variables = falcon
    engine = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                              page_size=4, prefill_chunk=8, min_bucket=4)
    slots = [engine.alloc_slot() for _ in range(2)]
    for s, n in zip(slots, (5, 13)):
        engine.prefill(s, prompt_of(n, seed=n))
    ids = engine._held(None, slots, [5, 13])
    per = engine.cache.spec.part_bytes_per_slot
    assert ids == {"state_slots_held": 2,
                   "state_bytes": 2 * (per["conv"] + per["ssm"]),
                   "state_conv_bytes": 2 * per["conv"],
                   "state_ssm_bytes": 2 * per["ssm"],
                   # 2 + 4 pages of 4 tokens, 3 layers, K and V of 2 heads
                   # of 8 in float32
                   "kv_bytes_held": 6 * 4 * 3 * 2 * 2 * 8 * 4,
                   "kv_pages_full": 6 * 3}
    snap = engine.metrics.snapshot()
    assert {k: snap[k] for k in ids if k != "kv_pages_full"} \
        == {k: v for k, v in ids.items() if k != "kv_pages_full"}


def test_the_cache_holds_an_array_a_part_a_layer_and_builds_no_index(falcon):
    model, _ = falcon
    spec = model.kv_cache_spec()
    cache = PagedKVCache(spec, 4, 64, page_size=4)
    # a part a tuple of its layers' arrays
    assert [[a.shape for a in part] for part in cache.state] == [
        LAYERS * [(5, 3 * 96)], LAYERS * [(5, 4, 8, 16)]]
    assert cache.state_bytes == 5 * spec.bytes_per_slot
    assert cache.max_prefix_entries == 0


# ---- (f) the comparison sees each branch ----

def without(what: str):
    """The model with one piece of its mathematics left out of the
    program."""
    model, variables = make(seed=4)
    if what == "the mixer":
        model._mixer = lambda p, l, a, call: jnp.zeros_like(a)
    elif what == "attention":
        model._attention = lambda pa, l, a, call: jnp.zeros_like(a)
    elif what == "the key's rotation":
        rotate, kv = model._rotate, model.c.num_kv_heads
        model._rotate = lambda x, cos, sin: \
            x if x.shape[2] == kv else rotate(x, cos, sin)
    elif what == "the gate's multiplier":
        del model.multipliers["gate"]
    elif what == "the key's multiplier":
        del model.multipliers["key"]
    else:
        assert what == "nothing"
    return model, variables


@pytest.mark.parametrize("what", ["the mixer", "attention",
                                  "the key's rotation",
                                  "the gate's multiplier",
                                  "the key's multiplier"])
def test_the_comparison_sees_what_is_left_out(what):
    """At the configuration's rule of stds each piece moves the logits by
    more than the cell's ``logit_err`` limit: a std of 0.02 throughout would
    leave the scores' std at 0.02 and hide the keys and the rotation."""
    limit = arch.TOLERANCES["logit_err"]["limit"]
    ids = np.random.default_rng(6).integers(0, VOCAB, (2, 40))
    whole, variables = without("nothing")
    want = ref_logits(whole, variables["params"], ids)
    assert rel_err(np.asarray(whole.apply(variables, jnp.asarray(ids))[0]),
                   want) < F32_TOL
    model, variables = without(what)
    got = np.asarray(model.apply(variables, jnp.asarray(ids))[0])
    assert rel_err(got, want) > 2 * limit, what


# ---- (g) the models that share the edited code keep their programs ----

# program_digest of each program traced at the parent commit (c6c9997, PR
# 46), tests/paged_programs.py's tiny models: LFM2 (SlotStates), K-EXAONE
# and Mellum (GroupedHeads).  Recorded with the same functions on a copy
# of that commit; a change meant to alter one of these programs records
# its own.  K-EXAONE's two are ISSUE 55's: its entry points count
# ``moe_grouped`` behind the four ``MOE_STATS`` (one slice, one multiply by
# a constant and one concatenate more), and nothing else of them moved.
PARENT = {
    "lfm2.decode":
        "cc6e8f3f62fccd30775bfe98d26dcdb7537b78fabad7069b9879a5933bba8cf8",
    "lfm2.chunk":
        "fe1fab287e6977853cf38e5d88c8b9b778765fe2f04f7968a6716d56824994c0",
    "exaone.decode":
        "904d64e5f77832c373ed7662568b6233992b0c86c357bece4eae4735d8c509be",
    "exaone.chunk":
        "a8a381ead52a700e357e8aa7c399cdff23d33244bc2c8e275e60ffd5582abb2a",
    "mellum.train":
        "e76aa5ea5aa5e27f4d45ebeb771e7adc652df9699f3f8218c79ff99cab254dcc",
}


@pytest.mark.parametrize("name", ["lfm2.decode", "lfm2.chunk",
                                  "exaone.decode", "exaone.chunk"])
def test_a_served_model_that_shares_the_code_keeps_its_program(name):
    kind, program = name.split(".")
    model, variables, kw = tiny_served(kind)
    engine = PagedServeEngine(model, variables, **kw)
    closed = traced(engine, program, batch=4, chunk=8).jaxpr
    assert program_digest(closed.jaxpr) == PARENT[name]


def test_mellums_trained_step_keeps_its_program():
    from hetu_tpu.models.mellum import MellumConfig, MellumModel
    model = MellumModel(MellumConfig(
        vocab_size=128, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=16, expert_ffn_size=32, n_routed_experts=16,
        moe_topk=4, held=(4, 4), window=24, rope_theta=1e4, max_position=128,
        dtype=jnp.float32, expert_block_rows=8, attention_impl="xla",
        ce_row_chunk=32))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    loss = model.lm_loss_fn()
    closed = jax.make_jaxpr(lambda p, x: jax.value_and_grad(
        lambda q: loss(q, shapes["state"], (x,), jax.random.PRNGKey(0),
                       True)[0])(p))(
        shapes["params"], jax.ShapeDtypeStruct((2, 64), jnp.int32))
    assert program_digest(closed.jaxpr) == PARENT["mellum.train"]
