"""Qwen3-Next against its plain reference
(``benchmarks/reference/qwen3_next.py``) at small widths on the CPU, seeded
weights at the configuration's own rule of stds, every comparison one of
LOGITS: (c) the dense forward, float32 and bfloat16, and a CONTROL that fails
for each thing the comparison must see; (d) chunked prefill (several chunks,
a padded last one, buckets over and under the rule's chunk) then rounds
through ``PagedServeEngine`` over a cache of two-part state layers beside
FEWER cache layers than layers, idle slots between live ones, a slot handed
on; (e) a long decode with the rule's state in float32 and, failing, in
bfloat16; (f) the four expert-parallel shares and the gated shared expert
counted once add up to the uncut reference's layer; (g) Falcon-H1's programs,
equation for equation the parent's (K-EXAONE's, LFM2's and Mellum's are
``tests/test_falcon_h1.py``'s, unchanged)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.arch import qwen3_next as arch  # noqa: E402
from benchmarks.reference import qwen3_next as ref  # noqa: E402
from hetu_tpu.layers.moe import HeldExpertLayer  # noqa: E402
from hetu_tpu.models import qwen3_next  # noqa: E402
from hetu_tpu.models.block import GroupedHeads  # noqa: E402
from hetu_tpu.models.qwen3_next import (  # noqa: E402
    CONV, DELTA, Qwen3NextConfig, Qwen3NextModel,
)
from hetu_tpu.ops import moe_ops  # noqa: E402
from hetu_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.serve.kv_cache import PagedKVCache  # noqa: E402
from paged_programs import (  # noqa: E402
    LogitsOut, program_digest, tiny_served, traced,
)

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 97
LAYERS = 4          # one period: layers 0-2 DeltaNet, layer 3 a full layer


def tiny(**kw) -> Qwen3NextConfig:
    """Small widths in the published ratios (a key head serves two value
    heads, a quarter of the head rotated, a quarter of the experts held); a
    rule chunk of 8."""
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=LAYERS, num_heads=4,
        num_kv_heads=2, head_dim=16, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=8, gdn_value_dim=8, gdn_chunk=8, expert_ffn_size=16,
        shared_ffn_size=16, n_routed_experts=16, moe_topk=4, held=(4, 4),
        max_position=512, dtype=jnp.float32, param_dtype=jnp.float32,
        expert_block_rows=8)
    base.update(kw)
    return Qwen3NextConfig(**base)


def dims_of(c: Qwen3NextConfig) -> dict:
    return dict(
        head_dim=c.head_dim, theta=c.rope_theta, eps=c.rms_eps,
        rotary_dim=c.rotary_dim, gdn_key_heads=c.gdn_key_heads,
        gdn_value_heads=c.gdn_value_heads, gdn_key_dim=c.gdn_key_dim,
        gdn_value_dim=c.gdn_value_dim,
        full_interval=c.full_attention_interval, topk=c.moe_topk,
        held=c.held)


def make(seed=1, **kw):
    model = Qwen3NextModel(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def qwen():
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


# ---- (c) the dense forward, and what the comparison must see ----

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, 0.3)])
def test_dense_forward_equals_the_reference(dtype, tol):
    """bfloat16 against the float32 reference over the same (bfloat16)
    weights: at these widths (top-4 of 16 experts, a key of 8 dims under 21
    rows) a router choice exchanged at a near tie moves a row by a quarter of
    the expert layer and the rule's normed read-out turns with it, so the
    limit is wide; the chip's readings at the published widths set the
    cell's (PERF.md)."""
    model, variables = make(dtype=dtype, param_dtype=dtype)
    ids = np.random.default_rng(3).integers(0, VOCAB, (2, 21))
    got = np.asarray(model.apply(variables, jnp.asarray(ids))[0]
                     .astype(jnp.float32))
    err = rel_err(got, ref_logits(model, variables["params"], ids))
    assert err < tol
    assert dtype == jnp.float32 or err > 1e-4


def test_the_weights_are_drawn_to_the_rule_and_the_logits_are_of_order_one(
        qwen):
    model, variables = qwen
    c, p = model.c, variables["params"]
    layers = p["layers"]
    assert float(jnp.std(p["tok_emb"])) == pytest.approx(1.0, rel=0.05)
    # 1 + w weighs: the norms' w are drawn round 0, the DeltaNet's round 1
    assert abs(float(jnp.mean(layers["attn_norm"]))) < 0.05
    assert float(jnp.std(layers["attn_norm"])) == pytest.approx(0.1, rel=0.2)
    assert float(jnp.mean(layers["gdn"]["norm"])) == pytest.approx(1, abs=0.1)
    # a matrix a layer reads whole is a tuple of the layers' arrays; the
    # experts are stacked, as the grouped matmuls read them in place
    assert all(isinstance(layers["attn"][n], tuple)
               and len(layers["attn"][n]) == 1 for n in "qkvo")
    assert layers["attn"]["q"][0].shape == (2 * 4 * 16, 32)     # [q | gate]
    assert all(isinstance(layers["gdn"][n], tuple)
               and len(layers["gdn"][n]) == 3 for n in ("qkvz", "out"))
    assert layers["gdn"]["qkvz"][0].shape == (32, 2 * 16 + 2 * 32)
    assert layers["gdn"]["ba"].shape == (3, 32, 8)
    assert layers["gdn"]["conv_w"].shape == (3, 4, 64)
    moe = layers["moe"]
    assert moe["gate"].shape == (LAYERS, 4, 32, 16)
    assert moe["down"].shape == (LAYERS, 4, 16, 32)
    assert all(isinstance(moe[n], tuple) and len(moe[n]) == LAYERS
               for n in ("router", "shared_gate", "shared_up", "shared_down"))
    assert moe["router"][0].shape == (32, 16)       # as wide as published
    assert moe["router"][0].dtype == jnp.float32
    assert moe["shared_gate_w"].shape == (LAYERS, 32)
    assert "router_bias" not in moe
    gdn = layers["gdn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert float(gdn["A_log"].max()) <= np.log(16.0)
    assert np.isfinite(np.asarray(gdn["A_log"])).all()
    dt = np.asarray(jax.nn.softplus(gdn["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, 33))
    logits = ref_logits(model, p, ids)
    assert 0.3 < logits.std() < 3.0


def test_the_model_states_fewer_cache_layers_than_two_part_state_layers(
        qwen):
    model, _ = qwen
    spec = model.kv_cache_spec()
    assert len(spec.groups) == 1
    assert (spec.num_layers, spec.state_layers) == (1, 3)
    assert model.cache_layer == {3: (0, 0)} and model.attn_leaf == {3: 0}
    assert model.gdn_leaf == {0: 0, 1: 1, 2: 2}
    two = Qwen3NextModel(tiny(num_layers=8))        # two periods
    assert two.cache_layer == {3: (0, 0), 7: (0, 1)}
    assert two.gdn_leaf == {0: 0, 1: 1, 2: 2, 4: 3, 5: 4, 6: 5}
    assert (two.kv_cache_spec().num_layers,
            two.kv_cache_spec().state_layers) == (2, 6)
    assert [(n, sh, str(dt)) for n, sh, dt in spec.parts] == [
        ("conv", (3 * 64,), "float32"), ("delta", (4, 8, 8), "float32")]
    assert spec.part_bytes_per_slot == {
        "conv": 3 * 3 * 64 * 4, "delta": 3 * 4 * 8 * 8 * 4}
    assert spec.bytes_per_token == 1 * 2 * 16 * 2 * 4
    # bfloat16 compute keeps the rule's matrix in float32
    low = Qwen3NextModel(tiny(dtype=jnp.bfloat16)).kv_cache_spec()
    assert [str(dt) for _, _, dt in low.parts] == ["bfloat16", "float32"]
    assert (model.rotary_dim, model.gated_query, model.unit_offset_norms) \
        == (4, True, True)
    # every other model of the block states none of the three
    assert (GroupedHeads.rotary_dim, GroupedHeads.gated_query,
            GroupedHeads.unit_offset_norms) == (None, False, False)
    assert model.step_stats == ("moe_held", "moe_zero", "moe_absent",
                                "moe_hit", "moe_experts", "moe_grouped")


def without(what: str, monkeypatch):
    """The model with one piece of its mathematics changed in the
    program."""
    model, variables = make(seed=4)
    p = variables["params"]
    if what == "no gate on the attention":
        model._out = lambda pa, l, o, gate=None: \
            GroupedHeads._out(model, pa, l, o)
    elif what == "the whole head rotated":
        model.rotary_dim = None
    elif what == "w for 1 + w":
        model.unit_offset_norms = False
    elif what == "the shared expert's gate dropped":
        moe = {k: v for k, v in p["layers"]["moe"].items()
               if k != "shared_gate_w"}
        variables = {**variables, "params": {
            **p, "layers": {**p["layers"], "moe": moe}}}
    elif what == "beta = 1":
        scan = qwen3_next.delta_rule.gated_delta_chunk_scan
        monkeypatch.setattr(
            qwen3_next.delta_rule, "gated_delta_chunk_scan",
            lambda q, k, v, g, beta, *a, **kw: scan(
                q, k, v, g, jnp.ones_like(beta), *a, **kw))
    elif what == "the gate before the norm":
        def gate_first(o, gate, weight, eps):
            x = o.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
            return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                      + eps) * weight).astype(o.dtype)
        monkeypatch.setattr(qwen3_next, "rms_norm_then_gate", gate_first)
    elif what == "the keys not brought to unit length":
        monkeypatch.setattr(qwen3_next.delta_rule, "unit_rows",
                            lambda x, eps=1e-6: x.astype(jnp.float32))
    else:
        assert what == "nothing"
    return model, variables


@pytest.mark.parametrize("what", [
    "no gate on the attention", "the whole head rotated", "w for 1 + w",
    "the shared expert's gate dropped", "beta = 1",
    "the gate before the norm", "the keys not brought to unit length"])
def test_the_comparison_sees_what_is_changed(what, monkeypatch):
    """At the configuration's rule of stds each piece moves the logits by
    more than the cell's ``logit_err`` limit, so the comparison fails (the
    rotation of one full layer of four by 1.2 times it, every other piece
    by twice or more)."""
    limit = arch.TOLERANCES["logit_err"]["limit"]
    ids = np.random.default_rng(6).integers(0, VOCAB, (2, 40))
    whole, variables = without("nothing", monkeypatch)
    want = ref_logits(whole, variables["params"], ids)
    assert rel_err(np.asarray(whole.apply(variables, jnp.asarray(ids))[0]),
                   want) < F32_TOL
    model, changed = without(what, monkeypatch)
    got = np.asarray(model.apply(changed, jnp.asarray(ids))[0])
    assert rel_err(got, want) > limit, what


# ---- (d) chunks, padded and not, then rounds ----

@pytest.mark.parametrize("n,chunk", [
    (13, 8),    # the second chunk: 5 real rows in a bucket of 8 = one rule chunk
    (5, 8),     # one padded chunk, under the rule's chunk
    (21, 4),    # six chunks of half a rule chunk each
    (13, 16),   # a bucket of two rule chunks, the last real row in the second
    (7, 16),    # ... and in the first: the second rule chunk is all padding
    (40, 32),   # four rule chunks, then one real rule chunk in a bucket of 8
])
def test_chunked_prefill_and_decode_equal_the_reference(qwen, n, chunk):
    model, variables = qwen
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
    assert 1e-4 < rel_err(got.astype(np.float32), want[12:17]) < 0.3
    assert all(a.dtype == jnp.float32 for a in engine.cache.state[DELTA])
    assert all(a.dtype == jnp.bfloat16 for a in engine.cache.state[CONV])


def test_idle_slots_between_live_ones_and_a_slot_handed_on(qwen):
    """Four requests in flight; the second finishes, so a round's live slots
    are 0, 2 and 3 with an idle one BETWEEN them, whose state (both parts)
    the round leaves bit for bit; its slot then goes to a newcomer, which
    starts from nothing in both parts whatever its last owner left."""
    model, variables = qwen
    engine, calls = engine_of(model, variables)
    prompts = [prompt_of(n, seed=10 + n) for n in (5, 13, 9, 21)]
    slots = [engine.alloc_slot() for _ in prompts]
    toks = {s: [engine.prefill(s, p)] for s, p in zip(slots, prompts)}
    rows = {s: [] for s in slots}
    for _ in range(2):
        out = engine.decode()
        for i, s in enumerate(sorted(out)):
            toks[s].append(out[s])
            rows[s].append(calls[-1][i])
    engine.release(slots[1])
    left = [np.asarray(a[slots[1]]) for part in engine.cache.state
            for a in part]
    assert all(np.abs(a).max() > 0 for a in left)   # its owner's state
    for _ in range(2):                              # live: 0, 2, 3
        out = engine.decode()
        assert sorted(out) == [slots[0], slots[2], slots[3]]
        for i, s in enumerate(sorted(out)):
            toks[s].append(out[s])
            rows[s].append(calls[-1][i])
    after = [np.asarray(a[slots[1]]) for part in engine.cache.state
             for a in part]
    assert len(after) == 2 * 3
    for a, b in zip(left, after):
        np.testing.assert_array_equal(a, b)
    for s, p in zip(slots, prompts):
        if s == slots[1]:
            continue
        want = ref_logits(model, variables["params"], [p + toks[s]])[0]
        n = len(p)
        assert rel_err(np.stack(rows[s]), want[n:n + 4]) < F32_TOL
    # the freed slot to a newcomer, beside three that still decode
    new = engine.alloc_slot()
    assert new == slots[1]
    late = prompt_of(11, seed=99)
    late_toks, late_rows = [engine.prefill(new, late)], [calls[-1][0]]
    for _ in range(3):
        out = engine.decode()
        late_toks.append(out[new])
        late_rows.append(calls[-1][sorted(out).index(new)])
    want = ref_logits(model, variables["params"], [late + late_toks])[0]
    assert rel_err(np.stack(late_rows), want[10:14]) < F32_TOL
    assert engine.metrics.count("state_resets") == 5


def test_chunk_programs_with_the_flash_kernel_give_the_walks_logits(
        monkeypatch):
    """A prompt of five chunks over views of 64 rows, past a ``KEY_BLOCK``
    scaled down to 16 with them: the chunk programs whose full layers take
    the flash forward kernel (interpreted) give the tokens of the programs
    that walk the view's key blocks in XLA, and their logits within the
    float32 tolerance; the DeltaNet layers carry their state either way."""
    import sys

    from paged_programs import chunk_kernel_beside_the_walk

    monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"], "KEY_BLOCK",
                        16)
    model, v = make()
    worst, (walk, kernel), walk_plans, plans = chunk_kernel_beside_the_walk(
        monkeypatch, model, v, prompt_of(37, seed=5), 3, num_slots=2,
        max_len=64, page_size=4, prefill_chunk=8, min_bucket=4)
    assert kernel == walk and worst < F32_TOL
    c = model.c
    full = {(p["kernel"], p["why"]) for p in plans if p["why"] != "window"}
    assert full == {(1, "")} and {
        (p["kernel"], p["why"]) for p in walk_plans
        if p["why"] != "window"} == {(0, "backend")}
    assert {(p["heads"], p["kv_heads"], p["d"], p["rows"]) for p in plans
            if p["kernel"]} == {(c.num_heads, c.num_kv_heads, c.head_dim, 64)}


def test_a_view_within_a_key_block_is_short_on_any_backend(monkeypatch):
    """Under ``KEY_BLOCK`` rows the whole view is one softmax and no kernel
    is asked for: the choice says ``short``, a TPU backend or not."""
    from paged_programs import chunk_plans, engine_logits, on_a_tpu

    model, v = make()
    plans = chunk_plans(monkeypatch)
    on_a_tpu(monkeypatch)
    engine_logits(model, v, prompt_of(37, seed=5)[:11], 2, num_slots=2,
                  max_len=64, page_size=4, prefill_chunk=8, min_bucket=4)
    assert {(p["kernel"], p["why"]) for p in plans} == {(0, "short")}


def test_requests_in_flight_together_each_equal_the_reference(qwen):
    model, variables = qwen
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
    snap = engine.metrics.snapshot()
    # the expert layers' counts, through the engine's own books: every
    # held pair went through the grouped walk
    assert snap["moe_grouped"] == snap["moe_held"] > 0
    assert snap["moe_absent"] > snap["moe_held"] and snap["moe_zero"] == 0
    assert engine.metrics.count("prefix_state_refusals") == 6


def test_the_expert_walk_is_grouped_in_a_chunk_and_in_a_round():
    """Served + partly held + grouped: the static rule takes the grouped
    path at the published expert (2048 x 512 = 1 Mi) at any row count, and
    the row budget follows the router's width: a 2,048-row chunk's 20,480
    pairs are 5,760 expected here and an eighth more, in 256-row tiles; a
    round of 48 one tile."""
    assert moe_ops.held_expert_path(2048, 10, 128, 2048, 512) == "grouped"
    assert moe_ops.held_expert_path(48, 10, 128, 2048, 512) == "grouped"
    assert moe_ops.grouped_row_budget(2048, 10, 128, 512) == 5888
    assert moe_ops.grouped_row_budget(48, 10, 128, 512) == 256
    assert moe_ops.grouped_row_budget(1, 10, 128, 512) == 256


# ---- (e) a long decode: the state's precision shows only here ----

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
    """Everything float32 but the rule's matrix, rounded to bfloat16 once a
    round: the control.  The benchmark's check decodes eight tokens."""
    assert long_decode_err(jnp.bfloat16) > 2 * F32_TOL


# ---- (f) the share tied to the model ----

def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """16 experts over 4 chips: each share's routed part (the experts it
    holds, the router whole on every chip) summed, plus the gated shared
    expert counted ONCE, is what the uncut reference gives for the layer."""
    c = tiny(held=(0, 16))
    model = Qwen3NextModel(c)
    moe = jax.jit(model.init)(jax.random.PRNGKey(7))["params"]["layers"]["moe"]
    one = ref.at(moe, 2)                      # layer 2's leaves, all experts
    u = jax.random.normal(jax.random.PRNGKey(8), (3, 11, 32), jnp.float32)
    want = np.asarray(ref.expert_layer(one, u, dims_of(c)))
    tokens = u.reshape(-1, 32)
    total = np.zeros(tokens.shape, np.float32)
    for rank in range(4):
        share = HeldExpertLayer(
            n_routed=16, n_zero=0, k=4, scaling=1.0, held=(4 * rank, 4),
            block_rows=8, dtype=jnp.float32, scoring="softmax",
            renormalise=True, shared=False)
        leaves = {"router": one["router"],
                  **{k: one[k][4 * rank:4 * rank + 4]
                     for k in ("gate", "up", "down")}}
        routed, stats = share.combine(leaves, tokens,
                                      *share.route(leaves, tokens))
        assert int(stats[0]) + int(stats[2]) == tokens.shape[0] * 4
        total += np.asarray(routed)
    once = HeldExpertLayer(
        n_routed=16, n_zero=0, k=4, scaling=1.0, held=(0, 4),
        dtype=jnp.float32, shared=True)
    total += np.asarray(once.shared_expert(one, tokens))
    np.testing.assert_allclose(total.reshape(want.shape), want, atol=2e-5,
                               rtol=2e-5)
    # ... and one share alone is not: the absent experts' part is left out
    assert np.abs(np.asarray(routed).reshape(want.shape) - want).max() > 0.05


def test_a_shared_expert_without_the_gate_leaf_is_as_it_was():
    """``shared_gate_w`` absent = the layer every other model has."""
    layer = HeldExpertLayer(n_routed=8, n_zero=0, k=2, scaling=1.0,
                            held=(0, 8), dtype=jnp.float32, shared=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    p = {"shared_gate": jax.random.normal(ks[0], (16, 8)),
         "shared_up": jax.random.normal(ks[1], (16, 8)),
         "shared_down": jax.random.normal(ks[2], (8, 16))}
    x = jax.random.normal(ks[3], (5, 16))
    plain = layer.shared_expert(p, x)
    w = jax.random.normal(ks[4], (16,))
    gated = layer.shared_expert({**p, "shared_gate_w": w}, x)
    np.testing.assert_allclose(
        gated, plain * jax.nn.sigmoid(x @ w)[:, None], rtol=1e-5, atol=1e-5)


# ---- the cache's books ----

def test_the_cache_holds_an_array_a_part_a_layer_beside_two_cache_layers(
        qwen):
    model, _ = qwen
    spec = model.kv_cache_spec()
    cache = PagedKVCache(spec, 4, 64, page_size=4)
    assert cache.k.shape[0] == 1                # cache layers: the full one
    assert [[a.shape for a in part] for part in cache.state] == [
        3 * [(5, 3 * 64)], 3 * [(5, 4, 8, 8)]]
    assert cache.state_bytes == 5 * spec.bytes_per_slot
    assert cache.max_prefix_entries == 0


# ---- (g) the models that share the edited code keep their programs ----

# program_digest of Falcon-H1's two programs traced at the parent commit
# (fa150a6, PR 50; tests/paged_programs.py's tiny model: BlockDecoder,
# GroupedHeads, SlotStates' parts).  K-EXAONE's, LFM2's and Mellum's are
# held by tests/test_falcon_h1.py (g), whose recorded digests this change
# leaves as they are.  A change meant to alter one of these programs
# records its own.
PARENT = {
    "falcon.decode":
        "cf3ff444d0a504df51e3a02f4d719e448a074a142b1094f60c87111ab2e2c11d",
    "falcon.chunk":
        "160bc41cbbe0abb0829aae72b475b595591de2b6ebfa0a304500838a079357cf",
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_falcon_h1_keeps_its_programs(name):
    kind, program = name.split(".")
    model, variables, kw = tiny_served(kind)
    engine = PagedServeEngine(model, variables, **kw)
    closed = traced(engine, program, batch=4, chunk=8).jaxpr
    assert program_digest(closed.jaxpr) == PARENT[name]
