"""LongCat-Flash's language model against its plain reference
(``benchmarks/reference/longcat_flash.py``) at small widths on the CPU,
seeded weights: the dense forward, chunked prefill and absorbed decode
through ``PagedServeEngine`` and ``ContinuousBatchingScheduler``, the expert
layer's shares, training through ``Executor``, weights kept in their type,
slot migration over pools of two widths, and paths broken on purpose."""

import gc
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import hetu_tpu as ht  # noqa: E402
from benchmarks.reference import longcat_flash as ref  # noqa: E402
from hetu_tpu import optim  # noqa: E402
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer  # noqa: E402
from hetu_tpu.models.longcat_flash import (  # noqa: E402
    LatentAttention, LongcatFlashConfig, LongcatFlashModel,
)
from hetu_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, KVCacheSpec, PagedServeEngine, Request,
)
from hetu_tpu.serve import migrate  # noqa: E402
from paged_programs import (  # noqa: E402
    dense_greedy, engine_greedy, oversized, pad_writes, param_converts,
    program as paged_program,
)

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 96


def tiny(**kw) -> LongcatFlashConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=4,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, ffn_size=48, expert_ffn_size=16,
        n_routed_experts=8, zero_expert_num=4, moe_topk=3,
        routed_scaling_factor=6.0, held=(2, 4), max_position=128,
        dtype=jnp.float32, param_dtype=jnp.float32, init_std=0.2,
        router_init_std=0.5, router_bias_std=1e-3, expert_block_rows=4,
        attn_key_block=16)
    base.update(kw)
    return LongcatFlashConfig(**base)


@pytest.fixture(autouse=True)
def experts_over_the_grouped_limit(monkeypatch):
    """The published experts, 6144 x 2048, are three times over what the
    grouped kernels keep whole in VMEM, so the served cell EVALUATES them by
    the fused call cut along F and reverse mode walks them with the loop
    (``ops.moe_ops.held_expert_path``: ``"cut"``).  The tiny ones here are
    held to that path by limits scaled down with them
    (``paged_programs.cut_tiny_experts``)."""
    from paged_programs import cut_tiny_experts

    c = tiny()
    cut_tiny_experts(monkeypatch, c.hidden_size, c.expert_ffn_size)
    jax.clear_caches()
    yield
    jax.clear_caches()


def dims_of(c: LongcatFlashConfig) -> dict:
    return dict(heads=c.num_heads, q_rank=c.q_lora_rank,
                kv_rank=c.kv_lora_rank, nope=c.qk_nope_head_dim,
                rope=c.qk_rope_head_dim, v=c.v_head_dim,
                n_routed=c.n_routed_experts, n_zero=c.zero_expert_num,
                topk=c.moe_topk, scaling=c.routed_scaling_factor,
                held=c.held, theta=c.rope_theta, eps=c.rms_eps)


def make(seed=0, **kw):
    model = LongcatFlashModel(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


def ref_logits(model, params, ids):
    dims = dims_of(model.c)
    return np.asarray(jax.jit(lambda p, x: ref.logits(p, x, dims))(
        params, ids))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(got - want)) / (want.max() - want.min()))


def ids_of(shape, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, shape) \
        .astype(np.int32)


# ------------------------------------------------------------ (a) forward

@pytest.mark.parametrize("held", [(2, 4), (0, 8), (7, 1)])
def test_dense_forward_matches_the_reference(held):
    model, v = make(held=held)
    ids = ids_of((2, 24))
    got = np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            v["params"], ids))
    assert rel_err(got, ref_logits(model, v["params"], ids)) < F32_TOL


# --------------------------------------------- (b) the engine's own logits

class Recorded:
    """The logits the engine's two programs computed, caught on their way
    to the argmax: rows keyed by (tokens cached before the row's token,
    the row's input token)."""

    def __init__(self, model, monkeypatch):
        self.rows = {}
        chunk, decode = model.prefill_chunk_with_cache, \
            model.decode_with_cache

        def note(logits, lengths, tokens):
            for lg, n, t in zip(np.asarray(logits), np.asarray(lengths),
                                np.asarray(tokens)):
                self.rows[(int(n), int(t))] = lg

        def chunk_(variables, ids, k, v, start, *, last_index=None):
            out = chunk(variables, ids, k, v, start, last_index=last_index)
            jax.debug.callback(note, out[0], (start + last_index)[None],
                               ids[:, last_index])
            return out

        def decode_(variables, ids, k, v, lengths):
            out = decode(variables, ids, k, v, lengths)
            jax.debug.callback(note, out[0], lengths, ids)
            return out

        monkeypatch.setattr(model, "prefill_chunk_with_cache", chunk_)
        monkeypatch.setattr(model, "decode_with_cache", decode_)


def serve(model, v, prompts, decoded):
    engine = PagedServeEngine(model, v, num_slots=4, max_len=128,
                              page_size=8, prefill_chunk=16)
    sched = ContinuousBatchingScheduler(engine)
    reqs = [Request(prompt=list(map(int, p)), max_tokens=decoded)
            for p in prompts]
    sched.run(reqs)
    assert all(r.status == "ok" and len(r.tokens) == decoded for r in reqs)
    return engine, reqs


def engine_err(model, v, monkeypatch, *, decoded=6) -> float:
    """Largest error, over the reference's logit range, of the logits the
    engine computed at every decoded position of three requests in flight
    together, prompts of 13, 37 and 70 tokens over pages of 8 and chunks of
    16."""
    rec = Recorded(model, monkeypatch)
    prompts = [ids_of(n, seed=n) for n in (13, 37, 70)]
    engine, reqs = serve(model, v, prompts, decoded)
    assert engine.cache.spec.num_layers == 2 * model.c.num_layers
    worst = 0.0
    for r in reqs:
        seq = np.asarray(list(r.prompt) + list(r.tokens), np.int32)
        want = ref_logits(model, v["params"], seq[None])[0]
        n = len(r.prompt)
        for j in range(decoded):     # row n-1+j predicts tokens[j]
            got = rec.rows[(n - 1 + j, int(seq[n - 1 + j]))]
            worst = max(worst, rel_err(got, want[n - 1 + j]))
    return worst


def test_chunked_prefill_and_decode_match_the_reference(monkeypatch):
    model, v = make()
    assert engine_err(model, v, monkeypatch) < F32_TOL


def test_chunk_programs_with_the_flash_kernel_give_the_walks_logits(
        monkeypatch):
    """A prompt of five chunks over views of 64 rows, four key blocks of
    16: the chunk programs whose expanded attention rebuilds K and V and
    calls the flash forward kernel (interpreted) give the tokens of the
    programs that attend inside the walk, and their logits within the
    float32 tolerance; each program's attention says which it took."""
    from hetu_tpu.models import longcat_flash
    from paged_programs import chunk_kernel_beside_the_walk

    model, v = make()
    # 4 heads x 8 queries x 16 keys a block: the chunks of 8 are asked for,
    # the last chunk's bucket of 4 has too few queries
    monkeypatch.setattr(longcat_flash, "REBUILD_MIN_SCORES", 4 * 8 * 16)
    worst, (walk, kernel), walk_plans, plans = chunk_kernel_beside_the_walk(
        monkeypatch, model, v, ids_of(35, seed=5).tolist(), 3, num_slots=2,
        max_len=64, page_size=4, prefill_chunk=8, min_bucket=4)
    assert kernel == walk and worst < F32_TOL
    assert {(p["kernel"], p["why"]) for p in walk_plans} == {(0, "backend")}
    assert {(p["kernel"], p["why"], p["s_c"]) for p in plans} == {
        (1, "", 8), (0, "few_queries", 4)}
    plans = [p for p in plans if p["kernel"]]
    c = model.c
    assert {(p["heads"], p["d"], p["d_v"], p["rows"]) for p in plans} \
        == {(c.num_heads, c.qk_head_dim, c.v_head_dim, 64)}


def test_a_static_trip_keeps_the_walk_on_any_backend(monkeypatch):
    """Reverse mode needs the loop (a kernel call has no backward): under
    ``static_trip`` the choice says so, a TPU backend or not, and the
    training forward differentiates."""
    from hetu_tpu.models import longcat_flash
    from paged_programs import chunk_plans, on_a_tpu

    model, v = make()
    monkeypatch.setattr(longcat_flash, "REBUILD_MIN_SCORES", 0)
    plans = chunk_plans(monkeypatch)
    on_a_tpu(monkeypatch)
    ids = jnp.asarray(ids_of((1, 24), seed=2))
    grads = jax.grad(lambda p: model.apply(
        {"params": p, "state": {}}, ids, train=True)[0].sum())(v["params"])
    assert {(p["kernel"], p["why"]) for p in plans} == {(0, "static_trip")}
    assert float(jnp.abs(grads["layers"]["attn"]["kv_b"]).max()) > 0
    del plans[:]
    model.apply(v, ids)                  # the dense forward, not trained
    assert {(p["kernel"], p["why"]) for p in plans} == {(1, "")}


# --------------------------------------- (c) absorbed equals expanded

def test_absorbed_decode_equals_expanded_attention_on_one_cache():
    c = tiny()
    model, v = make()
    att = LatentAttention(c)
    p = jax.tree_util.tree_map(lambda a: a[0, 1],
                               v["params"]["layers"]["attn"])
    rng = np.random.default_rng(3)
    b, t = 3, 40
    lengths = jnp.asarray([5, 39, 17], jnp.int32)
    c_all = jnp.asarray(rng.normal(size=(b, t, c.kv_lora_rank)), jnp.float32)
    r_all = jnp.asarray(rng.normal(size=(b, t, c.qk_rope_head_dim)),
                        jnp.float32)
    q_n = jnp.asarray(rng.normal(size=(b, 1, c.num_heads,
                                       c.qk_nope_head_dim)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(b, 1, c.num_heads,
                                       c.qk_rope_head_dim)), jnp.float32)
    absorbed = att.absorbed(p, q_n, q_r, c_all, r_all, lengths)
    expanded = att.expanded(p, q_n, q_r, c_all, r_all, lengths[:, None])
    np.testing.assert_allclose(absorbed, expanded, rtol=2e-4, atol=2e-4)


# ------------------------------------------------ (d) the shares add up

def expert_layer_params(c, seed=0):
    _, v = make(seed, held=(0, c.n_routed_experts))
    return jax.tree_util.tree_map(lambda a: a[0],
                                  v["params"]["layers"]["moe"])


def share_of(c, p, first, count):
    layer = HeldExpertLayer(
        n_routed=c.n_routed_experts, n_zero=c.zero_expert_num, k=c.moe_topk,
        scaling=c.routed_scaling_factor, held=(first, count),
        block_rows=c.expert_block_rows, dtype=jnp.float32)
    mine = {k: (a[first:first + count] if k in ("gate", "up", "down") else a)
            for k, a in p.items()}
    return layer, mine


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_of_the_expert_layer_add_up(shares):
    """The outputs of all the shares, the identity part (which every chip
    computes alike) counted once, sum to the uncut reference's layer."""
    c = tiny(held=None)
    p = expert_layer_params(c)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 19, 32)),
                    jnp.float32)
    dims = dims_of(c)
    whole = ref.expert_layer(p, u, dims)
    w, idx = ref.expert_choice(p["router"], p["router_bias"], u, dims)
    identity = ref.identity_experts(u, w, idx, dims)
    count = c.n_routed_experts // shares
    total, pairs = -(shares - 1) * identity, np.zeros(4, np.int64)
    for i in range(shares):
        layer, mine = share_of(c, p, i * count, count)
        out, stats = layer.apply(mine, u)
        total, pairs = total + out, pairs + np.asarray(stats)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    held, zero, absent, _ = pairs
    n = u.shape[0] * u.shape[1] * c.moe_topk
    assert held + zero // shares == n    # every pair computed exactly once
    assert absent == (shares - 1) * held and zero % shares == 0
    assert MOE_STATS == ("moe_held", "moe_zero", "moe_absent", "moe_hit")


# ----------------------------------------------------------- (e) no drops

@pytest.mark.parametrize("differentiated", [False, True])
def test_every_token_on_one_held_expert_is_still_computed(differentiated):
    """Forward, and under reverse mode (the walk's own backward): all 37
    rows of the crowded expert are computed, ten blocks of four rows."""
    c = tiny()
    p = expert_layer_params(c)
    first, count = c.held
    crowded = first + 1
    p = dict(p, router_bias=p["router_bias"].at[crowded].set(10.0))
    layer, mine = share_of(c, p, first, count)
    tokens = 37                          # ten blocks of four rows, one short
    u = jnp.asarray(np.random.default_rng(2).normal(size=(1, tokens, 32)),
                    jnp.float32)
    if differentiated:
        (_, (out, stats)), du = jax.jit(jax.value_and_grad(
            lambda x, q: (lambda o, s: (jnp.sum(o), (o, s)))(
                *layer.apply(q, x)), has_aux=True))(u, mine)
        want_du = jax.grad(lambda x: jnp.sum(
            ref.expert_layer(mine, x, dims_of(c))))(u)
        np.testing.assert_allclose(du, want_du, rtol=1e-4, atol=1e-5)
    else:
        out, stats = jax.jit(lambda q, x: layer.apply(q, x))(mine, u)
    want = ref.expert_layer(mine, u, dims_of(c))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    _, idx = ref.expert_choice(p["router"], p["router_bias"], u, dims_of(c))
    assert int(np.sum(np.asarray(idx) == crowded)) == tokens
    assert int(stats[0]) >= tokens and int(stats[3]) >= 1
    assert sum(int(s) for s in stats[:3]) == tokens * c.moe_topk


# ---------------------------------------------- (f) loss and gradients

def test_loss_and_gradients_match_the_reference_through_executor():
    model, v = make()
    ids = ids_of((4, 20), seed=5)
    lr = 0.5
    ex = ht.Executor(model.lm_loss_fn(), optim.SGDOptimizer(lr), seed=0)
    state = ex.init_state(jax.tree_util.tree_map(jnp.copy, v))
    state, m = ex.run("train", state, (ids,))
    want_loss, want = jax.jit(
        lambda p, x: ref.loss_and_grads(p, x, dims_of(model.c)))(
            v["params"], ids)
    assert abs(float(m["loss"]) - float(want_loss)) < 1e-5 * float(want_loss)
    got = jax.tree_util.tree_map(lambda a, b: (a - b) / lr, v["params"],
                                 state.params)
    scale = float(ref.global_norm(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = want
        for k in path:
            w = w[k.key]
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * scale, path
    assert float(jnp.max(jnp.abs(want["layers"]["moe"]["down"]))) > 0


# ------------------------------------- (g) the weights keep their type

def test_bfloat16_weights_stay_bfloat16_and_are_held_once():
    gc.collect()
    before = sum(a.nbytes for a in jax.live_arrays())
    c = tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, hidden_size=64,
             ffn_size=256, expert_ffn_size=64)
    model = LongcatFlashModel(c)
    v = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = PagedServeEngine(model, v, num_slots=2, max_len=32, page_size=8,
                              prefill_chunk=16)
    own = sum(a.nbytes for a in jax.tree_util.tree_leaves(engine.params))
    for path, a in jax.tree_util.tree_leaves_with_path(engine.params):
        want = jnp.float32 if "router" in str(path) else jnp.bfloat16
        assert a.dtype == want, path
    # every leaf is in the type it is read in: the engine holds the very
    # arrays it was given (ISSUE 31), and says so; but for the two leaves
    # whose products are read head by head, held transposed (ISSUE 45):
    # arrays of the engine's own, so a caller that drops its tree holds
    # nothing twice
    given = dict(jax.tree_util.tree_leaves_with_path(v["params"]))
    held = dict(jax.tree_util.tree_leaves_with_path(engine.params))
    assert len(held) == len(given)
    assert all(a is given[path] for path, a in held.items() if path in given)
    assert sorted(str(path[-1]) for path in set(given) - set(held)) \
        == ["['kv_b']", "['q_b']"]
    snap = engine.metrics.snapshot()
    assert snap["params_retyped"] == 0 and snap["params_relaid"] == 2
    assert snap["params_bytes_held"] == snap["params_bytes_given"] == own
    del given, held, v
    slot = engine.alloc_slot()
    engine.prefill(slot, ids_of(20).tolist())
    engine.decode()
    jax.block_until_ready(engine.cache.k)
    gc.collect()
    pools = engine.cache.k.nbytes + engine.cache.v.nbytes
    live = sum(a.nbytes for a in jax.live_arrays()) - before - pools
    assert live < 1.1 * own, (live, own)


@pytest.mark.parametrize("program", ["decode", "chunk", "chunk_ext"])
def test_no_program_converts_a_bfloat16_leaf(program):
    """Weights made in the compute type cost no cast in any call: no paged
    program converts a parameter leaf, read whole or at ``[l, i]``, and each
    is the program it would be over the leaves as given (ISSUE 31)."""
    c = tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = LongcatFlashModel(c)
    v = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = PagedServeEngine(model, v, num_slots=2, max_len=64, page_size=8,
                              prefill_chunk=16)
    assert param_converts(engine, program, batch=2, chunk=16) == []
    assert param_converts(engine, program, batch=2, chunk=16,
                          params=v["params"]) == []
    # the program over the engine's leaves differs from the one over the
    # leaves as given by the two transposed holds alone (ISSUE 45)
    held, _ = paged_program(engine, program, batch=2, chunk=16)
    given, _ = paged_program(engine, program, batch=2, chunk=16,
                             params=v["params"])
    counts = [sum(e.primitive.name == "dot_general" for e in p.jaxpr.eqns)
              for p in (held, given)]
    assert counts[0] == counts[1]


def test_the_cache_spec_has_two_widths_and_its_own_layer_count():
    model, _ = make()
    spec = KVCacheSpec.from_model(model)
    assert (spec.num_layers, spec.num_kv_heads) == (4, 1)
    assert (spec.head_dim, spec.v_dim) == (8, 4)
    assert spec.bytes_per_token == 4 * (8 + 4) * 4
    full = LongcatFlashModel(LongcatFlashConfig(num_layers=4)) \
        .kv_cache_spec()
    assert full.bytes_per_token == 9216 and full.num_layers == 8
    # models that state no cache of their own get the spec they had
    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.models.llama import LlamaConfig, LlamaModel
    gpt = KVCacheSpec.from_model(GPTModel(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
        ffn_size=64, max_position=32)))
    assert gpt == KVCacheSpec(3, 4, 8, jnp.float32) and gpt.v_dim == 8
    llama = KVCacheSpec.from_model(LlamaModel(LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=64, max_position=32)))
    assert llama == KVCacheSpec(2, 2, 8, jnp.float32)


# ---------------- (g2) one cache layer's pages at a time (ISSUE 29)

@pytest.mark.parametrize("program", ["decode", "chunk", "chunk_ext"])
def test_no_program_holds_a_pool_or_a_view_of_every_layer(program):
    """As ``test_paged_kv.py``'s for GPT and Llama: nothing as large as the
    latent pool or as ``L x b x T x row`` but the carried pool itself."""
    model, v = make(kv_lora_rank=32, max_position=512)
    engine = PagedServeEngine(model, v, num_slots=2, max_len=512,
                              page_size=8, prefill_chunk=16)
    floor, found = oversized(engine, program, batch=2, chunk=16)
    assert floor >= 4 * 512 * 32 and found == []


@pytest.mark.parametrize("case", ["boundary", "cow", "tp2"])
def test_paged_tokens_equal_the_dense_caches(case):
    """Float32, token for token: a prompt whose padded final chunk runs
    past the slot's pages (the boundary program), two requests sharing a
    prefix with a copy-on-write page, and a ``tp=2`` mesh."""
    model, v = make()
    # max_len 60 = 15 pages of 4: the chunk [48, 57) pads to 16 -> 64 > 60
    prompt = ids_of(57 if case == "boundary" else 21, seed=29).tolist()
    n = 3 if case == "boundary" else 8
    want = dense_greedy(model, v, prompt, n, 60)
    engine = PagedServeEngine(
        model, v, num_slots=2, max_len=60, page_size=4, prefill_chunk=16,
        mesh=ht.make_mesh(tp=2) if case == "tp2" else None)
    assert engine_greedy(engine, prompt, n) == want
    if case == "boundary":
        assert engine._chunk_fn_ext is not None
    if case == "cow":
        assert engine_greedy(engine, prompt, n) == want
        assert engine.cache.cow_copies >= 1
        assert engine.cache.prefix_hit_tokens == len(prompt) - 1


def test_pad_positions_are_written_to_scratch_only():
    model, v = make()
    engine = PagedServeEngine(model, v, num_slots=4, max_len=64, page_size=8,
                              prefill_chunk=16, prefix_sharing=False)
    prompts = [ids_of(n, seed=n).tolist() for n in (5, 19, 9)]
    assert pad_writes(engine, prompts) == {
        "stray": [], "missed": [], "scratch_written": True}


# ------------------------------------------------- (h) a slot migrates

@pytest.mark.parametrize("codec", ["none", "bf16"])
def test_an_exported_slot_decodes_the_same_logits_where_imported(
        monkeypatch, codec):
    model, v = make()
    rec = Recorded(model, monkeypatch)

    def engine():
        return PagedServeEngine(model, v, num_slots=2, max_len=128,
                                page_size=8, prefill_chunk=16)

    src, dst = engine(), engine()
    slot = src.alloc_slot()
    src.prefill(slot, ids_of(29, seed=9).tolist())
    for _ in range(3):
        src.decode()
    snaps = src.export_slots([slot])
    assert snaps[0].k.shape == (4, 32, 1, 8) and \
        snaps[0].v.shape == (4, 32, 1, 4)
    spec_d, wired, _ = migrate.unpack(
        migrate.pack(src.cache.spec, snaps, codec=codec))
    migrate.check_spec(dst.cache.spec, spec_d)
    if codec == "none":
        np.testing.assert_array_equal(wired[0].v, snaps[0].v)
    moved = dst.adopt_slots(wired)[slot]
    src.resume_slots([slot])
    tol = 0.0 if codec == "none" else 0.02
    for step in range(4):
        rec.rows.clear()
        a = src.decode()[slot]
        (key, at_src), = rec.rows.items()
        rec.rows.clear()
        b = dst.decode()[moved]
        (key_dst, at_dst), = rec.rows.items()
        if codec == "none":
            assert a == b and key == key_dst
            np.testing.assert_array_equal(at_src, at_dst)
        else:
            assert key[0] == key_dst[0] == 32 + step
            assert rel_err(at_dst, at_src) < tol
            break      # a rounded cache may pick another token next


# ---------------------------------------- (i) paths broken on purpose

def test_a_cache_read_one_position_off_fails_the_comparison(monkeypatch):
    model, v = make()
    absorbed = model.attn.absorbed
    monkeypatch.setattr(
        model.attn, "absorbed",
        lambda p, q_n, q_r, c, r, lengths: absorbed(
            p, q_n, q_r, c, r, jnp.maximum(lengths - 1, 0)))
    assert engine_err(model, v, monkeypatch) > 50 * F32_TOL


def test_dropping_the_shortcut_term_fails_the_comparison(monkeypatch):
    model, v = make()
    apply = model.moe.apply
    monkeypatch.setattr(
        model.moe, "apply",
        lambda p, u, **kw: tuple(
            x * 0 if i == 0 else x for i, x in enumerate(apply(p, u, **kw))))
    assert engine_err(model, v, monkeypatch) > 50 * F32_TOL


@pytest.mark.parametrize("what", ["router", "compute"])
def test_a_lower_precision_than_stated_fails_at_float32_tolerance(what):
    """The router left in bfloat16, or the whole forward: each lands far
    outside what two float32 orders of operation differ by."""
    model, v = make()
    ids = ids_of((2, 24))
    want = ref_logits(model, v["params"], ids)
    if what == "compute":
        model = LongcatFlashModel(tiny(dtype=jnp.bfloat16))
    else:
        route = model.moe.route

        def in_bf16(p, tokens):
            p = dict(p, router=p["router"].astype(jnp.bfloat16)
                     .astype(jnp.float32))
            return route(p, tokens.astype(jnp.bfloat16))
        model.moe.route = in_bf16
    got = np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            v["params"], ids).astype(jnp.float32))
    assert rel_err(got, want) > 10 * F32_TOL


# ------------- the cut path: evaluated by the fused call, trained by the loop

def test_rounds_and_chunks_emit_the_tokens_the_loop_emitted(monkeypatch):
    """Requests in flight together through the engine, chunks and decode
    rounds: the fused call cut along F (four F tiles a visit here) emits
    the tokens the loop's forward emits, and counts every held pair as a
    grouped call's (``moe_grouped`` beside ``moe_held``)."""
    from hetu_tpu.ops import moe_ops
    from paged_programs import loop_evaluates

    model, v = make()
    prompts = [ids_of(n, seed=n) for n in (13, 37, 70)]
    engine, reqs = serve(model, v, prompts, 12)
    assert model.step_stats == MOE_STATS + ("moe_grouped",)
    assert engine.metrics.count("moe_grouped") \
        == engine.metrics.count("moe_held") > 0
    c = model.c
    routed = c.n_routed_experts + getattr(c, "zero_expert_num", 0)
    # a round's pairs fit one trip; a chunk's take the trips form
    for t, whole in ((4, True), (16, False)):
        assert moe_ops.held_expert_path(
            t, c.moe_topk, c.held[1], c.hidden_size,
            c.expert_ffn_size) == "cut"
        assert (moe_ops.grouped_row_budget(t, c.moe_topk, c.held[1], routed)
                >= t * c.moe_topk) == whole
    loop_evaluates(monkeypatch)
    jax.clear_caches()
    _, loop_reqs = serve(model, v, prompts, 12)
    assert [r.tokens for r in reqs] == [r.tokens for r in loop_reqs]


def test_a_training_step_past_the_limit_still_takes_the_loop():
    """The dense forward EVALUATED holds the grouped walk; differentiated,
    the loop's two ``while`` walks and no grouped call."""
    from paged_programs import eqn_names

    model, v = make()
    ids = jnp.asarray(ids_of((2, 24), seed=2))

    def loss(p):
        return model.apply({"params": p, "state": {}}, ids,
                           train=True)[0].sum()

    evaluated = eqn_names(jax.make_jaxpr(loss)(v["params"]).jaxpr)
    assert "_grouped_forward" in evaluated
    trained = eqn_names(jax.make_jaxpr(jax.grad(loss))(v["params"]).jaxpr)
    assert "_grouped_forward" not in trained and "while" in trained
    grads = jax.grad(loss)(v["params"])
    assert float(jnp.abs(grads["layers"]["moe"]["gate"]).max()) > 0
