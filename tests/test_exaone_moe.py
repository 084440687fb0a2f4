"""K-EXAONE's language model against its plain reference
(``benchmarks/reference/exaone_moe.py``) at small widths on the CPU, seeded
weights: the dense forward, chunked prefill and decode through
``PagedServeEngine`` and ``ContinuousBatchingScheduler`` over a cache of two
groups at lengths that wrap the window ring many times (float32 and
bfloat16), the expert layer's shares with the shared expert counted once,
one test for each point the configuration file lists as ``assumed``, and
paths broken on purpose."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import exaone_moe as ref  # noqa: E402
from hetu_tpu import ops  # noqa: E402
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer  # noqa: E402
from hetu_tpu.models.exaone_moe import (  # noqa: E402
    FULL, WINDOW, ExaoneMoeConfig, ExaoneMoeModel,
)
from hetu_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.serve.kv_cache import GroupedCacheNotPortable  # noqa: E402
from paged_programs import oversized, param_converts  # noqa: E402

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 96
WIN = 8


def tiny(**kw) -> ExaoneMoeConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=12, ffn_size=48, expert_ffn_size=16,
        first_dense=1, n_routed_experts=16, moe_topk=4,
        routed_scaling_factor=2.5, held=(4, 4), window=WIN,
        max_position=256, dtype=jnp.float32, param_dtype=jnp.float32,
        init_std=0.2, router_init_std=0.3, router_bias_std=0.05,
        expert_block_rows=4)
    base.update(kw)
    return ExaoneMoeConfig(**base)


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


def dims_of(c: ExaoneMoeConfig) -> dict:
    return dict(heads=c.num_heads, kv_heads=c.num_kv_heads,
                head_dim=c.head_dim, window=c.window,
                layer_types=c.layer_types, first_dense=c.first_dense,
                n_routed=c.n_routed_experts, topk=c.moe_topk,
                scaling=c.routed_scaling_factor, held=c.held,
                theta=c.rope_theta, eps=c.rms_eps)


def make(seed=0, **kw):
    model = ExaoneMoeModel(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


def ref_logits(model, params, ids):
    dims = dims_of(model.c)
    return np.asarray(jax.jit(lambda p, x: ref.logits(p, x, dims))(
        params, ids))


def sys_logits(model, params, ids):
    return np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            params, ids)).astype(np.float32)


def rel_err(got, want) -> float:
    return float(np.max(np.abs(got - want)) / (want.max() - want.min()))


def ids_of(shape, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, shape) \
        .astype(np.int32)


# ------------------------------------------------------------ (a) forward

@pytest.mark.parametrize("held", [(4, 4), (0, 16), (15, 1)])
def test_dense_forward_matches_the_reference(held):
    model, v = make(held=held)
    assert model.c.layer_types == (WINDOW, WINDOW, WINDOW, FULL, WINDOW)
    ids = ids_of((2, 40))          # five windows long
    assert rel_err(sys_logits(model, v["params"], ids),
                   ref_logits(model, v["params"], ids)) < F32_TOL


def test_head_dim_is_its_own_number():
    """12 is not hidden / heads = 8: the cache's rows and the projections
    follow ``head_dim``."""
    model, v = make()
    attn = v["params"]["layers"]["attn"]
    assert attn["q"].shape == (5, 4 * 12, 32) and \
        attn["k"].shape == (5, 2 * 12, 32) and \
        attn["v"].shape == (5, 32, 2 * 12)
    spec = model.kv_cache_spec()
    assert [(g.num_layers, g.window, g.row_shapes())
            for g in spec.groups] == [
        (1, None, ((2, 12), (2, 12))), (4, WIN, ((2, 12), (2, 12)))]


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
            # the first row of a key: a decode bucket's pad rows repeat
            # slot 0's length and token over scratch window pages
            for lg, n, t in zip(np.asarray(logits), np.asarray(lengths),
                                np.asarray(tokens)):
                self.rows.setdefault((int(n), int(t)), lg.astype(np.float32))

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


def serve(model, v, prompts, decoded, **kw):
    kw = {**dict(num_slots=4, max_len=160, page_size=4, prefill_chunk=8,
                 min_bucket=4), **kw}
    engine = PagedServeEngine(model, v, **kw)
    sched = ContinuousBatchingScheduler(engine)
    reqs = [Request(prompt=list(map(int, p)), max_tokens=decoded)
            for p in prompts]
    sched.run(reqs)
    assert all(r.status == "ok" and len(r.tokens) == decoded for r in reqs)
    return engine, reqs


def engine_err(model, v, monkeypatch, *, decoded=20, ref_params=None,
               lens=(5, 37, 70, 121)) -> float:
    """Largest error, over the reference's logit range, of the logits the
    engine computed at every decoded position of four requests in flight
    together: prompts of 5 to 121 tokens over a window of 8, pages of 4 and
    chunks of 8, so the longest wraps its ring of 5 pages a dozen times in
    prefill and again while decoding."""
    rec = Recorded(model, monkeypatch)
    prompts = [ids_of(n, seed=n) for n in lens]
    engine, reqs = serve(model, v, prompts, decoded)
    # every page that fell wholly behind a window was dropped on the way
    assert engine.metrics.count("kv_window_released") >= sum(
        (n + decoded - 2 * WIN) // 4 for n in lens if n > 2 * WIN)
    worst = 0.0
    for r in reqs:
        seq = np.asarray(list(r.prompt) + list(r.tokens), np.int32)
        want = ref_logits(model, ref_params or v["params"], seq[None])[0]
        n = len(r.prompt)
        for j in range(decoded):     # row n-1+j predicts tokens[j]
            got = rec.rows[(n - 1 + j, int(seq[n - 1 + j]))]
            worst = max(worst, rel_err(got, want[n - 1 + j]))
    return worst


def test_chunked_prefill_and_decode_match_the_reference(monkeypatch):
    model, v = make()
    assert engine_err(model, v, monkeypatch) < F32_TOL


def test_chunked_prefill_and_decode_in_bfloat16(monkeypatch):
    """bfloat16 weights and compute against the float32 reference over the
    same (bfloat16) weights: the arithmetic's error and nothing of the
    cache's, which float32 above holds to 2e-4.  At these widths a choice
    flipped by rounding moves a logit by several percent of the range, so
    the limit is wide; the chip's readings at the published widths set the
    cell's (PERF.md)."""
    model, v = make(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                    router_bias_std=0.0)
    assert v["params"]["layers"]["attn"]["q"].dtype == jnp.bfloat16
    assert v["params"]["layers"]["moe"]["router"].dtype == jnp.float32
    err = engine_err(model, v, monkeypatch, decoded=8, lens=(37, 70))
    assert 1e-4 < err < 0.15


def test_chunk_programs_with_the_flash_kernel_give_the_walks_logits(
        monkeypatch):
    """A prompt of five chunks over views of 64 rows, past a ``KEY_BLOCK``
    scaled down to 16 with them: the chunk programs whose full layer takes
    the flash forward kernel (interpreted) give the tokens of the programs
    that walk the view's key blocks in XLA, and their logits within the
    float32 tolerance; the window layers read their rings either way."""
    import sys

    from paged_programs import chunk_kernel_beside_the_walk

    monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"], "KEY_BLOCK",
                        16)
    model, v = make()
    worst, (walk, kernel), walk_plans, plans = chunk_kernel_beside_the_walk(
        monkeypatch, model, v, ids_of(37, seed=5).tolist(), 3, num_slots=2,
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
    engine_logits(model, v, ids_of(37, seed=5).tolist()[:11], 2, num_slots=2,
                  max_len=64, page_size=4, prefill_chunk=8, min_bucket=4)
    assert {(p["kernel"], p["why"]) for p in plans} == {
        (0, "short"), (0, "window")}


def test_a_window_read_one_position_off_fails_the_comparison(monkeypatch):
    model, v = make()
    ring = ops.ring_update

    def off_by_one(k_ring, v_ring, k_new, v_new, starts):
        return ring(k_ring, v_ring, k_new, v_new, starts + 1)

    monkeypatch.setattr(ops, "ring_update", off_by_one)
    assert engine_err(model, v, monkeypatch, decoded=4,
                      lens=(37,)) > 100 * F32_TOL


def test_a_window_one_key_wider_fails_the_comparison(monkeypatch):
    model, v = make()
    chunk = ops.chunk_attention
    monkeypatch.setattr(
        ops, "chunk_attention",
        lambda *a, window=None, **kw: chunk(
            *a, window=None if window is None else window + 1, **kw))
    assert engine_err(model, v, monkeypatch, decoded=4,
                      lens=(37,)) > 100 * F32_TOL


# --------------------------------------------------- (c) the two programs

@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_no_program_holds_a_pool_or_converts_a_bfloat16_leaf(program):
    """Neither group's pool is copied and no view of every layer is made;
    bfloat16 leaves are read as they are held."""
    model, v = make(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    engine = PagedServeEngine(model, v, num_slots=4, max_len=160,
                              page_size=4, prefill_chunk=8, min_bucket=4)
    k_pool, v_pool = engine._pool_args()
    n_pg = engine.cache.pages_per_slot
    if program == "decode":
        fn = engine._build_decode()
        aux = (4, n_pg + 4 + sum(r + 1 for r in engine._ring_decode))
    else:
        fn = engine._build_chunk(n_pg)
        aux = (3 * 8 + n_pg + 2 + sum(8 + r for r in engine._ring_chunk),)
    closed = jax.make_jaxpr(fn)(engine.params, k_pool, v_pool,
                                jax.ShapeDtypeStruct(aux, np.int32))
    from paged_programs import CARRIERS, _leaf_converts, _results
    pools = {tuple(p.shape) for p in (*k_pool, *v_pool)}
    floor = min(int(np.prod(s)) for s in pools)
    # the full group has one cache layer here: that layer's gathered view
    # of every page of the bucket is as large as its pool, and is allowed
    # (ROADMAP S2); nothing else of a pool's size is, and no view of the
    # window group's four layers together
    b = 4 if program == "decode" else 1
    one_view = b * n_pg * 4 * 24
    seen = list(_results(closed.jaxpr))
    for shape in pools:
        assert any(p == "scatter" and s == shape for p, s in seen)
    assert [(p, s) for p, s in seen if int(np.prod(s)) >= floor
            and not (s in pools and p in CARRIERS)
            and int(np.prod(s)) != one_view] == []
    ring = (engine._ring_decode if program == "decode"
            else engine._ring_chunk)[0]
    assert not [s for _, s in seen
                if int(np.prod(s)) == 4 * b * ring * 4 * 24]
    n = len(jax.tree_util.tree_leaves(engine.params))
    assert list(_leaf_converts(closed.jaxpr,
                               set(closed.jaxpr.invars[:n]))) == []
    assert oversized and param_converts     # the one-group helpers' kin


def test_a_grouped_cache_refuses_to_export_its_slots():
    model, v = make()
    engine, _ = serve(model, v, [ids_of(9)], 2)
    slot = engine.alloc_slot()
    engine.prefill(slot, ids_of(21, seed=5))
    with pytest.raises(GroupedCacheNotPortable, match="window group"):
        engine.export_slots([slot])
    with pytest.raises(GroupedCacheNotPortable):
        engine.cache.import_slots([])


def test_a_drain_of_a_grouped_cache_moves_its_requests_by_re_prefill():
    """``export_inflight_with_slots`` over an engine that refuses to export
    its slots hands every request over folded (``slot=None``, no snapshot),
    and a peer that adopts them ends with the tokens an undisturbed run
    gives."""
    model, v = make()
    prompts = [ids_of(n, seed=n) for n in (13, 30)] + [ids_of(9, seed=3)]
    _, want = serve(model, v, prompts, 12)

    kw = dict(num_slots=2, max_len=160, page_size=4, prefill_chunk=8,
              min_bucket=4)
    src = ContinuousBatchingScheduler(PagedServeEngine(model, v, **kw))
    dst = ContinuousBatchingScheduler(PagedServeEngine(model, v, **kw))
    reqs = [Request(prompt=list(map(int, p)), max_tokens=12)
            for p in prompts]
    for r in reqs:
        src.submit(r)
    while min(len(r.tokens) for r in reqs[:2]) < 5:   # two decode, one waits
        src.step()
    pairs, snaps = src.export_inflight_with_slots()
    assert snaps == [] and [slot for _, slot in pairs] == [None] * 3
    assert [r for r, _ in pairs] == reqs
    assert src.engine.cache.num_free == 2 and not src.engine.active.any()
    assert src.metrics.count("exports_folded") == 1
    assert all(len(r.prompt) == len(p) + len(r.tokens)
               for r, p in zip(reqs[:2], prompts)) and reqs[2].tokens == []
    dst.adopt_inflight(pairs)
    while not all(r.done.is_set() for r in reqs):
        dst.step()
    assert [(r.status, r.tokens) for r in reqs] == \
        [("ok", w.tokens) for w in want]


# ------------------------------------------------ (d) the shares add up

def expert_layer_params(c, seed=0):
    _, v = make(seed, held=(0, c.n_routed_experts))
    return jax.tree_util.tree_map(lambda a: a[0],
                                  v["params"]["layers"]["moe"])


def share_of(c, p, first, count):
    layer = HeldExpertLayer(
        n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
        scaling=c.routed_scaling_factor, held=(first, count),
        block_rows=c.expert_block_rows, dtype=jnp.float32,
        scoring="sigmoid", renormalise=True, shared=True)
    mine = {k: (a[first:first + count] if k in ("gate", "up", "down") else a)
            for k, a in p.items()}
    return layer, mine


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_of_the_expert_layer_add_up(shares):
    """The routed parts of all the shares plus the shared expert (which
    every chip computes alike) counted once sum to the uncut 16-expert
    reference layer: each share keeps all four chosen scores in its
    renormalising sum."""
    c = tiny(held=None)
    p = expert_layer_params(c)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 19, 32)),
                    jnp.float32)
    dims = dims_of(c)
    whole = ref.expert_layer(p, u, dims)
    shared = ref.shared_expert(p, u)
    count = c.n_routed_experts // shares
    total, pairs = -(shares - 1) * shared, np.zeros(4, np.int64)
    for i in range(shares):
        layer, mine = share_of(c, p, i * count, count)
        out, stats = layer.apply(mine, u)
        total, pairs = total + out, pairs + np.asarray(stats)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    held, zero, absent, _ = pairs
    assert held == u.shape[0] * u.shape[1] * c.moe_topk and zero == 0
    assert absent == (shares - 1) * held
    assert MOE_STATS == ("moe_held", "moe_zero", "moe_absent", "moe_hit")


@pytest.mark.parametrize("scoring,renormalise,shared", [
    ("softmax", False, False), ("softmax", True, False),
    ("sigmoid", False, False), ("sigmoid", True, False),
    ("sigmoid", True, True), ("softmax", False, True)])
def test_the_expert_layers_rules_by_hand(scoring, renormalise, shared):
    """Each scoring rule, with and without renormalisation and the shared
    expert, against the sum written out in numpy: scores over all experts,
    the k largest of score + bias chosen, weights the bare scores (divided
    by their own sum when renormalised) times the scaling, held experts'
    SwiGLU added, the shared expert added once for every token."""
    c = tiny(held=None)
    held_by = expert_layer_params(c)
    p = jax.tree_util.tree_map(np.asarray, held_by)
    u = np.random.default_rng(4).normal(size=(13, 32)).astype(np.float32)
    first, count = 4, 8
    layer = HeldExpertLayer(
        n_routed=16, n_zero=0, k=4, scaling=2.5, held=(first, count),
        block_rows=4, dtype=jnp.float32, scoring=scoring,
        renormalise=renormalise, shared=shared)
    mine = {k: (a[first:first + count] if k in ("gate", "up", "down") else a)
            for k, a in held_by.items()}
    out, stats = layer.apply(mine, jnp.asarray(u))

    def swiglu(x, gate, up, down):
        g = x @ gate
        return (g / (1 + np.exp(-g)) * (x @ up)) @ down

    logits = u.astype(np.float64) @ p["router"]
    scores = 1 / (1 + np.exp(-logits)) if scoring == "sigmoid" \
        else np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = np.zeros((13, 32))
    held = 0
    for t in range(13):
        chosen = np.argsort(-(scores[t] + p["router_bias"]))[:4]
        w = scores[t, chosen]
        if renormalise:
            w = w / (w.sum() + 1e-20)
        for e, w_e in zip(chosen, 2.5 * w):
            if first <= e < first + count:
                held += 1
                want[t] += w_e * swiglu(u[t], p["gate"][e], p["up"][e],
                                        p["down"][e])
        if shared:
            want[t] += swiglu(u[t], p["shared_gate"], p["shared_up"],
                              p["shared_down"])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    assert int(stats[0]) == held and int(stats[1]) == 0
    assert int(stats[0]) + int(stats[2]) == 13 * 4
    with pytest.raises(ValueError, match="scoring"):
        HeldExpertLayer(n_routed=16, n_zero=0, k=4, scaling=1.0,
                        held=(0, 16), scoring="tanh")


# ------------------------------- (e) the points the file lists as assumed

def other_reading(monkeypatch, name, replacement, **kw):
    """The error of the program against a reference whose ``name`` reads the
    open point the other way."""
    model, v = make(**kw)
    ids = ids_of((2, 40))
    got = sys_logits(model, v["params"], ids)
    agreed = rel_err(got, ref_logits(model, v["params"], ids))
    monkeypatch.setattr(ref, name, replacement)
    return agreed, rel_err(got, ref_logits(model, v["params"], ids))


def test_assumed_pre_norm_is_what_both_sides_compute(monkeypatch):
    """A reference that norms AFTER each sub-layer (EXAONE 4.0's placement)
    is another model."""
    def post_norm_layer(layers, l, h, dims):
        eps, dense = dims["eps"], dims["first_dense"]
        window = dims["window"] if dims["layer_types"][l] == WINDOW else None
        h = h + ref.rms_norm(
            ref.attention(ref.at(layers["attn"], l), h, dims, window),
            layers["attn_norm"][l], eps)
        m = ref.dense_ffn(ref.at(layers["ffn"], l), h) if l < dense \
            else ref.expert_layer(ref.at(layers["moe"], l - dense), h, dims)
        return h + ref.rms_norm(m, layers["ffn_norm"][l], eps)

    agreed, other = other_reading(monkeypatch, "layer", post_norm_layer)
    assert agreed < F32_TOL and other > 100 * F32_TOL


def test_assumed_rope_on_window_layers_only(monkeypatch):
    """A reference that rotates the full layer's q and k too is another
    model; and the program's full layer has no position in it at all: its
    attention over a permuted history gives the same last row."""
    qkv = ref.qkv
    agreed, other = other_reading(
        monkeypatch, "qkv",
        lambda p, x, dims, rotate, first=0: qkv(p, x, dims, True, first))
    assert agreed < F32_TOL and other > 100 * F32_TOL


def test_assumed_half_rotation_layout(monkeypatch):
    """A reference that rotates neighbouring pairs (x[2i], x[2i+1]) is
    another model."""
    def interleaved(x, theta, first=0):
        d = x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = (first + jnp.arange(x.shape[1])).astype(jnp.float32)[:, None] \
            * inv
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)

    agreed, other = other_reading(monkeypatch, "rope_halves", interleaved)
    assert agreed < F32_TOL and other > 100 * F32_TOL


def test_assumed_correction_bias_steers_the_choice_and_never_the_weight():
    """With a large bias the chosen experts change and the weights are still
    the bare scores renormalised: program and reference agree, and both
    differ from the same layer without the bias."""
    c = tiny(held=None)
    p = expert_layer_params(c)
    p = dict(p, router_bias=jnp.asarray(
        np.random.default_rng(2).normal(size=p["router_bias"].shape) * 0.5,
        jnp.float32))
    u = jnp.asarray(np.random.default_rng(1).normal(size=(1, 23, 32)),
                    jnp.float32)
    layer, mine = share_of(c, p, 0, c.n_routed_experts)
    out, _ = layer.apply(mine, u)
    np.testing.assert_allclose(out, ref.expert_layer(p, u, dims_of(c)),
                               rtol=1e-4, atol=1e-5)
    w, idx = layer.route(p, u.reshape(-1, 32))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    _, idx0 = layer.route(dict(p, router_bias=jnp.zeros(16)),
                          u.reshape(-1, 32))
    assert (np.sort(idx, -1) != np.sort(idx0, -1)).any()
    scores = jax.nn.sigmoid(u.reshape(-1, 32) @ p["router"])
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


# ------------- the cut path: evaluated by the fused call, trained by the loop

def test_rounds_and_chunks_emit_the_tokens_the_loop_emitted(monkeypatch):
    """Requests in flight together through the engine, chunks and decode
    rounds: the fused call cut along F (four F tiles a visit here) emits
    the tokens the loop's forward emits, and counts every held pair as a
    grouped call's (``moe_grouped`` beside ``moe_held``)."""
    from hetu_tpu.ops import moe_ops
    from paged_programs import loop_evaluates

    model, v = make()
    prompts = [ids_of(n, seed=n) for n in (5, 37, 70)]
    engine, reqs = serve(model, v, prompts, 12)
    assert model.step_stats == MOE_STATS + ("moe_grouped",)
    assert engine.metrics.count("moe_grouped") \
        == engine.metrics.count("moe_held") > 0
    c = model.c
    routed = c.n_routed_experts + getattr(c, "zero_expert_num", 0)
    # a round's pairs fit one trip; a chunk's take the trips form
    for t, whole in ((4, True), (8, False)):
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
