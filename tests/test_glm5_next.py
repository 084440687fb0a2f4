"""GLM-5.3-Flash against its plain reference
(``benchmarks/reference/glm5_next.py``) at small widths on the CPU, seeded
weights at the configuration's own rule of stds, every comparison one of
LOGITS: (a) the dense forward PAST ``index_topk`` (every late token chooses 8
of up to 24 groups), float32 and bfloat16, and a control for each thing the
comparison must see: a wrong choice, an unclamped SwiGLU, a stream mix left
out; (b) chunked prefill (several chunks, a padded last one, groups that
straddle pages, chunks and rounds) then rounds through ``PagedServeEngine``
over the latent pages, the pooled keys beside them and the three-part state;
(c) the SHARE: eight shares of the experts, the shared expert counted once,
add up to the uncut reference layer; (d) the clamp on the loop path and the
grouped paths alike; (e) a decode with the rule's state in float32 and,
failing, in bfloat16; (f) the cache's books."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import glm5_next as ref  # noqa: E402
from hetu_tpu import ops  # noqa: E402
from hetu_tpu.layers.moe import HeldExpertLayer  # noqa: E402
from hetu_tpu.models.glm5_next import (  # noqa: E402
    DSA, INDEX_STATS, KDA, GLM5NextConfig, GLM5NextModel,
)
from hetu_tpu.ops import moe_ops  # noqa: E402
from hetu_tpu.ops.pallas_kernels import grouped_matmul  # noqa: E402
from hetu_tpu.serve import PagedServeEngine  # noqa: E402
from hetu_tpu.serve.kv_cache import GroupedCacheNotPortable  # noqa: E402
from paged_programs import LogitsOut  # noqa: E402

F32_TOL = 2e-4      # both sides float32: the order of operations only
VOCAB = 97


def tiny(**kw) -> GLM5NextConfig:
    """Small widths in the published pattern: one leading dense layer, then
    a DSA layer among KDA layers; ``index_topk`` 32 = 8 groups of 4, pages
    of 8 = two groups, the rule's chunk 8 in sub-blocks of 4."""
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=4,
        layer_types=(KDA, DSA, KDA, KDA),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
        num_heads=4, head_dim=8, v_head_dim=8, q_lora_rank=16,
        kv_lora_rank=16, index_n_heads=2, index_head_dim=8, index_topk=32,
        index_kpool=4, index_rope_dim=4, index_query_block=8, kda_heads=4,
        kda_head_dim=8, kda_gate_rank=4, kda_chunk=8, kda_sub=4,
        ffn_size=64, expert_ffn_size=16, n_routed_experts=8, moe_topk=2,
        held=(0, 8), max_position=512, dtype=jnp.float32,
        param_dtype=jnp.float32)
    base.update(kw)
    return GLM5NextConfig(**base)


def dims_of(c: GLM5NextConfig) -> dict:
    return dict(
        n=c.hc_mult, hc_iters=c.hc_sinkhorn_iters, hc_eps=c.hc_eps,
        eps=c.rms_eps, first_dense=c.first_dense, kda_heads=c.kda_heads,
        kda_dim=c.kda_head_dim, taps=c.conv_taps, lower=c.gate_lower_bound,
        heads=c.num_heads, qk=c.head_dim, v_dim=c.v_head_dim,
        index_heads=c.index_n_heads, index_dim=c.index_head_dim,
        index_rope_dim=c.index_rope_dim, theta=c.rope_theta,
        pool=c.index_kpool, limit=c.swiglu_limit, topk=c.moe_topk,
        scaling=c.routed_scaling_factor, held=c.held,
        layer_types=c.layer_types, topk_groups=c.index_groups)


def make(seed=1, **kw):
    model = GLM5NextModel(tiny(**kw))
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def glm():
    return make()


REF_LEN = 128       # the reference runs at one length: one compile


@functools.lru_cache(maxsize=None)
def _reference(dims: tuple):
    return jax.jit(lambda p, x: ref.logits(p, x, dict(dims)))


def ref_logits(model, params, ids):
    """The reference over ``ids`` padded to ``REF_LEN`` (it is causal: the
    rows asked for see no padding)."""
    ids = np.asarray(ids)
    padded = np.zeros((ids.shape[0], REF_LEN), ids.dtype)
    padded[:, :ids.shape[1]] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(tuple(sorted(dims_of(model.c).items())))(
            params, padded))[:, :ids.shape[1]]


def rel_err(got, want) -> float:
    return float(np.max(np.abs(got - want)) / (want.max() - want.min()))


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


def engine_of(model, variables, **kw):
    """An engine whose two programs hand their logits on as their counts,
    and the list they land in, one entry a call: [B, V]."""
    kw = {"num_slots": 3, "max_len": 192, "page_size": 8,
          "prefill_chunk": 16, "min_bucket": 4, **kw}
    engine = PagedServeEngine(LogitsOut(model), variables, **kw)
    calls = []
    engine._count = lambda stats: calls.append(np.asarray(stats[0]))
    return engine, calls


@pytest.fixture(scope="module")
def served(glm):
    """ONE engine for the module's float32 comparisons (its programs compile
    once); a test releases the slots it took."""
    with jax.default_matmul_precision("highest"):
        return engine_of(*glm)


def served_logits(engine, calls, prompt, n: int):
    """``n`` rows of logits: the prompt's last and ``n - 1`` rounds'; the
    slot is released."""
    slot = engine.alloc_slot()
    toks = [engine.prefill(slot, prompt)]
    rows = [calls[-1][0]]
    for _ in range(n - 1):
        toks.append(engine.decode()[slot])
        rows.append(calls[-1][0])
    engine.release(slot)
    return np.stack(rows), toks


# ---- (a) the dense forward, and what the comparison must see ----

@pytest.mark.parametrize("dtype,tol,typical", [
    (jnp.float32, F32_TOL, F32_TOL), (jnp.bfloat16, 0.5, 0.03)])
def test_dense_forward_equals_the_reference(dtype, tol, typical):
    """96 tokens over ``index_topk`` 32: every token from position 35 on
    chooses 8 of up to 24 groups.  bfloat16 against the float32 reference
    over the same (bfloat16) weights: at a hidden size of 32 a group
    exchanged at a near tie moves a whole row (the worst reads 0.33 of the
    range), so the worst row's limit is wide and the MEDIAN row is held
    tight; the chip's readings at the published widths set the cell's
    (PERF.md)."""
    model, variables = make(dtype=dtype, param_dtype=dtype)
    ids = np.random.default_rng(3).integers(0, VOCAB, (1, 96))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda v, x: model.apply(v, x)[0])(
            variables, jnp.asarray(ids)).astype(jnp.float32))
    want = ref_logits(model, variables["params"], ids)
    assert rel_err(got, want) < tol
    by_row = np.max(np.abs(got - want), -1) / (want.max() - want.min())
    assert float(np.median(by_row)) < typical


@pytest.mark.parametrize("wrong", ["first-groups", "no-clamp", "no-mix",
                                   "no-tail"])
def test_the_comparison_sees(glm, monkeypatch, wrong):
    """Each thing the reference states, left out of the program, fails the
    float32 tolerance by a wide margin: the BEST groups (not the first
    ones), the open group's rows, the clamp, the stream mix."""
    model, variables = glm
    ids = np.random.default_rng(4).integers(0, VOCAB, (1, 80))
    scale = 1.0
    if wrong == "first-groups":
        select = ops.select_groups

        def first(qi, w, kbar, pos, **how):
            idx, n = select(qi, w, kbar, pos, **how)
            return jnp.broadcast_to(jnp.arange(idx.shape[-1]), idx.shape), n

        monkeypatch.setattr(ops, "select_groups", first)
    elif wrong == "no-tail":
        rows = ops.chosen_rows

        def headless(idx, n, pos, **how):
            r, valid = rows(idx, n, pos, **how)
            # the open group's rows stand behind the chosen groups'
            tail = how["pool"] * idx.shape[-1]
            return r, valid.at[..., tail:tail + how["pool"]].set(False) \
                | (r == pos[..., None])
        monkeypatch.setattr(ops, "chosen_rows", headless)
    elif wrong == "no-clamp":
        # weights scaled so that the clamp bites, in both
        scale = 40.0
        monkeypatch.setattr(model, "swiglu_limit", None)
        monkeypatch.setattr(model.moe, "swiglu_limit", None)
    else:
        from hetu_tpu.ops import hyper
        monkeypatch.setattr(
            hyper, "stream_write",
            lambda x, res, post, y: (x.astype(jnp.float32) + post[..., None]
                                     * y[..., None, :]).astype(x.dtype))
    params = jax.tree_util.tree_map(lambda a: a, variables["params"])
    if scale != 1.0:
        layers = dict(params["layers"])
        layers["ffn"] = {k: tuple(a * scale for a in v) if k != "down" else v
                         for k, v in layers["ffn"].items()}
        params = dict(params, layers=layers)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, x: model.apply(
            {"params": p, "state": {}}, x)[0])(params, jnp.asarray(ids)))
    assert rel_err(got, ref_logits(model, params, ids)) > 50 * F32_TOL


# ---- (b) chunks, then rounds, through the engine ----

@pytest.mark.parametrize("n_prompt", [
    70,     # five chunks, the last of 6 rows in a bucket of 8; 17 groups
    45,     # a prompt that ends one row into a group
    20,     # under index_topk: everything is read, rounds cross into choice
])
def test_engine_logits_equal_the_reference(glm, served, n_prompt):
    """Prefill in chunks of 16 over pages of 8, then 20 rounds: the logits
    the engine's own programs computed against the reference's full forward
    over prompt + answer."""
    model, variables = glm
    with jax.default_matmul_precision("highest"):
        prompt = prompt_of(n_prompt, seed=n_prompt)
        got, toks = served_logits(*served, prompt, 20)
    ids = np.asarray([prompt + toks[:-1]])
    want = ref_logits(model, variables["params"], ids)[0, n_prompt - 1:]
    assert rel_err(got, want) < F32_TOL


def test_two_requests_in_one_round_and_a_reused_slot(glm, served):
    """Two sequences of different lengths decode together (one reads a
    choice, one everything), a slot is freed and taken again: the second
    owner reads nothing of the first's state, pages or pooled keys."""
    model, variables = glm
    engine, calls = served
    with jax.default_matmul_precision("highest"):
        a, b = prompt_of(60, 1), prompt_of(13, 2)
        sa, sb = engine.alloc_slot(), engine.alloc_slot()
        ta, tb = [engine.prefill(sa, a)], [engine.prefill(sb, b)]
        rows_a, rows_b = [], []
        for _ in range(6):
            out = engine.decode()
            order = sorted((sa, sb))
            rows_a.append(calls[-1][order.index(sa)])
            rows_b.append(calls[-1][order.index(sb)])
            ta.append(out[sa]); tb.append(out[sb])
        engine.release(sa)
        c = prompt_of(50, 3)
        got_c, tc = served_logits(engine, calls, c, 4)
        engine.release(sb)
    for prompt, toks, rows in ((a, ta, rows_a), (b, tb, rows_b)):
        ids = np.asarray([prompt + toks[:-1]])
        want = ref_logits(model, variables["params"], ids)[0, len(prompt):]
        assert rel_err(np.stack(rows), want) < F32_TOL
    ids = np.asarray([c + tc[:-1]])
    want = ref_logits(model, variables["params"], ids)[0, len(c) - 1:]
    assert rel_err(got_c, want) < F32_TOL


def test_a_chunk_fetches_its_chosen_groups_in_the_kernel_where_the_rule_says(
        glm, monkeypatch):
    """A chunk program's ``index.plan`` says ``kernel`` when the rule's
    conditions hold (``ops.index_kernel_why``: latents of whole lane tiles,
    a TPU backend, no mesh; forced here, the kernel in interpret mode) and
    ``index_kernel_queries`` counts the chunks' real queries, a padded last
    chunk's rows left out; a round still gathers through the tables; and
    the logits are the reference's within the float32 tolerance."""
    from hetu_tpu.serve import ContinuousBatchingScheduler, Request

    model, variables = glm
    att = sys.modules["hetu_tpu.ops.attention"]
    monkeypatch.setattr(att, "INDEX_LANES", 16)
    monkeypatch.setattr(att, "_default_backend_is_tpu", lambda: True)
    plans = []
    monkeypatch.setattr(att.trace, "instant",
                        lambda name, attrs=None, cat="hetu":
                        plans.append((name, attrs)))
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=128,
                              page_size=8, prefill_chunk=16, min_bucket=4)
    sched = ContinuousBatchingScheduler(engine)
    requests = [Request(prompt=prompt_of(n, n), max_tokens=3)
                for n in (53, 21)]
    sched.run(requests)
    assert all(r.status == "ok" for r in requests)
    forms = {(a["form"], a["why"], a["query_block"] > 1)
             for name, a in plans if name == "index.plan"}
    assert forms == {("kernel", "", True), ("gathered", "round", False)}
    snap = engine.metrics.snapshot()
    assert snap["index_kernel_queries"] == 53 + 21
    # ... of the 53 + 21 prompt positions and two rounds' each
    assert snap["sparse_queries"] + snap["dense_queries"] == 53 + 21 + 4
    with jax.default_matmul_precision("highest"):
        prompt = prompt_of(57, 9)
        got, toks = served_logits(*engine_of(model, variables), prompt, 3)
    ids = np.asarray([prompt + toks[:-1]])
    want = ref_logits(model, variables["params"], ids)[0, len(prompt) - 1:]
    assert rel_err(got, want) < F32_TOL


def test_counters_and_the_cache_books(glm):
    model, variables = glm
    engine = PagedServeEngine(model, variables, num_slots=2, max_len=128,
                              page_size=8, prefill_chunk=16, min_bucket=4)
    slot = engine.alloc_slot()
    engine.prefill(slot, prompt_of(50, 5))
    for _ in range(3):
        engine.decode()
    snap = engine.metrics.snapshot()
    # positions 0..34 have at most 8 complete groups: dense; 35..52 choose
    assert snap["dense_queries"] == 35 and snap["sparse_queries"] == 18
    assert snap["groups_chosen"] == sum(
        min(8, (t + 1) // 4) for t in range(53))
    assert snap["groups_visible"] == sum((t + 1) // 4 for t in range(53))
    spec = engine.cache.spec
    assert spec.v_dim == 0 and engine.cache.v.shape[-1] == 0
    assert spec.comp_width == model.c.index_head_dim
    assert spec.part_layers == (3, 3, 1)
    assert spec.bytes_per_token == 4 * (16 + 8 // 4)
    with pytest.raises(GroupedCacheNotPortable):
        engine.cache.export_slots([slot])
    # a round takes the WHOLE table whatever the histories: one page bucket,
    # so one decode program a slot bucket
    assert spec.whole_tables
    assert {n for _, n in engine._seen_page_buckets} \
        == {engine.cache.pages_per_slot}
    assert engine.compiled_executables() <= engine.max_executables
    # the pooled keys the cache holds are the sequence's complete groups'
    g = engine.cache.groups[0]
    table = g.tables[slot]
    held = np.concatenate([np.asarray(g.comp[0, p]) for p in table])[:13]
    assert np.all(np.abs(held).sum(-1) > 0)


# ---- (c) the share ----

def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of 2 experts each, the router 16 wide on every one: the
    shares' routed parts, with the shared expert (which every chip computes
    alike) counted once, add up to the reference's layer over all 16."""
    layer = lambda held: HeldExpertLayer(
        n_routed=16, n_zero=0, k=4, scaling=2.5, held=held,
        dtype=jnp.float32, scoring="sigmoid", renormalise=True, shared=True,
        swiglu_limit=1.5)
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 9)
    H, F = 32, 16
    p = {"router": jax.random.normal(ks[0], (H, 16)) / H ** 0.5,
         "router_bias": 0.1 * jax.random.normal(ks[1], (16,)),
         "gate": jax.random.normal(ks[2], (16, H, F)),
         "up": jax.random.normal(ks[3], (16, H, F)),
         "down": jax.random.normal(ks[4], (16, F, H)) / F ** 0.5,
         "shared_gate": jax.random.normal(ks[5], (H, F)),
         "shared_up": jax.random.normal(ks[6], (H, F)),
         "shared_down": jax.random.normal(ks[7], (F, H)) / F ** 0.5}
    u = jax.random.normal(ks[8], (24, H))
    dims = {"topk": 4, "scaling": 2.5, "limit": 1.5, "held": (0, 16)}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(p, u, dims)
        shared = ref.shared_expert(p, u, dims)
        total = shared
        for chip in range(8):
            own = dict(p, **{k: p[k][2 * chip:2 * chip + 2]
                             for k in ("gate", "up", "down")})
            out, stats = layer((2 * chip, 2)).apply(own, u)
            total = total + (out - shared)
            # and the reference's own share is the program's
            np.testing.assert_allclose(
                out, ref.expert_layer(own, u, dims, (2 * chip, 2)),
                atol=2e-4)
    np.testing.assert_allclose(total, want, atol=5e-4)


# ---- (d) the clamp on every path ----

@pytest.fixture
def small_kernels(monkeypatch):
    """The grouped kernels' constants cut to a test's widths, so that H x F
    = 32 x 256 experts take the fused call cut along F
    (``tests/test_exaone_moe.py``'s fixture)."""
    monkeypatch.setattr(grouped_matmul, "_LANES", 128)
    monkeypatch.setattr(grouped_matmul, "CUT_TILE_ROWS", 8)
    monkeypatch.setattr(grouped_matmul, "_FFN_WEIGHT_BYTES",
                        3 * 32 * 128 * 4 * 2)
    monkeypatch.setattr(grouped_matmul, "_CUT_WEIGHT_BYTES",
                        3 * 32 * 128 * 4 * 2)
    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", 32 * 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


def clamped_want(x, w, idx, wg, wu, wd, limit):
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        g = jnp.minimum(x @ wg[e], limit)
        u = jnp.clip(x @ wu[e], -limit, limit)
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        out = out + weight * ((jax.nn.silu(g) * u) @ wd[e])
    return out


@pytest.mark.parametrize("path", ["grouped", "cut", "loop"])
def test_the_clamp_is_the_models_on_every_path(path, small_kernels,
                                               monkeypatch):
    F = {"grouped": 128, "cut": 256, "loop": 256}[path]
    if path == "loop":
        monkeypatch.setattr(grouped_matmul, "ffn_tiles", lambda *a: None)
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 6)
    T, H, E, k = 24, 32, 4, 2
    x = jax.random.normal(ks[0], (T, H))
    wg = jax.random.normal(ks[1], (E, H, F))       # |gate| well over 1
    wu = jax.random.normal(ks[2], (E, H, F))
    wd = jax.random.normal(ks[3], (E, F, H)) / F ** 0.5
    idx = jax.random.randint(ks[4], (T, k), 0, E)
    idx = idx.at[:, 1].set((idx[:, 0] + 1) % E)
    w = jax.random.uniform(ks[5], (T, k))
    assert moe_ops.held_expert_path(T, k, E, H, F, 4) == path
    with jax.default_matmul_precision("highest"):
        got, _ = moe_ops.held_expert_ffn(x, w, idx, wg, wu, wd, first=0,
                                         block_rows=8, limit=1.0)
        plain, _ = moe_ops.held_expert_ffn(x, w, idx, wg, wu, wd, first=0,
                                           block_rows=8)
        want = clamped_want(x, w, idx, wg, wu, wd, 1.0)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert float(jnp.max(jnp.abs(plain - want))) > 1.0   # the clamp bites
    with pytest.raises(NotImplementedError, match="evaluated"):
        jax.grad(lambda a: moe_ops.held_expert_ffn(
            a, w, idx, wg, wu, wd, first=0, block_rows=8,
            limit=1.0)[0].sum())(x)


# ---- (e) the rule's state in float32, and in bfloat16 ----

def test_a_bfloat16_state_fails_where_float32_holds(glm, served):
    """24 rounds after a prompt: with the matrix in float32 the engine's
    logits stay on the reference (1.5e-6); held in bfloat16 every chunk and
    round rounds the matrix again and the logits leave the float32
    tolerance, seven times over and nine hundredfold the float32 reading."""
    errs = {}
    prompt = prompt_of(40, 9)
    for name in ("float32", "bfloat16"):
        with jax.default_matmul_precision("highest"):
            if name == "float32":
                (model, variables), pair = glm, served
            else:
                model, variables = make(state_dtype=jnp.bfloat16)
                pair = engine_of(model, variables)
            got, toks = served_logits(*pair, prompt, 24)
        ids = np.asarray([prompt + toks[:-1]])
        want = ref_logits(model, variables["params"], ids)[0, 39:]
        errs[name] = rel_err(got, want)
    assert errs["float32"] < F32_TOL
    assert errs["bfloat16"] > 5 * F32_TOL
    assert errs["bfloat16"] > 20 * errs["float32"]


def test_step_stats_name_the_index_counters(glm):
    model, _ = glm
    assert model.step_stats[-len(INDEX_STATS):] == INDEX_STATS
    assert model.call_stats == INDEX_STATS
