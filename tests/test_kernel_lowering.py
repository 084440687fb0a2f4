"""Every Pallas kernel lowers for TPU at chip_smoke.py's full shapes — from
the CPU host, no chip: ``jit(...).trace(...).lower(lowering_platforms=
("tpu",))`` runs the Pallas->Mosaic lowering, which is where a block shape
Mosaic cannot move (a ``(1, D)`` row block) is refused.  The slow lane goes
one step further and COMPILES them with the installed libtpu for a v5e
topology, which is where Mosaic itself refuses (a one-row DMA out of a tiled
table, a dynamic sublane index on a packed dtype).
"""

import functools
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chip_smoke import FULL  # noqa: E402

from hetu_tpu.ops.pallas_kernels import (  # noqa: E402
    embedding_gather, embedding_scatter_add, flash_attention, routed_gather,
    topk_gating,
)
from hetu_tpu.ops.pallas_kernels import grouped_matmul  # noqa: E402,F401
from hetu_tpu.ops.pallas_kernels.chosen_groups import (  # noqa: E402
    chosen_groups_attention,
)
from hetu_tpu.ops.pallas_kernels.flash_attention import (  # noqa: E402
    flash_chunk_attention, flash_sparse_chunk_attention, write_rows,
)
from hetu_tpu.ops.pallas_kernels.paged_attention import (  # noqa: E402
    paged_decode_attention,
)

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

# the flash calls of the two training cells of BENCHMARK.json: gpt2-small at
# 64 x 1024 on one chip, and gpt2-large's per-chip shard under dp=2 x tp=2
BENCH_FLASH_SHAPES = ((64, 12, 1024, 64), (8, 10, 1024, 64))
# the flash calls of mellum2-12b-a2.5b-instruct.train-ep4: one 16,384-token
# sequence, 32 query heads over 4 KV heads of 128, a window of 1,024 on three
# layers of four (streamed); and a resident call with grouped heads
MASKED_FLASH_CASES = (
    ("mellum window", (1, 32, 16384, 128), (1, 4, 16384, 128), 1024),
    ("mellum full", (1, 32, 16384, 128), (1, 4, 16384, 128), None),
    ("resident grouped", (2, 32, 512, 128), (2, 4, 512, 128), 200))
# the paged decode kernel at the top slot and page bucket of the two serving
# cells it runs in: (slots, query heads, KV heads, head width, cache layers,
# pages in the pool, page size, pages a slot)
# the held-expert walk of the two expert training cells, grouped path: (tokens
# a step, choices a token, the router's width, held experts, hidden, expert
# FFN)
BENCH_GROUPED_SHAPES = {
    "kanana-2-30b-a3b-instruct-2601.train-ep8": (16384, 6, 128, 16, 2048, 768),
    "mellum2-12b-a2.5b-instruct.train-ep4": (16384, 8, 64, 16, 2304, 896)}
# the walk of the served expert cell whose experts fit the grouped kernels,
# one fused call a walk: (tokens a call, choices, held = routed experts,
# expert layers in the stacked leaves, hidden, expert FFN)
BENCH_SERVED_GROUPED_SHAPES = {
    "lfm2-8b-a1b.batch-docs decode": (64, 4, 32, 12, 2048, 1792),
    "lfm2-8b-a1b.batch-docs chunk": (2048, 4, 32, 12, 2048, 1792)}
# the walk of the two served cells whose experts do not, one fused call a
# trip, cut along F (ISSUE 55): a round's pairs in one trip, a chunk's in the
# trips form: (tokens a call, choices, the router's width, held experts,
# expert layers in the stacked leaves, hidden, expert FFN)
BENCH_SERVED_CUT_SHAPES = {
    "k-exaone-236b-a23b.batch-mixed decode": (16, 8, 128, 16, 4, 6144, 2048),
    "k-exaone-236b-a23b.batch-mixed chunk": (512, 8, 128, 16, 4, 6144, 2048),
    "longcat-flash-omni.batch-long decode": (16, 12, 768, 16, 4, 6144, 2048),
    "longcat-flash-omni.batch-long chunk": (512, 12, 768, 16, 4, 6144, 2048)}
BENCH_PAGED_SHAPES = {
    "gpt2-large.batch": (8, 20, 20, 64, 36, 385, 16, 48),
    "k-exaone-236b-a23b.batch-mixed": (16, 64, 8, 128, 1, 4225, 128, 264)}
# the chunk call of the flash forward kernel in the serving cells whose
# views are longer than ``ops.attention.KEY_BLOCK``, at the smallest and the
# largest chunk bucket that takes it over the widest view: (query heads, KV heads, d_qk,
# d_v, view rows, chunk buckets)
BENCH_CHUNK_SHAPES = {
    "k-exaone-236b-a23b.batch-mixed": (64, 8, 128, 128, 34304, (16, 512)),
    "qwen3-next-80b-a3b-instruct.batch-mixed":
        (16, 2, 256, 256, 35968, (16, 2048)),
    "longcat-flash-omni.batch-long": (64, 64, 256, 128, 9216, (512,)),
    "lfm2-8b-a1b.batch-docs": (32, 8, 64, 64, 10240, (16, 2048))}
# the SPARSE chunk call of the flash forward kernel in the cell whose chunks
# attend under their queries' block choice (ISSUE 57): (query heads, KV
# heads, head width, view rows, block, chunk buckets)
BENCH_SPARSE_CHUNK_SHAPES = {
    "minicpm-sala.batch-context": (32, 2, 128, 66624, 64, (16, 2048))}
# the call that fetches the groups a chunk's queries chose and attends them
# (ISSUE 59), a block of queries at a time: (queries a block, heads, latent
# width, groups in the slot's view, rows a group, groups a query chooses)
BENCH_CHOSEN_GROUPS_SHAPES = {
    "glm-5.3-flash.batch-context": (128, 64, 512, 16656, 4, 512)}


@pytest.fixture(autouse=True)
def _compiled_kernels(monkeypatch):
    """Lower the kernels as a TPU backend would: never interpret mode.  The
    grouped expert walk is a module-level jitted function
    (``ops.moe_ops``), and JAX keeps its trace by shapes alone: the caches
    are emptied on the way in and out, so that no test meets a walk another
    traced in the other mode."""
    for mod in ("embedding", "flash_attention", "paged_attention",
                "grouped_matmul", "chosen_groups"):
        m = sys.modules[f"hetu_tpu.ops.pallas_kernels.{mod}"]
        name = "_auto_interpret" if mod == "embedding" else "auto_interpret"
        monkeypatch.setattr(m, name, lambda interpret: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _flash_vjp(q, k, v, g):
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       q, k, v)
    return (out, *vjp(g))


def _flash_vjp_masked(window, causal=True):
    def fn(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window), q, k, v)
        return (out, *vjp(g))
    return fn


def _held_experts_vjp(routed):
    from hetu_tpu.ops.moe_ops import held_expert_ffn

    def fn(x, w, idx, wg, wu, wd, ct):
        (out, _), vjp = jax.vjp(
            lambda x, w, wg, wu, wd: held_expert_ffn(
                x, w, idx, wg, wu, wd, first=0, routed=routed),
            x, w, wg, wu, wd)
        return (out, *vjp((ct, jnp.zeros((wg.shape[0],), i32))))
    return fn


def _held_experts_forward(x, w, idx, wg, wu, wd, layer, routed=None):
    from hetu_tpu.ops.moe_ops import held_expert_ffn

    return held_expert_ffn(x, w, idx, wg, wu, wd, first=0, layer=layer,
                           routed=routed or wg.shape[1])


def _cases():
    rows, width, n = FULL.emb
    tokens, experts, k = FULL.topk
    for qs, ks in FULL.flash_shapes:
        for dt in (bf16, f32):
            yield (f"flash fwd+bwd {dt.__name__} q{qs} kv{ks}", _flash_vjp,
                   [(qs, dt), (ks, dt), (ks, dt), (qs, dt)], 3)
    for shape in BENCH_FLASH_SHAPES:
        yield (f"flash fwd+bwd bf16 benchmark {shape}", _flash_vjp,
               [(shape, bf16)] * 4, 3)
    for name, qs, ks, window in MASKED_FLASH_CASES:
        yield (f"flash fwd+bwd bf16 {name} q{qs} kv{ks} w{window}",
               _flash_vjp_masked(window),
               [(qs, bf16), (ks, bf16), (ks, bf16), (qs, bf16)], 3)
    for dt in (f32, bf16):
        yield (f"embedding_gather {dt.__name__}", embedding_gather,
               [((rows, width), dt), ((n,), i32)], 1)
        yield (f"embedding_scatter_add {dt.__name__}",
               lambda g, i: embedding_scatter_add(g, i, rows),
               [((n, width), dt), ((n,), i32)], 1)
    yield ("embedding_gather narrow rows", embedding_gather,
           [((rows, 16), f32), ((n + 3,), i32)], 1)
    yield ("routed_gather value+grad",
           lambda t, i: jax.value_and_grad(lambda t: jnp.sum(
               routed_gather(t, i, kernel=True)))(t),
           [((rows, width), f32), ((n,), i32)], 2)
    yield (f"topk_gating {k} of {experts}",
           lambda x: topk_gating(x, k, kernel=True),
           [((tokens, experts), f32)], 1)
    for cell, (t, k, routed, e, h, f) in BENCH_GROUPED_SHAPES.items():
        # forward: gate, up, down; backward: gate and up again, the down
        # projection's backward in one call, three dW, one dx
        yield (f"held experts grouped fwd+bwd {cell}",
               _held_experts_vjp(routed),
               [((t, h), bf16), ((t, k), f32), ((t, k), i32),
                ((e, h, f), bf16), ((e, h, f), bf16), ((e, f, h), bf16),
                ((t, h), f32)], 10)
    for cell, (t, k, e, layers, h, f) in BENCH_SERVED_GROUPED_SHAPES.items():
        # a whole expert a visit: gate, up, SwiGLU and down in one call,
        # three whole weights double-buffered in VMEM
        yield (f"held experts grouped forward {cell}", _held_experts_forward,
               [((t, h), bf16), ((t, k), f32), ((t, k), i32),
                ((layers, e, h, f), bf16), ((layers, e, h, f), bf16),
                ((layers, e, f, h), bf16), ((), i32)], 1)
    for cell, (t, k, routed, e, layers, h, f) in \
            BENCH_SERVED_CUT_SHAPES.items():
        # an expert a visit in F tiles of 1,024 columns: three weight tiles
        # double-buffered and the float32 sum in VMEM
        yield (f"held experts cut along F forward {cell}",
               functools.partial(_held_experts_forward, routed=routed),
               [((t, h), bf16), ((t, k), f32), ((t, k), i32),
                ((layers, e, h, f), bf16), ((layers, e, h, f), bf16),
                ((layers, e, f, h), bf16), ((), i32)], 1)
    for cell, (b, nh, g, d, layers, pool, ps, n_pg) in \
            BENCH_PAGED_SHAPES.items():
        pool_of = ((layers, pool, ps, g * d), bf16)
        yield (f"paged_decode_attention {cell}",
               lambda q, k, v, layer, tables, lengths, g=g:
               paged_decode_attention(q, k, v, layer, tables, lengths,
                                      kv_heads=g),
               [((b, nh, 1, d), bf16), pool_of, pool_of, ((), i32),
                ((b, n_pg), i32), ((b,), i32)], 1)


    for cell, (h, h_kv, d, d_v, rows, buckets) in BENCH_CHUNK_SHAPES.items():
        for s_c in buckets:
            yield (f"flash chunk call {cell} S_c={s_c}",
                   flash_chunk_attention,
                   [((1, h, s_c, d), bf16), ((1, rows, h_kv, d), bf16),
                    ((1, rows, h_kv, d_v), bf16), ((1,), i32)], 1)


    for cell, (h, h_kv, d, rows, block, buckets) in \
            BENCH_SPARSE_CHUNK_SHAPES.items():
        for s_c in buckets:
            yield (f"flash sparse chunk call {cell} S_c={s_c}",
                   functools.partial(flash_sparse_chunk_attention,
                                     block=block),
                   [((1, h, s_c, d), bf16), ((1, rows, h_kv, d), bf16),
                    ((1, rows, h_kv, d), bf16), ((1,), i32),
                    ((1, s_c, h_kv, rows // block), jnp.bool_)], 1)

    for cell, (s_q, h, c, groups, pool, chosen) in \
            BENCH_CHOSEN_GROUPS_SHAPES.items():
        yield (f"chosen groups fetched and attended {cell}",
               functools.partial(chosen_groups_attention, pool=pool,
                                 scale=1.0 / 16),
               [((1, s_q, h, c), bf16), ((1, groups, pool, c), bf16),
                ((1, s_q, chosen), i32), ((1, s_q), i32), ((1, s_q), i32)], 1)

    # LongCat's rebuild: a block of 1,024 keys of 64 heads put in place by
    # one DMA, keys at 256 lanes and values at 128
    for width in (256, 128):
        yield (f"write_rows LongCat {width}",
               lambda whole, rows, at: write_rows(whole, rows, at,
                                                  multiple_of=128),
               [((1, 64, 9216, width), bf16), ((1, 64, 1024, width), bf16),
                ((), i32)], 1)


CASES = list(_cases())


@pytest.mark.parametrize("name,fn,args,n_calls", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_lowers_for_tpu(name, fn, args, n_calls):
    abstract = [jax.ShapeDtypeStruct(s, d) for s, d in args]
    text = jax.jit(fn).trace(*abstract).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= n_calls


def test_flash_calls_read_as_the_benchmarks_reader_expects():
    """``benchmarks/layer_metrics/flash_roofline.json`` tells the kernels
    apart by the HLO result of their custom calls: the forward is the ONE
    call returning (bf16, f32), the backward the TWO returning (bf16, bf16)
    (dK, dV) and a single bf16 (dQ), and backward passes are counted by the
    latter.  A kernel PR that changes a signature (a fused backward
    returning three arrays, a Pallas delta with an f32 result) would make
    ``flash_roofline`` count the wrong work without failing anything else.
    HLO text reads as the trace's event names do, less the leading '%'."""
    params = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "layer_metrics" / "flash_roofline.json"
                         ).read_text())["params"]
    abstract = [jax.ShapeDtypeStruct(BENCH_FLASH_SHAPES[0], bf16)] * 4
    text = jax.jit(_flash_vjp).trace(*abstract).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo")
    calls = ["%" + line.strip().removeprefix("ROOT ").removeprefix("%")
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    hits = {name: [c.split(" = ")[1].split(" custom-call")[0]
                   for c in calls if re.search(rx, c)]
            for name, rx in params.items()}
    shape = "bf16[768,1024,64]{2,1,0}"
    assert len(hits["fwd"]) == 1 and hits["fwd"][0].startswith(
        f"({shape}, f32["), hits
    assert sorted(hits["bwd"]) == sorted([f"({shape}, {shape})", shape]), hits
    assert hits["bwd_count"] == [shape], hits


def test_grouped_window_calls_keep_k_v_dk_and_dv_at_the_kv_heads():
    """The new cell's calls as ``flash_roofline.train-ep4`` reads them: the
    forward still the ONE call returning (bf16, f32), the backward the TWO
    returning (bf16, bf16) and a single bf16, now with K, V, dK and dV at
    the 4 KV heads: every call takes K and V as ``[4, S, D]`` beside Q's
    ``[32, S, D]`` and dK/dV leaves as two ``[4, S, D]``, so no ``[B, heads,
    S, D]`` K, V, dK or dV exists for the kernels to read or XLA to sum.  In
    a trace the calls are named after their ``jax.named_scope``; a lowering
    has none, so the patterns' ``%s`` is filled with nothing."""
    params = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "layer_metrics" / "flash_roofline.train-ep4.json"
                         ).read_text())["params"]
    _, qs, ks, window = MASKED_FLASH_CASES[0]
    abstract = [jax.ShapeDtypeStruct(s, bf16) for s in (qs, ks, ks, qs)]
    text = jax.jit(_flash_vjp_masked(window)).trace(*abstract).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo")
    calls = ["%" + line.strip().removeprefix("ROOT ").removeprefix("%")
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    hits = {name: [c for c in calls if re.search(rx % "", c)]
            for name, rx in params.items()}
    q, kv = "bf16[32,16384,128]{2,1,0}", "bf16[4,16384,128]{2,1,0}"

    def result(c):
        return c.split(" = ")[1].split(" custom-call")[0]

    def operands(c):
        return re.findall(
            r"(?:bf16|f32)\[[\d,]+\]\{[\d,]+\}",
            c.split("operand_layout_constraints={")[1].split(
                ", frontend_attributes")[0])

    (fwd,) = hits["fwd"]
    assert result(fwd).startswith(f"({q}, f32[") and \
        operands(fwd) == [q, kv, kv]
    assert sorted(map(result, hits["bwd"])) == sorted([f"({kv}, {kv})", q])
    assert [result(c) for c in hits["bwd_count"]] == [q]
    for c in hits["bwd"]:
        assert operands(c)[:4] == [q, kv, kv, q]


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` in ``jaxpr``, in program order."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


def test_a_streamed_window_call_walks_its_longest_live_walk(monkeypatch):
    """Mellum's window call (``[32 | 4, 16384, 128]``, W = 1,024, blocks of
    512: streamed, 32 blocks a row) lowers for TPU with the walked axis of
    each kernel's grid as long as the longest live walk, 3 blocks, and not
    the row's 32: ``flash.plan`` reads 3,072 ``steps`` a kernel beside 2,976
    ``tiles_live`` (32,768 before ISSUE 49).  The full call beside it keeps
    the whole row."""
    seen = []
    monkeypatch.setattr(
        sys.modules["hetu_tpu.ops.pallas_kernels.flash_attention"].trace,
        "instant", lambda name, attrs=None, cat="hetu": seen.append(attrs))
    for (_, qs, ks, window), grids, steps, tiles in zip(
            MASKED_FLASH_CASES,
            ([(32, 32, 3), (4, 32, 8, 3), (32, 32, 3)],
             [(32, 32, 32), (4, 32, 8, 32), (32, 32, 32)]),
            (3072, 32768), (2976, 16896)):
        del seen[:]
        abstract = [jax.ShapeDtypeStruct(s, bf16) for s in (qs, ks, ks, qs)]
        traced = jax.jit(_flash_vjp_masked(window)).trace(*abstract)
        assert _pallas_grids(traced.jaxpr.jaxpr) == grids   # fwd, dkdv, dq
        assert traced.lower(lowering_platforms=("tpu",)).as_text().count(
            "tpu_custom_call") == 3
        assert [(a["kernel"], a["resident"], a["steps"], a["tiles_live"])
                for a in seen] == [(k, 0, steps, tiles)
                                   for k in ("fwd", "dkdv", "dq")]


# every windowless STREAMED call lowers to the text it lowered to at the
# parent of ISSUE 49 (9b9e92f): a grid as long as the row, the one-ended
# clamp.  (The step digests below hold the resident ones.)
STREAMED_DIGESTS = {
    "mellum full": (
        (1, 32, 16384, 128), (1, 4, 16384, 128), True,
        "0ad240f3b760272430a0a51bd4dd2368687b5b523318dc8ebd225eab92b457f6"),
    "causal sq_lt_sk": (
        (1, 2, 8192, 128), (1, 2, 16384, 128), True,
        "9b9181f3c9b265f08231febc211b57115d5e0b88942a64b41b99e3d1a240371b"),
    "causal sq_gt_sk": (
        (1, 2, 16384, 128), (1, 2, 8192, 128), True,
        "608afdd9c0f28d062d9ace0619c3aaa3b05917b98c81a69b4f55f7151c924025"),
    "unmasked grouped": (
        (1, 4, 16384, 128), (1, 2, 16384, 128), False,
        "e9bd9abe7de2472d9d149be993e4fb8b05a6566cd661b250c0717e78baf3fab7")}


@pytest.mark.parametrize("case", sorted(STREAMED_DIGESTS))
def test_a_windowless_streamed_call_lowers_to_the_text_it_lowered_to(
        case, monkeypatch):
    """The kernels in interpret mode, as the step digests are taken: a
    Mosaic call's payload carries the checkout's path."""
    import hashlib

    monkeypatch.setattr(
        sys.modules["hetu_tpu.ops.pallas_kernels.flash_attention"],
        "auto_interpret", lambda interpret: True)
    qs, ks, causal, digest = STREAMED_DIGESTS[case]
    abstract = [jax.ShapeDtypeStruct(s, bf16) for s in (qs, ks, ks, qs)]
    text = jax.jit(_flash_vjp_masked(None, causal)).trace(*abstract).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_a_chunk_call_lowers_to_one_text(monkeypatch):
    """K-EXAONE's chunk call (512 queries of 64 heads over a 33,792-row view
    of 8 KV heads, read where it lies), in interpret mode as the digests above:
    one grid whose
    walked axis is a traced bound, the starts prefetched."""
    import hashlib

    monkeypatch.setattr(
        sys.modules["hetu_tpu.ops.pallas_kernels.flash_attention"],
        "auto_interpret", lambda interpret: True)
    abstract = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, 64, 512, 128), bf16), ((1, 33792, 8, 128), bf16),
        ((1, 33792, 8, 128), bf16), ((1,), i32))]
    traced = jax.jit(flash_chunk_attention).trace(*abstract)
    (grid,), = _pallas_grids(traced.jaxpr.jaxpr),
    assert grid[:2] == (64, 1) and not isinstance(grid[2], int)
    text = traced.lower(lowering_platforms=("tpu",)).as_text(
        debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == CHUNK_DIGEST


CHUNK_DIGEST = (
    "5643625dda529f3d7e0c9b5816183ef692001d5a6a67202ff24538728b75ae70")


def _gpt2_small():
    """(model, batch shape, scanned layer bodies)."""
    from hetu_tpu.models.gpt import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(
        vocab_size=FULL.vocab, hidden_size=FULL.hidden,
        num_layers=FULL.layers, num_heads=FULL.heads, ffn_size=FULL.ffn,
        max_position=FULL.seq, dropout_rate=0.0, dtype=bf16,
        attention_impl="flash", fused_ce=True, remat=True))
    return model, (FULL.batch, FULL.seq), 1


def _tiny_deepseek_v3():
    from hetu_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3Model

    model = DeepseekV3Model(DeepseekV3Config(
        vocab_size=512, hidden_size=256, num_layers=3, num_heads=2,
        kv_lora_rank=64, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, ffn_size=512, expert_ffn_size=128,
        n_routed_experts=8, moe_topk=2, held=(2, 2), expert_block_rows=128,
        ce_row_chunk=256, max_position=256))
    return model, (2, 256), 2


def _tiny_mellum():
    from hetu_tpu.models.mellum import MellumConfig, MellumModel

    model = MellumModel(MellumConfig(
        vocab_size=512, hidden_size=256, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=128, expert_ffn_size=128,
        n_routed_experts=8, moe_topk=2, held=(2, 2), window=128,
        expert_block_rows=128, ce_row_chunk=256, max_position=256))
    return model, (2, 256), 4


def _step_text(model, batch_shape, debug_info: bool = True):
    """The train step of ``model`` as lowered for TPU, locations included
    (a Mosaic call's ``jax.named_scope`` is in its location only) unless
    ``debug_info`` is off."""
    import hetu_tpu as ht
    from hetu_tpu import optim
    from hetu_tpu.train.executor import TrainState

    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-4))

    def state():
        v = model.init(jax.random.PRNGKey(0))
        return TrainState(params=v["params"],
                          opt_state=ex.optimizer.init_state(v["params"]),
                          model_state=v["state"], rng=jax.random.PRNGKey(0),
                          step=jnp.zeros((), i32))

    batch = (jax.ShapeDtypeStruct(batch_shape, i32),)
    return ex._compile("train").trace(jax.eval_shape(state), batch).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=debug_info)


@pytest.mark.parametrize("build", [_gpt2_small, _tiny_deepseek_v3,
                                   _tiny_mellum])
def test_a_train_step_lowers_with_three_flash_calls_a_layer_body(build):
    """Per-layer remat keeps the forward kernel's output and LSE rows
    (``ops.remat``), so a scanned layer body holds the forward kernel once,
    in the forward scan, and dK/dV and dQ in the backward scan: three Mosaic
    calls, not four with a recomputed forward.  The ``deepseek_v3`` model
    scans two bodies, the dense layer's and the expert layers'; the
    ``mellum`` model scans a PERIOD, whose body holds three window layers
    and a full one, and every call of it takes K and V at the KV heads."""
    model, batch_shape, bodies = build()
    text = _step_text(model, batch_shape)
    assert text.count("tpu_custom_call") - _grouped_calls(text) == 3 * bodies
    if build is _tiny_mellum:
        # 2 x 4 query heads of [256, 128] over 2 x 2 KV heads: no call reads
        # a K or V at the query heads, and dK/dV leave at the KV heads
        q, kv = "tensor<8x256x128xbf16>", "tensor<4x256x128xbf16>"
        sigs = [s for s in re.findall(
            r"stablehlo.custom_call @tpu_custom_call.*? : "
            r"\((.*?)\) -> (.*)", text) if "hetu.moe.gmm" not in s[1]]
        sigs = [s for s in sigs if s[0].startswith(q)]   # the flash calls
        assert len(sigs) == 12
        assert all(s[0].split(", ")[:3] == [q, kv, kv] for s in sigs)
        assert sum(s[1].count(kv) == 2 for s in sigs) == 4


def _row_scatters(text, hidden):
    """Rows of every scatter's update into a float32 ``[T, hidden]``."""
    return [int(m) for m in re.findall(
        rf"stablehlo\.scatter.*?tensor<(\d+)x{hidden}xf32>\)\s*->", text,
        flags=re.S)]


@pytest.mark.parametrize("build", [_tiny_deepseek_v3, _tiny_mellum])
def test_a_train_step_whose_experts_fit_lowers_with_grouped_calls(
        build, monkeypatch):
    """Experts that fit the grouped kernels' VMEM take the grouped path
    (``ops.moe_ops.held_expert_path``): the walk's Mosaic calls carry
    ``hetu.moe.gmm`` and no block of ``expert_block_rows`` rows is added into
    the float32 ``[T, H]`` result; under a limit these experts pass the same
    model's step, which differentiates, holds the loop (past the limit the
    fused call cut along F EVALUATES a walk and reverse mode keeps the
    loop's rules): a block's scatter-add inside a ``while``."""
    from hetu_tpu.ops import moe_ops

    model, _, bodies = build()
    rows, hidden = model.c.expert_block_rows, model.c.hidden_size
    assert moe_ops.held_expert_path(1024, 2, 2, hidden, 128) == "grouped"
    text = _step_text(model, (4, 256))
    # a walk: gate, up, down forward; gate, up, the down projection's
    # backward, three dW and one dx backward (the recomputed forward walk's
    # result is read by nobody, and is not traced); one walk's calls a
    # program, however many expert layers a scanned body holds
    assert text.count("tpu_custom_call") == 3 * bodies + 10
    assert _grouped_calls(text) == 10
    adds = _row_scatters(text, hidden)
    assert moe_ops.grouped_row_budget(1024, 2, 2, 8) in adds \
        and rows not in adds
    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", hidden * 128 - 1)
    assert moe_ops.held_expert_path(1024, 2, 2, hidden, 128) == "cut"
    text = _step_text(model, (4, 256))
    assert text.count("tpu_custom_call") == 3 * bodies
    assert "hetu.moe.gmm" not in text
    assert rows in _row_scatters(text, hidden)


def _grouped_calls(text, part=None):
    """The Mosaic calls of a lowered program ``text`` (of ``part`` of it)
    whose location carries ``hetu.moe.gmm``."""
    scoped = set(re.findall(r'^(#loc\d+) = loc\("[^"]*hetu\.moe\.gmm', text,
                            flags=re.M))
    return sum(loc in scoped for loc in re.findall(
        r"custom_call @tpu_custom_call.*loc\((#loc\d+)\)$",
        text if part is None else part, flags=re.M))


def _grouped_holders(text) -> dict:
    """{function of the lowered program ``text`` that holds a grouped Mosaic
    call: how many times the program calls it}."""
    holders = {}
    for fn in re.split(r"\n(?=\s*func\.func )", text):
        name = re.match(r"\s*func\.func \w+ @([\w.]+)", fn)
        if name and _grouped_calls(text, fn):
            holders[name.group(1)] = len(re.findall(
                rf"call @{re.escape(name.group(1))}\(", text))
    return holders


@pytest.mark.parametrize("build,walks", [(_tiny_deepseek_v3, 1),
                                         (_tiny_mellum, 4)])
def test_a_scan_body_lowers_each_grouped_kernel_once(build, walks):
    """What a program costs to BUILD: a ``pallas_call`` is lowered to its
    Mosaic module in Python every time the step is traced, compile cache
    warm or not, and ``setup_s`` pays it (PERF.md section 6, PR 42).  The
    grouped walk is a jitted function, so the four expert layers of
    ``mellum``'s scanned period, each under its own ``ops.remat``, call ONE
    lowered forward and ONE lowered backward: 10 grouped Mosaic calls in
    the step, where four separately lowered walks made 40; ``deepseek_v3``
    scans a one-layer body and holds what one walk makes."""
    model, _, _ = build()
    text = _step_text(model, (4, 256))
    assert _grouped_calls(text) == 10
    holders = _grouped_holders(text)
    # the walk forward (gate, up, down) and the walk backward (the other
    # seven), each lowered once and called once a layer of the body
    assert sorted(n.split("_")[2] for n in holders) == ["backward",
                                                        "forward"]
    assert set(holders.values()) == {walks}


def _program_text(engine, program: str, slots: int = 4, chunk: int = 8):
    """The engine's decode program at ``slots`` sequences or its chunk
    program at ``chunk`` tokens, lowered for TPU with locations."""
    from paged_programs import traced

    return traced(engine, program, batch=slots, chunk=chunk).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def _tiny_exaone():
    from paged_programs import tiny_model

    return tiny_model("exaone")


def _tiny_longcat():
    from paged_programs import tiny_model

    return tiny_model("longcat")


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("build", [_tiny_exaone, _tiny_longcat])
def test_a_serving_program_of_wide_experts_lowers_with_the_cut_call(
        build, program, monkeypatch):
    """K-EXAONE's and LongCat's experts (6144 x 2048) are three times what
    the grouped kernels keep whole in VMEM: their decode rounds and prefill
    chunks evaluate them by ONE fused Mosaic call a walk, in one lowered
    ``_grouped_forward`` that every expert layer of the program calls, and
    no loop walk is left.  At the test's widths the rule's limit is scaled
    down with the experts; their 128 columns are one lane tile, so what
    lowers here is the call at ONE F tile, and the cut lowers at the cells'
    own shapes among ``CASES`` (``held experts cut along F``)."""
    from hetu_tpu.ops import moe_ops
    from hetu_tpu.serve import PagedServeEngine

    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", 128 * 128 // 3)
    model = build()
    engine = PagedServeEngine(model, jax.jit(model.init)(
        jax.random.PRNGKey(0)), num_slots=4, max_len=160, page_size=4,
        prefill_chunk=8, min_bucket=4)
    text = _program_text(engine, program)
    assert "hetu.moe.experts" in text
    assert _grouped_calls(text) == 1
    holders = _grouped_holders(text)
    layers = model.c.num_layers - getattr(model.c, "first_dense", 0)
    # LongCat scans its double layers: one call of the walk in the body
    assert list(holders.values()) == [1 if build is _tiny_longcat
                                      else layers]
    assert next(iter(holders)).startswith("_grouped_forward")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_a_serving_program_of_lfm2_lowers_one_grouped_call_for_twelve_layers(
        program, monkeypatch):
    """LFM2's experts fit the kernels: a decode round and a prefill chunk
    take the grouped path, every pair in one trip, a whole expert a visit in
    ONE Mosaic call.  The walk is a jitted function (``layer`` reaches it
    as an array), so the twelve expert layers of the unrolled program call
    one lowered ``_grouped_forward``: one grouped Mosaic call a program,
    which is what a program's build costs (PERF.md section 6, PR 42 and
    44); and no loop walk is left."""
    from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
    from hetu_tpu.ops import moe_ops
    from hetu_tpu.serve import PagedServeEngine

    model = Lfm2MoeModel(Lfm2MoeConfig(
        vocab_size=96, hidden_size=128, num_layers=14, num_heads=4,
        num_kv_heads=2, head_dim=32, ffn_size=256, expert_ffn_size=128,
        first_dense=2, n_routed_experts=8, moe_topk=4, max_position=256,
        dtype=bf16, param_dtype=bf16, expert_block_rows=24))
    engine = PagedServeEngine(model, jax.jit(model.init)(
        jax.random.PRNGKey(0)), num_slots=4, max_len=160, page_size=4,
        prefill_chunk=8, min_bucket=4)
    text = _program_text(engine, program)
    assert _grouped_calls(text) == 1
    holders = _grouped_holders(text)
    assert list(holders.values()) == [12]
    assert next(iter(holders)).startswith("_grouped_forward")
    # no loop walk is left: no block of ``expert_block_rows`` rows anywhere
    assert "hetu.moe.experts" in text
    assert "tensor<24x128xf32>" not in text
    # nor under a limit these experts pass: evaluated, the fused call still
    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", 0)
    jax.clear_caches()
    text = _program_text(engine, program)
    assert _grouped_calls(text) == 1 and "tensor<24x128xf32>" not in text


# the lowered text (no locations) of the serving programs of the two served
# families whose experts the kernels keep WHOLE, by digest, taken on the
# parent of ISSUE 55 (33e299d): the F-cut call is a second kernel beside
# ``_ffn_kernel``, which these programs keep, and a PR that changes what
# they lower to replaces a digest and says why
SERVED_WHOLE_DIGESTS = {
    ("lfm2", "decode"):
        "383ee2118cb772af5e7ec4eabc58e7ab0eee0c7c1b7decfca5d955ba47d4c219",
    ("lfm2", "chunk"):
        "370fe727f8907dd895fa03d438981420f35abbdcf56293d5e9004c2aa5776545",
    ("qwen3_next", "decode"):
        "a326af72ac2175f6fc0f476bfd42c285036897926d55e7b4aa5539eb5fe5914f",
    ("qwen3_next", "chunk"):
        "12bbb7c27e01cb2bab21672596e816ba73d6ff1c910df04d709996a338f19ff1"}


def _tiny_qwen3_next():
    from hetu_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel

    return Qwen3NextModel(Qwen3NextConfig(
        vocab_size=96, hidden_size=128, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=32, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=16, gdn_value_dim=16, gdn_chunk=8, expert_ffn_size=128,
        shared_ffn_size=128, n_routed_experts=8, moe_topk=2, held=(0, 8),
        max_position=256, dtype=bf16, param_dtype=bf16,
        expert_block_rows=8))


@pytest.mark.parametrize("family,program", sorted(SERVED_WHOLE_DIGESTS))
def test_a_serving_program_of_whole_experts_lowers_to_the_text_it_lowered_to(
        family, program, monkeypatch):
    """ISSUE 55 cut ``gmm_ffn`` along F for experts past the kernels' VMEM;
    LFM2's and Qwen3-Next's programs, whose experts fit, lower to the text
    of the parent commit.  The kernels in interpret mode, as the step
    digests are taken."""
    import hashlib

    from paged_programs import tiny_model, traced
    from hetu_tpu.serve import PagedServeEngine

    for mod in ("flash_attention", "grouped_matmul"):
        monkeypatch.setattr(sys.modules[f"hetu_tpu.ops.pallas_kernels.{mod}"],
                            "auto_interpret", lambda interpret: True)
    jax.clear_caches()
    model = tiny_model("lfm2") if family == "lfm2" else _tiny_qwen3_next()
    engine = PagedServeEngine(model, jax.jit(model.init)(
        jax.random.PRNGKey(0)), num_slots=4, max_len=160, page_size=4,
        prefill_chunk=8, min_bucket=4)
    text = traced(engine, program, batch=4, chunk=8).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == SERVED_WHOLE_DIGESTS[family, program]


# the attention projection leaves of each served family, by what their leaf
# paths hold, and how a program over the engine's own leaves reads each: the
# chains of primitives from the parameter to the product (``weight_reads``)
PROJECTION_READS = {
    # layers scanned: the fused projection's leaf held with its contracting
    # axis minor and its columns split by head, the scan's own slice of it
    # the product's operand as it lies
    "gpt": {"qkv_weight_t": ("scan", "dot_general"),
            "out_weight": ("scan", "dot_general")},
    # layers a Python loop: a leaf a layer, whole into the product (q and k
    # are stored [out, in], ``trans_w``)
    "exaone": {"['q'][": ("transpose", "dot_general"),
               "['k'][": ("transpose", "dot_general"),
               "['v'][": ("dot_general",), "['o'][": ("dot_general",)},
    "lfm2": {"['q'][": ("transpose", "dot_general"),
             "['k'][": ("transpose", "dot_general"),
             "['v'][": ("dot_general",), "['o'][": ("dot_general",)},
    # double layers scanned over their NUMBER, a leaf read at the traced
    # ``[l, i]``: the two leaves whose products are read head by head held
    # transposed
    "longcat": {"q_b_t": ("dynamic_slice", "squeeze", "dot_general"),
                "['o']": ("dynamic_slice", "squeeze", "dot_general")}}


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("kind", sorted(PROJECTION_READS))
def test_a_serving_program_reads_each_projection_weight_where_it_lies(
        kind, program):
    """No decode or chunk program over the leaves the engine holds cuts a
    layer out of a stacked projection leaf at a STATIC index (``slice``: on
    a v5e the compiler writes that layer into a buffer of its own in every
    call, K-EXAONE's ``fusion.647``), copies one, reshapes one, or
    transposes one other than into the product that contracts it (``q`` and
    ``k`` of the grouped heads are stored ``[out, in]``; the compiler folds
    that into the product).  A leaf whose product's result is read head by
    head is held with its contracting axis minor and its columns split by
    head, so that neither a relayout nor a reshape lies between the leaf and
    the product: GPT-2's ``copy.31``, LongCat's ``copy.148``.  A layer taken
    at a TRACED index, a scan's own slice or a ``dynamic_slice``, is read in
    place (``PERF.md`` section 6, PR 45).  Over the leaves as GIVEN the same
    walk finds the static slices and no transposed hold: the check can see
    them."""
    from hetu_tpu.serve import PagedServeEngine
    from paged_programs import tiny_served, weight_reads

    model, variables, kw = tiny_served(kind)
    engine = PagedServeEngine(model, variables, **kw)
    want = PROJECTION_READS[kind]
    reads = weight_reads(engine, program, lambda path: "attn" in path
                         and any(name in path for name in want))
    layers = {"gpt": 1, "exaone": 5, "lfm2": 2, "longcat": 1}[kind]
    assert len(reads) == layers * len(want)
    for path, chains in reads.items():
        (name,) = [n for n in want if n in path]
        assert chains == {want[name]}, (path, chains)
    given = weight_reads(engine, program, lambda path: "attn" in path,
                         params=variables["params"])
    seen = {step for chains in given.values() for c in chains for step in c}
    if kind in ("exaone", "lfm2"):
        assert "slice" in seen
    else:
        assert not any("_t'" in path for path in given)


# the lowered text (no locations) of each train step, by digest: what a step
# computes reads ``params`` and never the rendering a server holds, so a PR
# that touches the serving side alone leaves these as they are, and one that
# changes a step replaces its digest and says why.  Mellum's step lowers to
# one of two texts by the process's hash seed (which of two equal rotation
# tables an equation names), on this tree and on its parent alike.  ISSUE 49
# changed the streamed WINDOW calls and left all three as they are: no call
# of these steps has both (``_tiny_mellum``'s window calls, 256 keys of 128,
# are resident)
STEP_DIGESTS = {
    "_gpt2_small": {
        "c8a54ffcd3a3be6b47a5cbe48b53842fb43443d4042d34451982b543756b2e51"},
    "_tiny_deepseek_v3": {
        "3dee0100d2c80b2a1c6f9d6fbb1d10ad02dfa91deb746975198d998542d7b852"},
    "_tiny_mellum": {
        "3111c1dfcb94c458968ed1fa55dcd0f09793e3d184c281bc732687138f707782",
        "70143e83c599f4d9c8edbfcc5da16961278b0091e4f5900e1eb40397e177ae78"}}


@pytest.mark.parametrize("build", [_gpt2_small, _tiny_deepseek_v3,
                                   _tiny_mellum])
def test_a_train_step_lowers_to_the_text_it_lowered_to(build, monkeypatch):
    """ISSUE 45 (e): the held rendering is the serving engine's alone; the
    train steps of the three trained families lower to the text of the
    parent commit (481cdaf), digest for digest.  The kernels in interpret
    mode, for once: a Mosaic call's payload carries the checkout's path."""
    import hashlib

    for mod in ("flash_attention", "grouped_matmul"):
        monkeypatch.setattr(sys.modules[f"hetu_tpu.ops.pallas_kernels.{mod}"],
                            "auto_interpret", lambda interpret: True)
    jax.clear_caches()
    model, batch_shape, _ = build()
    text = _step_text(model, batch_shape, debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() \
        in STEP_DIGESTS[build.__name__]


@pytest.mark.slow
@pytest.mark.parametrize("name,fn,args,n_calls", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(name, fn, args, n_calls):
    """Mosaic + XLA:TPU, ahead of time, for a topology instead of a device."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    sh = SingleDeviceSharding(topo.devices[0])
    abstract = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in args]
    jax.jit(fn).lower(*abstract).compile()


# ---- a pool laid over a tensor-parallel mesh: no Mosaic call to partition

def _tp2_decode(monkeypatch, devices=None):
    """The decode program of a tiny GQA engine under tp=2 as a TPU backend
    traces it, with its arguments: on the engine's own (CPU) mesh, or
    abstract over ``devices``.  Returns (jitted program, arguments, pool)."""
    import hetu_tpu as ht
    from hetu_tpu.models.llama import LlamaConfig, LlamaModel
    from hetu_tpu.serve import PagedServeEngine
    from jax.sharding import NamedSharding, PartitionSpec

    monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"],
                        "_default_backend_is_tpu", lambda: True)
    model = LlamaModel(LlamaConfig(
        vocab_size=128, hidden_size=256, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=512, max_position=64, dtype=bf16))
    engine = PagedServeEngine(
        model, model.init(jax.random.PRNGKey(0)), num_slots=4, max_len=64,
        page_size=16, mesh=ht.make_mesh(tp=2))
    cache = engine.cache
    assert cache.k.sharding.spec[3] == "tp"
    args = (engine.params, cache.k, cache.v,
            jnp.zeros((4, cache.pages_per_slot + 4), i32))
    if devices is not None:
        mesh = ht.make_mesh(tp=2, devices=devices)
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=(
                NamedSharding(mesh, getattr(a.sharding, "spec",
                                            PartitionSpec())))), args)
    return engine._build_decode(), args, cache.k


def test_a_tp_sharded_decode_program_lowers_for_tpu_without_the_kernel(
        monkeypatch):
    """The partitioner cannot split a Mosaic call ("cannot be automatically
    partitioned" when the program is lowered), so a pool laid over a mesh
    keeps the view, which it splits by KV head: the rule is on the cache,
    not on the backend alone."""
    fn, args, _ = _tp2_decode(monkeypatch)
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text


@pytest.mark.slow
def test_a_tp_sharded_decode_program_compiles_for_v5e_and_gathers_no_pool(
        monkeypatch):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    fn, args, pool = _tp2_decode(monkeypatch, topo.devices[:2])
    text = fn.lower(*args).compile().as_text()
    assert "custom_call_target=\"tpu_custom_call\"" not in text
    # no collective brings a pool together: nothing of the whole pool's
    # shape, nor of one layer's pages at their full width
    whole = ",".join(map(str, pool.shape))
    layer = ",".join(map(str, pool.shape[1:]))
    gathers = [line for line in text.splitlines() if "all-gather" in line]
    assert not [g for g in gathers if f"[{whole}]" in g or f"[{layer}]" in g]
    assert f"bf16[{whole}]" not in text    # each chip holds half the width


# ---- the meshed train step's asynchronous all-reduces (PR 50)

def _dp2_tp2_step_text(devices) -> str:
    """A tiny Megatron dp=2 x tp=2 GPT step compiled for ``devices`` through
    ``Executor._compile("train")``."""
    import hetu_tpu as ht
    from hetu_tpu import optim
    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.parallel.mesh import mesh_context
    from hetu_tpu.parallel.strategies import simple
    from hetu_tpu.train.executor import TrainState
    from jax.sharding import NamedSharding, PartitionSpec

    model = GPTModel(GPTConfig(
        vocab_size=512, hidden_size=256, num_layers=2, num_heads=4,
        ffn_size=1024, max_position=256, dropout_rate=0.0, dtype=bf16,
        attention_impl="flash", fused_ce=True, remat=True))
    mesh = ht.make_mesh(devices=devices, dp=2, tp=2)
    strategy = simple.MegatronLM()
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-4),
                     mesh=mesh, dist_strategy=strategy)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, PartitionSpec())

    def abstract(tree, sharding):
        if isinstance(sharding, NamedSharding):
            sharding = jax.tree_util.tree_map(lambda _: sharding, tree)
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)

    slots = strategy.slot_shardings(shapes["params"], mesh)
    opt = jax.eval_shape(ex.optimizer.init_state, shapes["params"])
    state = TrainState(
        params=abstract(shapes["params"],
                        strategy.shardings(shapes["params"], mesh)),
        opt_state={k: ({n: abstract(s, slots) for n, s in v.items()}
                       if k == "slots" else abstract(v, rep))
                   for k, v in opt.items()},
        model_state={}, rng=jax.ShapeDtypeStruct((2,), jnp.uint32,
                                                 sharding=rep),
        step=jax.ShapeDtypeStruct((), i32, sharding=rep))
    batch = (jax.ShapeDtypeStruct((4, 256), i32, sharding=NamedSharding(
        mesh, PartitionSpec("dp"))),)
    with mesh_context(mesh):
        return ex._compile("train").lower(state, batch).compile().as_text()


def _backward_scan_body(text: str) -> list:
    """The instructions of the computation that holds the backward scan's
    activation all-reduces, fused or plain."""
    site = "transpose(jvp())/while/body/closed_call/checkpoint/dot_general"
    for comp in re.split(r"\n(?=%[\w.\-]+ \()", text):
        head = comp.split("\n", 1)[0]
        lines = [ln for ln in comp.split("\n") if re.match(
            r"\s+%(all-reduce|async-collective)[\w.\-]* = ", ln)]
        if head.startswith("%wide.") and any(site in ln for ln in lines):
            return lines
    return []


@pytest.mark.slow
def test_a_meshed_train_step_compiles_for_v5e_with_asynchronous_all_reduces(
        monkeypatch):
    """With the executor's options XLA:TPU starts the backward scan's two
    input-gradient all-reduces (``bf16[2,256,256]``: of ``ffn_in`` and of
    ``qkv``) asynchronously and puts matmuls between each start and its
    done; with the options taken off both are plain all-reduces and the
    program holds no pair.  This libtpu (0.0.34) spells a pair
    ``async-collective-start`` / ``-done`` (fusions that hold the
    all-reduce); an ``all-reduce-start`` / ``-done`` spelling passes too.
    The test guards the option NAMES against a libtpu that renames them."""
    from hetu_tpu.train import executor as executor_module
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    start = re.compile(r"\s+%(all-reduce|async-collective)-start[\w.]* = ")
    done = re.compile(r"\s+%(all-reduce|async-collective)-done[\w.]* = ")
    plain = re.compile(r" = bf16\[2,256,256\]\S* all-reduce\(")

    body = _backward_scan_body(_dp2_tp2_step_text(topo.devices))
    assert sum(bool(start.match(ln)) for ln in body) == 2, body
    assert sum(bool(done.match(ln)) for ln in body) == 2, body
    assert not [ln for ln in body if plain.search(ln)]

    monkeypatch.setattr(executor_module, "async_collective_options",
                        lambda mesh: {})
    text = _dp2_tp2_step_text(topo.devices)
    body = _backward_scan_body(text)
    assert len([ln for ln in body if plain.search(ln)]) == 2, body
    assert not re.search(r"(all-reduce|async-collective)-(start|done)", text)


# ---- ISSUE 58: selection by rows and the per-channel rule at the shapes of
# glm-5.3-flash.batch-context (plain XLA: what the chip's compiler accepts
# and how much it keeps beside its arguments)

def _glm_query_block(q, qi, w, kbar, view, pos):
    """One query block of a chunk's DSA layer off the kernel: 128 queries
    score 16,656 pooled keys, choose 512 groups, gather 2,052 rows of the
    slot's view, brought up to 2,064 (whole tiles of 16), and attend in the
    absorbed form."""
    from hetu_tpu import ops

    idx, n = ops.select_groups(qi, w, kbar, pos, topk=512, pool=4)
    rows, valid = ops.chosen_rows(idx, n, pos, pool=4,
                                  tile=ops.INDEX_ROW_TILE)
    latents = jax.vmap(lambda v, r: v[r])(view, jnp.clip(rows, 0, 66623))
    return ops.chosen_rows_attention(q, latents, valid, scale=1.0 / 16)


def _glm_rule(q, k, v, g, beta, state):
    from hetu_tpu.ops import delta_rule

    return delta_rule.kda_chunk_scan(q, k, v, g, beta, state, chunk=64,
                                     sub=16, last=2000)


GLM_CASES = (
    ("a DSA query block", _glm_query_block,
     (((1, 128, 64, 512), bf16), ((1, 128, 32, 128), bf16),
      ((1, 128, 32), f32), ((1, 16656, 128), bf16), ((1, 66624, 512), bf16),
      ((1, 128), i32)), 400 << 20),
    ("the KDA rule over a chunk", _glm_rule,
     (((1, 2048, 64, 128), bf16), ((1, 2048, 64, 128), bf16),
      ((1, 2048, 64, 128), bf16), ((1, 2048, 64, 128), f32),
      ((1, 2048, 64), f32), ((1, 64, 128, 128), f32)), 2 << 30))


@pytest.mark.slow
@pytest.mark.parametrize("name,fn,args,most", GLM_CASES,
                         ids=[c[0] for c in GLM_CASES])
def test_row_selection_and_the_channel_rule_compile_for_v5e(name, fn, args,
                                                            most):
    """XLA:TPU accepts the gather, the exact top-k and the sub-blocked rule
    at the published shapes, and keeps under ``most`` bytes of temporaries
    (a block's gathered latents are 271 MB, the rule's sub-block factors
    268 MB).  The gathered rows are read where the gather left them: at
    2,052 rows a query they were COPIED to ``[128, 2052, 512]`` (a
    ``reshape`` lent scoped memory, 539 MB of temporaries: ISSUE 59); at
    2,064 the reshape is a bitcast."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    sh = SingleDeviceSharding(topo.devices[0])
    abstract = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in args]
    compiled = jax.jit(fn).lower(*abstract).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < most
    relays = [ln for ln in compiled.as_text().splitlines()
              if re.search(r"= bf16\[\d+,\d+,512\]\S* reshape\(", ln)]
    assert not relays, relays
