"""Every Pallas kernel lowers for TPU at chip_smoke.py's full shapes — from
the CPU host, no chip: ``jit(...).trace(...).lower(lowering_platforms=
("tpu",))`` runs the Pallas->Mosaic lowering, which is where a block shape
Mosaic cannot move (a ``(1, D)`` row block) is refused.  The slow lane goes
one step further and COMPILES them with the installed libtpu for a v5e
topology, which is where Mosaic itself refuses (a one-row DMA out of a tiled
table, a dynamic sublane index on a packed dtype).
"""

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

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

# the flash calls of the two training cells of BENCHMARK.json: gpt2-small at
# 64 x 1024 on one chip, and gpt2-large's per-chip shard under dp=2 x tp=2
BENCH_FLASH_SHAPES = ((64, 12, 1024, 64), (8, 10, 1024, 64))


@pytest.fixture(autouse=True)
def _compiled_kernels(monkeypatch):
    """Lower the kernels as a TPU backend would: never interpret mode."""
    for mod in ("embedding", "flash_attention"):
        m = sys.modules[f"hetu_tpu.ops.pallas_kernels.{mod}"]
        name = "_auto_interpret" if mod == "embedding" else "auto_interpret"
        monkeypatch.setattr(m, name, lambda interpret: False)


def _flash_vjp(q, k, v, g):
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       q, k, v)
    return (out, *vjp(g))


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


def test_gpt2_small_train_step_lowers_with_the_flash_calls():
    import hetu_tpu as ht
    from hetu_tpu import optim
    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.train.executor import TrainState

    model = GPTModel(GPTConfig(
        vocab_size=FULL.vocab, hidden_size=FULL.hidden,
        num_layers=FULL.layers, num_heads=FULL.heads, ffn_size=FULL.ffn,
        max_position=FULL.seq, dropout_rate=0.0, dtype=bf16,
        attention_impl="flash", fused_ce=True, remat=True))
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-4))

    def state():
        params = model.init(jax.random.PRNGKey(0))["params"]
        return TrainState(params=params,
                          opt_state=ex.optimizer.init_state(params),
                          model_state={}, rng=jax.random.PRNGKey(0),
                          step=jnp.zeros((), i32))

    batch = (jax.ShapeDtypeStruct((FULL.batch, FULL.seq), i32),)
    text = ex._compile("train").trace(jax.eval_shape(state), batch).lower(
        lowering_platforms=("tpu",)).as_text()
    # flash forward, its remat recompute, dK/dV and dQ
    assert text.count("tpu_custom_call") >= 4


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
