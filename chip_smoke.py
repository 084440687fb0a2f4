"""First light on the chip: the main path, once, at full width.

    python chip_smoke.py

One process.  Drives the system through the entry points a user calls —
``ht.Executor`` for training, ``PagedServeEngine`` + scheduler +
``InferenceServer`` + ``InferenceClient`` over the van for serving — at
GPT-2-small width (random weights from a seed), with every Pallas kernel
compiled, and checks each result against the repo's own reference.  With
four chips it also runs the trainer on the ``dp=4`` and ``dp=2,tp=2``
meshes.  Any failed check raises, so the exit code is nonzero; nothing is
caught and nothing is skipped.  Prints as its last line
``{"ok": true, "device": {...}}`` with the device as JAX reports it.

It needs a TPU whose kind is in the peaks table (profiler/cost_model.py)
and exits nonzero, printing no result, anywhere else.  The same phases run
at tiny sizes in interpret mode on CPU from tests/test_chip_smoke.py — that
lane keeps the script from rotting between chip runs and is NOT a chip
pass (it prints ``platform=cpu``).

Step and phase times are printed as information only: nothing here is a
benchmark and no number it prints is a metric.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from dataclasses import dataclass


@dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    heads: int
    ffn: int
    seq: int            # training sequence = serving max_len
    batch: int
    steps: int
    slots: int
    prompt_lens: tuple  # several prefill buckets + a multi-chunk prompt
    max_tokens: int
    flash_shapes: tuple  # (q [B,H,S,D], kv [B,H,S,D]) pairs
    emb: tuple          # (rows, width, ids) for gather / scatter-add
    topk: tuple         # (tokens, experts, k)


# GPT-2-small: the one model every cell of the roadmap shares code with
FULL = Sizes(
    vocab=50304, hidden=768, layers=12, heads=12, ffn=3072, seq=1024,
    batch=16, steps=5, slots=8, prompt_lens=(5, 23, 70, 150, 300),
    max_tokens=16,
    flash_shapes=(((16, 12, 1024, 64), (16, 12, 1024, 64)),  # train step
                  ((1, 12, 16, 64), (1, 12, 16, 64)),    # smallest bucket
                  ((1, 12, 64, 64), (1, 12, 256, 64))),  # chunk over cache
    emb=(50304, 768, 4096), topk=(16384, 64, 8))

TINY = Sizes(
    vocab=512, hidden=64, layers=2, heads=4, ffn=128, seq=64, batch=8,
    steps=5, slots=4, prompt_lens=(3, 9, 20, 37), max_tokens=6,
    flash_shapes=(((2, 4, 64, 16), (2, 4, 64, 16)),
                  ((1, 4, 16, 16), (1, 4, 16, 16)),
                  ((1, 4, 16, 16), (1, 4, 32, 16))),
    emb=(100, 32, 40), topk=(64, 16, 4))


def _rel_err(got, want) -> float:
    import numpy as np
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        raise AssertionError(f"chip_smoke: {name} {detail}")


def _run(fn, *args, compiled: bool):
    """jit, and — when the kernels are meant to be compiled — prove the
    program holds a Mosaic custom call before running it."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    if compiled and "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("kernel was lowered without a Mosaic call")
    return jax.block_until_ready(lowered.compile()(*args))


# --------------------------------------------------------------- kernels

def phase_kernels(sz: Sizes, *, compiled: bool) -> None:
    """Every Pallas kernel at the main path's shapes against its XLA
    oracle, on the device.  bf16 tolerance is the repo's own
    (tests/test_flash_attention.py): 5e-2 of the reference's range."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu import ops
    from hetu_tpu.ops.pallas_kernels import (
        embedding_gather, embedding_scatter_add, flash_attention,
        routed_gather, topk_gating,
    )
    interpret = not compiled
    f32 = jnp.float32
    print(f"[kernels] compiled={compiled}", flush=True)

    for qs, ks in sz.flash_shapes:
        keys = jax.random.split(jax.random.PRNGKey(len(qs) + qs[2]), 4)
        q, g = (jax.random.normal(k, qs, jnp.bfloat16) for k in keys[:2])
        k, v = (jax.random.normal(kk, ks, jnp.bfloat16) for kk in keys[2:])

        def flash(q, k, v, g):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=interpret), q, k, v)
            return (out, *vjp(g))

        def oracle(q, k, v, g):
            out, vjp = jax.vjp(ops.causal_attention, *(
                t.astype(f32) for t in (q, k, v)))
            return (out, *vjp(g.astype(f32)))

        got = _run(flash, q, k, v, g, compiled=compiled)
        want = _run(oracle, q, k, v, g, compiled=False)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            err = _rel_err(a, b)
            _check(f"flash {name} q{qs} kv{ks}", err < 5e-2,
                   f"rel_err={err:.2e}")

    rows, width, n = sz.emb
    key = jax.random.PRNGKey(1)
    table = jax.random.normal(key, (rows, width), f32)
    # duplicates, a negative and an out-of-range id: the kernels' contract
    ids = jax.random.randint(key, (n,), 0, rows).at[:3].set(
        jnp.array([-1, rows, 7])).at[5].set(7)
    grads = jax.random.normal(jax.random.PRNGKey(2), (n, width), f32)
    valid = ((ids >= 0) & (ids < rows))[:, None]
    safe = jnp.clip(ids, 0, rows - 1)

    got = _run(lambda t, i: embedding_gather(t, i, interpret=interpret),
               table, ids, compiled=compiled)
    _check("embedding_gather", bool(jnp.array_equal(
        got, jnp.where(valid, table[safe], 0))))
    got = _run(lambda g, i: embedding_scatter_add(
        g, i, rows, interpret=interpret), grads, ids, compiled=compiled)
    want = jnp.zeros((rows, width), f32).at[safe].add(
        jnp.where(valid, grads, 0))
    _check("embedding_scatter_add", _rel_err(got, want) < 1e-5)

    def routed(kernel):
        return lambda t, i, g: jax.value_and_grad(lambda t: jnp.sum(
            routed_gather(t, i, kernel=kernel) * g))(t)
    # kernel=True means compiled on a TPU and interpret mode on CPU
    got = _run(routed(True), table, ids, grads, compiled=compiled)
    want = _run(routed(False), table, ids, grads, compiled=False)
    _check("routed_gather value+grad", all(
        _rel_err(a, b) < 1e-5 for a, b in zip(got, want)))

    tokens, experts, k = sz.topk
    logits = jax.random.normal(jax.random.PRNGKey(3), (tokens, experts), f32)
    gates, idx = _run(lambda x: topk_gating(x, k, kernel=True), logits,
                      compiled=compiled)
    ref_gates, ref_idx = _run(lambda x: topk_gating(x, k, kernel=False),
                              logits, compiled=False)
    _check(f"topk_gating {k} of {experts}",
           bool(jnp.array_equal(idx, ref_idx))
           and _rel_err(gates, ref_gates) < 1e-5)


# --------------------------------------------------------------- trainer

def _gpt(sz: Sizes, **kw):
    import jax.numpy as jnp

    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    return GPTModel(GPTConfig(
        vocab_size=sz.vocab, hidden_size=sz.hidden, num_layers=sz.layers,
        num_heads=sz.heads, ffn_size=sz.ffn, max_position=sz.seq,
        dropout_rate=0.0, dtype=jnp.bfloat16, **kw))


def phase_trainer(sz: Sizes, *, compiled: bool, mesh=None, strategy=None):
    """A few AdamW steps through ``ht.Executor`` on a fixed batch.  Returns
    (first loss, final TrainState, the batch as placed, compiled HLO)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import hetu_tpu as ht
    from hetu_tpu import optim

    where = "one device" if mesh is None else \
        "mesh " + str({a: n for a, n in mesh.shape.items() if n > 1})
    print(f"[trainer] {where}", flush=True)
    model = _gpt(sz, attention_impl="flash", fused_ce=True, remat=True)
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(3e-4),
                     mesh=mesh, dist_strategy=strategy)
    state = ex.init_state(model.init(jax.random.PRNGKey(0)),
                          rng_key=jax.random.PRNGKey(1))
    ids = np.random.default_rng(0).integers(
        0, sz.vocab, (sz.batch, sz.seq)).astype(np.int32)
    if mesh is not None:  # where Executor.run would put it: rows over dp
        ids = jax.device_put(ids, NamedSharding(mesh, P("dp")))
    batch = (ids,)

    hlo = ex.lower("train", state, batch).compile().as_text()
    n_mosaic = hlo.count('custom_call_target="tpu_custom_call"')
    if compiled:  # flash fwd + bwd really are in the program
        _check("train step holds the Mosaic calls", n_mosaic >= 3,
               f"n={n_mosaic}")
    losses = []
    for i in range(sz.steps):
        t0 = time.perf_counter()
        state, metrics = ex.run("train", state, batch)
        jax.block_until_ready(state)
        losses.append(float(metrics["loss"]))
        print(f"  step {i} loss={losses[-1]:.4f} "
              f"wall={time.perf_counter() - t0:.3f}s (information only)",
              flush=True)
    _check("loss finite", bool(np.all(np.isfinite(losses))))
    _check("loss falls on the fixed batch", losses[-1] < losses[0],
           f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    stats = jax.devices()[0].memory_stats()
    print(f"  peak_bytes_in_use="
          f"{stats['peak_bytes_in_use'] if stats else 'not reported'}",
          flush=True)
    return losses[0], state, ids, hlo


# ---------------------------------------------------------------- server

def phase_server(sz: Sizes) -> None:
    """The serving stack end to end over the van, checked against a float32
    full forward of the same weights.  The model keeps the serving default
    ``attention_impl='xla'`` (what ``serve.crosshost.build_engine`` builds);
    the flash kernel's serving shapes are covered by the kernel phase."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hetu_tpu.models.gpt import GPTModel
    from hetu_tpu.serve import (
        ContinuousBatchingScheduler, InferenceClient, InferenceServer,
        PagedServeEngine,
    )
    print("[server]", flush=True)
    model = _gpt(sz)
    variables = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(0, sz.vocab, n)]
               for n in sz.prompt_lens]

    engine = PagedServeEngine(model, variables, num_slots=sz.slots,
                              max_len=sz.seq)
    n_clients = 3
    server = InferenceServer(ContinuousBatchingScheduler(engine),
                             max_clients=n_clients, request_timeout_s=900.0)
    served, errors = {}, []

    def client(cid: int) -> None:
        c = InferenceClient("127.0.0.1", server.port, cid)
        try:
            for j in range(cid, len(prompts), n_clients):
                served[j] = c.generate(prompts[j], max_tokens=sz.max_tokens,
                                       timeout_s=900.0)
        except Exception as e:  # a thread's failure must reach the main one
            errors.append(repr(e))
        finally:
            c.close()

    threads = [threading.Thread(target=client, args=(cid,))
               for cid in range(n_clients)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(1000.0)
    finally:
        server.close()
    _check("clients finished", not errors and len(served) == len(prompts),
           f"errors={errors} served={sorted(served)}")
    _check("every response ok with the requested token count", all(
        r["status"] == "ok" and len(r["tokens"]) == sz.max_tokens
        for r in served.values()),
        str({j: (r["status"], len(r["tokens"])) for j, r in served.items()}))
    _check("paged executables bounded",
           engine.compiled_executables() <= engine.max_executables,
           f"{engine.compiled_executables()} <= {engine.max_executables}")
    snap = engine.metrics.snapshot()
    decoded = sum(len(r["tokens"]) - 1 for r in served.values())
    _check("more than one prefill bucket and a batched decode ran",
           snap["prefill_compiles"] > 1 and snap["decode_steps"] < decoded,
           f"prefill_compiles={snap['prefill_compiles']} decode_steps="
           f"{snap['decode_steps']} for {decoded} decoded tokens")

    # Every served token must be the greedy token of an f32 full forward of
    # the same weights over the stream served so far (the model is causal:
    # ONE forward of prompt + tokens gives the logits each token was drawn
    # from).  The bf16 programs may round a near-tie between the top logits
    # differently; a served token that is not the f32 argmax is accepted
    # only if the f32 forward says the two ARE tied to bf16 resolution at
    # that position (a wrong token from a real defect is O(1) logits away).
    f32_model = GPTModel(dataclasses.replace(model.c, dtype=jnp.float32))
    forward = jax.jit(lambda p, ids: f32_model.apply(
        {"params": p, "state": {}}, ids)[0])
    for j, prompt in enumerate(prompts):
        got = served[j]["tokens"]
        ctx = prompt + got[:-1]
        ids = np.zeros((1, sz.seq), np.int32)
        ids[0, :len(ctx)] = ctx
        rows = np.asarray(forward(variables["params"], ids)[
            0, len(prompt) - 1:len(ctx)])        # [max_tokens, vocab]
        off = [i for i, t in enumerate(got) if t != int(np.argmax(rows[i]))]
        if not off:
            _check(f"request {j} tokens equal the f32 forward's", True)
            continue
        gap = [float(np.max(rows[i]) - rows[i, got[i]]) for i in off]
        tie = [2.0 ** -5 * float(np.max(np.abs(rows[i]))) for i in off]
        _check(f"request {j} differs from the f32 forward at tokens {off} "
               f"only on bf16 near-ties",
               all(g <= t for g, t in zip(gap, tie)),
               f"f32 logit gaps={[round(g, 4) for g in gap]} "
               f"ties<={[round(t, 4) for t in tie]}")


# ------------------------------------------------------------ four chips

def phase_four_chips(sz: Sizes, *, compiled: bool, loss_one: float) -> None:
    """The trainer again on dp=4 and on dp=2 x tp=2 (Megatron preset):
    same global batch and seed as the one-device run."""
    import jax

    import hetu_tpu as ht
    from hetu_tpu.parallel.planner import gathers_feeding
    from hetu_tpu.parallel.strategies.simple import DataParallel, MegatronLM

    for axes, strategy in (({"dp": 4}, DataParallel()),
                           ({"dp": 2, "tp": 2}, MegatronLM())):
        mesh = ht.make_mesh(**axes)
        loss, state, ids, hlo = phase_trainer(
            sz, compiled=compiled, mesh=mesh, strategy=strategy)
        _check(f"{axes} first loss equals one device's",
               abs(loss - loss_one) <= 1e-2 * abs(loss_one),
               f"{loss:.4f} vs {loss_one:.4f}")
        want = strategy.shardings(state.params, mesh)
        bad = [jax.tree_util.keystr(path) for (path, a), s in zip(
            jax.tree_util.tree_leaves_with_path(state.params),
            jax.tree_util.tree_leaves(want))
            if len({sh.device for sh in a.addressable_shards}) != 4
            or a.addressable_shards[0].data.shape != s.shard_shape(a.shape)]
        _check(f"{axes} params on 4 devices in the specs' shard shapes",
               not bad, f"off-spec: {bad[:4]}")
        _check(f"{axes} batch rows split over dp on 4 devices",
               len({sh.device for sh in ids.addressable_shards}) == 4
               and ids.addressable_shards[0].data.shape
               == (sz.batch // axes["dp"], sz.seq))
        n_split = sum(s.shard_shape(a.shape) != a.shape for a, s in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(want)))
        _check(f"{axes} tensor-parallel leaves are really split",
               (n_split > 0) == ("tp" in axes), f"split leaves={n_split}")
        if compiled:
            feeding = gathers_feeding(hlo)
            _check(f"{axes} no all-gather in front of the attention calls",
                   not feeding, "; ".join(f[:120] for f in feeding))


# ------------------------------------------------------------------ main

def run_phases(sz: Sizes, *, compiled: bool) -> None:
    import jax
    t0 = time.perf_counter()
    phase_kernels(sz, compiled=compiled)
    t1 = time.perf_counter()
    loss_one = phase_trainer(sz, compiled=compiled)[0]
    t2 = time.perf_counter()
    phase_server(sz)
    t3 = time.perf_counter()
    if jax.device_count() >= 4:
        phase_four_chips(sz, compiled=compiled, loss_one=loss_one)
    t4 = time.perf_counter()
    print(f"phase wall (information only): kernels={t1 - t0:.1f}s "
          f"trainer={t2 - t1:.1f}s server={t3 - t2:.1f}s "
          f"four_chips={t4 - t3:.1f}s", flush=True)


def main() -> int:
    import jax

    from hetu_tpu.profiler.cost_model import chip_for_device
    from hetu_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"jax={jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} device_count={jax.device_count()} "
          f"compile_cache={cache}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform={dev.platform}",
              file=sys.stderr)
        return 1
    chip_for_device(dev)  # a TPU kind outside the peaks table raises
    run_phases(FULL, compiled=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
