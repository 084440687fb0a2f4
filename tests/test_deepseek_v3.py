"""The ``deepseek_v3`` block on the training path (``models/deepseek_v3.py``)
against the benchmark's plain reference (``benchmarks/reference/deepseek_v3.py``)
at a small size: loss and every gradient leaf, three steps with the bias
state moving on both sides, the eight shares adding up to the uncut layer,
and the rope layout that is another model."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import hetu_tpu as ht  # noqa: E402
from benchmarks.reference import deepseek_v3 as ref  # noqa: E402
from hetu_tpu import optim  # noqa: E402
from hetu_tpu.models.deepseek_v3 import (  # noqa: E402
    MOE_STEP_IDS, DeepseekV3Config, DeepseekV3Model,
)

ROUTED, TOPK, SHARES = 16, 4, 8


def tiny(held=(4, 2), **kw):
    return DeepseekV3Config(**{**dict(
        vocab_size=96, hidden_size=32, num_layers=3, num_heads=2,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, ffn_size=64, expert_ffn_size=24,
        n_routed_experts=ROUTED, moe_topk=TOPK, held=held,
        dtype=jnp.float32, expert_block_rows=8, ce_row_chunk=32,
        max_position=64, router_init_std=0.5), **kw})


def dims_of(c):
    return dict(heads=c.num_heads, nope=c.qk_nope_head_dim,
                rope=c.qk_rope_head_dim, v_dim=c.v_head_dim,
                kv_rank=c.kv_lora_rank, theta=c.rope_theta, eps=c.rms_eps,
                topk=c.moe_topk, scaling=c.routed_scaling_factor,
                n_routed=c.n_routed_experts, held=c.held)


def ids_of(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 96, shape),
                       jnp.int32)


def some_bias(c, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(c.num_layers - c.first_dense, ROUTED)) * 0.05, jnp.float32)


def test_loss_and_every_gradient_leaf_match_the_reference():
    c = tiny()
    model = DeepseekV3Model(c)
    v = model.init(jax.random.PRNGKey(0))
    ids, bias = ids_of((2, 32)), some_bias(c)
    fn = model.lm_loss_fn()
    (loss, (metrics, state)), grads = jax.jit(jax.value_and_grad(
        lambda p: fn(p, {"router_bias": bias}, (ids,), None, True),
        has_aux=True))(v["params"])
    (want, chosen), want_grads = jax.jit(
        lambda p: ref.loss_and_grads(p, ids, dims_of(c), bias))(v["params"])
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_array_equal(metrics["moe_chosen"], chosen)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == 27
    for (path, got), exp in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            got, exp, rtol=2e-4, atol=2e-7 + 1e-4 * float(jnp.abs(exp).max()),
            err_msg=jax.tree_util.keystr(path))
    # the group the trainer puts on its instant: scalars, named
    assert sorted(metrics["moe"]) == sorted(MOE_STEP_IDS)   # jit sorts keys
    held = int(chosen[:, 4:6].sum())
    assert int(metrics["moe"]["moe_held"]) == held
    assert int(metrics["moe"]["moe_absent"]) == int(chosen.sum()) - held
    blocks = int(np.sum(-(-np.asarray(chosen[:, 4:6]) // 8)))
    assert int(metrics["moe"]["moe_blocks_fwd"]) == blocks
    assert int(metrics["moe"]["moe_blocks_bwd"]) == blocks
    np.testing.assert_allclose(
        state["router_bias"], ref.next_bias(bias, chosen, 0.001))


def test_the_reference_a_layer_at_a_time_is_the_reference_whole(monkeypatch):
    c = tiny()
    v = DeepseekV3Model(c).init(jax.random.PRNGKey(3))
    ids, bias = ids_of((1, 32), 4), some_bias(c, 5)
    (want, _), grads = ref.loss_and_grads(v["params"], ids, dims_of(c), bias)
    # tiles of one head and eight rows, and eight rows of the head at a time
    monkeypatch.setattr(ref, "SCORES_BYTES", 4 * 8 * 32)
    monkeypatch.setattr(ref, "ROWS", 8)
    loss, norm = ref.loss_and_grad_norm_by_layer(v["params"], ids,
                                                 dims_of(c), bias)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(norm, ref.global_norm(grads), rtol=1e-5)


def test_three_steps_move_the_bias_state_as_the_reference_moves_it():
    """Executor steps with plain SGD beside the reference's own three
    steps: loss, parameters and the bias agree after each."""
    c = tiny(bias_update_rate=0.01)
    model = DeepseekV3Model(c)
    v = model.init(jax.random.PRNGKey(1))
    lr = 0.1
    ex = ht.Executor(model.lm_loss_fn(), optim.SGDOptimizer(lr))
    state = ex.init_state(v)
    params = jax.tree_util.tree_map(jnp.asarray, v["params"])
    bias = jnp.zeros((2, ROUTED), jnp.float32)
    step = jax.jit(lambda p, x, b: ref.loss_and_grads(p, x, dims_of(c), b))
    for i in range(3):
        ids = ids_of((2, 32), 10 + i)
        state, metrics = ex.run("train", state, (ids,))
        (want, chosen), grads = step(params, ids, bias)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                        grads)
        bias = ref.next_bias(bias, chosen, 0.01)
        np.testing.assert_allclose(metrics["loss"], want, rtol=1e-5)
        np.testing.assert_allclose(state.model_state["router_bias"], bias,
                                   atol=1e-7)
        assert float(jnp.abs(bias).max()) == pytest.approx(0.01 * (i + 1))
    for got, exp in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(got, exp, rtol=1e-3, atol=2e-6)
    # the bias is state: the optimizer holds no slot for it
    assert "router_bias" not in str(jax.tree_util.tree_structure(
        state.opt_state))


def test_an_absent_state_reads_as_a_zero_bias():
    c = tiny()
    model = DeepseekV3Model(c)
    v = model.init(jax.random.PRNGKey(2))
    ids = ids_of((2, 16), 6)
    fn = model.lm_loss_fn()
    a, (_, st) = fn(v["params"], {}, (ids,), None, True)
    b, _ = fn(v["params"], v["state"], (ids,), None, True)
    assert float(a) == float(b)
    assert float(jnp.abs(st["router_bias"]).max()) == pytest.approx(0.001)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of the eight shares, plus the shared expert and
    everything replicated counted once, are the uncut reference layer."""
    c = tiny(held=(0, ROUTED))
    model = DeepseekV3Model(c)
    v = model.init(jax.random.PRNGKey(4))
    p = jax.tree_util.tree_map(lambda a: a[0], v["params"]["sparse"]["moe"])
    bias = some_bias(c)[0]
    u = jnp.asarray(np.random.default_rng(7).normal(size=(40, 32)),
                    jnp.float32)
    whole, chosen = ref.expert_layer(p, bias, u, dims_of(c))
    shared = ref.swiglu(u, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    per = ROUTED // SHARES
    total, pairs = shared, 0
    for r in range(SHARES):
        share = DeepseekV3Model(tiny(held=(r * per, per))).moe
        mine = dict(p, router_bias=bias,
                    **{k: p[k][r * per:(r + 1) * per]
                       for k in ("gate", "up", "down")})
        out, stats = share.apply(mine, u)
        total = total + (out - shared)
        pairs += int(stats[0])
        assert int(stats[0]) + int(stats[2]) == 40 * TOPK
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert pairs == 40 * TOPK == int(chosen.sum())


def test_the_other_rope_layout_is_another_model():
    """Program and reference agree under the deepseek_v3 reading of
    ``rope_interleave``; the stored order read as the half layout moves the
    logits by far more than the two differ."""
    c = tiny()
    model = DeepseekV3Model(c)
    v = model.init(jax.random.PRNGKey(5))
    v["params"] = jax.tree_util.tree_map(lambda a: a * 4.0, v["params"])
    ids = ids_of((2, 32), 8)
    got = model.apply(v, ids)[0]
    want = ref.logits(v["params"], ids, dims_of(c))
    other = ref.logits(v["params"], ids, dims_of(c), interleaved=False)
    span = float(want.max() - want.min())
    assert float(jnp.abs(got - want).max()) / span < 1e-4
    assert float(jnp.abs(other - want).max()) / span > 1e-2


def test_flash_and_composed_attention_are_one_model():
    c = tiny()
    v = DeepseekV3Model(c).init(jax.random.PRNGKey(6))
    ids = ids_of((2, 32), 9)
    a = DeepseekV3Model(c).apply(v, ids)[0]
    b = DeepseekV3Model(tiny(attention_impl="xla", remat=False,
                             fused_ce=False)).apply(v, ids)[0]
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
