"""``models/mellum.py`` against its plain reference
(``benchmarks/reference/mellum.py``) on seeded weights at a small size: loss
and every gradient leaf, the YaRN table against numbers worked by hand, one
test for each reading the configuration's ``assumed`` takes, and the share
test: the four ranks' expert parts add up to the uncut layer."""

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks.reference import mellum as ref  # noqa: E402

from hetu_tpu.models.mellum import (  # noqa: E402
    MOE_STEP_IDS, MellumConfig, MellumModel, yarn_inv_freq,
)

# rope_parameters.full_attention of the published config.json
YARN = {"factor": 16, "original_max_position_embeddings": 8192,
        "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
# the same section where 32 positions are the original ones: the ramp then
# runs inside a head of 16
TINY_YARN = dict(YARN, original_max_position_embeddings=32)


def tiny(impl="xla", held=(4, 4), layers=8, **over):
    """Two periods of (window, window, window, full) at hidden 64: 4 | 2
    heads of 16, 16 experts of 32 with 4 a token, a window of 24."""
    return MellumModel(MellumConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_layers=layers, num_heads=4,
        num_kv_heads=2, head_dim=16, expert_ffn_size=32, n_routed_experts=16,
        moe_topk=4, held=held, window=24, rope_theta=1e4, yarn=TINY_YARN,
        max_position=128, dtype=jnp.float32, expert_block_rows=8,
        attention_impl=impl, ce_row_chunk=32, embedding_init_std=1.0),
        **over}))


def dims(model):
    c = model.c
    return {"heads": c.num_heads, "kv_heads": c.num_kv_heads,
            "head_dim": c.head_dim, "window": c.window,
            "theta": c.rope_theta, "yarn": c.yarn, "eps": c.rms_eps,
            "topk": c.moe_topk, "n_routed": c.n_routed_experts,
            "held": c.held, "period": c.period}


def case(model, seed=0, seq=64):
    params = model.init(jax.random.PRNGKey(seed))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, seq), 0,
                             model.c.vocab_size)
    return params, ids


def program(model, params, ids):
    """(loss, gradient of every leaf, the step's counts)."""
    fn = model.lm_loss_fn()
    (loss, (metrics, _)), grads = jax.value_and_grad(
        lambda p: fn(p, {}, (ids,), None, True), has_aux=True)(params)
    return loss, grads, metrics["moe"]


def assert_leaves_agree(got, want, rtol=2e-3):
    got = jax.tree_util.tree_leaves_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == 15
    for (path, a), b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(b).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=rtol * np.abs(b).max(),
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------- program and reference

@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_loss_and_every_gradient_leaf_match_the_reference(impl):
    """Float32, two periods, a share of 4 of 16 experts: the composed
    oracle and the flash kernels (interpret mode; window and full calls, K
    and V at 2 heads) against the plain reference."""
    model = tiny(impl)
    params, ids = case(model)
    loss, grads, counts = program(model, params, ids)
    want, want_grads = ref.loss_and_grads(params, ids, dims(model))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert_leaves_agree(grads, want_grads)
    assert set(counts) == set(MOE_STEP_IDS)
    pairs = 8 * ids.size * 4                      # layers x tokens x top-k
    assert int(counts["moe_held"]) + int(counts["moe_absent"]) == pairs
    assert 0 < int(counts["moe_hit"]) <= 8 * 4
    assert int(counts["moe_blocks_fwd"]) == int(counts["moe_blocks_bwd"]) \
        >= int(counts["moe_held"]) / 8


def test_logits_match_the_reference_and_the_stack_is_one_leaf_a_weight():
    model = tiny()
    params, ids = case(model, seed=3)
    got, _ = model.apply({"params": params, "state": {}}, ids)
    want = ref.logits(params, ids, dims(model))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    assert model.c.period == (ref.SLIDING,) * 3 + (ref.FULL,)
    # [periods, layers a period, ...]: one leaf a kind of weight
    assert params["layers"]["attn"]["k"].shape == (2, 4, 32, 64)
    assert params["layers"]["moe"]["gate"].shape == (2, 4, 4, 64, 32)


def test_it_trains_through_the_executor_and_hands_over_its_counts():
    import hetu_tpu as ht
    from hetu_tpu import optim

    model = tiny("flash", layers=4)
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(3e-3))
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))
    ids = np.asarray(case(model)[1])
    losses = []
    for _ in range(4):
        state, metrics = ex.run("train", state, (ids,))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert set(metrics["moe"]) == set(MOE_STEP_IDS)


# --------------------------------------------------------- the two tables

def test_the_yarn_table_is_the_published_sections_by_hand():
    """theta 500,000, D 128, factor 16 over 8,192: c(n) = 128 ln(8192 / (2
    pi n)) / (2 ln 500000) gives c(32) = 18.08 and c(1) = 34.98, so the ramp
    runs from 18 to 35; below it the plain frequency, above it a sixteenth,
    between them the mix."""
    ln = math.log(5) + 5 * math.log(10)                    # ln 500000
    assert 128 * math.log(8192 / (64 * math.pi)) / (2 * ln) \
        == pytest.approx(18.081, abs=1e-3)
    assert 128 * math.log(8192 / (2 * math.pi)) / (2 * ln) \
        == pytest.approx(34.984, abs=1e-3)
    inv, (low, high) = yarn_inv_freq(128, 5e5, YARN)
    assert (low, high) == (18, 35) and inv.shape == (64,)
    inv = np.asarray(inv, np.float64)
    assert inv[0] == 1.0
    # i = 18, the ramp's foot: plain, exp(-36/128 ln theta)
    assert inv[18] == pytest.approx(math.exp(-0.28125 * ln), rel=1e-5) \
        == pytest.approx(0.0249554, rel=1e-5)
    # i = 26: ramp 8/17, so plain x (9/17 + 8/17/16)
    assert inv[26] == pytest.approx(
        math.exp(-0.40625 * ln) * (9 / 17 + 8 / 17 / 16), rel=1e-5) \
        == pytest.approx(0.00270438, rel=1e-5)
    # i = 35, the ramp's head, and beyond: a sixteenth of plain
    assert inv[35] == pytest.approx(math.exp(-0.546875 * ln) / 16, rel=1e-5) \
        == pytest.approx(4.77811e-5, rel=1e-5)
    assert inv[63] == pytest.approx(math.exp(-126 / 128 * ln) / 16, rel=1e-5)
    assert YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-12)


def test_full_layers_rotate_by_yarn_and_window_layers_by_the_plain_table():
    model = MellumModel(MellumConfig(
        vocab_size=64, num_layers=4, held=(16, 16), yarn=YARN))
    pos = jnp.asarray([[0, 1, 9000]])
    cos_w, sin_w = model.rope_at(pos, ref.SLIDING)
    cos_f, sin_f = model.rope_at(pos, ref.FULL)
    plain = 5e5 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(np.asarray(cos_w[0, 2]),
                               np.cos(np.float32(9000) * plain.astype(
                                   np.float32)), atol=2e-3)
    inv, _ = yarn_inv_freq(128, 5e5, YARN)
    factor = YARN["attention_factor"]
    np.testing.assert_allclose(np.asarray(sin_f[0, 2]),
                               factor * np.sin(9000 * np.asarray(inv)),
                               atol=2e-3)
    # position 0: cos is the factor itself on a full layer, one on a window
    np.testing.assert_allclose(np.asarray(cos_f[0, 0]), factor, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cos_w[0, 0]), 1.0)
    # the reference's tables are the same numbers
    r_inv, r_factor = ref.inv_freq(
        {"head_dim": 128, "theta": 5e5, "yarn": YARN}, ref.FULL)
    np.testing.assert_allclose(np.asarray(r_inv), np.asarray(inv), rtol=1e-6)
    assert r_factor == factor


def test_one_table_for_both_kinds_is_another_model():
    """YaRN on the full layers ONLY: with the plain table there too the
    logits are others."""
    model = tiny()
    params, ids = case(model, seed=5)
    variables = {"params": params, "state": {}}
    got, _ = model.apply(variables, ids)
    plain, _ = tiny(yarn=None).apply(variables, ids)
    want = np.asarray(ref.logits(params, ids, dims(model)))
    span = float(np.ptp(want))
    assert float(np.abs(np.asarray(got) - want).max()) < 2e-4 * span
    assert float(np.abs(np.asarray(plain) - want).max()) > 3e-3 * span


# ------------------------------------- what the configuration's file assumes

def test_assumed_qk_norm_leaving_it_out_is_another_model():
    """Per-head RMSNorm on q and k before the rotation is the reading taken
    (config.json is silent): program and reference agree on it, and the
    reference without the norms gives other logits."""
    model = tiny()
    params, ids = case(model, seed=7)
    # weights that are not one, so that the norm's scale shows as well
    params["layers"]["attn"]["q_norm"] = params["layers"]["attn"][
        "q_norm"] * 1.5
    got, _ = model.apply({"params": params, "state": {}}, ids)
    with_norm = ref.logits(params, ids, dims(model))
    without = ref.logits(params, ids, dims(model), qk_norm=False)
    span = float(np.ptp(np.asarray(with_norm)))
    assert float(np.abs(np.asarray(got) - with_norm).max()) < 1e-3 * span
    assert float(np.abs(np.asarray(got) - without).max()) > 1e-2 * span


def test_assumed_no_balance_loss_a_coefficient_is_another_loss():
    """No auxiliary balance loss is the reading taken (config.json gives no
    coefficient): the program's loss is the reference's without the term,
    and with a coefficient of 0.001 loss and router gradients are others."""
    model = tiny()
    params, ids = case(model, seed=9)
    loss, grads, _ = program(model, params, ids)
    plain, plain_grads = ref.loss_and_grads(params, ids, dims(model))
    aux, aux_grads = ref.loss_and_grads(params, ids, dims(model),
                                        aux_coef=0.001)
    assert abs(float(loss) - float(plain)) < 1e-5 * float(plain)
    # 8 layers x (about 1, the term of even routing) x 0.001
    assert float(aux) - float(plain) > 5e-3

    def router(g):
        return np.asarray(g["layers"]["moe"]["router"])

    scale = np.abs(router(plain_grads)).max()
    assert np.abs(router(grads) - router(plain_grads)).max() < 2e-3 * scale
    assert np.abs(router(aux_grads) - router(plain_grads)).max() \
        > 2e-2 * scale


def test_assumed_no_next_token_head_and_the_file_says_so():
    """config.json has no key for the next-token head the catalog's
    ``described_as`` mentions: the model holds no leaf for one, and the
    configuration's ``scope`` and ``assumed`` say it is absent."""
    params, _ = case(tiny())
    assert set(params) == {"tok_emb", "lm_head", "norm_f", "layers"}
    assert set(params["layers"]) == {"attn_norm", "ffn_norm", "attn", "moe"}
    assert set(params["layers"]["moe"]) == {"router", "gate", "up", "down"}
    cfg = json.loads((ROOT / "benchmarks" / "configs"
                      / "mellum2-12b-a2.5b-instruct.json").read_text())
    assert "ABSENT" in cfg["scope"]
    assert cfg["assumed"]["next_token_head"] == "absent"
    assert not [k for k in cfg if "nextn" in k or "mtp" in k.lower()]


def test_the_out_projections_initialiser_is_its_own_and_touches_no_other_leaf():
    """``out_init_std`` scales the attention's W_o alone (the same draws at
    another std); left out, every leaf is drawn at ``init_std``."""
    plain, _ = case(tiny())
    small, _ = case(tiny(out_init_std=0.02 / 8))
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(plain), flat(small)):
        scale = 1 / 8 if path[-1].key == "o" else 1.0
        np.testing.assert_allclose(np.asarray(b), np.asarray(a) * scale,
                                   rtol=1e-6, err_msg=str(path))
    assert float(jnp.std(small["layers"]["attn"]["o"])) == \
        pytest.approx(0.0025, rel=0.02)


# ----------------------------------------------------------------- the share

def test_the_four_ranks_expert_parts_add_up_to_the_uncut_layer():
    """One expert layer, 16 experts over 4 ranks of 4: every rank routes
    over all 16 and computes its own experts' part (the program's
    ``HeldExpertLayer`` as the model builds it); nothing is computed by all
    alike (no shared expert), so the four parts add up to what the
    reference gives with every expert held; and no rank's part is the
    whole."""
    whole = tiny(held=(0, 16), layers=4)
    params, _ = case(whole, seed=11)
    layer = jax.tree_util.tree_map(lambda a: a[0, 1], params["layers"])
    u = jax.random.normal(jax.random.PRNGKey(12), (96, 64))
    want, _ = ref.expert_layer(
        jax.tree_util.tree_map(np.asarray, layer["moe"]), u, dims(whole))
    parts = []
    for rank in range(4):
        model = tiny(held=(4 * rank, 4), layers=4)
        p = {"router": layer["moe"]["router"],
             "router_bias": jnp.zeros((16,), jnp.float32),
             **{k: layer["moe"][k][4 * rank:4 * rank + 4]
                for k in ("gate", "up", "down")}}
        out, stats = model.moe.apply(p, u)
        held, _, absent, _ = (int(x) for x in stats)
        assert held + absent == 96 * 4 and 0 < held < 96 * 4
        parts.append(np.asarray(out))
        # and the reference given this share gives this part
        mine, _ = ref.expert_layer(p, u, dims(model))
        np.testing.assert_allclose(parts[-1], np.asarray(mine), rtol=1e-4,
                                   atol=1e-5)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(sum(parts), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * scale)
    assert all(np.abs(p - np.asarray(want)).max() > 0.05 * scale
               for p in parts)
