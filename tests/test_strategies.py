"""Strategy presets: MegatronLM TP placement must reproduce the single-device
training trajectory through the Executor (reference analog:
examples/auto_parallel/transformer/test_megatronlm.py)."""

import re
import sys

import jax
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import models, optim
from hetu_tpu.parallel.mesh import mesh_context
from hetu_tpu.parallel.strategies import DataParallel, MegatronLM, Strategy
from hetu_tpu.train.executor import TrainState


def _place_state(state, shardings):
    return TrainState(
        params=jax.tree_util.tree_map(jax.device_put, state.params,
                                      shardings),
        opt_state={"step": state.opt_state["step"],
                   "slots": {k: jax.tree_util.tree_map(
                       jax.device_put, v, shardings)
                       for k, v in state.opt_state["slots"].items()}},
        model_state=state.model_state, rng=state.rng, step=state.step)


def test_megatron_tp_matches_single_device():
    cfg = models.GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                           num_heads=4, ffn_size=64, max_position=32,
                           dropout_rate=0.0)
    model = models.GPTModel(cfg)
    ids = np.random.default_rng(0).integers(0, 128, (8, 16)).astype(np.int32)

    ex1 = ht.Executor(model.lm_loss_fn(), optim.AdamOptimizer(1e-2), seed=0)
    s1 = ex1.init_state(model.init(jax.random.PRNGKey(0)))

    mesh = ht.make_mesh(dp=2, tp=4)
    ex8 = ht.Executor(model.lm_loss_fn(), optim.AdamOptimizer(1e-2),
                      mesh=mesh, seed=0)
    s8 = ex8.init_state(model.init(jax.random.PRNGKey(0)))
    strat = MegatronLM()
    s8 = _place_state(s8, strat.shardings(s8.params, mesh))

    for _ in range(4):
        s1, m1 = ex1.run("train", s1, (ids,))
        s8, m8 = ex8.run("train", s8, (ids,))
    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]),
                               rtol=2e-4)
    # params still tp-sharded after donated updates
    spec = s8.params["blocks"]["ffn_in"]["weight"].sharding.spec
    assert "tp" in str(spec), spec


def test_megatron_spec_assignments():
    strat = MegatronLM()
    import jax.numpy as jnp
    w = jnp.zeros((2, 8, 32))
    assert str(strat.param_spec("['blocks']['attn']['qkv_weight']", w)) == \
        str(jax.sharding.PartitionSpec(None, None, "tp"))
    assert "tp" in str(strat.param_spec("['tok_emb']", jnp.zeros((100, 8))))
    # row-parallel bias replicated
    b = jnp.zeros((2, 8))
    assert strat.param_spec("['blocks']['ffn_out']['bias']", b) == \
        jax.sharding.PartitionSpec()


def test_cnn_model_parallel_specs():
    """ModelParallel4CNN: FC weights tp-split, convs replicated
    (reference simple.py:46,119)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.parallel.strategies import (ModelParallel4CNN,
                                              OneWeirdTrick4CNN)
    strat = ModelParallel4CNN()
    conv_w = jnp.zeros((64, 3, 3, 3))
    fc_w = jnp.zeros((512, 10))
    assert strat.param_spec("['conv1']['weight']", conv_w) == P()
    assert strat.param_spec("['fc']['weight']", fc_w) == P(None, "tp")
    assert strat.param_spec("['fc']['bias']", jnp.zeros((10,))) == P("tp")
    # OneWeirdTrick inherits the same spec table
    assert OneWeirdTrick4CNN().param_spec("['fc']['weight']", fc_w) == \
        P(None, "tp")
    # ModelParallel4LM (upstream: MP4CNN with a flag, simple.py:113) too
    from hetu_tpu.parallel.strategies import ModelParallel4LM
    assert ModelParallel4LM().param_spec("['dense']['weight']", fc_w) == \
        P(None, "tp")
    assert ModelParallel4LM().param_spec("['conv1']['weight']",
                                         conv_w) == P()


def test_cnn_mp_trains_on_mesh():
    """ResNet with tp-split FC head trains identically to replicated."""
    import numpy as np
    from hetu_tpu.parallel.strategies import ModelParallel4CNN
    model = models.ResNet18(num_classes=10)
    x = np.random.default_rng(0).standard_normal((8, 3, 32, 32)).astype(
        np.float32)
    y = np.random.default_rng(1).integers(0, 10, 8).astype(np.int32)

    ex1 = ht.Executor(model.loss_fn(), optim.SGDOptimizer(0.1), seed=0)
    s1 = ex1.init_state(model.init(jax.random.PRNGKey(0)))
    mesh = ht.make_mesh(dp=2, tp=4)
    ex2 = ht.Executor(model.loss_fn(), optim.SGDOptimizer(0.1), mesh=mesh,
                      seed=0)
    s2 = ex2.init_state(model.init(jax.random.PRNGKey(0)))
    s2 = _place_state(s2, ModelParallel4CNN().shardings(s2.params, mesh))
    for _ in range(2):
        s1, m1 = ex1.run("train", s1, (x, y))
        s2, m2 = ex2.run("train", s2, (x, y))
    np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]),
                               rtol=2e-4)


def test_json_roundtrip(tmp_path):
    strat = MegatronLM()
    import jax.numpy as jnp
    params = {"blocks": {"attn": {"qkv_weight": jnp.zeros((2, 4, 12)),
                                  "out_weight": jnp.zeros((2, 4, 4))}},
              "tok_emb": jnp.zeros((10, 4))}
    path = tmp_path / "strategy.json"
    strat.save_json(params, path)
    loaded = Strategy.load_json(path)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for p, leaf in flat:
        key = jax.tree_util.keystr(p)
        assert strat.param_spec(key, leaf) == loaded.param_spec(key, leaf)


def test_data_parallel_all_replicated():
    strat = DataParallel()
    import jax.numpy as jnp
    specs = strat.param_specs({"a": jnp.zeros((2, 2)), "b": jnp.zeros((3,))})
    assert all(s == jax.sharding.PartitionSpec()
               for s in jax.tree_util.tree_leaves(
                   specs, is_leaf=lambda x: isinstance(
                       x, jax.sharding.PartitionSpec)))


# ---- a recomputed tensor-parallel layer keeps the value that crossed chips
# (ISSUE 38): ``ops.remat`` keeps the attention out-projection's summed
# result by name where the mesh in context splits 'tp', and only there

def _tp_model(kind, *, remat=True, policy="full"):
    if kind == "gpt":
        return models.GPTModel(models.GPTConfig(
            vocab_size=128, hidden_size=128, num_layers=3, num_heads=4,
            ffn_size=256, max_position=32, dropout_rate=0.0,
            attention_impl="xla", remat=remat, remat_policy=policy))
    from hetu_tpu.models.llama import LlamaConfig, LlamaModel
    return LlamaModel(LlamaConfig(
        vocab_size=128, hidden_size=128, num_layers=3, num_heads=4,
        num_kv_heads=4, ffn_size=256, max_position=32,
        attention_impl="xla", remat=remat))


def _tp_grad(kind, mesh_axes, use, strategy=MegatronLM, **model_kw):
    """``use(jitted loss-and-gradient, placed parameters)`` of a tiny model
    under ``strategy`` on ``mesh_axes`` (None: no mesh at all), called with
    that mesh in context."""
    model = _tp_model(kind, **model_kw)
    params = model.init(jax.random.PRNGKey(0))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 128)
    mesh = ht.make_mesh(**mesh_axes) if mesh_axes else None
    if mesh is not None:
        params = jax.device_put(params, strategy().shardings(params, mesh))
    loss_fn = model.lm_loss_fn()
    grad = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {}, (ids,), None, True)[0]))
    with mesh_context(mesh):
        return use(grad, params)


def _unnamed(monkeypatch):
    """The parent's program: the out-projection's result carries no name.
    (``setattr`` raises if a module stops holding ``checkpoint_name``, so a
    change of import style fails here and not silently.)"""
    for mod in ("hetu_tpu.layers.attention", "hetu_tpu.models.llama"):
        monkeypatch.setattr(sys.modules[mod], "checkpoint_name",
                            lambda x, name: x)


def _all_reduces(kind):
    """``op_name`` of every all-reduce in the gradient compiled for
    dp=2 x tp=2."""
    text = _tp_grad(kind, {"dp": 2, "tp": 2},
                    lambda grad, p: grad.lower(p).compile().as_text())
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if re.search(r"= .* all-reduce(-start)?\(", line)]


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_recomputed_tp_layer_does_not_reduce_the_out_projection_again(
        kind, monkeypatch):
    """Under dp=2 x tp=2 no all-reduce of the compiled gradient sits in the
    recomputed layer; with the name taken off one does (the RECOMPUTED
    out-projection's), and it is the only one the parent has more."""
    kept = _all_reduces(kind)
    assert kept and not [op for op in kept
                         if "rematted_computation" in op], kept
    _unnamed(monkeypatch)
    parent = _all_reduces(kind)
    assert sum("rematted_computation" in op for op in parent) == 1, parent
    assert len(parent) == len(kept) + 1, (parent, kept)


@pytest.mark.parametrize("mesh_axes", [None, {"dp": 4}],
                         ids=["no-mesh", "dp4-tp1"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_without_a_split_tp_axis_the_step_lowers_to_the_parents_text(
        kind, mesh_axes, monkeypatch):
    """No mesh, or a mesh whose 'tp' is one: the policy does not ask for the
    name, the name is the identity, and the lowered gradient is the text of
    a program that never carried it.  Under tp=2 the two texts differ (the
    kept value is a residual of the scan), so the comparison can fail."""
    def lowered(axes):
        return _tp_grad(kind, axes,
                        lambda grad, p: grad.lower(p).as_text())

    named, named_tp = lowered(mesh_axes), lowered({"dp": 2, "tp": 2})
    _unnamed(monkeypatch)
    assert named == lowered(mesh_axes)
    assert named_tp != lowered({"dp": 2, "tp": 2})


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_kept_reduction_leaves_loss_and_gradients_as_they_were(policy):
    """dp=2 x tp=2: loss and every gradient leaf with the layers recomputed
    (the summed out-projection kept) equal those with nothing recomputed."""
    def run(remat):
        return _tp_grad("gpt", {"dp": 2, "tp": 2},
                        lambda grad, p: grad(p), remat=remat, policy=policy)

    loss, grads = run(True)
    want_loss, want = run(False)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-4)
    got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(got) == len(jax.tree_util.tree_leaves(want)) > 10
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(b)).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_a_tp_mesh_whose_strategy_splits_nothing_keeps_the_value_too(
        monkeypatch):
    """Where the rule mis-fires: it reads the mesh, and the split is the
    strategy's.  dp=2 x tp=2 under ``DataParallel``: no all-reduce sits in
    the recomputed layer with or without the name (nothing crosses 'tp'),
    yet the lowered step differs from the parent's, the out-projection's
    result held as one more residual a layer."""
    def texts():
        return _tp_grad(
            "gpt", {"dp": 2, "tp": 2}, strategy=DataParallel,
            use=lambda grad, p: (grad.lower(p).as_text(),
                                 grad.lower(p).compile().as_text()))

    def recomputed_reductions(compiled):
        return [line for line in compiled.splitlines()
                if " all-reduce" in line and "rematted_computation" in line]

    lowered, compiled = texts()
    assert not recomputed_reductions(compiled)
    _unnamed(monkeypatch)
    parent_lowered, parent_compiled = texts()
    assert not recomputed_reductions(parent_compiled)
    assert lowered != parent_lowered


def test_a_named_value_exports_as_an_identity(tmp_path):
    """``checkpoint_name`` leaves a ``name`` equation in every jaxpr of the
    attention layer, inference too: both exporters carry it as an
    identity."""
    from hetu_tpu import onnx as honnx
    from hetu_tpu.layers.attention import MultiHeadAttention

    layer = MultiHeadAttention(16, 2, attention_impl="xla")
    v = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))

    def fn(x):
        return layer.apply(v, x)[0]

    assert "name" in {e.primitive.name for e in jax.make_jaxpr(fn)(x).eqns}
    honnx.export_onnx(fn, (x,), tmp_path / "mha.onnx")
    np.testing.assert_allclose(
        np.asarray(honnx.import_onnx(tmp_path / "mha.onnx")[0](x)),
        np.asarray(fn(x)), rtol=2e-4, atol=2e-5)

    def named(x):
        return jax.ad_checkpoint.checkpoint_name(x * 2.0, "hetu.tp.reduced")

    path = honnx.export_graph(named, (x,), tmp_path / "named.json")
    np.testing.assert_allclose(
        np.asarray(honnx.import_graph(path)(x)), np.asarray(x) * 2.0)

