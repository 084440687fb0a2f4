"""``reference/gpt2.py`` against the program at tiny widths on the CPU,
through GPT-2's adapter (``benchmarks/arch/gpt2.py``), which is how the
harness reaches both: ``GPTModel`` on logits, loss and gradients, and
prefill + decode through ``PagedServeEngine`` against the reference's full
forward.

Tolerances: both sides compute in float32 here, so they differ only by the
order of operations: 1e-4 of the logits' range, 1e-5 relative on the loss,
1e-4 of the largest gradient entry per leaf.  A float32 program that skipped
a term (a bias, the mask, a LayerNorm's epsilon) lands orders of magnitude
outside."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.arch import gpt2 as adapter
from benchmarks.harness import build, check, spec

HEADS = 4
# the keys of a GPT-2 configuration, at tiny widths and in float32
CONFIG = {"n_embd": 64, "n_head": HEADS, "n_layer": 3, "n_positions": 128,
          "n_inner": 128, "vocab_size": 500,
          "assumed": {"embedding_rows": 512}, "compute_dtype": "float32",
          "adapter": "benchmarks.arch.gpt2",
          "reference": "benchmarks/reference/gpt2.py",
          "train": {"attention_impl": "xla", "fused_ce": False,
                    "remat": False},
          "serve": {"attention_impl": "xla", "num_slots": 4, "max_len": 128,
                    "page_size": 16, "prefill_chunk": 32}}
reference = adapter.reference(CONFIG)
TOL = {k: t["limit"] for k, t in adapter.tolerances(CONFIG).items()}


def _model(dtype="float32", **train):
    config = {**CONFIG, "compute_dtype": dtype,
              "train": {**CONFIG["train"], **train}}
    return adapter.make_model(config, "train" if train else "serve")


@pytest.fixture(scope="module")
def setup():
    model = _model()
    variables = model.init(jax.random.PRNGKey(3))
    # biases and LayerNorm parameters start at 0 and 1: perturb them, or a
    # reference that dropped one would still agree
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape) for a, k in zip(leaves,
                                                                 keys)])
    ids = np.random.default_rng(0).integers(0, 500, (3, 48)).astype(np.int32)
    return model, {"params": params, "state": {}}, ids


def test_logits_match_the_model(setup):
    model, variables, ids = setup
    ref = adapter.reference_logits(variables["params"], ids, CONFIG)
    got = adapter.system_logits(model, variables["params"], ids)
    assert ref.dtype == got.dtype == np.float32
    assert ref.shape == got.shape == (3, 48, 512)
    assert np.max(np.abs(ref - got)) <= 1e-4 * (ref.max() - ref.min())


@pytest.mark.parametrize("attention_impl,fused_ce,remat", [
    ("xla", False, False), ("xla", True, True), ("flash", True, True)])
def test_loss_and_gradients_match_the_model(setup, attention_impl, fused_ce,
                                            remat):
    _, variables, _ = setup
    ids = np.random.default_rng(1).integers(0, 500, (2, 64)).astype(np.int32)
    model = _model(attention_impl=attention_impl, fused_ce=fused_ce,
                   remat=remat)
    loss_fn = model.lm_loss_fn()
    got_loss, got = jax.value_and_grad(lambda p: loss_fn(
        p, {}, (jnp.asarray(ids),), jax.random.PRNGKey(0), True)[0])(
            variables["params"])
    ref_loss, ref = reference.loss_and_grads(variables["params"], ids, HEADS)
    assert abs(float(got_loss) - float(ref_loss)) \
        <= 1e-5 * abs(float(ref_loss))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ref),
                            jax.tree_util.tree_leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-4 * np.max(np.abs(a)) + 1e-9, \
            jax.tree_util.keystr(path)
    # the remat form the chip check uses changes no number
    loss2, norm2 = adapter.reference_loss_and_grad_norm(
        variables["params"], ids, CONFIG)
    assert loss2 == pytest.approx(float(ref_loss), rel=1e-6)
    assert norm2 == pytest.approx(float(reference.global_norm(ref)),
                                  rel=1e-5)
    assert norm2 == pytest.approx(float(check.global_norm(got)), rel=1e-4)


def test_prefill_and_decode_through_the_paged_engine(setup):
    """The comparison the benchmark makes on the chip, at tiny widths:
    chunked prefill (several chunks) and eight decoded tokens through the
    page tables, four requests in flight, against the reference's full
    forward."""
    model, variables, _ = setup
    engine, scheduler = build.make_serving(model, variables, CONFIG)
    verdict = check.serving(model, variables, engine, scheduler, CONFIG, 7)
    assert verdict["ok"], verdict
    assert verdict["limits"] == {k: TOL[k] for k in ("logit_err",
                                                     "token_gap")}
    assert verdict["prompts"][0] == 24 and max(verdict["prompts"]) > 64
    # float32 on both sides: far inside the bf16 tolerances of the chip run
    assert verdict["logit_err"] < 1e-4
    assert verdict["token_gap"] < 1e-4


def test_a_wrong_cache_read_fails_the_check(setup, monkeypatch):
    """The serving check has teeth: an engine whose decode reads one page
    too few of its cache emits tokens the reference ranks far from best."""
    model, variables, _ = setup
    engine, scheduler = build.make_serving(model, variables, CONFIG)
    inner = model.decode_with_cache
    monkeypatch.setattr(
        model, "decode_with_cache",
        lambda v, ids, k, vv, lengths: inner(
            v, ids, k, vv, jnp.maximum(lengths - 16, 0)))
    verdict = check.serving(model, variables, engine, scheduler, CONFIG, 7)
    assert not verdict["ok"] and verdict["token_gap"] > TOL["token_gap"]


def test_bf16_where_f32_is_stated_fails_at_f32_tolerance(setup):
    """What 'tight enough' means: the model run in bfloat16 misses the
    float32 tolerance of this file by two orders of magnitude, and sits
    inside the chip check's bfloat16 tolerance."""
    _, variables, ids = setup
    low = _model("bfloat16")
    ref = adapter.reference_logits(variables["params"], ids, CONFIG)
    got = adapter.system_logits(low, variables["params"], ids)
    err = np.max(np.abs(ref - got)) / (ref.max() - ref.min())
    assert 1e-3 < err < TOL["logit_err"]


def test_rehearsal_widths_are_never_the_published_ones():
    man = spec.manifest()
    for c in man["configs"]:
        full = spec.config(man, c["name"])
        tiny = spec.config(man, c["name"], rehearse=True)
        assert tiny["n_embd"] < full["n_embd"] and tiny["n_embd"] <= 64
