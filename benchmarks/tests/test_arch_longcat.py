"""LongCat-Flash's adapter (``benchmarks/arch/longcat_flash.py``): its counts
against numbers written out by hand, the blockwise reference against the
whole one, the new cell through ``run.py`` at rehearsal widths, and paths of
a lower precision than the configuration states failing its limits."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, check, spec

CELL = "longcat-flash-omni.batch-long"


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), "longcat-flash-omni")
    return config, spec.adapter(config)


def tiny():
    config = spec.config(spec.manifest(), "longcat-flash-omni",
                         rehearse=True)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """6144 wide, 64 heads, ranks 1536 / 512, heads of 128 | 64 and 128,
    dense FFN 12288, experts of 2048, a router of 768; 4 double layers, 16
    experts and 16384 rows of the vocabulary held."""
    config, adapter = full
    attention = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                 + 512 * 64 * 256 + 8192 * 6144)
    assert adapter.attention_params(config) == attention == 90_570_752
    dense = 2 * attention + 2 * 3 * 6144 * 12288 + 6144 * 768
    assert adapter.dense_layer_params(config) == dense == 638_844_928
    assert adapter.expert_params(config) == 3 * 6144 * 2048 == 37_748_736
    head = 16384 * 6144
    norms = 4 * 6144 + 2 * (1536 + 512)
    layer = dense + 16 * 37_748_736 + norms + 768
    assert layer == 1_242_854_144
    assert adapter.total_params(config) == 4 * layer + 2 * head + 6144 \
        == 5_172_749_312
    assert adapter.cache_bytes_per_token(config) == 8 * 576 * 2 == 9216
    # a decode step reads the dense weights of four layers and the head
    # once, and 9216 B a cached token; the experts hit are left out
    assert adapter.decode_step_bytes(config, 0) \
        == 2 * (4 * 638_844_928 + 100_663_296) == 5_312_086_016
    assert adapter.decode_step_bytes(config, 50_000) \
        == 5_312_086_016 + 9216 * 50_000
    # 12 choices over 768, 16 held: a quarter of an expert a token a layer
    assert adapter.expected_held_pairs(config) == 0.25
    per_token = 4 * (dense + 0.25 * 37_748_736) + head
    assert adapter.decode_step_flops(config, 16, 1000) \
        == 2 * per_token * 16 + 8 * 2 * 64 * (2 * 512 + 64) * 1000
    assert adapter.train_flops_per_token(config, 1024) \
        == 6 * per_token + 8 * 3 * 64 * 320 * 1024
    assert adapter.id_range(config) == (0, 16384)
    assert adapter.positions(config) == 8832
    assert adapter.attention_call_shape(config, {"batch": 2, "seq": 64}) \
        == (2, 64, 64, 192)


def test_the_program_holds_what_the_adapter_counts(full):
    config, adapter = full
    model = adapter.make_model(config, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    assert sum(int(np.prod(a.shape)) for _, a in leaves) \
        == adapter.total_params(config)
    for path, a in leaves:
        want = jnp.float32 if "router" in str(path) else jnp.bfloat16
        assert a.dtype == want, path
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for _, a in leaves)
    assert held == 10_383_253_504
    assert model.c.held == (176, 16) and model.kv_cache_spec().num_layers == 8
    with pytest.raises(ValueError, match="no 'train' section"):
        adapter.make_model(config, "train")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every number of the source's config under the same key, but for the
    keys listed as reduced; no width among them."""
    config, _ = full
    source = {"vocab_size": 131072, "hidden_size": 6144,
              "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
              "num_layers": 28, "num_attention_heads": 64,
              "kv_lora_rank": 512, "q_lora_rank": 1536,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "qk_nope_head_dim": 128, "routed_scaling_factor": 6,
              "n_routed_experts": 512, "max_position_embeddings": 131072,
              "rms_norm_eps": 1e-05, "rope_theta": 10000000,
              "zero_expert_num": 256, "moe_topk": 12}
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs == {"num_layers", "n_routed_experts", "vocab_size"}
    assert differs | {"serve.max_len"} == set(config["reduced"])
    dep = config["deployment"]
    assert (dep["n_routed_experts_published"], dep["num_layers_published"],
            dep["vocab_size_published"]) == (512, 28, 131072)


def test_the_blockwise_reference_is_the_whole_reference(monkeypatch):
    config, adapter = tiny()
    model = adapter.make_model(config, "serve")
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 500, (2, 40)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    blocks = adapter.reference_logits(params, ids, config)
    assert blocks.dtype == np.float32 and blocks.shape == (2, 40, 500)
    np.testing.assert_allclose(blocks, whole, rtol=2e-5, atol=2e-5)
    # two heads at a time, as an 8,000-token comparison forces
    monkeypatch.setattr(adapter, "SCORES_BYTES", 4 * 2 * 2 * 40 * 40)
    np.testing.assert_allclose(
        adapter.reference_logits(params, ids, config), whole, rtol=2e-5,
        atol=2e-5)


def test_the_new_cell_runs_through_run_py_and_is_correct(capsys,
                                                         monkeypatch):
    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):   # --rehearse sets them
        monkeypatch.setenv(var, os.environ.get(var, ""))
    rc = bench.main(["--workload", CELL, "--seconds", "1", "--seed",
                     "3000000019", "--rehearse"])
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] and line["failed"] == 0, err
    assert line["detail"]["compiles_in_window"] == 0
    assert set(line["detail"]["check"]["limits"]) == {"logit_err",
                                                      "token_gap"}
    assert line["metric_names"] == ["serve_tokens_per_s", "setup_s"]


def test_bfloat16_where_float32_is_stated_fails_at_float32_tolerance():
    """The comparison's teeth at rehearsal widths, where float32 is stated:
    the same weights computed in bfloat16 miss by two orders of magnitude
    what two float32 orders of operation differ by.  (The 8-bit reading
    against the bfloat16 the full configuration states is taken on the chip:
    ``benchmarks/tools/check_seeds.py --control``, PERF.md.)"""
    ids = np.random.default_rng(0).integers(0, 500, (3, 48)).astype(np.int32)
    errs = {}
    for dtype in ("float32", "bfloat16"):
        config, adapter = tiny()
        params = jax.jit(adapter.make_model(config, "serve").init)(
            build.key_for(3))["params"]
        model = adapter.make_model({**config, "compute_dtype": dtype},
                                   "serve")
        ref = adapter.reference_logits(params, ids, config)
        got = adapter.system_logits(model, params, ids)
        assert ref.shape == got.shape == (3, 48, 500)
        errs[dtype] = float(np.max(np.abs(ref - got)) / (ref.max() - ref.min()))
    assert errs["float32"] < 1e-4 < 1e-3 < errs["bfloat16"], errs


def test_a_wrong_cache_read_fails_the_serving_check(monkeypatch):
    config, adapter = tiny()
    model = adapter.make_model(config, "serve")
    variables = jax.jit(model.init)(build.key_for(7))
    engine, scheduler = build.make_serving(model, variables, config)
    assert check.serving(model, variables, engine, scheduler, config, 7)["ok"]
    engine, scheduler = build.make_serving(model, variables, config)
    absorbed = model.attn.absorbed
    monkeypatch.setattr(
        model.attn, "absorbed",
        lambda p, q_n, q_r, c, r, lengths: absorbed(
            p, q_n, q_r, c, r, jnp.maximum(lengths - 16, 0)))
    broken = check.serving(model, variables, engine, scheduler, config, 7)
    assert not broken["ok"]
    assert broken["token_gap"] > 3 * broken["limits"]["token_gap"]


# ------------------------------- the time shares over recorded operations

SHARES = {"hetu.mla.": "mla_time_share", "hetu.moe.": "moe_time_share",
          "hetu.ffn.dense": "dense_ffn_time_share"}


def _recorded():
    """(share of busy time in %, the event's whole name, its scope) of the
    400 operations with the most own time in a traced run of the cell on
    the v5e (PR 53, call 1, seed 5300000021: 99.8% of the chip's busy
    time), the scope by the ``op_name`` of the instruction with the same
    result types, op kind and operand count in the chunk and decode
    programs compiled for a described v5e over the tree the engine holds
    (every chunk bucket, the decode programs of 16 and 8 slots at every
    page bucket): ``outside`` where the model computes it outside its
    scopes (the attention's projections), ``a|b`` where instructions of two
    scopes share that key, ``?`` where none has it."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "longcat_batch_long_ops.txt")
    for line in open(path):
        share, scope, name = line.rstrip("\n").split("\t")
        yield float(share), name, scope.split("|")


def test_the_time_share_patterns_take_their_own_scopes_and_no_other():
    """``mla_time_share.batch-long`` was re-pointed in PR 53 at what a chunk's
    attention runs since PR 52: the flash kernel's chunk call, the rebuild's
    ``write_rows`` and ``unwritten`` calls BY NAME, and the head-major K and
    V blocks, the queries padded to 256 lanes and the carried arrays by
    shape.  Over the recorded operations: none is counted in two shares
    (the layer scans' ``while`` containers aside: their text holds every
    shape and their own time is loop glue, 0.03% of busy time); one whose
    instruction carries ANOTHER share's scope is not taken; under 1% of
    busy time inside ``hetu.mla.*`` is missed; the attention's share is a
    third of busy time where the stale pattern read a fifth."""
    import re

    rx = {m: re.compile(spec.layer_metric_file(f"{m}.batch-long")
                        ["params"]["pattern"]) for m in SHARES.values()}
    taken, missed, glue = dict.fromkeys(rx, 0.0), 0.0, 0.0
    by_name = {}
    for share, name, scopes in _recorded():
        hit = {m for m in rx if rx[m].search(name)}
        if name.startswith("%while") and len(hit) > 1:   # a layer scan
            glue += share
            continue
        assert len(hit) < 2, (hit, name)
        own = {m for s in scopes for pre, m in SHARES.items()
               if s.startswith(pre)}
        if own:
            assert hit <= own, (hit, scopes, name)
        if own == {"mla_time_share"} and not hit:
            missed += share
        for m in hit:
            taken[m] += share
        kernel = re.match(r"%(_flash_chunk|write_rows|unwritten)", name)
        if kernel:
            assert hit == {"mla_time_share"}, name
            by_name[kernel.group(1)] = by_name.get(kernel.group(1), 0) + share
    assert glue < 0.1 and missed < 1.0, (glue, missed)
    assert 8.0 < by_name["_flash_chunk"] < 9.0       # two calls a double layer
    assert 2.5 < by_name["write_rows"] < 4.0      # ``unwritten`` has no body
    assert 33.0 < taken["mla_time_share"] < 36.0, taken
    assert 20.0 < taken["moe_time_share"] < 25.0, taken
    assert 25.0 < taken["dense_ffn_time_share"] < 29.0, taken
