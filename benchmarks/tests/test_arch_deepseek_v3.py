"""The ``deepseek_v3`` adapter and the trained cell
``kanana-2-30b-a3b-instruct-2601.train-ep8``: the adapter's counts against
arithmetic by hand at the published widths, the configuration against the
catalog's numbers, the new readers on a trace recorded here, the training
control, and the cell's rehearsal through ``run.py``."""

import importlib
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, check, spec

NAME = "kanana-2-30b-a3b-instruct-2601"
CELL = NAME + ".train-ep8"
# the catalog row's ``config``, as published (model-configs guide)
PUBLISHED = json.loads("""{"attention_bias": false,
 "first_k_dense_replace": 1,
 "head_dim": 64,
 "hidden_act": "silu",
 "hidden_size": 2048,
 "intermediate_size": 6144,
 "kv_lora_rank": 512,
 "max_position_embeddings": 32768,
 "model_type": "deepseek_v3",
 "moe_intermediate_size": 768,
 "moe_layer_freq": 1,
 "n_group": 1,
 "n_routed_experts": 128,
 "n_shared_experts": 2,
 "norm_topk_prob": true,
 "num_attention_heads": 32,
 "num_experts_per_tok": 6,
 "num_hidden_layers": 48,
 "num_key_value_heads": 32,
 "q_lora_rank": null,
 "qk_head_dim": 192,
 "qk_nope_head_dim": 128,
 "qk_rope_head_dim": 64,
 "rms_norm_eps": 1e-06,
 "rope_interleave": true,
 "rope_scaling": null,
 "rope_theta": 1000000,
 "routed_scaling_factor": 2.448,
 "scoring_func": "sigmoid",
 "tie_word_embeddings": false,
 "topk_group": 1,
 "topk_method": "noaux_tc",
 "v_head_dim": 128,
 "vocab_size": 128256}""")
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
          "blob/main/config.json")


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def tiny():
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """2048 wide, 32 heads of 128 + 64 | 128, kv rank 512, dense FFN 6144,
    experts of 768, two shared, 16 of 128 held, 5 layers, 16,032 rows."""
    config, a = full
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert a.attention_params(config) == attn == 26_345_472
    assert a.expert_params(config) == 3 * 2048 * 768 == 4_718_592
    dense = 5 * attn + 3 * 2048 * 6144 + 4 * (2 * 4_718_592 + 2048 * 128)
    assert a.dense_params(config) == dense
    norms = 5 * (2 * 2048 + 512) + 2048
    total = dense + 4 * 16 * 4_718_592 + 2 * 16_032 * 2048 + norms
    assert a.total_params(config) == total == 575_955_456
    # a layer with its norms, as the issue's arithmetic has it
    assert attn + 512 + 2 * 2048 == 26_350_080
    assert 26_350_080 + 262_144 + 9_437_184 + 16 * 4_718_592 == 111_546_880
    assert a.expected_held_pairs(config) == 6 * 16 / 128
    token = dense + 16_032 * 2048 + 4 * 0.75 * 4_718_592
    assert a.token_matmul_params(config) == token == 255_262_720
    flops = 6 * token + 3 * 32 * 8192 * (192 + 128) * 5
    assert a.train_flops_per_token(config, 8192) == flops
    assert 45.6e12 < flops * 2 * 8192 < 45.8e12          # a step
    assert a.attention_call_shape(config, {"batch": 2, "seq": 8192}) \
        == (2, 32, 8192, 192)
    assert a.attention_call_widths(config) == (192, 128)
    assert a.cache_bytes_per_token(config) == (512 + 64) * 2
    assert a.decode_step_bytes(config, 1000) == \
        2 * (dense + 16_032 * 2048) + 1152.0 * 5 * 1000
    assert a.decode_step_flops(config, 4, 1000) == \
        2.0 * token * 4 + 2.0 * 32 * (2 * 512 + 64) * 5 * 1000
    assert a.id_range(config) == (0, 16_032)
    assert a.positions(config) == 32_768


def test_the_flash_counts_at_two_widths(full):
    reader = importlib.import_module("benchmarks.readers.flash_roofline_widths")
    ops = reader.call_flops(2, 32, 8192, 192, 128)
    assert ops["fwd"] == 2 * 32 * 8192 ** 2 * 320          # 2 S^2 (..) / 2
    assert ops["bwd"] == 2 * 32 * 8192 ** 2 * (3 * 192 + 2 * 128)
    byt = reader.call_bytes(2, 32, 8192, 192, 128)
    assert byt["fwd"] == 2 * 32 * 8192 * 2 * (2 * 192 + 2 * 128)
    assert byt["bwd"] == 2 * 32 * 8192 * 2 * (4 * 192 + 4 * 128)
    # equal widths: the stock counts
    from benchmarks.harness import flops
    assert reader.call_flops(4, 12, 1024, 64, 64) == \
        flops.flash_call_flops(4, 12, 1024, 64)
    assert reader.call_bytes(4, 12, 1024, 64, 64) == \
        flops.flash_call_bytes(4, 12, 1024, 64)


def test_the_program_holds_what_the_adapter_counts(full):
    config, a = full
    model = a.make_model(config, "train")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    held = sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == a.total_params(config)
    assert {x.dtype for x in jax.tree_util.tree_leaves(shapes["params"])} \
        == {jnp.dtype("float32")}
    assert shapes["state"]["router_bias"].shape == (4, 128)
    assert shapes["params"]["sparse"]["moe"]["gate"].shape \
        == (4, 16, 2048, 768)
    with pytest.raises(ValueError, match="no 'serve' section"):
        a.make_model(config, "serve")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    config, _ = full
    row = {"config": PUBLISHED}
    assert config["source"] == SOURCE
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16_032)
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] * config["n_routed_experts"] \
        == dep["n_routed_experts_published"] \
        == row["config"]["n_routed_experts"]
    assert dep["vocab_size_published"] == row["config"]["vocab_size"] \
        == 8 * config["vocab_size"]
    assert "serve" not in config and "TRAINING ONLY" in config["scope"]
    assert config["assumed"]["bias_update_rate"] == 0.001


def test_the_traffic_is_the_issues():
    man = spec.manifest()
    cell = spec.cell(man, CELL)
    tr = spec.traffic(cell["traffic"])
    assert (cell["chips"], tr["kind"], tr["batch"], tr["seq"]) \
        == (1, "train_steps", 2, 8192)
    assert (tr["distinct_batches"], tr["lookahead_steps"],
            tr["check_sequences"]) == (8, 1, 1)
    names = {m["name"] for m in spec.metrics_of(man["per_layer"], CELL)}
    # the issue's list, with what PRs 36 and 42 added for every train cell
    # and without ``moe_block_fill``, which PR 53 retired: it divided by
    # blocks no program of this cell has run since PR 42
    assert names == {
        "train_step_ms", "train_dispatch_ms", "train_program_ms", "mfu_pct",
        "device_idle_share.train", "hbm_heap_gb.train", "hbm_stack_gb.train",
        "flash_roofline.train-ep8", "flash_time_share.train-ep8",
        "mla_time_share.train-ep8", "moe_time_share.train-ep8",
        "moe_rows_per_hit_expert.train-ep8", "moe_grouped_share.train-ep8",
        "moe_gmm_time_share.train-ep8"}
    assert {m["name"] for m in spec.metrics_of(man["end_to_end"], CELL)} \
        == {"train_tokens_per_s", "setup_s"}


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
    assert set(line["detail"]["check"]["limits"]) == {"loss_rel",
                                                      "grad_norm_rel"}
    assert line["metric_names"] == ["setup_s", "train_tokens_per_s"]


def test_the_moe_readers_read_the_trainers_instant(tmp_path):
    """``moe_rows_per_hit_expert`` and ``moe_grouped_share`` off a trace
    recorded here: the ids of ``train.moe``; a trace without them (GPT-2's
    trainer, the parent's) gives nothing."""
    import hetu_tpu as ht
    from hetu_tpu import optim

    def trace_of(config, where):
        arch = spec.adapter(config)
        model = arch.make_model(config, "train")
        ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3))
        state = ex.init_state(jax.jit(model.init)(build.key_for(7)))
        low, high = arch.id_range(config)
        ids = np.random.default_rng(5).integers(low, high, (2, 64)).astype(
            np.int32)
        with jax.profiler.trace(str(where)):
            for _ in range(4):
                state, metrics = ex.run("train", state, (ids,))
                jax.block_until_ready(metrics)
        return str(sorted(where.glob("plugins/profile/*/*.xplane.pb"))[-1])

    def read(name, path):
        f = spec.layer_metric_file(name)
        reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
        return reader.read(SimpleNamespace(run=SimpleNamespace(
            trace_path=path)), **f["params"])

    config, _ = tiny()
    path = trace_of(config, tmp_path / "experts")
    # 2 x 64 tokens x 4 choices, 4 of 16 held: about 128 pairs a layer on 4
    # experts, every one of them computed by the grouped path
    rows = read("moe_rows_per_hit_expert.train-ep8", path)
    assert 10.0 < rows < 80.0
    assert read("moe_grouped_share.train-ep8", path) == 100.0
    gpt = spec.config(spec.manifest(), "gpt2-small", rehearse=True)
    path = trace_of(gpt, tmp_path / "dense")
    for name in ("moe_rows_per_hit_expert.train-ep8",
                 "moe_grouped_share.train-ep8"):
        assert read(name, path) is None
        assert read(name, None) is None


def test_the_flash_reader_leaves_a_run_without_a_trace_alone(full):
    config, _ = full
    f = spec.layer_metric_file("flash_roofline.train-ep8")
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    assert f["reader"] == "flash_roofline_widths"
    assert reader.read(SimpleNamespace(trace=None, peaks=None),
                       **f["params"]) is None
    none = SimpleNamespace(kernel_events=lambda pattern: [])
    assert reader.read(SimpleNamespace(trace=none, peaks={"bf16_flops": 1}),
                       **f["params"]) is None


def test_the_training_control_rounds_both_passes():
    """``benchmarks/tools/check_control_train.py``: the lower precision in
    the loss function's place moves loss and gradient norm by several times
    what bfloat16 as stated does, and its rounding reaches the backward
    pass (the cotangent of a rounded value is rounded)."""
    from benchmarks.tools import check_control_train as cct
    from benchmarks.tools.check_control import three_mantissa_bits as low

    x = jnp.linspace(-2.0, 2.0, 64).astype(jnp.bfloat16).reshape(8, 8)
    w = (jnp.arange(64.0).reshape(8, 8) / 41 - 0.7).astype(jnp.bfloat16)

    def f(x, w):
        return jnp.sum(jnp.tanh(x @ w).astype(jnp.float32))

    got = jax.grad(cct.lowered(f, True))(x, w)
    plain = jax.grad(f)(x, w)
    # the gradient came back through the rounding of the matmul's operand:
    # it holds three mantissa bits, and is near the unrounded one, not it
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(low(got), np.float32))
    assert not np.array_equal(np.asarray(got, np.float32),
                              np.asarray(plain, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(plain, np.float32), rtol=0.3,
                               atol=0.05)

    config, arch = tiny()
    config = {**config, "compute_dtype": "bfloat16"}
    model = arch.make_model(config, "train")
    params = jax.jit(model.init)(build.key_for(5))["params"]
    ids = np.random.default_rng(5).integers(0, 504, (1, 64)).astype(np.int32)
    stated = check.training(model, params, params, config, ids)
    control = check.training(cct._Lowered(model, True), params, params,
                             config, ids)
    assert control["loss_rel"] > 3 * stated["loss_rel"]
    assert control["grad_norm_rel"] > 2 * stated["grad_norm_rel"]
