"""LFM2-MoE's adapter (``benchmarks/arch/lfm2_moe.py``): its counts against
numbers written out by hand, the configuration against the catalog's row,
the traffic against the issue's, the piecewise reference against the whole
one, the new cell through ``run.py`` at rehearsal widths, and the four
``*_time_share`` patterns against instruction names of each scope."""

import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, schedule, spec

CELL = "lfm2-8b-a1b.batch-docs"
NAME = "lfm2-8b-a1b"


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """2048 wide, 32 query and 8 KV heads of 64, dense FFN 7168, 32 experts
    of 1792, three taps; 13 layers: one dense, 10 conv and 3 attention."""
    config, adapter = full
    w = adapter.widths(config)
    assert w["layer_types"] == ("conv",) + 3 * ("full_attention", "conv",
                                                "conv", "conv")
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert adapter.conv_params(config) == conv == 16_783_360
    attention = 2048 * (2048 + 2 * 512) + 2048 * 2048
    assert adapter.attention_params(config) == attention == 10_485_760
    assert adapter.expert_params(config) == 3 * 2048 * 1792 == 11_010_048
    dense = 10 * conv + 3 * attention + 3 * 2048 * 7168 + 12 * 2048 * 32
    assert adapter.dense_params(config) == dense == 244_117_504
    head = 65536 * 2048
    norms = 13 * 2 * 2048 + 3 * 2 * 64 + 2048
    total = dense + 12 * 32 * (11_010_048 + 1) + head + norms
    assert adapter.total_params(config) == total == 4_606_249_728
    assert adapter.cache_bytes_per_token(config) == 2 * 8 * 64 * 2 == 2048
    assert adapter.state_bytes_per_slot(config) == 2 * 2048 * 2 == 8192
    # of 32 experts a layer, 64 tokens of 4 choices miss 32 x (7/8)^64
    hit = 32 * (1 - 0.875 ** 64)
    assert adapter.experts_hit(config) == pytest.approx(hit) \
        and 31.99 < hit < 32
    weights = 2 * (dense + head + 12 * hit * 11_010_048)
    state = 8192 * 10 * 64
    assert adapter.decode_step_bytes(config, 0) \
        == pytest.approx(weights + state)
    assert adapter.decode_step_bytes(config, 150_000) \
        == pytest.approx(weights + state + 3 * 2048 * 150_000)
    per_token = dense + head + 12 * 4 * 11_010_048
    assert adapter.token_matmul_params(config) == per_token == 906_817_536
    assert adapter.decode_step_flops(config, 64, 150_000) == pytest.approx(
        (2 * per_token + 2 * 3 * 2048 * 10) * 64
        + 2 * 32 * 128 * 3 * 150_000)
    assert adapter.id_range(config) == (0, 65536)
    assert adapter.positions(config) == 8192


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every key of the catalog row's config under the same key and value,
    but for the keys listed as reduced; no width among them."""
    config, _ = full
    kinds = ["conv", "conv"] + 4 * ["full_attention", "conv", "conv",
                                    "conv"] + ["full_attention", "conv",
                                               "conv", "full_attention",
                                               "conv", "conv"]
    source = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
              "intermediate_size": 7168, "layer_types": kinds,
              "max_position_embeddings": 128000, "model_type": "lfm2_moe",
              "moe_intermediate_size": 1792, "norm_eps": 1e-05,
              "norm_topk_prob": True, "num_attention_heads": 32,
              "num_dense_layers": 2, "num_experts": 32,
              "num_experts_per_tok": 4, "num_hidden_layers": 24,
              "num_key_value_heads": 8, "rope_theta": 1000000,
              "routed_scaling_factor": 1, "use_expert_bias": True,
              "vocab_size": 65536}
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "num_dense_layers"}
    assert differs | {"serve.max_len"} == set(config["reduced"]) \
        == set(config["reduced_why"])
    dep = config["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["num_experts_held"],
            dep["first_layer_run"]) == (1, 32, 1)
    assert {"tie_word_embeddings", "conv_split", "renorm_eps",
            "router_bias_std", "init_std"} <= set(config["assumed"])
    assert (config["serve"]["num_slots"], config["serve"]["max_len"],
            config["serve"]["page_size"]) == (64, 8192, 128)


def test_the_traffic_is_the_issues():
    tr = spec.traffic("batch-docs")
    assert (tr["kind"], tr["pool_requests"], tr["schedule_seed"],
            tr["queue_depth_slots"], tr["trace_s"],
            tr["warmup_finished_requests"]) == ("backlog", 512, 43, 2, 3.0,
                                                16)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                "sigma": 1.0, "min": 128, "max": 7680}
    assert tr["output_len"] == {"dist": "lognormal", "median": 192,
                                "sigma": 0.8, "min": 16, "max": 512}
    lengths = schedule.backlog_lengths(tr)
    prompts = np.array([p for p, _ in lengths])
    answers = np.array([o for _, o in lengths])
    assert 2200 < prompts.mean() < 2400 and 220 < answers.mean() < 250
    # no request of the fixed pool runs past the 8192 positions served
    assert max(p + o for p, o in lengths) <= 8192


def test_the_piecewise_reference_is_the_whole_reference(monkeypatch):
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    adapter = spec.adapter(config)
    model = adapter.make_model(config, "serve")
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 504, (2, 45)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    monkeypatch.setattr(adapter, "ROWS", 16)     # the last block a short one
    pieces = adapter.reference_logits(params, ids, config)
    assert pieces.dtype == np.float32 and pieces.shape == (2, 45, 504)
    np.testing.assert_allclose(pieces, whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(adapter.system_logits(model, params, ids),
                               whole, rtol=2e-4, atol=2e-4)


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


# instruction names as the device trace has them (an ``XLA Ops`` event is
# the HLO instruction with its operands' types and without its metadata),
# cut short, from a traced run of the cell on the v5e (``tools/top_ops.py``,
# PR 43); the scope of each by the same instruction's ``op_name`` in the
# program compiled for a described v5e (``tools/compile_v5e_state.py --hlo``)
NAMES = {
    "moe_time_share": [
        "%fusion.1618 = f32[128,2048]{1,0:T(8,128)S(1)} fusion(bf16[128,1792]"
        "{1,0:T(8,128)(2,1)S(1)} %fusion.1615, bf16[12,32,1792,2048]{3,2,1,0",
        "%fusion.1614 = bf16[128,1792]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[128"
        ",2048]{1,0:T(8,128)(2,1)S(1)} %fusion.1613, bf16[12,32,2048,1792]{3",
        "%sort.12 = (s32[4096]{0:T(1024)}, s32[4096]{0:T(1024)}) sort(",
        "%fusion.77 = (f32[64,32]{1,0:T(8,128)}, f32[64,32]{1,0}) fusion("],
    "dense_ffn_time_share": [
        "%fusion.466 = bf16[1024,7168]{1,0:T(8,128)(2,1)} fusion(bf16[1,2048,"
        "7168]{2,1,0:T(8,128)(2,1)S(1)} %copy-done, bf16[1024,2048]{1,0:T(8",
        "%fusion.567 = (f32[1024]{0:T(1024)S(1)}, bf16[1024,2048]{1,0:T(8,128"
        ")(2,1)S(1)}, bf16[1024,2048]{1,0}) fusion(bf16[1024,2048]{1,0} %get-"
        "tuple-element.2627, bf16[1024,7168]{1,0} %fusion.529, bf16[1,7168,"
        "2048]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.126), kind=kOutput"],
    "conv_time_share": [
        "%fusion.532 = bf16[1,1024,6144]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16"
        "[10,2048,6144]{2,1,0:T(8,128)(2,1)} %params__layers____conv____in__",
        "%fusion.40 = bf16[10,65,2,2048]{3,2,1,0:T(2,128)(2,1)} fusion(",
        "%fusion.41 = (f32[64]{0}, bf16[64,2048]{1,0}) fusion(bf16[64,2048]"
        "{1,0} %fusion.39, bf16[10,2048,2048]{2,1,0} %get-tuple-element.9)"],
    "attn_full_time_share": [
        "%_attend.1 = bf16[64,32,512]{2,1,0:T(8,128)(2,1)S(1)} custom-call("
        "s32[1]{0:T(128)} %constant.779.clone.1, s32[64]{0:T(128)S(1)} %copy",
        "%fusion.1691 = (f32[8,4,1024]{2,1,0:T(4,128)S(1)}, f32[8,4,1024,1024"
        "]{2,3,1,0:T(8,128)}) fusion(bf16[1,8192,8,64]{1,3,2,0:T(8,128)(2,1)",
        "%fusion.5 = bf16[3,4097,128,512]{3,2,1,0:T(8,128)(2,1)} fusion("],
}


@pytest.mark.parametrize("metric", sorted(NAMES))
def test_a_time_share_pattern_takes_its_own_scope_and_no_other(metric):
    rx = {m: re.compile(spec.layer_metric_file(f"{m}.batch-docs")
                        ["params"]["pattern"]) for m in NAMES}
    for name in NAMES[metric]:
        assert [m for m in sorted(NAMES) if rx[m].search(name)] == [metric], \
            name


def test_no_recorded_operation_is_counted_in_two_shares():
    """The 400 operations with the most own time in a traced run of the
    cell on the v5e (``tools/top_ops.py 400``, PR 43, call 3: 92% of the
    chip's busy time; ``data/lfm2_batch_docs_ops.txt``: share of busy time
    in %, occurrences, the event's whole name): none is matched by two of
    the four patterns, every pattern matches some, and what no pattern
    takes (the norms between layers, the head, the embedding) is under 2%
    of busy time.  The patterns read result and operand SHAPES: the trace's
    event name is the instruction without its metadata, so an ``op_name``
    scope is not there to anchor on."""
    rx = {m: re.compile(spec.layer_metric_file(f"{m}.batch-docs")
                        ["params"]["pattern"]) for m in NAMES}
    path = os.path.join(os.path.dirname(__file__), "data",
                        "lfm2_batch_docs_ops.txt")
    taken, nowhere = dict.fromkeys(rx, 0.0), 0.0
    for line in open(path):
        share, _, name = line.rstrip("\n").split("\t")
        hit = [m for m in sorted(rx) if rx[m].search(name)]
        assert len(hit) < 2, (hit, name)
        for m in hit:
            taken[m] += float(share)
        nowhere += 0.0 if hit else float(share)
    assert all(taken.values()), taken
    assert taken["moe_time_share"] > 75.0 and nowhere < 2.0, (taken, nowhere)
