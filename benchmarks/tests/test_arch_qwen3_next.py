"""Qwen3-Next's adapter (``benchmarks/arch/qwen3_next.py``): its counts
against ``jax.eval_shape`` of the program's ``init`` and against numbers
written out by hand, the configuration against the catalog's row, the
traffic against the issue's, the piecewise reference against the whole one,
the new cell through ``run.py`` at rehearsal widths, the manifest's entries,
and the ``gdn_time_share`` pattern against a recorded op list of the cell on
the chip."""

import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, schedule, spec

CELL = "qwen3-next-80b-a3b-instruct.batch-mixed"
NAME = "qwen3-next-80b-a3b-instruct"


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """2048 wide; DeltaNet 16 | 32 heads of 128, 4 taps; attention 16 | 2
    heads of 256 with a gate a head; 128 of 512 experts of 512, a gated
    shared one of 512; 8 layers = 6 + 2; a quarter of the vocabulary."""
    config, adapter = full
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048 + 192
    assert adapter.gdn_params(config) == gdn == 33_718_464
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512
    assert adapter.attention_params(config) == attention == 27_263_488
    outside = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048
    assert adapter.router_params(config) + adapter.shared_params(config) \
        + 4096 == outside == 4_200_448
    assert gdn + outside == 37_918_912 and attention + outside == 31_463_936
    expert = 3 * 2048 * 512
    assert adapter.expert_params(config) == expert == 3_145_728
    assert 128 * expert == 402_653_184
    head = 37_984 * 2048
    assert adapter.head_params(config) == head
    total = 6 * 37_918_912 + 2 * 31_463_936 + 8 * 128 * expert \
        + 2 * head + 2048
    assert adapter.total_params(config) == total == 3_667_251_328
    w = adapter.widths(config)
    assert (w["gdn_layers"], w["full_layers"], w["first"], w["held"],
            w["n_routed"], w["topk"]) == (6, 2, 128, 128, 512, 10)
    assert (w["qkvz_width"], w["conv_channels"]) == (12288, 8192)
    assert adapter.dims(config)["rotary_dim"] == 64
    assert adapter.cache_bytes_per_token(config) == 2 * 2 * 256 * 2 == 2048
    state = 3 * 8192 * 2 + 32 * 128 * 128 * 4
    assert adapter.state_bytes_per_slot(config) == state == 2_146_304
    assert 6 * state == 12_877_824
    # a round, at the least: the dense weights and the head once (the
    # routers float32), the cached rows of the 2 full layers, the rule's
    # matrix of all 49 rows of the 6 whole-layer updates read AND written;
    # no hit expert
    dense = 6 * (gdn - 4 * 8192 - 192) + 2 * (attention - 512) \
        + 8 * (outside - 4096)
    assert adapter.dense_matmul_params(config) == dense
    slots = config["serve"]["num_slots"]
    least = 2 * (dense + head) + 2 * 8 * 2048 * 512 \
        + 2 * 4 * 32 * 128 * 128 * 6 * (slots + 1)
    assert adapter.decode_step_bytes(config, 0) == pytest.approx(least)
    assert adapter.decode_step_bytes(config, 200_000) == pytest.approx(
        least + 2 * 2048 * 200_000)
    assert adapter.expected_held_pairs(config) == 2.5
    per_token = dense + head + 8 * 2.5 * expert
    assert adapter.token_matmul_params(config) == pytest.approx(per_token)
    update = 6 * (8 * 32 * 128 * 128 + 2 * 4 * 8192)
    assert adapter.decode_step_flops(config, 48, 200_000) == pytest.approx(
        (2 * per_token + update) * 48 + 2 * 16 * 2 * 256 * 2 * 200_000)
    assert adapter.id_range(config) == (0, 37_984)
    assert adapter.positions(config) == 33_920


def test_the_counts_are_the_programs_leaves(full):
    config, adapter = full
    model = adapter.make_model(config, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) \
        == adapter.total_params(config)
    # bfloat16 but the routers and the rule's A_log and dt_bias
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) \
        == 2 * adapter.total_params(config) \
        + 2 * (8 * 2048 * 512 + 6 * 2 * 32)
    spec_ = model.kv_cache_spec()
    assert (spec_.num_layers, spec_.state_layers) == (2, 6)
    assert spec_.bytes_per_slot == 6 * adapter.state_bytes_per_slot(config)
    assert spec_.bytes_per_token == 2 * adapter.cache_bytes_per_token(config)
    assert spec_.bytes_per_token == 4096
    assert shapes["layers"]["moe"]["gate"].shape == (8, 128, 2048, 512)
    # the file's stds are the rule's at the published widths (make_model
    # refuses a file whose numbers are not)
    assert config["assumed"]["init"]["std"] == pytest.approx(
        model.c.unit_stds(), rel=1e-5)
    off = {**config, "assumed": {**config["assumed"], "init": {
        **config["assumed"]["init"],
        "std": {**config["assumed"]["init"]["std"], "attn.k": 0.02}}}}
    with pytest.raises(ValueError, match="not the program's rule"):
        adapter.make_model(off, "serve")
    with pytest.raises(ValueError, match="no 'train' section"):
        adapter.make_model(config, "train")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every key of the catalog row's config under the same key and value,
    but for the keys listed as reduced; no width among them."""
    config, _ = full
    source = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs | {"serve.max_len"} == set(config["reduced"]) \
        == set(config["reduced_why"])
    entry = next(c for c in spec.manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    dep = config["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"],
            dep["num_experts_published"], dep["num_hidden_layers_published"],
            dep["vocab_size_published"]) == (4, 6, 512, 48, 151936)
    # the floors: two whole periods, 128 >= 8 experts, a quarter >= an eighth
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0
    assert config["vocab_size"] * 4 == dep["vocab_size_published"]
    assert {"projection_order", "conv_over", "rule", "rule_chunk",
            "delta_state_dtype", "norms", "rope_layout", "router",
            "init"} <= set(config["assumed"])
    assert (config["serve"]["max_len"], config["serve"]["page_size"]) \
        == (33920, 128)
    assert config["serve"]["num_slots"] in (48, 32)
    assert config["serve"]["prefill_chunk"] in (512, 1024, 2048)
    assert config["n_embd"] == config["hidden_size"]


def test_the_traffic_is_the_accepted_file_and_fits_the_positions_served():
    tr = spec.traffic("batch-mixed")
    assert (tr["kind"], tr["pool_requests"], tr["schedule_seed"],
            tr["queue_depth_slots"], tr["trace_s"],
            tr["warmup_finished_requests"]) == ("backlog", 256, 32, 2, 3.0, 8)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                "sigma": 1.4, "min": 128, "max": 32768}
    assert tr["output_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.8, "min": 16, "max": 1024}
    lengths = schedule.backlog_lengths(tr)
    assert max(p + o for p, o in lengths) + 1 <= 33920
    prompts = sum(p for p, _ in lengths)
    answers = sum(o for _, o in lengths)
    assert 10 < prompts / answers < 16      # the issue: 12.6 to one


def test_the_piecewise_reference_is_the_whole_reference(monkeypatch):
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    adapter = spec.adapter(config)
    model = adapter.make_model(config, "serve")
    assert model.c.held == (8, 4) and model.c.gdn_chunk == 8
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 504, (2, 45)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    # blocks that do not divide: a short last block of the head
    monkeypatch.setattr(adapter, "VOCAB_ROWS", 200)
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


# one entry a quantity since PR 53: the serving family's entries list the
# six serving cells (``decode_device_ms`` / ``prefill_device_ms`` went)
SERVING = ("decode_step_ms", "prefill_chunk_ms", "decode_roofline",
           "decode_batch_mean", "engine_compiles", "device_idle_share.serve",
           "hbm_heap_gb.serve", "hbm_stack_gb.serve", "decode_host_ms",
           "prefill_host_ms", "sched_host_ms", "host_gap_share",
           "decode_program_ms", "prefill_program_ms", "decode_issue_ms",
           "decode_runtime_ms", "decode_readback_ms")
DOCS = ("state_bytes_share", "moe_experts_hit_share", "moe_gmm_time_share",
        "moe_grouped_share")


def test_one_new_entry_and_the_accepted_ones_this_cell_is_appended_to():
    """ONE entry of its own, and the cell in the ``workloads`` of the
    serving family's seventeen entries, of
    ``moe_rows_per_hit_expert.serve`` and of four ``*.batch-docs`` readers
    of ids and names this model emits too; not in K-EXAONE's own
    shape-pattern shares nor in its second page group's.  ``per_layer``
    held 128 of 128 when the cell came (PR 51) and holds at most 72 since
    PR 53 folded a quantity's per-cell entries into one."""
    man = spec.manifest()
    assert len(man["per_layer"]) <= 72
    (mine,) = [m for m in man["per_layer"] if m["workloads"] == [CELL]]
    assert mine == {"name": "gdn_time_share.batch-mixed", "unit": "%",
                    "better": "lower", "source": "device_trace",
                    "layer": "model", "moves": "serve_tokens_per_s",
                    "workloads": [CELL]}
    assert man["per_layer"][-1] == mine
    appended = list(SERVING) + ["moe_rows_per_hit_expert.serve"] \
        + [f"{m}.batch-docs" for m in DOCS]
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in appended:
        assert by_name[name]["workloads"][-1] == CELL, name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in spec.metrics_of(man["per_layer"], CELL)} \
        == set(appended) | {mine["name"]}
    for name in ("moe_time_share.batch-mixed",
                 "dense_ffn_time_share.batch-mixed",
                 "attn_full_time_share.batch-mixed",
                 "attn_window_time_share.batch-mixed",
                 "kv_pages_held_share.batch-mixed"):
        assert by_name[name]["workloads"] == ["k-exaone-236b-a23b.batch-mixed"]
    for m in spec.metrics_of(man["per_layer"], CELL):
        assert spec.layer_metric_file(m["name"])["reader"]
    cell = spec.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "batch-mixed", 1)
    assert len(cell["why"]) <= 200
    assert man["workloads"][-1] == cell and man["configs"][-1]["name"] == NAME
    rate = next(m for m in man["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    assert os.path.getsize(spec.ROOT / "BENCHMARK.json") < 64 * 1024


def _recorded():
    """(share of busy time in %, the event's whole name, its scope) of the
    operations with the most own time in a traced run of the cell on the
    v5e (my chip run, PR 51), the scope by the same instruction's
    ``op_name`` in the programs compiled for a described v5e
    (``tools/compile_v5e_parts_gmm.py --hlo``; ``?`` where the chip's
    bucket was not among those compiled or the instruction carries none:
    asynchronous copies)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "qwen3_next_batch_mixed_ops.txt")
    for line in open(path):
        share, scope, name = line.rstrip("\n").split("\t")
        yield float(share), name, scope


def test_the_gdn_pattern_takes_the_mixers_scopes_and_no_other():
    """Over the recorded operations: one whose instruction carries another
    named scope (attention, the expert layer) is never counted; under 1% of
    busy time inside the five ``hetu.gdn.*`` scopes is missed; the grouped
    matmuls are counted by ``moe_gmm_time_share``'s accepted pattern and not
    by this one.  The pattern reads result and operand SHAPES only this
    configuration's mixer has: the trace's event name is the instruction
    without its metadata, so a scope is not there to anchor on."""
    rx = re.compile(spec.layer_metric_file("gdn_time_share.batch-mixed")
                    ["params"]["pattern"])
    gmm = re.compile(spec.layer_metric_file("moe_gmm_time_share.batch-docs")
                     ["params"]["pattern"])
    taken = missed = wrong = grouped = seen = 0.0
    for share, name, scope in _recorded():
        seen += share
        hit = bool(rx.search(name))
        own = "hetu.gdn." in scope
        other = any(s in scope for s in ("hetu.attn.", "hetu.moe."))
        assert not (hit and other), (scope, name[:200])
        assert not (hit and gmm.search(name)), name[:200]
        taken += share if hit else 0.0
        missed += share if own and not hit else 0.0
        grouped += share if gmm.search(name) else 0.0
    assert seen > 90.0
    assert taken > 10.0 and grouped > 5.0, (taken, grouped)
    assert missed < 1.0, missed
