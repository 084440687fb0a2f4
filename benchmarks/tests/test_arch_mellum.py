"""The ``mellum`` adapter and the trained cell
``mellum2-12b-a2.5b-instruct.train-ep4``: the adapter's counts against
arithmetic by hand at the published widths, the configuration against the
catalog's numbers, the rehearsal widths, the operations a token requires
against a sum over positions, the new reader on a trace recorded on the chip
(window calls told from full calls), and the cell's rehearsal through
``run.py``."""

import importlib
import json
import os
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, check, reduce, spec

NAME = "mellum2-12b-a2.5b-instruct"
CELL = NAME + ".train-ep4"
SLIDING, FULL = "sliding_attention", "full_attention"
# the catalog row's ``config``, as published (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
TRACE = Path(__file__).parent / "data" / "masked_flash_v5e.xplane.pb"


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def tiny():
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """2304 wide, 32 | 4 heads of 128, experts of 896, a 64-wide router, 16
    of 64 held, 4 layers, 24,576 rows: the issue's arithmetic."""
    config, a = full
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert a.attention_params(config) == attn == 21_233_664
    assert a.expert_params(config) == 3 * 2304 * 896 == 6_193_152
    router, norms = 2304 * 64, 2 * 2304 + 2 * 128
    assert (router, norms) == (147_456, 4_864)
    layer = attn + router + norms + 16 * 6_193_152
    assert layer == 120_476_416 and 4 * layer == 481_905_664
    head = 24_576 * 2304
    assert 2 * head == 113_246_208
    assert a.total_params(config) == 4 * layer + 2 * head + 2304 \
        == 595_154_176
    assert 9.52e9 < a.total_params(config) * 16 < 9.53e9
    assert a.dense_params(config) == 4 * (attn + router)
    assert a.expected_held_pairs(config) == 8 * 16 / 64 == 2.0
    token = 4 * (attn + router) + head + 4 * 2.0 * 6_193_152
    assert a.token_matmul_params(config) == token
    assert a.attention_call_shape(config, {"batch": 1, "seq": 16384}) \
        == (1, 32, 16384, 128)
    calls = a.attention_calls(config, {"batch": 1, "seq": 16384})
    assert set(calls) == {"hetu.attn.window", "hetu.attn.full"}
    assert calls["hetu.attn.window"] == {
        "batch": 1, "heads": 32, "kv_heads": 4, "seq": 16384, "d_qk": 128,
        "d_v": 128, "window": 1024}
    assert calls["hetu.attn.full"]["window"] is None
    assert a.id_range(config) == (0, 24_576)
    assert a.positions(config) == 131_072
    d = a.dims(config)
    assert d["held"] == (16, 16) and d["period"] == (SLIDING,) * 3 + (FULL,)
    assert d["yarn"]["attention_factor"] == 1.2772588722239782


def test_a_tokens_operations_against_a_sum_over_positions(full):
    """The window layers at their live scores: a query at position ``i``
    sees ``min(i + 1, 1024)`` keys on a sliding layer and ``i + 1`` on a
    full one; a live score is 4 x 128 operations a head forward, three
    times that with the backward."""
    config, a = full
    seq = 16384
    i = np.arange(seq)
    window = int(np.minimum(i + 1, 1024).sum())
    whole = int((i + 1).sum())
    assert a.live_scores(seq, 1024) == window == 1024 * seq - 1024 * 1023 // 2
    assert a.live_scores(seq) == whole == seq * (seq + 1) // 2
    assert a.live_scores(512, 1024) == 512 * 513 // 2     # shorter than it
    attention = 3 * 32 * 4 * 128 * (3 * window + whole) / seq
    want = 6 * a.token_matmul_params(config) + attention
    assert a.train_flops_per_token(config, seq) == pytest.approx(want,
                                                                 rel=1e-12)
    # the issue's forward budget, MFLOP a token: window 16.3 a layer (16.8
    # were every query to see 1,024), full 134.2, the matmuls 383.4
    assert 32 * 4 * 128 * window / seq / 1e6 == pytest.approx(16.25, abs=0.01)
    assert 32 * 4 * 128 * whole / seq / 1e6 == pytest.approx(134.2, abs=0.1)
    assert 2 * a.token_matmul_params(config) / 1e6 \
        == pytest.approx(383.4, abs=0.1)
    step = a.train_flops_per_token(config, seq) * seq
    assert 27.7e12 < step < 27.9e12
    # four causal layers would be 66 block-rows where these are 25
    assert 4 * whole / (3 * window + whole) == pytest.approx(2.93, abs=0.01)


def test_the_masked_flash_counts(full):
    reader = importlib.import_module("benchmarks.readers.flash_roofline_masked")
    call = dict(batch=1, heads=32, kv_heads=4, seq=16384, d_qk=128, d_v=128)
    live_w = 1024 * 16384 - 1024 * 1023 // 2
    ops = reader.call_flops(**call, window=1024)
    assert ops == {"fwd": 2 * 32 * live_w * 256,
                   "bwd": 2 * 32 * live_w * (3 * 128 + 2 * 128)}
    byt = reader.call_bytes(**call, window=1024)
    q_side, k_side = 32 * 256, 4 * 256
    assert byt == {"fwd": 16384 * 2 * (q_side + k_side),
                   "bwd": 16384 * 2 * 2 * (q_side + k_side)}
    # a causal-only call at as many KV heads as query heads: the reading of
    # flash_roofline_widths at equal widths, but for the diagonal itself
    # (S (S + 1) / 2 live scores where that file counts S^2 / 2)
    widths = importlib.import_module("benchmarks.readers.flash_roofline_widths")
    plain = dict(call, kv_heads=32)
    mine, theirs = reader.call_flops(**plain), widths.call_flops(
        1, 32, 16384, 128, 128)
    for kind in ("fwd", "bwd"):
        assert mine[kind] == pytest.approx(theirs[kind], rel=1.01 / 16384)
        assert mine[kind] == theirs[kind] * (16384 + 1) / 16384
    assert reader.call_bytes(**plain) == widths.call_bytes(
        1, 32, 16384, 128, 128)


def test_the_program_holds_what_the_adapter_counts(full):
    config, a = full
    model = a.make_model(config, "train")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    held = sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert held == a.total_params(config) == 595_154_176
    assert {x.dtype for x in jax.tree_util.tree_leaves(shapes["params"])} \
        == {jnp.dtype("float32")}
    assert shapes["state"] == {}
    layers = shapes["params"]["layers"]
    assert layers["moe"]["gate"].shape == (1, 4, 16, 2304, 896)
    assert layers["moe"]["router"].shape == (1, 4, 2304, 64)
    assert layers["attn"]["k"].shape == (1, 4, 512, 2304)
    assert model.c.held == (16, 16) and model.c.window == 1024
    assert model.c.period == (SLIDING,) * 3 + (FULL,)
    with pytest.raises(ValueError, match="no 'serve' section"):
        a.make_model(config, "serve")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    config, _ = full
    assert config["source"] == SOURCE
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 24_576)
    dep = config["deployment"]
    assert dep["chips_sharing_a_layer"] * config["num_experts"] \
        == dep["num_experts_published"] == PUBLISHED["num_experts"]
    assert dep["vocab_size_published"] == PUBLISHED["vocab_size"] \
        == 4 * config["vocab_size"]
    assert dep["num_hidden_layers_published"] == 28
    assert (dep["expert_parallel_rank"], dep["experts_held"]) == (1, "16-31")
    assert "serve" not in config and "TRAINING ONLY" in config["scope"]
    assert {"qk_norm", "balance_loss", "next_token_head", "init_std",
            "embedding_init_std", "attention_out_init_std"} \
        <= set(config["assumed"])
    # init_std / sqrt(2 x the PUBLISHED depth), handed to the model
    assert config["assumed"]["attention_out_init_std"] == pytest.approx(
        config["assumed"]["init_std"] / (2 * 28) ** 0.5)
    assert spec.adapter(config).make_model(config, "train").c.out_init_std \
        == config["assumed"]["attention_out_init_std"]
    man = spec.manifest()
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == SOURCE


def test_the_rehearsal_widths_are_tiny_and_keep_the_shape_of_the_cut():
    config, a = tiny()
    w = a.widths(config)
    assert (w["hidden"], w["heads"], w["kv_heads"], w["head_dim"],
            w["window"], w["expert_ffn"]) == (64, 4, 2, 16, 24, 32)
    assert (w["layers"], w["held"], w["first"], w["n_routed"], w["topk"]) \
        == (4, 4, 4, 16, 4)
    assert w["layer_types"] == (SLIDING,) * 3 + (FULL,)
    assert w["yarn"]["original_max_position_embeddings"] == 32
    assert w["yarn"]["factor"] == 16        # the section's other numbers stay
    assert config["compute_dtype"] == "float32"
    tr = spec.traffic(spec.cell(spec.manifest(), CELL)["traffic"],
                      rehearse=True)
    assert (tr["batch"], tr["seq"]) == (1, 64)


def test_the_traffic_is_the_issues():
    man = spec.manifest()
    cell = spec.cell(man, CELL)
    tr = spec.traffic(cell["traffic"])
    assert cell["traffic"] == "train-1x16384"
    assert (cell["chips"], tr["kind"], tr["batch"], tr["seq"]) \
        == (1, "train_steps", 1, 16384)
    assert (tr["distinct_batches"], tr["lookahead_steps"],
            tr["check_sequences"]) == (8, 1, 1)
    names = {m["name"] for m in spec.metrics_of(man["per_layer"], CELL)}
    assert names == {
        "train_step_ms", "train_dispatch_ms", "train_program_ms", "mfu_pct",
        "device_idle_share.train", "hbm_heap_gb.train", "hbm_stack_gb.train",
        "flash_roofline.train-ep4", "attn_window_time_share.train-ep4",
        "attn_full_time_share.train-ep4", "moe_time_share.train-ep4",
        "moe_rows_per_hit_expert.train-ep4", "moe_grouped_share.train-ep4",
        "moe_gmm_time_share.train-ep4"}
    assert {m["name"] for m in spec.metrics_of(man["end_to_end"], CELL)} \
        == {"train_tokens_per_s", "setup_s"}
    # every metric this PR adds is this cell's alone
    for m in man["per_layer"]:
        if m["name"].endswith(".train-ep4"):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s"


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


# ------------------------------------------------ the readers on a trace

def _read(name, ctx):
    f = spec.layer_metric_file(name)
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    return reader.read(ctx, **f["params"]), f


def _recorded(full):
    """A ``ReadCtx`` over the trace ``record_masked_flash_trace.py`` left:
    the configuration at that tool's shapes."""
    from benchmarks.tools import record_masked_flash_trace as rec

    config, _ = full
    config = {**config, "num_attention_heads": rec.HEADS,
              "num_key_value_heads": rec.KV_HEADS, "head_dim": rec.HEAD_DIM,
              "sliding_window": rec.WINDOW}
    summary = reduce.summarize(reduce.load(str(TRACE)))
    return SimpleNamespace(
        trace=summary, config=config,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        run=SimpleNamespace(values={"batch": rec.BATCH, "seq": rec.SEQ})), rec


def test_the_masked_reader_tells_window_calls_from_full_calls(full):
    """On a trace recorded on the v5e (three window calls and a full one a
    step, forward and backward, K and V at half the heads): the calls are
    told apart by the scope in their names, each kind's work counted by its
    own mask, and the share lies between 0 and 100."""
    ctx, rec = _recorded(full)
    value, f = _read("flash_roofline.train-ep4", ctx)
    assert f["reader"] == "flash_roofline_masked"
    assert 0.0 < value < 100.0
    counts = {}
    for scope in ("hetu.attn.window", "hetu.attn.full"):
        counts[scope] = [len(ctx.trace.kernel_events(rx % scope.replace(
            ".", "\\.")))
            for rx in (f["params"]["fwd"], f["params"]["bwd"],
                       f["params"]["bwd_count"])]
    assert counts["hetu.attn.full"] == [rec.STEPS, 2 * rec.STEPS, rec.STEPS]
    assert counts["hetu.attn.window"] == [3 * n for n in
                                          counts["hetu.attn.full"]]
    # were the full calls counted as window calls, the share would read
    # lower: the window's live scores are fewer
    reader = importlib.import_module("benchmarks.readers.flash_roofline_masked")
    arch = spec.adapter(ctx.config)
    calls = arch.attention_calls(ctx.config, ctx.run.values)
    assert reader.call_flops(**calls["hetu.attn.window"])["fwd"] \
        < 0.5 * reader.call_flops(**calls["hetu.attn.full"])["fwd"]
    shares = {n: _read(n, ctx)[0] for n in (
        "attn_window_time_share.train-ep4", "attn_full_time_share.train-ep4")}
    assert all(0.0 < v < 100.0 for v in shares.values())
    assert sum(shares.values()) < 100.0


def test_the_readers_leave_a_run_without_a_trace_alone(full):
    config, _ = full
    _, f = _read("flash_roofline.train-ep4", SimpleNamespace(
        trace=None, peaks=None, config=config))
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    none = SimpleNamespace(kernel_events=lambda pattern: [])
    assert reader.read(SimpleNamespace(
        trace=none, peaks={"bf16_flops": 1, "hbm_bytes_per_s": 1},
        config=config, run=SimpleNamespace(values={"batch": 1, "seq": 64})),
        **f["params"]) is None
    # a configuration whose adapter states no kinds of call (every other
    # one): nothing, and no error
    gpt = spec.config(spec.manifest(), "gpt2-small")
    assert reader.read(SimpleNamespace(trace=none, peaks={}, config=gpt),
                       **f["params"]) is None


def test_the_moe_readers_read_the_trainers_instant(tmp_path):
    """``moe_rows_per_hit_expert.train-ep4`` and
    ``moe_grouped_share.train-ep4`` off a trace recorded here: the ids of
    ``train.moe`` (``moe_block_fill``, which divided by the loop's blocks,
    went in PR 53)."""
    import hetu_tpu as ht
    from hetu_tpu import optim

    config, arch = tiny()
    model = arch.make_model(config, "train")
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3))
    state = ex.init_state(jax.jit(model.init)(build.key_for(7)))
    ids = np.random.default_rng(5).integers(0, 504, (2, 64)).astype(np.int32)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            state, metrics = ex.run("train", state, (ids,))
            jax.block_until_ready(metrics)
    path = str(sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1])
    ctx = SimpleNamespace(run=SimpleNamespace(trace_path=path))
    # 2 x 64 tokens x 4 choices, 4 of 16 held: about 128 pairs a layer on 4
    # experts, every one of them computed by the grouped path
    rows, _ = _read("moe_rows_per_hit_expert.train-ep4", ctx)
    assert 10.0 < rows < 80.0
    assert _read("moe_grouped_share.train-ep4", ctx)[0] == 100.0
    assert _read("moe_grouped_share.train-ep4", SimpleNamespace(
        run=SimpleNamespace(trace_path=None)))[0] is None


def test_the_training_control_moves_this_models_numbers():
    """``benchmarks/tools/check_control_train.py``'s lower precision in the
    loss function's place (flash calls with a window and grouped heads and
    the held-expert walk bound whole, operands and results rounded) moves
    loss and gradient norm by several times what bfloat16 as stated does."""
    from benchmarks.tools import check_control_train as cct

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
