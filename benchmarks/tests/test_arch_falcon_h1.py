"""Falcon-H1's adapter (``benchmarks/arch/falcon_h1.py``): its counts against
``jax.eval_shape`` of the program's ``init`` and against numbers written out
by hand, the configuration against the catalog's row, the traffic against
the issue's, the piecewise reference against the whole one, the new cell
through ``run.py`` at rehearsal widths, and the four ``*_time_share``
patterns against a recorded op list of the cell on the chip."""

import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, schedule, spec

CELL = "falcon-h1-34b-instruct.batch-gen"
NAME = "falcon-h1-34b-instruct"


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """5120 wide; attention 20 | 4 heads of 128; the mixer 32 heads of 128
    over a state of 256 in 2 groups, 4 taps; SwiGLU 21504; 6 layers."""
    config, adapter = full
    attention = 5120 * (2560 + 512 + 512) + 2560 * 5120
    assert adapter.attention_params(config) == attention == 31_457_280
    in_width = 4096 + 4096 + 512 + 512 + 32
    assert adapter.widths(config)["in_width"] == in_width == 9248
    matmuls = 5120 * 9248 + 4096 * 5120
    assert adapter.mixer_matmul_params(config) == matmuls == 68_321_280
    mixer = matmuls + (4 + 1) * 5120 + 4096 + 3 * 32
    assert adapter.mixer_params(config) == mixer == 68_351_072
    ffn = 3 * 5120 * 21504
    assert adapter.ffn_params(config) == ffn == 330_301_440
    layer = attention + mixer + ffn + 2 * 5120
    assert adapter.layer_params(config) == layer == 430_120_032
    head = 261120 * 5120
    assert adapter.head_params(config) == head == 1_336_934_400
    total = 6 * layer + 2 * head + 5120
    assert adapter.total_params(config) == total == 5_254_594_112
    per_token = 6 * (attention + matmuls + ffn) + head
    assert adapter.token_matmul_params(config) == per_token
    assert adapter.cache_bytes_per_token(config) == 2 * 4 * 128 * 2 == 2048
    state = 3 * 5120 * 2 + 32 * 128 * 256 * 4
    assert adapter.state_bytes_per_slot(config) == state == 4_225_024
    # a round: every matmul weight and the head once, the cached rows, and
    # all 64 slots' state read AND written
    assert adapter.decode_step_bytes(config, 0) == pytest.approx(
        2 * per_token + 2 * state * 6 * 64)
    assert adapter.decode_step_bytes(config, 52_000) == pytest.approx(
        2 * per_token + 2 * state * 6 * 64 + 6 * 2048 * 52_000)
    update = 6 * (6 * 32 * 128 * 256 + 2 * 4 * 5120)
    assert adapter.decode_step_flops(config, 64, 52_000) == pytest.approx(
        (2 * per_token + update) * 64 + 2 * 20 * 2 * 128 * 6 * 52_000)
    assert adapter.id_range(config) == (0, 261120)
    assert adapter.positions(config) == 3072


def test_the_counts_are_the_programs_leaves(full):
    config, adapter = full
    model = adapter.make_model(config, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) \
        == adapter.total_params(config)
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) \
        == 2 * adapter.total_params(config) + 2 * 6 * 3 * 32
    spec_ = model.kv_cache_spec()
    assert spec_.bytes_per_slot == 6 * adapter.state_bytes_per_slot(config)
    assert spec_.bytes_per_token == 6 * adapter.cache_bytes_per_token(config)
    # the file's stds are the rule's at the published widths (make_model
    # refuses a file whose numbers are not)
    assert config["assumed"]["init"]["std"] == pytest.approx(
        model.c.unit_stds(), rel=1e-5)
    off = {**config, "assumed": {**config["assumed"], "init": {
        **config["assumed"]["init"],
        "std": {**config["assumed"]["init"]["std"], "attn.k": 0.02}}}}
    with pytest.raises(ValueError, match="not the program's rule"):
        adapter.make_model(off, "serve")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every key of the catalog row's config under the same key and value,
    but for the keys listed as reduced; no width among them."""
    config, _ = full
    source = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs == {"num_hidden_layers"}
    assert differs | {"serve.max_len"} == set(config["reduced"]) \
        == set(config["reduced_why"])
    entry = next(c for c in spec.manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    dep = config["deployment"]
    assert (dep["chips_sharing_a_layer"],
            dep["num_hidden_layers_published"]) == (1, 72)
    assert {"ssm_segments", "branch_multipliers", "rope_layout",
            "gated_norm", "dt", "conv_state", "ssm_state_dtype",
            "init"} <= set(config["assumed"])
    assert (config["serve"]["num_slots"], config["serve"]["max_len"],
            config["serve"]["page_size"]) == (64, 3072, 128)
    assert config["n_embd"] == config["hidden_size"]


def test_the_traffic_is_the_issues():
    tr = spec.traffic("batch-gen")
    assert (tr["kind"], tr["pool_requests"], tr["schedule_seed"],
            tr["queue_depth_slots"], tr["trace_s"],
            tr["warmup_finished_requests"]) == ("backlog", 512, 47, 2, 3.0,
                                                16)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 1.0, "min": 32, "max": 960}
    assert tr["output_len"] == {"dist": "lognormal", "median": 768,
                                "sigma": 0.7, "min": 64, "max": 2048}
    lengths = schedule.backlog_lengths(tr)
    prompts = np.array([p for p, _ in lengths])
    answers = np.array([o for _, o in lengths])
    assert 340 < prompts.mean() < 370 and 890 < answers.mean() < 930
    # no request of the fixed pool runs past the positions served, with
    # room for the engine's own rows
    assert max(p + o for p, o in lengths) <= 3008 < 3072


def test_the_piecewise_reference_is_the_whole_reference(monkeypatch):
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    adapter = spec.adapter(config)
    model = adapter.make_model(config, "serve")
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 504, (2, 45)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    # blocks that do not divide: a short last block of each
    monkeypatch.setattr(adapter, "FFN_COLS", 48)
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


def test_every_new_metric_names_this_cell_alone_and_moves_the_rate():
    """The four shares that need pattern files of their own are this
    cell's entries; the serving family's seventeen are the six serving
    cells' (one entry a quantity since PR 53), and ``state_bytes_share``
    is read under the entry LFM2's cell brought."""
    man = spec.manifest()
    assert len(man["per_layer"]) <= 72
    mine = [m for m in man["per_layer"] if m["name"].endswith(".batch-gen")]
    assert sorted(m["name"] for m in mine) \
        == sorted(f"{m}.batch-gen" for m in SHARES)
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s" for m in mine)
    shared = [m for m in man["per_layer"]
              if m not in mine and CELL in m["workloads"]]
    assert len(shared) == 18
    assert [m["name"] for m in shared if len(m["workloads"]) != 6] \
        == ["state_bytes_share.batch-docs"]
    assert all(m["workloads"][:2] == ["lfm2-8b-a1b.batch-docs", CELL]
               or len(m["workloads"]) == 6 for m in shared)
    assert all(m["moves"] == "serve_tokens_per_s" for m in shared)
    assert {m["name"] for m in spec.metrics_of(man["per_layer"], CELL)} \
        == {m["name"] for m in mine + shared}
    for m in mine + shared:
        assert spec.layer_metric_file(m["name"])["reader"]
    cell = spec.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "batch-gen", 1)


SHARES = ("ssm_state_time_share", "ssm_time_share", "attn_full_time_share",
          "dense_ffn_time_share")


def _patterns():
    return {m: re.compile(spec.layer_metric_file(f"{m}.batch-gen")
                          ["params"]["pattern"]) for m in SHARES}


def _recorded():
    """(share of busy time in %, the event's whole name, its scope) of the
    operations with the most own time in a traced run of the cell on the
    v5e (``tools/top_ops.py 400``, PR 47, call 3), the scope by the same instruction's
    ``op_name`` in the program compiled for a described v5e
    (``tools/compile_v5e_parts.py --hlo``; ``?`` or a bare ``outside:``
    where the instruction carries none: asynchronous copies)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "falcon_h1_batch_gen_ops.txt")
    for line in open(path):
        share, scope, name = line.rstrip("\n").split("\t")
        yield float(share), name, scope


FAMILY = {"hetu.ssm.": "ssm_time_share",
          "hetu.attn.full": "attn_full_time_share",
          "hetu.ffn.dense": "dense_ffn_time_share"}


def test_a_time_share_pattern_takes_its_own_scopes_and_no_other():
    """Over the 400 operations with the most own time of a traced run of
    the cell (98.7% of the chip's busy time): no operation is counted in two
    of the three branches' shares; one whose instruction carries a branch's
    scope is counted in that branch's share or in none; the state's share is
    INSIDE the mixer's (the operations that read or write the float32
    matrix); under 1% of busy time in a branch's scope is missed; and what
    no pattern takes is the head (a fifth of busy time with 6 of 72
    layers), the norms, the embedding and the compiler's own copies.  The
    patterns read result and operand SHAPES: the trace's event name is the
    instruction without its metadata, so a scope is not there to anchor on;
    a weight's asynchronous copy carries no scope at all and is counted by
    its shape."""
    rx = _patterns()
    taken, missed, nowhere = dict.fromkeys(rx, 0.0), {}, 0.0
    for share, name, scope in _recorded():
        hit = {m for m in rx if rx[m].search(name)}
        assert len(hit - {"ssm_state_time_share"}) < 2, (hit, name)
        if "ssm_state_time_share" in hit:
            assert "ssm_time_share" in hit, name
        for m in hit:
            taken[m] += share
        own = {m for s, m in FAMILY.items() if s in scope}
        if own:
            assert hit - {"ssm_state_time_share"} <= own, (hit, scope, name)
            if not hit:
                (m,) = own
                missed[m] = missed.get(m, 0.0) + share
        nowhere += 0.0 if hit else share
    assert all(taken.values()), taken
    assert all(v < 1.0 for v in missed.values()), missed
    assert 20.0 < taken["ssm_state_time_share"] < taken["ssm_time_share"]
    assert taken["dense_ffn_time_share"] > 25.0 and nowhere < 30.0, \
        (taken, nowhere)
