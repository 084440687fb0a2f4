"""GLM-5.3-Flash's adapter (``benchmarks/arch/glm5_next.py``): its counts
against ``jax.eval_shape`` of the program's ``init`` and against the numbers
the issue wrote out by hand, the configuration against the catalog's row,
the traffic unedited, the piecewise reference against the whole one (rows,
head groups and the choices handed back included), the new cell through
``run.py`` at rehearsal widths, and the manifest's entries."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, spec

CELL = "glm-5.3-flash.batch-context"
NAME = "glm-5.3-flash"
KDA, DSA = "linear_attention", "deepseek_sparse_attention"
PUBLISHED = [DSA if i % 4 == 3 else KDA for i in range(45)]


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """The issue's arithmetic, recounted: 4096 wide; KDA 64 heads of 128
    with two rank-128 gates; latent attention 64 heads of 256 | 256 over a
    512 latent, a 1536 query latent, an indexer of 32 x 128; experts 3 x
    4096 x 2048, 36 held; an eighth of the vocabulary; 5 layers."""
    config, adapter = full
    kda = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert adapter.kda_matmul_params(config) == kda
    assert adapter.kda_params(config) == kda + 3 * 8192 * 4 + 64 + 8192 + 128
    assert round(adapter.kda_params(config) / 1e6, 1) == 137.7
    mla = 4096 * 1536 + 1536 * 16384 + 4096 * 512 + 512 * 32768 \
        + 16384 * 4096
    indexer = 1536 * 4096 + 4096 * 128 + 4096 * 32
    assert adapter.dsa_matmul_params(config) == mla + indexer
    assert (round(mla / 1e6, 1), round(indexer / 1e6, 1)) == (117.4, 6.9)
    assert round(adapter.dsa_params(config) / 1e6, 1) == 124.4
    assert adapter.expert_params(config) == 3 * 4096 * 2048 == 25_165_824
    assert adapter.dense_ffn_params(config) == 3 * 4096 * 12288
    assert round(adapter.hyper_params(config) / 1e6, 1) == 0.8
    assert 2 * adapter.head_params(config) == 2 * 19_360 * 4096
    assert round(adapter.total_params(config) / 1e6) == 4718
    w = adapter.widths(config)
    assert (w["kda_layers"], w["dsa_layers"], w["first_dense"],
            w["expert_layers"], w["held"], w["routed"], w["topk"]) \
        == (4, 1, 1, 4, (0, 36), 288, 8)
    # a token: one latent of 512 and a quarter of a pooled key of 128
    assert adapter.cache_bytes_per_token(config) == 1024 + 64 == 1088
    # a slot: 4 matrices of 64 x 128 x 128 float32, 4 x 3 rows of 24,576,
    # and the open group's float32 sum
    assert adapter.state_bytes_per_slot(config) \
        == 4 * (4 * 64 * 128 * 128 + 3 * 24576 * 2) + 4 * 128
    assert round(adapter.state_bytes_per_slot(config) / 1e6, 1) == 17.4
    # a round reads 2,048 + the open group's rows a slot, never the history
    assert adapter.chosen_rows(config, 100) == 100
    assert adapter.chosen_rows(config, 60_000) == 2052
    slots = config["serve"]["num_slots"]
    least = 2 * (adapter.always_read_params(config)
                 + adapter.head_params(config)) \
        + 2 * 4 * 64 * 128 * 128 * 4 * (slots + 1)
    assert adapter.decode_step_bytes(config, 0) == pytest.approx(least)
    assert adapter.decode_step_bytes(config, 10 ** 6) == pytest.approx(
        least + 2 * (512 * 2052 + 128 * 513))
    for cached in (2053, 8192, 600_000):
        assert adapter.decode_step_bytes(config, cached) \
            == adapter.decode_step_bytes(config, 2052)
    grows = [adapter.decode_step_flops(config, 32, n)
             for n in (0, 64, 2048, 2052)]
    assert grows == sorted(grows) and len(set(grows)) == 4
    assert adapter.decode_step_flops(config, 32, 10 ** 6) == grows[-1]
    assert adapter.id_range(config) == (0, 19_360)
    assert adapter.positions(config) == 66_624 == 1041 * 64


def test_the_counts_are_the_programs_leaves(full):
    config, adapter = full
    model = adapter.make_model(config, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) \
        == adapter.total_params(config)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    # bfloat16 but for the float32 leaves: routers, their biases, the
    # hyper-connections' projections, alphas and biases, A_log and dt_bias
    assert 2 * adapter.total_params(config) < held \
        < 2 * adapter.total_params(config) + 20e6
    assert round(held / 1e9, 2) == 9.45
    spec_ = model.kv_cache_spec()
    assert (spec_.num_layers, spec_.state_layers, spec_.comp_stride,
            spec_.comp_width, spec_.v_dim) == (1, 4, 4, 128, 0)
    assert spec_.part_layers == (4, 4, 1)
    assert spec_.bytes_per_slot == adapter.state_bytes_per_slot(config)
    assert spec_.bytes_per_token == adapter.cache_bytes_per_token(config)
    assert len(shapes["layers"]["kda"]["qkv"]) == 4
    assert shapes["layers"]["kda"]["qkv"][0].shape == (4096, 24576)
    assert shapes["layers"]["dsa"]["kb"][0].shape == (64, 512, 256)
    assert shapes["layers"]["moe"]["gate"].shape == (4, 36, 4096, 2048)
    assert shapes["layers"]["moe"]["router"][0].shape == (4096, 288)
    assert model.c.layer_types == tuple(PUBLISHED[2:7])
    assert (model.c.index_groups, model.c.hc_mult, model.c.swiglu_limit,
            model.c.gate_lower_bound) == (512, 4, 10.0, -5.0)
    assert config["assumed"]["init"]["std"] == pytest.approx(
        model.c.unit_stds(), rel=1e-5)
    off = {**config, "assumed": {**config["assumed"], "init": {
        **config["assumed"]["init"],
        "std": {**config["assumed"]["init"]["std"], "kda.o": 0.02}}}}
    with pytest.raises(ValueError, match="not the program's rule"):
        adapter.make_model(off, "serve")
    with pytest.raises(ValueError, match="no 'train' section"):
        adapter.make_model(config, "train")
    with pytest.raises(ValueError, match="unrotated latent attention"):
        adapter.make_model({**config, "qk_rope_head_dim": 64}, "serve")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every key of the catalog row's config under the same key and value,
    but for the keys listed as reduced; no width among them."""
    config, _ = full
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in open(catalog)
               if f'"{"GLM-5.3-Flash"}"' in line)
    source = row["config"]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs | {"serve.max_len"} == set(config["reduced"]) \
        == set(config["reduced_why"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in differs)
    lin, was = config["linear_attn_config"], source["linear_attn_config"]
    assert {k for k in was if lin[k] != was[k]} \
        == {"kda_layers", "full_attn_layers"}
    entry = next(c for c in spec.manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the cut: published layers 2-6, one leading dense layer and one period
    assert config["layer_types"] == source["layer_types"][2:7] \
        == [KDA, DSA, KDA, KDA, KDA]
    assert config["mlp_layer_types"] == source["mlp_layer_types"][2:7] \
        == ["dense"] + ["sparse"] * 4
    dep = config["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["first_layer"],
            dep["num_hidden_layers_published"], dep["experts_held"],
            dep["n_routed_experts_published"], dep["vocab_size_published"]) \
        == (8, 2, 45, [0, 36], 288, 154880)
    assert config["vocab_size"] * 8 == 154880 and 36 * 8 == 288
    assert {"index_pooling", "index_topk_counts", "index_tail", "indexer",
            "index_rope_dim", "kda_gate", "kda_gate_rank", "kda_state_dtype",
            "kda_chunk", "kda_sub_block", "swiglu_limit_form",
            "hyper_connections", "init"} <= set(config["assumed"])
    assert (config["serve"]["max_len"], config["serve"]["page_size"],
            config["serve"]["prefill_chunk"]) == (66624, 64, 2048)
    assert config["serve"]["num_slots"] in (32, 24, 48)
    assert config["n_embd"] == config["hidden_size"]


def test_the_piecewise_reference_is_the_whole_reference(monkeypatch):
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    adapter = spec.adapter(config)
    model = adapter.make_model(config, "serve")
    assert model.c.index_groups == 8 and model.c.kda_chunk == 8
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 504, (2, 77)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    # blocks that do not divide: short last blocks of the head, of a
    # sublayer's rows and of a DSA layer's queries; heads two at a time
    monkeypatch.setattr(adapter, "VOCAB_ROWS", 200)
    monkeypatch.setattr(adapter, "ROWS", 32)
    monkeypatch.setattr(adapter, "QUERY_ROWS", 20)
    monkeypatch.setattr(adapter, "LONG", 64)
    monkeypatch.setattr(adapter, "HEADS_AT_LENGTH", 2)
    adapter._JITS.clear()
    choices = {}
    pieces = adapter.reference_logits(params, ids, config, choices=choices)
    assert pieces.dtype == np.float32 and pieces.shape == (2, 77, 504)
    np.testing.assert_allclose(pieces, whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(adapter.system_logits(model, params, ids),
                               whole, rtol=2e-4, atol=2e-4)
    some = adapter.reference_logits(params, ids, config, rows=slice(39, 50))
    np.testing.assert_allclose(some, whole[:, 39:50], rtol=2e-5, atol=2e-5)
    # the choices: a mask over POSITIONS a query block of the one DSA layer
    assert sorted(choices) == [0]
    assert [lo for lo, _ in choices[0]] == [0, 20, 40, 60]
    masks = np.concatenate([m for _, m in choices[0]], 1)     # [2, 77, 77]
    t = np.arange(77)
    want = 4 * np.minimum(8, (t + 1) // 4) + (t + 1) % 4
    assert (masks.sum(-1) == want).all()
    adapter._JITS.clear()


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
    # the rehearsal's prompts pass index_topk 32: the check DOES choose
    assert max(line["detail"]["check"]["prompts"]) > 32
    assert line["metric_names"] == ["serve_tokens_per_s", "setup_s"]


MINE = ("kda_time_share.batch-context",
        "index_select_time_share.batch-context",
        "index_attend_time_share.batch-context",
        "mhc_time_share.batch-context", "moe_time_share.batch-context",
        "index_rows_read_share")


def test_six_new_entries_and_the_accepted_ones_this_cell_is_appended_to():
    man = spec.manifest()
    mine = [m for m in man["per_layer"] if m["workloads"] == [CELL]]
    assert tuple(m["name"] for m in mine) == MINE
    assert man["per_layer"][-6:] == mine
    for m in mine:
        assert (m["unit"], m["moves"]) == ("%", "serve_tokens_per_s")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["layer"] for m in mine] == ["model"] * 5 + ["serving engine"]
    by_name = {m["name"]: m for m in man["per_layer"]}
    family = [m["name"] for m in man["per_layer"]
              if "minicpm-sala.batch-context" in m["workloads"]
              and len(m["workloads"]) >= 7]
    assert len(family) == 23
    for name in family + ["state_bytes_share.batch-docs",
                          "moe_rows_per_hit_expert.serve",
                          "moe_experts_hit_share.batch-docs",
                          "moe_grouped_share.serve"]:
        assert by_name[name]["workloads"][-1] == CELL, name
    for m in spec.metrics_of(man["per_layer"], CELL):
        assert spec.layer_metric_file(m["name"])["reader"]
    cell = spec.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "batch-context", 1)
    assert len(cell["why"]) <= 200 and len(man["configs"][-1]["why"]) <= 200
    assert man["workloads"][-1] == cell and man["configs"][-1]["name"] == NAME
    rate = next(m for m in man["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    assert os.path.getsize(spec.ROOT / "BENCHMARK.json") < 64 * 1024
    assert len(man["per_layer"]) <= 128 and len(man["workloads"]) <= 24


def _recorded():
    """(share of busy time in %, the event's whole name) of the 400
    operations with the most own time in a traced run of the cell on the
    chip (my chip run, PR 58, call 2: seed 2900000013, ``tools/top_ops.py``;
    96.95% of the busy time)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "glm5_next_batch_context_ops.txt")
    for line in open(path):
        share, name = line.rstrip("\n").split("\t")
        yield float(share), name


def test_the_five_patterns_part_the_recorded_operations():
    """Over the recorded operations no operation is counted by two of the
    five shares, each finds what the trace showed to be its own (the row
    gather ``[262656, 512]`` and the gathered attention are the attend's,
    the full ``sort`` of 18,432 scores the selection's, ``[.., 24576]`` and
    the chunked rule's ``[32, 64, 64, 64]`` the KDA's, ``[.., 4, 4096]`` and
    ``f32[.., 16384]`` the stream mix's, the grouped calls the experts'),
    and the DSA layer's latent projections (``dsa____o``, ``qb``, ``vb``)
    and the dense feed-forward are nobody's."""
    import re

    rx = {m: re.compile(spec.layer_metric_file(m)["params"]["pattern"])
          for m in MINE[:5]}
    total = {m: 0.0 for m in rx}
    for share, name in _recorded():
        hit = [m for m, r in rx.items() if r.search(name)]
        assert len(hit) <= 1, (hit, name[:200])
        for m in hit:
            total[m] += share
        if "dsa____o" in name or "ffn____" in name:
            assert not hit, name[:200]
        if " sort(" in name:
            assert hit == ["index_select_time_share.batch-context"]
        if "[262656,512]" in name.split(" fusion(")[0]:
            assert hit == ["index_attend_time_share.batch-context"]
    assert {m: round(v, 1) for m, v in total.items()} == {
        "kda_time_share.batch-context": 20.4,
        "index_select_time_share.batch-context": 17.5,
        "index_attend_time_share.batch-context": 36.3,
        "mhc_time_share.batch-context": 6.2,
        "moe_time_share.batch-context": 6.6}
