"""MiniCPM-SALA's adapter (``benchmarks/arch/minicpm_sala.py``): its counts
against ``jax.eval_shape`` of the program's ``init`` and against the numbers
the issue wrote out by hand, the configuration against the catalog's row,
the traffic against the issue's, the piecewise reference against the whole
one (rows, a prompt's length and the choices handed back included), the new
cell through ``run.py`` at rehearsal widths, ``tools/check_rows.py`` there
too, the manifest's entries, and the
four time-share patterns against a recorded op list of the cell on the
chip."""

import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, schedule, spec

CELL = "minicpm-sala.batch-context"
NAME = "minicpm-sala"
PUBLISHED = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] \
    + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 4 \
    + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """4096 wide; sparse layers 32 | 2 heads of 128 with a gate projection;
    Lightning layers 32 | 32 of 128, five projections; SwiGLU 16,384; 16
    layers = 4 + 12; the whole vocabulary."""
    config, adapter = full
    ffn = 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + ffn
    assert adapter.sparse_layer_matmul_params(config) == sparse \
        == 253_755_392                                      # 253.8M
    lightning = 5 * 4096 * 4096 + ffn
    assert adapter.lightning_layer_matmul_params(config) == lightning \
        == 285_212_672                                      # 285.2M
    head = 73_448 * 4096
    assert adapter.head_params(config) == head and 2 * head == 601_686_016
    layers = 4 * sparse + 12 * lightning
    assert adapter.layer_matmul_params(config) == layers == 4_437_573_632
    norms = 16 * 2 * 4096 + 4 * 2 * 128 + 12 * 3 * 128 + 4096
    assert adapter.total_params(config) == layers + 2 * head + norms
    assert round(adapter.total_params(config) / 1e6, 1) == 5039.4
    w = adapter.widths(config)
    assert (w["sparse_layers"], w["lightning_layers"], w["first_layer"],
            w["published_layers"]) == (4, 12, 9, 32)
    assert (w["stride"], w["kernel"], w["block"], w["topk"],
            w["init_blocks"], w["local"], w["dense_len"]) \
        == (16, 32, 64, 64, 1, 2048, 8192)
    # a token: K and V of 4 layers x 2 heads x 128 x 2 B, and a sixteenth of
    # a compressed key a layer
    assert adapter.cache_bytes_per_token(config) == 4 * 1024 + 4 * 32 == 4224
    assert adapter.state_bytes_per_slot(config) == 12 * 32 * 128 * 128 * 4 \
        == 25_165_824                                       # 25.2 MB
    # a round, at the least: the weights and the head once, the rule's
    # matrix of all 17 rows of the 12 whole-layer updates read AND written,
    # and the rows the sparse semantics reads: never more than 64 pages
    slots = config["serve"]["num_slots"]
    least = 2 * (layers + head) + 2 * 4 * 32 * 128 * 128 * 12 * (slots + 1)
    assert adapter.decode_step_bytes(config, 0) == pytest.approx(least)
    row = 2 * 128 * 2
    at = lambda n: least + 4 * (2 * row * n + row * (n // 16))  # noqa: E731
    assert adapter.decode_step_bytes(config, 1000) == pytest.approx(at(1000))
    assert adapter.decode_step_bytes(config, 4096) == pytest.approx(at(4096))
    for cached in (4097, 8192, 32_768, 600_000):
        assert adapter.decode_step_bytes(config, cached) \
            == adapter.decode_step_bytes(config, 4096)
    grows = [adapter.decode_step_bytes(config, n) for n in (0, 64, 2048, 4096)]
    assert grows == sorted(grows) and len(set(grows)) == 4
    assert adapter.decode_step_flops(config, 16, 32_768) == pytest.approx(
        (2 * (layers + head) + 5 * 32 * 128 * 128 * 12) * 16
        + 4 * (2 * 32 * 2 * 128 * 4096 + 2 * 32 * 128 * 256))
    assert adapter.id_range(config) == (0, 73_448)
    assert adapter.positions(config) == 66_624 == 1041 * 64


def test_the_counts_are_the_programs_leaves(full):
    config, adapter = full
    model = adapter.make_model(config, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) \
        == adapter.total_params(config)
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) \
        == 2 * adapter.total_params(config)          # bfloat16 throughout
    spec_ = model.kv_cache_spec()
    assert (spec_.num_layers, spec_.state_layers, spec_.comp_stride) \
        == (4, 12, 16)
    assert spec_.bytes_per_slot == adapter.state_bytes_per_slot(config)
    assert spec_.bytes_per_token == adapter.cache_bytes_per_token(config)
    assert len(shapes["layers"]["ffn"]["gate"]) == 16
    assert shapes["layers"]["ffn"]["gate"][0].shape == (4096, 16384)
    assert len(shapes["layers"]["attn"]["g"]) == 4
    assert len(shapes["layers"]["lin"]["q"]) == 12
    assert model.c.mixer_types == tuple(PUBLISHED[9:25])
    assert model.c.max_position == 524_288
    # the decays use the PUBLISHED index: layer 0 here is published layer 9
    np.testing.assert_allclose(
        model.c.decay_rates(0)[-1], 2.0 ** -8 * (1 - 9 / 31 + 1e-5),
        rtol=1e-6)
    assert model.multipliers == pytest.approx({
        "embed": 12.0, "branch": 1.4 / 32 ** 0.5, "head": 1 / 16})
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
    with pytest.raises(ValueError, match="unrotated sparse layers"):
        adapter.make_model({**config, "attn_use_rope": True}, "serve")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every key of the catalog row's config under the same key and value,
    but for the keys listed as reduced; no width among them."""
    config, _ = full
    source = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "mixer_types": PUBLISHED, "num_attention_heads": 32,
        "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
        "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
        "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
        "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "mixer_types"}
    assert differs | {"serve.max_len"} == set(config["reduced"]) \
        == set(config["reduced_why"])
    entry = next(c for c in spec.manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    dep = config["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"],
            dep["num_hidden_layers_published"], dep["first_layer"]) \
        == (1, 2, 32, 9)
    # the cut: entries 9 to 24 of the published list, in the published ratio
    assert config["mixer_types"] == PUBLISHED[9:25]
    assert config["mixer_types"].count("minicpm4") * 3 \
        == config["mixer_types"].count("lightning-attn") == 12
    assert PUBLISHED.count("minicpm4") * 3 \
        == PUBLISHED.count("lightning-attn") == 24
    assert {"sparse_config", "block_score", "score", "dense_switch", "gates",
            "qk_norm", "output_norm", "lightning_decay", "lightning_chunk",
            "lightning_state_dtype", "norms", "init"} \
        <= set(config["assumed"])
    assert (config["serve"]["max_len"], config["serve"]["page_size"]) \
        == (66624, 64)
    assert config["serve"]["page_size"] \
        == config["assumed"]["sparse_config"]["block_size"]
    assert config["serve"]["num_slots"] in (16, 12)
    assert config["serve"]["prefill_chunk"] in (1024, 2048)
    assert config["n_embd"] == config["hidden_size"]


def test_the_traffic_is_the_issues_and_fits_the_positions_served():
    tr = spec.traffic("batch-context")
    assert (tr["kind"], tr["pool_requests"], tr["queue_depth_slots"]) \
        == ("backlog", 128, 2)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 16384,
                                "sigma": 0.8, "min": 2048, "max": 65536}
    assert tr["output_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.8, "min": 32, "max": 1024}
    lengths = schedule.backlog_lengths(tr)
    assert max(p + o for p, o in lengths) + 1 <= 66_624
    short = sum(p < 8192 for p, _ in lengths) / len(lengths)
    assert 0.15 < short < 0.25        # about a fifth take the dense branch
    prompts = sum(p for p, _ in lengths)
    answers = sum(o for _, o in lengths)
    assert 40 < prompts / answers < 80


def test_the_piecewise_reference_is_the_whole_reference(monkeypatch):
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    adapter = spec.adapter(config)
    model = adapter.make_model(config, "serve")
    assert model.c.sparse.dense_len == 48 and model.c.lightning_chunk == 8
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 504, (2, 77)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    # blocks that do not divide: short last blocks of the head, of a
    # feed-forward's rows and of a sparse layer's queries
    monkeypatch.setattr(adapter, "VOCAB_ROWS", 200)
    monkeypatch.setattr(adapter, "ROWS", 32)
    monkeypatch.setattr(adapter, "QUERY_ROWS", 20)
    adapter._JITS.clear()
    pieces = adapter.reference_logits(params, ids, config)
    # at length: half the queries and two Lightning heads at a time
    monkeypatch.setattr(adapter, "LONG", 64)
    monkeypatch.setattr(adapter, "HEADS_AT_LENGTH", 2)
    np.testing.assert_allclose(
        adapter.reference_logits(params, ids, config), pieces, rtol=2e-5,
        atol=2e-5)
    monkeypatch.setattr(adapter, "LONG", 16384)
    assert pieces.dtype == np.float32 and pieces.shape == (2, 77, 504)
    np.testing.assert_allclose(pieces, whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(adapter.system_logits(model, params, ids),
                               whole, rtol=2e-4, atol=2e-4)
    # the rows asked for alone, a prompt's length, and the choices
    choices = {}
    some = adapter.reference_logits(params, ids, config, rows=slice(39, 50),
                                    prompt_len=40, choices=choices)
    short = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d, 40))(
        params, ids))
    np.testing.assert_allclose(some, short[:, 39:50], rtol=2e-5, atol=2e-5)
    # (at the rehearsal's sizes dense_len = topk x block, so a token under
    # the dense length sees no more blocks than it reads and the switch
    # moves nothing: that is what lets check.py's whole-sequence reference
    # stand for the engine's per-request switch there)
    np.testing.assert_array_equal(short, whole)
    assert sorted(choices) == [0, 1]
    firsts = [lo for lo, _ in choices[0]]
    assert firsts == [0, 20, 40, 60]
    masks = np.concatenate([m for _, m in choices[1]], 2)  # [2, g, 77, nb]
    assert masks.shape == (2, 2, 77, 10)
    assert (masks.sum(-1) == np.minimum(6, np.arange(77) // 8 + 1)).all()
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
    assert set(line["detail"]["check"]["limits"]) == {"logit_err",
                                                      "token_gap"}
    # the rehearsal's prompts reach the sparse branch (100-333 tokens over
    # a dense length of 48)
    assert max(line["detail"]["check"]["prompts"]) > 48
    assert line["metric_names"] == ["serve_tokens_per_s", "setup_s"]


def test_check_rows_reads_the_check_a_row_and_a_layer_at_a_time(capsys):
    """``tools/check_rows.py`` at rehearsal widths (float32): every checked
    row of every request caught from the engine's own programs and as near
    the reference as the dense forward's, and the stream of request 0 a
    layer, its Lightning layers with position 0's products."""
    from benchmarks.tools import check_rows

    assert check_rows.main([CELL, "--rehearse", "--layers", "0",
                            "3000000019"]) == 0
    first, *layers = [json.loads(l) for l in
                      capsys.readouterr().out.strip().splitlines()]
    assert first["logit_err"] < 1e-5 and first["token_gap"] < 1e-5
    for one in first["requests"]:
        assert one["engine_rows_caught"] == 9
        assert one["eng_max"][1] < 1e-5 and one["eng_rms"][1] < 1e-5
    config = spec.config(spec.manifest(), "minicpm-sala", rehearse=True)
    assert [l["kind"] for l in layers] == config["mixer_types"]
    for l in layers:
        assert l["largest"] < 1e-5
        assert ("first_products_nearest_zero" in l) \
            == (l["kind"] == "lightning-attn")


SERVING = ("decode_step_ms", "prefill_chunk_ms", "decode_roofline",
           "decode_batch_mean", "engine_compiles", "device_idle_share.serve",
           "hbm_heap_gb.serve", "hbm_stack_gb.serve", "decode_host_ms",
           "prefill_host_ms", "sched_host_ms", "host_gap_share",
           "decode_program_ms", "prefill_program_ms", "decode_issue_ms",
           "decode_runtime_ms", "decode_readback_ms",
           "window_decode_fetch_ms", "window_decode_launch_ms",
           "window_decode_host_ms", "window_chunk_fetch_ms",
           "window_outside_ms", "window_stall_share")
MINE = ("sparse_attn_time_share.batch-context",
        "sparse_select_time_share.batch-context",
        "lightning_time_share.batch-context",
        "dense_ffn_time_share.batch-context", "sparse_blocks_read_share")


def test_five_new_entries_and_the_accepted_ones_this_cell_is_appended_to():
    """Five entries of its own at the end, and the cell LAST in the
    ``workloads`` of the serving family's 23 entries and of
    ``state_bytes_share.batch-docs``; 77 entries, 11 cells, 10
    configurations."""
    man = spec.manifest()
    assert (len(man["per_layer"]), len(man["workloads"]),
            len(man["configs"])) == (77, 11, 10)
    mine = [m for m in man["per_layer"] if m["workloads"] == [CELL]]
    assert tuple(m["name"] for m in mine) == MINE
    assert man["per_layer"][-5:] == mine
    for m in mine:
        assert (m["unit"], m["moves"]) == ("%", "serve_tokens_per_s")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["layer"] for m in mine] == ["model"] * 4 + ["serving engine"]
    appended = list(SERVING) + ["state_bytes_share.batch-docs"]
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in appended:
        assert by_name[name]["workloads"][-1] == CELL, name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in spec.metrics_of(man["per_layer"], CELL)} \
        == set(appended) | set(MINE)
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


def _recorded():
    """(share of busy time in %, the event's whole name, its scope) of the
    operations of a traced run of the cell on the chip (my chip run, PR 56):
    ``tools/top_ops.py``'s list, each operation's scope looked up by its
    name in the programs compiled for a described v5e
    (``tools/compile_v5e_sparse.py --hlo``; ``?`` where the chip's bucket
    was not among those compiled or the instruction carries none)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "minicpm_sala_batch_context_ops.txt")
    for line in open(path):
        share, scope, name = line.rstrip("\n").split("\t")
        yield float(share), name, scope


OWN = {"sparse_attn_time_share.batch-context": ("hetu.sparse.",),
       "sparse_select_time_share.batch-context": ("hetu.sparse.compress",
                                                  "hetu.sparse.select"),
       "lightning_time_share.batch-context": ("hetu.lightning.",),
       "dense_ffn_time_share.batch-context": ("hetu.ffn.dense",)}


@pytest.mark.parametrize("metric", sorted(OWN))
def test_a_pattern_takes_its_own_scopes_and_no_other(metric):
    """Over the recorded operations: one whose instruction carries another
    mixer's or the feed-forward's scope is never counted, and little of the
    busy time inside the metric's own scopes is missed.  A pattern reads
    result and operand SHAPES and the parameters' names: the trace's event
    name is the instruction without its metadata, so a scope is not there
    to anchor on."""
    rx = re.compile(spec.layer_metric_file(metric)["params"]["pattern"])
    others = tuple(s for scopes in OWN.values() for s in scopes
                   if not any(s.startswith(o) or o.startswith(s)
                              for o in OWN[metric]))
    taken = inside = missed = seen = 0.0
    for share, name, scope in _recorded():
        seen += share
        hit = bool(rx.search(name))
        own = any(s in scope for s in OWN[metric])
        assert not (hit and any(s in scope for s in others)
                    and not own), (scope, name[:200])
        taken += share if hit else 0.0
        inside += share if own else 0.0
        missed += share if own and not hit else 0.0
    assert seen > 90.0
    assert taken > 0.5 * inside > 0.0, (taken, inside)
    assert missed < 0.25 * inside + 0.5, (missed, inside)
