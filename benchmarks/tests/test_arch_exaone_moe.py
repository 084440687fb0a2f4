"""K-EXAONE's adapter (``benchmarks/arch/exaone_moe.py``): its counts against
numbers written out by hand, the blockwise reference against the whole one,
the new cell through ``run.py`` at rehearsal widths, paths of a lower
precision than the configuration states failing its limits, and the new
per-layer metric read off a recorded trace."""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, check, spec

CELL = "k-exaone-236b-a23b.batch-mixed"
NAME = "k-exaone-236b-a23b"


@pytest.fixture(scope="module")
def full():
    config = spec.config(spec.manifest(), NAME)
    return config, spec.adapter(config)


def tiny():
    config = spec.config(spec.manifest(), NAME, rehearse=True)
    return config, spec.adapter(config)


def test_the_counts_by_hand(full):
    """6144 wide, 64 query and 8 KV heads of 128, dense FFN 18432, experts
    of 2048, a router of 128; 5 layers (one dense, one full), 16 experts and
    19200 rows of the vocabulary held."""
    config, adapter = full
    attention = 6144 * (8192 + 2 * 1024) + 8192 * 6144
    assert adapter.attention_params(config) == attention == 113_246_208
    assert adapter.expert_params(config) == 3 * 6144 * 2048 == 37_748_736
    dense = 5 * attention + 3 * 6144 * 18432 \
        + 4 * (37_748_736 + 6144 * 128)
    assert adapter.dense_params(config) == dense == 1_060_110_336
    head = 19200 * 6144
    norms = 5 * (2 * 6144 + 2 * 128) + 6144
    total = dense + 4 * (16 * 37_748_736 + 128) + 2 * head + norms
    assert adapter.total_params(config) == total == 3_712_028_416
    assert adapter.cache_bytes_per_token(config) == 2 * 8 * 128 * 2 == 4096
    # a decode step reads the dense weights and the head once; a cached
    # token's 4096 B in the one full layer, and in the four window layers
    # the last 128 rows of each of at most 16 slots; hit experts left out
    weights = 2 * (dense + head)
    assert adapter.decode_step_bytes(config, 0) == weights == 2_356_150_272
    assert adapter.decode_step_bytes(config, 1000) \
        == weights + 4096 * (1000 + 4 * 1000)
    assert adapter.decode_step_bytes(config, 50_000) \
        == weights + 4096 * (50_000 + 4 * 16 * 128)
    # 8 choices over 128, 16 held: one expert a token a sparse layer
    assert adapter.expected_held_pairs(config) == 1.0
    per_token = dense + head + 4 * 37_748_736
    assert adapter.token_matmul_params(config) == per_token == 1_329_070_080
    assert adapter.decode_step_flops(config, 16, 50_000) \
        == 2 * per_token * 16 + 2 * 64 * 256 * (50_000 + 4 * 2048)
    assert adapter.train_flops_per_token(config, 1024) \
        == 6 * per_token + 3 * 64 * 256 * (1024 + 4 * 2 * 128)
    assert adapter.id_range(config) == (0, 19200)
    assert adapter.positions(config) == 33792
    assert adapter.attention_call_shape(config, {"batch": 2, "seq": 64}) \
        == (2, 64, 64, 128)


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
    assert held == 7_430_349_312
    assert model.c.held == (48, 16)
    assert model.c.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention", "sliding_attention")
    groups = model.kv_cache_spec().groups
    assert [(g.num_layers, g.window, g.bytes_per_token) for g in groups] \
        == [(1, None, 4096), (4, 128, 4 * 4096)]
    assert [g.ring_pages(rows, 128) for g in groups for rows in (512, 1)] \
        == [None, None, 6, 2]
    with pytest.raises(ValueError, match="no 'train' section"):
        adapter.make_model(config, "train")


def test_the_configuration_keeps_the_catalogs_numbers(full):
    """Every number of the source's config under the same key, but for the
    keys listed as reduced; no width among them; the lists kept whole."""
    config, _ = full
    source = {"first_k_dense_replace": 1, "head_dim": 128,
              "hidden_size": 6144, "intermediate_size": 18432,
              "max_position_embeddings": 262144,
              "moe_intermediate_size": 2048, "n_group": 1,
              "num_attention_heads": 64, "num_experts": 128,
              "num_experts_per_tok": 8, "num_hidden_layers": 48,
              "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
              "num_shared_experts": 1, "rms_norm_eps": 1e-05,
              "routed_scaling_factor": 2.5, "sliding_window": 128,
              "topk_group": 1, "vocab_size": 153600}
    differs = {k for k, v in source.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs | {"serve.max_len"} == set(config["reduced"])
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) \
        == len(config["sliding_windows"]) == 48
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert (config["scoring_func"], config["norm_topk_prob"],
            config["tie_word_embeddings"]) == ("sigmoid", True, False)
    dep = config["deployment"]
    assert (dep["num_experts_published"], dep["num_hidden_layers_published"],
            dep["vocab_size_published"], dep["chips_sharing_a_layer"]) \
        == (128, 48, 153600, 8)
    assert "next-token-prediction module" in config["scope"]
    assert {"norm_placement", "rope_layers", "rope_layout",
            "router_bias_std"} <= set(config["assumed"])


def test_the_traffic_is_the_issues(full):
    from benchmarks.harness import schedule

    tr = spec.traffic("batch-mixed")
    assert tr["kind"] == "backlog" and tr["pool_requests"] == 256
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                "sigma": 1.4, "min": 128, "max": 32768}
    assert tr["output_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.8, "min": 16, "max": 1024}
    lengths = schedule.backlog_lengths(tr)
    prompts = np.array([p for p, _ in lengths])
    assert (prompts < 800).mean() == 0.25
    assert 0.09 < (prompts > 12000).mean() < 0.11
    assert (prompts == 32768).sum() == 6
    # no request of the fixed pool runs into the 33792 positions served
    assert max(p + o for p, o in lengths) < 33792 - 1


def test_the_blockwise_reference_is_the_whole_reference(monkeypatch):
    config, adapter = tiny()
    model = adapter.make_model(config, "serve")
    params = jax.jit(model.init)(build.key_for(3))["params"]
    ids = np.random.default_rng(0).integers(0, 504, (2, 45)).astype(np.int32)
    ref, d = adapter.reference(config), adapter.dims(config)
    whole = np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))
    blocks = adapter.reference_logits(params, ids, config)
    assert blocks.dtype == np.float32 and blocks.shape == (2, 45, 504)
    np.testing.assert_allclose(blocks, whole, rtol=2e-5, atol=2e-5)
    # sixteen rows at a time and one KV head's tiles, as a 30,000-token
    # comparison forces: the last block of rows is a short one, the last
    # tile ends with the rows
    monkeypatch.setattr(adapter, "ROWS", 16)
    monkeypatch.setattr(adapter, "SCORES_BYTES", 4 * 2 * 2 * 16 * 16)
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
    ``benchmarks/tools/check_control.py``, PERF.md.)"""
    ids = np.random.default_rng(0).integers(0, 504, (3, 48)).astype(np.int32)
    errs = {}
    for dtype in ("float32", "bfloat16"):
        config, adapter = tiny()
        params = jax.jit(adapter.make_model(config, "serve").init)(
            build.key_for(3))["params"]
        model = adapter.make_model({**config, "compute_dtype": dtype},
                                   "serve")
        ref = adapter.reference_logits(params, ids, config)
        got = adapter.system_logits(model, params, ids)
        assert ref.shape == got.shape == (3, 48, 504)
        errs[dtype] = float(np.max(np.abs(ref - got)) / (ref.max() - ref.min()))
    assert errs["float32"] < 1e-4 < 1e-3 < errs["bfloat16"], errs


def test_a_wrong_window_read_fails_the_serving_check(monkeypatch):
    from hetu_tpu import ops

    config, adapter = tiny()
    model = adapter.make_model(config, "serve")
    variables = jax.jit(model.init)(build.key_for(7))
    engine, scheduler = build.make_serving(model, variables, config)
    assert check.serving(model, variables, engine, scheduler, config, 7)["ok"]
    engine, scheduler = build.make_serving(model, variables, config)
    decode = ops.decode_attention
    monkeypatch.setattr(
        ops, "decode_attention",
        lambda q, k, v, lengths, *, window=None, **kw: decode(
            q, k, v, lengths if window is None
            else jnp.maximum(lengths - 3, 0), window=window, **kw))
    broken = check.serving(model, variables, engine, scheduler, config, 7)
    assert not broken["ok"]
    assert broken["token_gap"] > 3 * broken["limits"]["token_gap"]


def test_kv_pages_held_share_reads_the_post_spans_ids(tmp_path):
    """The new metric off a trace recorded here: the grouped cache's ids on
    the ``post`` spans, full + window pages over what one group would hold,
    in percent; a trace without them (a one-group model's, the parent's)
    gives nothing."""
    import importlib

    from hetu_tpu.serve import Request

    def trace_of(model, variables, config, where):
        engine, scheduler = build.make_serving(model, variables, config)
        low, high = adapter.id_range(config)
        rng = np.random.default_rng(5)
        reqs = [Request(prompt=rng.integers(low, high, n).tolist(),
                        max_tokens=6) for n in (70, 9, 33)]
        with jax.profiler.trace(str(where)):
            scheduler.run(reqs)
        assert all(r.status == "ok" for r in reqs)
        return str(sorted(where.glob("plugins/profile/*/*.xplane.pb"))[-1])

    f = spec.layer_metric_file("kv_pages_held_share.batch-mixed")
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    config, adapter = tiny()
    model = adapter.make_model(config, "serve")
    variables = jax.jit(model.init)(build.key_for(7))
    path = trace_of(model, variables, config, tmp_path / "grouped")
    share = reader.read(SimpleNamespace(run=SimpleNamespace(trace_path=path)),
                        **f["params"])
    # 5 cache layers; a 70-token slot holds 18 full pages and at most 5 x 4
    # window pages of a one-group cache's 90
    assert 20.0 < share < 80.0
    assert reader.read(SimpleNamespace(run=SimpleNamespace(trace_path=None)),
                       **f["params"]) is None
    gpt = spec.config(spec.manifest(), "gpt2-small", rehearse=True)
    model = spec.adapter(gpt).make_model(gpt, "serve")
    adapter = spec.adapter(gpt)
    path = trace_of(model, jax.jit(model.init)(build.key_for(7)), gpt,
                    tmp_path / "one")
    assert reader.read(
        SimpleNamespace(run=SimpleNamespace(trace_path=path)),
        **f["params"]) is None


def test_the_control_rounds_what_the_program_computes():
    """``benchmarks/tools/check_control.py``: a function walked by
    ``lowered`` computes what it computes with every bfloat16 result
    rounded by hand, through a loop, a branch and a nested jit; float32
    values pass untouched; and the rehearsal-size model's forward moves by
    far more than bfloat16's own rounding."""
    from benchmarks.tools import check_control as cc

    low = cc.three_mantissa_bits
    x = jnp.linspace(-3.0, 3.0, 64).astype(jnp.bfloat16).reshape(8, 8)
    w = (jnp.arange(64.0).reshape(8, 8) / 37 - 0.8).astype(jnp.bfloat16)
    assert float(low(jnp.bfloat16(1.0625))) in (1.0, 1.125)
    assert float(jnp.max(jnp.abs(low(x).astype(jnp.float32)
                                 - x.astype(jnp.float32)
                                 ) / jnp.abs(x.astype(jnp.float32)))) <= 2 ** -4

    def f(x, w):
        def step(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(step, x, None, length=2)
        y = jax.lax.cond(y[0, 0] > 10, lambda a: a * 2, lambda a: a + 1, y)
        return jax.jit(lambda a: a * a)(y), y.astype(jnp.float32) / 3

    def by_hand(x, w):
        y = x
        for _ in range(2):
            y = low(jnp.tanh(low(low(y) @ low(w))))
        y = low(y + 1)
        return low(y * y), low(y).astype(jnp.float32) / 3

    got, want = jax.jit(cc.lowered(f, True))(x, w), by_hand(x, w)
    assert [g.dtype for g in got] == [h.dtype for h in want]
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)   # float32: / 3
    only = jax.jit(cc.lowered(f, False))(x, w)      # matmul operands alone
    assert not np.array_equal(np.asarray(only[0], np.float32),
                              np.asarray(got[0], np.float32))

    config, adapter = tiny()
    config = {**config, "compute_dtype": "bfloat16"}
    ids = np.random.default_rng(1).integers(0, 504, (2, 40)).astype(np.int32)
    model = adapter.make_model(config, "serve")
    params = jax.jit(model.init)(build.key_for(5))["params"]
    ref = adapter.reference_logits(params, ids, config)
    span = float(ref.max() - ref.min())
    stated = np.max(np.abs(adapter.system_logits(model, params, ids) - ref))
    model.apply = cc.lowered(model.apply, True)
    control = np.max(np.abs(adapter.system_logits(model, params, ids) - ref))
    assert control / span > 3 * stated / span > 0, (stated, control, span)


# ------------------------------- the time shares over recorded operations

SHARES = {"hetu.attn.full": "attn_full_time_share",
          "hetu.attn.window": "attn_window_time_share",
          "hetu.moe.": "moe_time_share",
          "hetu.ffn.dense": "dense_ffn_time_share"}


def _recorded():
    """(share of busy time in %, the event's whole name, its scopes) of the
    400 operations with the most own time in a traced run of the cell on
    the v5e (PR 53, call 1, seed 5300000031: 99.7% of the chip's busy
    time), the scope by the ``op_name`` of the instruction with the same
    result types, op kind and operand count in the chunk and decode
    programs compiled for a described v5e over the tree the engine holds:
    ``outside`` where the model computes it outside its scopes, ``a|b``
    where instructions of two scopes share that key (the out-projections,
    which XLA fuses with the residual add and the next norm), ``?`` where
    none has it."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "kexaone_batch_mixed_ops.txt")
    for line in open(path):
        share, scope, name = line.rstrip("\n").split("\t")
        yield float(share), name, scope.split("|")


def test_the_time_share_patterns_take_their_own_scopes_and_no_other():
    """``attn_full_time_share.batch-mixed`` counts, since PR 53, the decode
    round's paged kernel BY NAME (``_attend.N`` in the trace; PR 33's
    traces read ``hetu.attn.full.N``, the pattern takes both) beside the
    chunk program's flash call, which it catches by its operand, the
    view.  Over the recorded operations: none is counted in two shares,
    one whose instruction carries ANOTHER share's scope is not taken, and
    the full layer's share is the chunk call's and the decode kernel's."""
    import re

    rx = {m: re.compile(spec.layer_metric_file(f"{m}.batch-mixed")
                        ["params"]["pattern"]) for m in SHARES.values()}
    taken, kernels = dict.fromkeys(rx, 0.0), {}
    for share, name, scopes in _recorded():
        hit = {m for m in rx if rx[m].search(name)}
        assert len(hit) < 2, (hit, name)
        own = {m for s in scopes for pre, m in SHARES.items()
               if s.startswith(pre)}
        if own:
            assert hit <= own, (hit, scopes, name)
        for m in hit:
            taken[m] += share
        kernel = re.match(r"%(_attend|_flash_chunk)", name)
        if kernel:
            assert hit == {"attn_full_time_share"}, name
            kernels[kernel.group(1)] = kernels.get(kernel.group(1), 0) + share
    assert 2.0 < kernels["_attend"] < 3.0 and 3.5 < kernels["_flash_chunk"]
    assert 7.0 < taken["attn_full_time_share"] < 8.0, taken
    assert kernels["_attend"] + kernels["_flash_chunk"] \
        > 0.8 * taken["attn_full_time_share"]
    assert 1.0 < taken["attn_window_time_share"] < 3.0, taken
    assert taken["moe_time_share"] > 50.0, taken
    assert 8.0 < taken["dense_ffn_time_share"] < 11.0, taken
