"""The seam between the harness and an architecture, at tiny widths on the
CPU, for GPT-2 (``benchmarks/arch/gpt2.py``) and for a second architecture
that is no benchmark entry (``arch_llama.py``, ``reference_llama.py`` and
``data/llama-tiny.json`` beside this file).  The second one is added by
those three files and by entries of a manifest made here; that it trains,
serves and is ``correct`` through ``run.py`` shows that nothing under
``harness/``, ``readers/`` or in ``run.py`` has to know an architecture."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import build, check, device, loops, spec
from benchmarks.harness.spans import Recorder

LLAMA = {"name": "llama-tiny", "source": "test fixture",
         "file": "benchmarks/tests/data/llama-tiny.json", "reduced": [],
         "why": "the seam's second architecture"}
# architecture -> (configuration, its training cell, its backlog cell)
ARCHS = {"gpt2": ("gpt2-small", "gpt2-small.train", "gpt2-large.batch"),
         "llama": ("llama-tiny", "llama-tiny.train", "llama-tiny.batch")}
F32_LOGIT_TOL = 1e-4    # both sides float32: the order of operations only


@pytest.fixture
def man(monkeypatch):
    """``BENCHMARK.json`` plus the second architecture's configuration and
    two cells over traffic files that are there: entries, no edit."""
    real = spec.manifest()
    cells = [{"name": "llama-tiny.train", "config": "llama-tiny",
              "traffic": "train-64x1024", "chips": 1, "why": "seam"},
             {"name": "llama-tiny.batch", "config": "llama-tiny",
              "traffic": "batch", "chips": 1, "why": "seam"}]
    both = {**real, "configs": real["configs"] + [LLAMA],
            "workloads": real["workloads"] + cells}
    for m in ("end_to_end", "per_layer"):
        both[m] = [dict(e) for e in real[m]]
        for e in both[m]:
            if "gpt2-small.train" in e.get("workloads", ()):
                e["workloads"] = e["workloads"] + ["llama-tiny.train"]
            if "gpt2-large.batch" in e.get("workloads", ()):
                e["workloads"] = e["workloads"] + ["llama-tiny.batch"]
    monkeypatch.setattr(spec, "manifest", lambda: both)
    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):   # --rehearse sets them
        monkeypatch.setenv(var, os.environ.get(var, ""))
    return both


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("kind", ["train", "backlog"])
def test_a_cell_runs_through_run_py_and_is_correct(man, capsys, arch, kind):
    cell = ARCHS[arch][1 if kind == "train" else 2]
    rc = bench.main(["--workload", cell, "--seconds", "1", "--seed",
                     "3000000019", "--rehearse"])
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] and line["failed"] == 0, err
    verdict = line["detail"]["check"]
    assert verdict["ok"] and line["detail"]["compiles_in_window"] == 0
    numbers = {"train": {"loss_rel", "grad_norm_rel"},
               "backlog": {"logit_err", "token_gap"}}[kind]
    assert set(verdict["limits"]) == numbers
    # each number compared stands beside its limit on standard error
    for n in numbers:
        assert f"check {n} = {verdict[n]} (limit {verdict['limits'][n]})" \
            in err
    assert line["attempted"] > 0 and len(line["metric_names"]) == 2


def _tiny(man, arch, dtype=None):
    config = spec.config(man, ARCHS[arch][0], rehearse=True)
    if dtype:
        config["compute_dtype"] = dtype
    return config, spec.adapter(config)


def _serving(config, adapter, seed=7):
    model = adapter.make_model(config, "serve")
    variables = jax.jit(model.init)(build.key_for(seed))
    engine, scheduler = build.make_serving(model, variables, config)
    return model, variables, engine, scheduler


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_a_wrong_cache_read_fails_the_serving_check(man, monkeypatch, arch):
    """Through the adapter, whatever the architecture: a decode that reads
    one page too few of its cache emits tokens the reference ranks far from
    best, and the sound engine passes."""
    config, adapter = _tiny(man, arch)
    model, variables, engine, scheduler = _serving(config, adapter)
    sound = check.serving(model, variables, engine, scheduler, config, 7)
    assert sound["ok"], sound

    model, variables, engine, scheduler = _serving(config, adapter)
    inner = model.decode_with_cache
    monkeypatch.setattr(
        model, "decode_with_cache",
        lambda v, ids, k, vv, lengths: inner(
            v, ids, k, vv, jnp.maximum(lengths - 16, 0)))
    broken = check.serving(model, variables, engine, scheduler, config, 7)
    assert not broken["ok"]
    assert broken["token_gap"] > 3 * broken["limits"]["token_gap"]


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_bf16_where_f32_is_stated_fails_at_f32_tolerance(man, arch):
    errs = {}
    ids = np.random.default_rng(0).integers(0, 500, (3, 48)).astype(np.int32)
    for dtype in ("float32", "bfloat16"):
        config, adapter = _tiny(man, arch, dtype)
        model = adapter.make_model(config, "serve")
        params = jax.jit(model.init)(build.key_for(3))["params"]
        ref = adapter.reference_logits(params, ids, config)
        got = adapter.system_logits(model, params, ids)
        assert ref.dtype == got.dtype == np.float32
        assert ref.shape == got.shape == (3, 48, 512)
        errs[dtype] = np.max(np.abs(ref - got)) / (ref.max() - ref.min())
    assert errs["float32"] < F32_LOGIT_TOL < 1e-3 < errs["bfloat16"]


def test_a_step_left_out_of_the_batch_fails_the_training_check(man):
    """The training check's teeth, through the second architecture: the
    system's loss over HALF of the sequences the reference sees."""
    config, adapter = _tiny(man, "llama")
    model = adapter.make_model(config, "train")
    params = jax.jit(model.init)(build.key_for(5))["params"]
    ids = np.random.default_rng(1).integers(0, 500, (4, 64)).astype(np.int32)
    assert check.training(model, params, params, config, ids)["ok"]
    loss_fn = model.lm_loss_fn()
    model.lm_loss_fn = lambda: (
        lambda p, s, batch, rng, train: loss_fn(
            p, s, (batch[0][:2],), rng, train))
    broken = check.training(model, params, params, config, ids)
    assert not broken["ok"]
    assert broken["loss_rel"] > 3 * broken["limits"]["loss_rel"]


def test_the_second_architectures_counts_by_hand(man):
    """64 wide, 4 heads over 2 key/value heads, SwiGLU of 128, 2 layers, an
    untied head over 500 ids."""
    config, adapter = _tiny(man, "llama")
    per_layer = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128
    assert adapter.matmul_params(config) == 2 * per_layer + 500 * 64 == 105_728
    assert adapter.train_flops_per_token(config, 64) \
        == 6 * 105_728 + 6 * 2 * 64 * 64 == 683_520
    assert adapter.decode_step_bytes(config, 100) \
        == 2 * (105_728 + 2 * 2 * 32 * 100)
    shapes = jax.eval_shape(adapter.make_model(config, "serve").init,
                            jax.random.PRNGKey(0))["params"]
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    # the program pads the vocabulary to 512 rows, twice (untied)
    assert held - adapter.total_params(config) == 2 * 12 * 64
    assert adapter.id_range(config) == (0, 500)
    assert adapter.positions(config) == 128


def test_readers_count_by_the_cells_own_adapter(man):
    """``mfu_pct`` of a run is the adapter's operations per token times the
    run's rate: the same tokens and window read differently under the two
    architectures' configurations."""
    run = loops.Run(end_to_end={}, values={"tokens": 4096, "window_s": 2.0,
                                           "seq": 64, "batch": 4},
                    attempted=1, failed=0, check={}, compiles_in_window=0,
                    setup_done=0.0)
    peaks = device.peaks("TPU v5 lite")
    got = {}
    for arch in ARCHS:
        config, adapter = _tiny(man, arch)
        rctx = bench.ReadCtx(run=run, rec=Recorder(), trace=None,
                             config=config, traffic={}, cell={"chips": 1},
                             peaks=peaks, memory={})
        got[arch] = bench.read_layer_metrics(
            rctx, [{"name": "mfu_pct", "unit": "%"}])["mfu_pct"]["value"]
        assert got[arch] == pytest.approx(
            100 * adapter.train_flops_per_token(config, 64) * 2048
            / peaks["bf16_flops"])
    assert got["gpt2"] != got["llama"]


def test_the_harness_names_no_architecture():
    """No file of the harness, of the readers, or ``run.py`` reads a key
    that only GPT-2's configuration has, or names its model."""
    files = sorted((spec.BENCH / "harness").glob("*.py")) \
        + sorted((spec.BENCH / "readers").glob("*.py")) \
        + [spec.BENCH / "run.py"]
    assert len(files) > 25
    pattern = re.compile(
        r"gpt|GPT|n_embd|n_head|n_layer|n_positions|vocab_size|llama")
    found = [(f.name, n, line.strip()) for f in files
             for n, line in enumerate(f.read_text().splitlines(), 1)
             if pattern.search(line)]
    assert not found, found
