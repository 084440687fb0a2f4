"""The contract of a chip run, checked without a chip: a run that finds no
TPU fails instead of falling back, the compile cache lives where it is
told to, one process per chip, the native library is built from the
sources on disk, and no kernel gives way to another implementation.
"""

import os
import subprocess
import sys
import threading
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _py(code: str, **env) -> subprocess.CompletedProcess:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", code], env=full, cwd="/",
                          capture_output=True, text=True, timeout=120)


# ---------------------------------------------------------- compile cache

_CACHE_PROBE = ("import jax; from hetu_tpu.utils.platform import "
                "enable_compile_cache as e; "
                "print(e(), jax.config.jax_compilation_cache_dir)")


def test_cache_helper_leaves_an_outside_directory_alone(tmp_path):
    r = _py(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr
    # returned AND in force: the env var's directory, nothing set in code
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_cache_helper_same_in_checkout_path_from_two_processes():
    outs = [_py(_CACHE_PROBE) for _ in range(2)]
    assert all(r.returncode == 0 for r in outs), outs[0].stderr
    want = str(REPO / ".jax_cache")
    assert [r.stdout.split() for r in outs] == [[want, want]] * 2


def test_only_the_helper_sets_the_cache_directory():
    roots = [REPO / d for d in ("hetu_tpu", "examples", "tools", "tests")]
    files = [f for r in roots for f in r.rglob("*.py")] + list(
        REPO.glob("*.py"))
    hits = sorted(str(f.relative_to(REPO)) for f in files
                  if "jax_compilation_cache_dir" in f.read_text())
    assert hits == ["hetu_tpu/utils/platform.py",
                    "tests/test_chip_contract.py"]


# ------------------------------------------------------------ peaks table

def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_detect_chip_raises_on_an_unknown_tpu_kind():
    from hetu_tpu.profiler.cost_model import CHIPS, chip_for_device
    with pytest.raises(ValueError, match="TPU v9 mega"):
        chip_for_device(_dev("tpu", "TPU v9 mega"))
    assert chip_for_device(_dev("tpu", "TPU v5 lite")) is CHIPS["v5e"]
    # exact match, not a prefix catch-all: an unlisted v5 variant is unknown
    with pytest.raises(ValueError, match="TPU v5 ultra"):
        chip_for_device(_dev("tpu", "TPU v5 ultra"))
    assert chip_for_device(_dev("cpu", "cpu")) is CHIPS["cpu"]


# ------------------------------------------------------------- no fallback

def test_flash_with_a_mask_raises():
    from hetu_tpu.layers.attention import MultiHeadAttention
    mha = MultiHeadAttention(32, 4, attention_impl="flash")
    v = mha.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 16, 32))
    with pytest.raises(ValueError, match="no explicit mask"):
        mha.apply(v, x, mask=jnp.ones((16, 16)))
    y, _ = mha.apply(v, x)  # unmasked flash still runs
    assert y.shape == x.shape


def test_auto_interpret_refuses_other_backends(monkeypatch):
    from hetu_tpu.utils import platform
    assert platform.auto_interpret(None) is True      # the CPU test backend
    assert platform.auto_interpret(False) is False    # explicit wins
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        platform.auto_interpret(None)


def test_executor_int8_grad_sync_takes_a_step():
    import hetu_tpu as ht
    from hetu_tpu import optim

    def loss_fn(params, model_state, batch, rng, train):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2), ({}, model_state)

    ex = ht.Executor(loss_fn, optim.SGDOptimizer(0.1),
                     mesh=ht.make_mesh(dp=8), grad_sync="int8")
    state = ex.init_state({"params": {"w": jnp.ones((4, 2))}})
    batch = (np.ones((16, 4), np.float32), np.zeros((16, 2), np.float32))
    state, m0 = ex.run("train", state, batch)
    state, m1 = ex.run("train", state, batch)
    assert float(m1["loss"]) < float(m0["loss"])


def test_first_step_under_a_mesh_compiles_once():
    """Placement must match the step's own output shardings, or the second
    call recompiles the whole train step (a minute on the chip)."""
    import hetu_tpu as ht
    from hetu_tpu import optim
    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.parallel.strategies.simple import MegatronLM

    model = GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                               num_heads=4, ffn_size=64, max_position=16,
                               dropout_rate=0.0))
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamWOptimizer(1e-3),
                     mesh=ht.make_mesh(dp=2, tp=2),
                     dist_strategy=MegatronLM())
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))
    batch = (np.zeros((4, 16), np.int32),)
    for _ in range(3):
        state, _ = ex.run("train", state, batch)
    assert ex._compiled["train"]._cache_size() == 1


# ------------------------------------------------------ one process per chip

def _pool(**kw):
    from hetu_tpu.serve.crosshost import CrossProcessServingPool
    return CrossProcessServingPool(2, **kw)


def test_pool_refuses_more_device_members_than_chips(tmp_path, monkeypatch):
    from hetu_tpu.utils import platform
    monkeypatch.setattr(platform, "local_tpu_chips", lambda: 1)
    spawned = []
    monkeypatch.setattr("hetu_tpu.resilience.shardproc.spawn_module",
                        lambda *a, **k: spawned.append(a))
    with pytest.raises(ValueError, match="2 members need one TPU chip each"):
        _pool(workdir=str(tmp_path), member_env={"JAX_PLATFORMS": ""})
    assert not spawned  # loudly, BEFORE spawning


def test_pool_pins_member_i_to_chip_i(tmp_path, monkeypatch):
    from hetu_tpu.utils import platform
    monkeypatch.setattr(platform, "local_tpu_chips", lambda: 4)
    monkeypatch.setattr(platform, "backend_initialized", lambda: False)
    envs, sleepers = [], []

    def fake_spawn(workdir, tag, module, args, *, extra_env=None, **kw):
        envs.append(extra_env)
        if len(envs) == 2:
            raise RuntimeError("stop after both spawn environments")
        sleepers.append(subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"]))
        return sleepers[-1]

    monkeypatch.setattr("hetu_tpu.resilience.shardproc.spawn_module",
                        fake_spawn)
    try:
        with pytest.raises(RuntimeError, match="stop after both"):
            _pool(workdir=str(tmp_path), member_env={"JAX_PLATFORMS": ""})
    finally:
        for p in sleepers:
            p.kill()
            p.wait(10)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)


def test_pool_controller_must_not_hold_a_backend(tmp_path, monkeypatch):
    from hetu_tpu.utils import platform
    monkeypatch.setattr(platform, "local_tpu_chips", lambda: 4)
    monkeypatch.setattr(platform, "backend_initialized", lambda: True)
    with pytest.raises(RuntimeError, match="holds a JAX backend"):
        _pool(workdir=str(tmp_path), member_env={"JAX_PLATFORMS": ""})


def test_importing_the_controller_modules_starts_no_backend():
    r = _py("import hetu_tpu, hetu_tpu.serve.crosshost, "
            "hetu_tpu.serve.engine, hetu_tpu.train.executor; "
            "from hetu_tpu.utils.platform import backend_initialized; "
            "print(backend_initialized())")
    assert r.returncode == 0 and r.stdout.strip() == "False", r.stderr


# ------------------------------------------------------------ native build

_TINY_CPP = 'extern "C" int answer() { return %d; }\n'


def _tiny_binding(monkeypatch, tmp_path, value):
    from hetu_tpu.ps import binding
    src = tmp_path / "tiny.cpp"
    src.write_text(_TINY_CPP % value)
    monkeypatch.setattr(binding, "_SRCS", [src])
    monkeypatch.setattr(binding, "_HDRS", [])
    monkeypatch.setattr(binding, "_BUILD", tmp_path / "_build")
    return binding, src


def test_binding_rebuilds_on_content_change_with_unchanged_mtime(
        monkeypatch, tmp_path):
    import ctypes
    binding, src = _tiny_binding(monkeypatch, tmp_path, 41)
    first = binding._build()
    assert ctypes.CDLL(str(first)).answer() == 41
    st = src.stat()
    src.write_text(_TINY_CPP % 42)
    os.utime(src, ns=(st.st_atime_ns, st.st_mtime_ns))  # mtime says "fresh"
    second = binding._build()
    assert second != first and ctypes.CDLL(str(second)).answer() == 42
    assert binding._build() == second  # and now it IS fresh: no rebuild
    assert not list((tmp_path / "_build").glob("*.tmp"))


def test_binding_concurrent_first_users_do_not_tear_the_library(
        monkeypatch, tmp_path):
    import ctypes
    binding, _ = _tiny_binding(monkeypatch, tmp_path, 7)
    paths, errs = [], []

    def build():
        try:
            paths.append(binding._build())
        except Exception as e:  # surfaced below, in the main thread
            errs.append(e)

    ts = [threading.Thread(target=build) for _ in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    assert not errs and not any(t.is_alive() for t in ts), errs
    assert len(set(paths)) == 1 and len(paths) == 6
    assert ctypes.CDLL(str(paths[0])).answer() == 7
    assert not list((tmp_path / "_build").glob("*.tmp"))


def test_binding_failed_build_raises_where_the_library_is_needed(
        monkeypatch, tmp_path):
    binding, src = _tiny_binding(monkeypatch, tmp_path, 1)
    src.write_text("this is not C++\n")
    monkeypatch.setattr(binding, "_lib", None)
    monkeypatch.setattr(binding, "_err", None)
    assert binding.available() is False
    with pytest.raises(RuntimeError, match="g\\+\\+ failed"):
        binding.lib.ps_van_start


# ------------------------------------------------- gathers in front of kernels

_HLO = """
%fused_mm (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %c = bf16[8]{0} convolution(%p, %p), dim_labels=bf_io->bf
}

ENTRY %main (a: bf16[8]) -> bf16[16] {
  %a = bf16[8]{0} parameter(0)
  %proj = bf16[8]{0} fusion(%a), kind=kOutput, calls=%fused_mm
  %ag.1 = bf16[16]{0} all-gather(%proj), dimensions={0}
  %bc = bf16[16]{0} bitcast(%ag.1)
  %cc = bf16[16]{0} custom-call(%bc), custom_call_target="tpu_custom_call"
  %ag.2 = bf16[16]{0} all-gather(%a), dimensions={0}
  %proj2 = bf16[16]{0} fusion(%ag.2), kind=kOutput, calls=%fused_mm
  ROOT %cc2 = bf16[16]{0} custom-call(%proj2), custom_call_target="tpu_custom_call"
}
"""


def test_gathers_feeding_stops_at_the_producing_matmul():
    from hetu_tpu.parallel.planner import gathers_feeding
    found = gathers_feeding(_HLO)
    # ag.1 sits between the projection and the kernel; ag.2 is behind one
    assert len(found) == 1 and found[0].startswith("%ag.1 ")
    assert gathers_feeding(_HLO, target="other_call") == []
