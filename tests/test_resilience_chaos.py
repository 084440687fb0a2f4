"""Chaos runs: kill real PS shard subprocesses mid-training and prove the
supervisor repairs them; SIGTERM a real training subprocess and prove
resume is step-exact; replay a full seeded fault schedule and prove the
final model matches the fault-free run.

Marked ``slow`` (multi-process, wall-clock) AND ``chaos`` (fault
injection) — the tier-1 lane never runs these; the full suite and
``-m chaos`` do.
"""

import hashlib
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

from hetu_tpu.ps import available

if not available():  # pragma: no cover
    pytest.skip("native PS lib unavailable", allow_module_level=True)

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import layers, optim
from hetu_tpu.ps import van
from hetu_tpu.resilience import (
    FaultEvent, FaultInjector, FaultSchedule, PSShardGuard, Supervisor,
)
from hetu_tpu.train.executor import Executor

from hetu_tpu.resilience.shardproc import free_port as _free_port
from hetu_tpu.resilience.shardproc import spawn_shard_server

REPO = Path(__file__).resolve().parent.parent


def _spawn_server(tmp_path, port: int, tag: str) -> subprocess.Popen:
    return spawn_shard_server(tmp_path, port, tag)


@pytest.fixture
def two_servers(tmp_path):
    ports = [_free_port(), _free_port()]
    procs = [_spawn_server(tmp_path, p, f"s{i}")
             for i, p in enumerate(ports)]
    yield ports, procs
    for p in procs:
        p.kill()
        p.wait()


def _respawner(tmp_path, ports, procs, stop_evt, respawned):
    """Watch the shard processes; restart any that die on the same port
    (the preemptible-fleet scheduler's role)."""
    while not stop_evt.is_set():
        for i, p in enumerate(procs):
            if p.poll() is not None and not stop_evt.is_set():
                time.sleep(0.2)  # a beat of real downtime
                procs[i] = _spawn_server(tmp_path, ports[i], f"r{i}")
                respawned.append(i)
        time.sleep(0.1)


# ---------------------------------------------------------------------------
# hybrid training problem: PS table rows + dense params, both convex
# ---------------------------------------------------------------------------

ROWS, DIM = 16, 4


def _make_problem(table, seed=0):
    """Dense regression (executor-owned params) + PS rows pulled per step
    and pushed toward fixed targets (server-side sgd) — two identifiable
    convex problems, so faults wash out and runs are comparable."""
    g = np.random.default_rng(seed)
    X = g.standard_normal((32, 4)).astype(np.float32)
    W_true = g.standard_normal((4, 2)).astype(np.float32)
    Ydense = X @ W_true
    targets = g.standard_normal((ROWS, DIM)).astype(np.float32)
    model = layers.Linear(4, 2)

    def loss_fn(params, model_state, batch, rng, train):
        pred, new_state = model.apply(
            {"params": params, "state": model_state}, batch["x"], train=train,
            rng=rng)
        dense_loss = jnp.mean((pred - batch["y"]) ** 2)
        diff = batch["rows"] - batch["targets"]
        row_loss = jnp.sum(diff * diff)
        # grads of row_loss wrt the pulled rows, pushed to the PS after the
        # step (ge rides metrics out of the jitted step)
        return dense_loss + row_loss, (
            {"ge": 2.0 * diff, "row_loss": row_loss}, new_state)

    def batch_fn(i):
        idx = np.arange(ROWS, dtype=np.int64)
        return {"x": X, "y": Ydense, "idx": idx,
                "rows": table.sparse_pull(idx),
                "targets": targets}

    def post_step(i, state, metrics, batch):
        table.sparse_push(batch["idx"], np.asarray(metrics["ge"]))

    ex = Executor(loss_fn, optim.SGDOptimizer(0.1), seed=seed)
    state = ex.init_state(model.init(jax.random.PRNGKey(seed)))
    return ex, state, batch_fn, post_step, targets


def _new_table(ports, table_id):
    eps = [("127.0.0.1", p) for p in ports]
    return van.PartitionedPSTable(eps, rows=ROWS, dim=DIM, init="zeros",
                                  optimizer="sgd", lr=0.3, seed=0,
                                  table_id=table_id, heartbeat_ms=100)


def test_shard_kill_is_repaired_from_snapshot(two_servers, tmp_path):
    """Kill shard 1 mid-training.  The supervisor's guard must replay the
    snapshot into the resurrected shard: post-repair ``sparse_pull``
    matches the pre-kill values exactly (shard 1 is never trained here),
    ``recovered == 1``, and training (on shard-0 rows) keeps descending."""
    ports, procs = two_servers
    t = _new_table(ports, table_id=901)

    # shard 1 (rows 8..15) holds "learned" values that training never
    # touches — repair exactness is then byte-comparable
    learned = np.arange(8 * DIM, dtype=np.float32).reshape(8, DIM) + 1.0
    shard1_rows = np.arange(8, 16, dtype=np.int64)
    t.sparse_set(shard1_rows, learned)

    g = np.random.default_rng(0)
    X = g.standard_normal((16, 4)).astype(np.float32)
    Yd = X @ g.standard_normal((4, 2)).astype(np.float32)
    targets = g.standard_normal((8, DIM)).astype(np.float32)
    model = layers.Linear(4, 2)

    def loss_fn(params, model_state, batch, rng, train):
        pred, new_state = model.apply(
            {"params": params, "state": model_state}, batch["x"],
            train=train, rng=rng)
        diff = batch["rows"] - batch["targets"]
        return jnp.mean((pred - batch["y"]) ** 2) + jnp.sum(diff * diff), (
            {"ge": 2.0 * diff, "row_mse": jnp.mean(diff * diff)}, new_state)

    idx0 = np.arange(8, dtype=np.int64)  # shard-0 rows only

    def batch_fn(i):
        # pace the run: all traffic stays on shard 0, so the loop never
        # blocks on the dead shard — real wall time must elapse for the
        # respawn + heartbeat + repair to land inside the run
        time.sleep(0.1)
        return {"x": X, "y": Yd, "rows": t.sparse_pull(idx0),
                "targets": targets}

    def post_step(i, state, metrics, batch):
        t.sparse_push(idx0, np.asarray(metrics["ge"]))

    ex = Executor(loss_fn, optim.SGDOptimizer(0.1), seed=0)
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))

    guard = PSShardGuard(t, snapshot_path=tmp_path / "snap.npz")
    guard.snapshot()  # pre-kill snapshot holds the learned shard-1 rows

    injector = FaultInjector(
        FaultSchedule([FaultEvent(6, "kill_shard", 1.0)]),
        shard_procs=procs)
    sup = Supervisor(ex, injector=injector, guards=[guard],
                     retries=25, backoff_base_s=0.05, backoff_max_s=0.5)

    row_mses = []

    def post_step_logged(i, s, m, b):
        post_step(i, s, m, b)
        row_mses.append(float(m["row_mse"]))

    stop_evt = threading.Event()
    respawned = []
    watcher = threading.Thread(
        target=_respawner, args=(tmp_path, ports, procs, stop_evt,
                                 respawned), daemon=True)
    watcher.start()
    try:
        rep = sup.run(state, batch_fn, 50, post_step=post_step_logged)
    finally:
        stop_evt.set()
        watcher.join(10)

    assert rep.step == 50
    assert rep.counters["shards_killed"] == 1
    assert respawned == [1]
    assert t.recovered == 1
    assert rep.counters["shard_repairs"] == 1
    # the repaired shard carries the learned embeddings, not fresh init
    np.testing.assert_array_equal(t.sparse_pull(shard1_rows), learned)
    # and training through the fault still descends
    assert row_mses[-1] < row_mses[0] * 1e-3, (row_mses[0], row_mses[-1])
    t.close()


def test_seeded_chaos_run_matches_fault_free(two_servers, tmp_path):
    """Acceptance chaos run: a SEEDED schedule with 1 shard kill + 2
    transient van faults + 1 NaN step completes training with final params
    (dense + PS rows) matching the fault-free run within tolerance, and the
    same seed regenerates the identical schedule."""
    ports, procs = two_servers
    STEPS = 60
    kw = dict(steps=STEPS, seed=11, van_errors=2, nan_steps=1,
              kill_shards=1, n_shards=2)
    sched = FaultSchedule.generate(**kw)
    assert sched.to_json() == FaultSchedule.generate(**kw).to_json()
    kinds = sorted(e.kind for e in sched.events)
    assert kinds == ["kill_shard", "nan_grad", "van_error", "van_error"]

    # ---- fault-free reference ----
    t_clean = _new_table(ports, table_id=902)
    ex, state, batch_fn, post_step, targets = _make_problem(t_clean)
    rep_clean = Supervisor(ex).run(state, batch_fn, STEPS,
                                   post_step=post_step)
    clean_rows = t_clean.sparse_pull(np.arange(ROWS))
    t_clean.close()

    # ---- chaos run, same seed everywhere ----
    t = _new_table(ports, table_id=903)
    ex2, state2, batch_fn2, post_step2, _ = _make_problem(t)
    guard = PSShardGuard(t, snapshot_path=tmp_path / "snap.npz")
    injector = FaultInjector(sched, shard_procs=procs)
    sup = Supervisor(ex2, injector=injector, guards=[guard],
                     ckpt_dir=tmp_path / "ckpt", ckpt_every=5,
                     retries=25, backoff_base_s=0.05, backoff_max_s=0.5)

    stop_evt = threading.Event()
    respawned = []
    watcher = threading.Thread(
        target=_respawner, args=(tmp_path, ports, procs, stop_evt,
                                 respawned), daemon=True)
    watcher.start()
    try:
        rep = sup.run(state2, batch_fn2, STEPS, post_step=post_step2)
    finally:
        stop_evt.set()
        watcher.join(10)

    assert rep.step == STEPS and not rep.preempted
    assert rep.counters["shards_killed"] == 1
    assert rep.counters["van_errors_injected"] == 2
    assert rep.counters["nan_injected"] == 1
    assert rep.counters["nonfinite_steps_skipped"] >= 1
    assert rep.counters["retries"] >= 2  # the van faults were survived
    assert t.recovered >= 1

    # both convex problems converged to the same place despite the chaos
    chaos_rows = t.sparse_pull(np.arange(ROWS))
    np.testing.assert_allclose(chaos_rows, targets, atol=2e-2)
    np.testing.assert_allclose(chaos_rows, clean_rows, atol=2e-2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2),
        rep.state.params, rep_clean.state.params)
    t.close()


# ---------------------------------------------------------------------------
# real-SIGTERM preemption of a training subprocess
# ---------------------------------------------------------------------------

TRAIN_SRC = '''
import hashlib, sys, time
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np
import hetu_tpu as ht
from hetu_tpu import layers, optim, rng as hrng
from hetu_tpu.resilience import Supervisor
from hetu_tpu.train.executor import Executor

ckpt_dir = sys.argv[1]
g = np.random.default_rng(0)
X = g.standard_normal((128, 4)).astype(np.float32)
Y = (X.sum(1) > 0).astype(np.int32)
model = layers.Sequential(layers.Linear(4, 16), layers.Relu(),
                          layers.Linear(16, 2))

def loss_fn(params, model_state, batch, rng, train):
    out, new_state = model.apply(
        {{"params": params, "state": model_state}}, batch["x"], train=train,
        rng=rng)
    loss = jnp.mean(ht.ops.softmax_cross_entropy_sparse(out, batch["y"]))
    return loss, ({{}}, new_state)

def batch_fn(i):
    time.sleep(0.15)  # give the parent a window to SIGTERM mid-run
    lo = (int(i) * 32) % 96
    return {{"x": X[lo:lo+32], "y": Y[lo:lo+32]}}

ex = Executor(loss_fn, optim.AdamOptimizer(0.01), seed=5)
state = ex.init_state(model.init(jax.random.PRNGKey(5)))
sup = Supervisor(ex, ckpt_dir=ckpt_dir, ckpt_every=100)
rep = sup.run(state, batch_fn, 12,
              post_step=lambda i, s, m, b: print("step", i, flush=True))
if rep.preempted:
    print("PREEMPTED", rep.step, flush=True)
else:
    leaves = jax.tree_util.tree_leaves(rep.state)
    h = hashlib.md5(b"".join(np.asarray(l).tobytes() for l in leaves))
    print("DONE", rep.step, h.hexdigest(), *hrng.get_seed_status(),
          flush=True)
'''


def _run_train(tmp_path, ckpt_dir, *, sigterm_after_step=None):
    script = tmp_path / "train.py"
    script.write_text(TRAIN_SRC.format(repo=str(REPO)))
    proc = subprocess.Popen([sys.executable, str(script), str(ckpt_dir)],
                            stdout=subprocess.PIPE, text=True)
    lines = []
    for line in proc.stdout:
        lines.append(line.strip())
        if (sigterm_after_step is not None
                and line.startswith(f"step {sigterm_after_step}")):
            proc.send_signal(signal.SIGTERM)
            sigterm_after_step = None  # once
    rc = proc.wait(timeout=120)
    return rc, lines


def test_sigterm_preemption_resume_is_step_exact(tmp_path):
    """A real SIGTERM to a training subprocess checkpoints and exits
    cleanly; rerunning resumes and finishes with the EXACT state (params
    hash + RNG seed/seqnum + step) of an uninterrupted run."""
    ref_dir = tmp_path / "ref_ckpt"
    rc, lines = _run_train(tmp_path, ref_dir)
    assert rc == 0, lines
    ref_done = [ln for ln in lines if ln.startswith("DONE")][0]

    pre_dir = tmp_path / "pre_ckpt"
    rc, lines = _run_train(tmp_path, pre_dir, sigterm_after_step=4)
    assert rc == 0, lines
    assert any(ln.startswith("PREEMPTED") for ln in lines), lines

    rc, lines = _run_train(tmp_path, pre_dir)  # auto-resume
    assert rc == 0, lines
    resumed_done = [ln for ln in lines if ln.startswith("DONE")][0]
    # fewer steps ran in the resumed process than the reference
    assert len([ln for ln in lines if ln.startswith("step")]) < 12
    assert resumed_done == ref_done  # step + params md5 + (seed, seqnum)
