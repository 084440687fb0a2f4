"""Elastic training: survive a permanent worker loss (and a rejoin)
without aborting or restarting.

A dp=4 run loses worker 2 mid-training — the ElasticSupervisor reforms
the mesh at width 3, re-places the full TrainState (params, optimizer
slots, step, RNG) under the surviving devices, and keeps stepping; when
the worker rejoins, the mesh regrows to 4.  The ElasticBatchSchedule
keeps the GLOBAL batch sequence identical at every width, so the run
converges to the same place as a run that never resized (asserted).

Run:  python examples/elastic_train.py [--steps 30] [--seed 7]

The same --seed replays the identical membership schedule
(--show-schedule prints it); see README "Elastic operation".
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import jax.numpy as jnp
import numpy as np

import hetu_tpu as ht
from hetu_tpu import layers, optim
from hetu_tpu.data.dataloader import ElasticBatchSchedule
from hetu_tpu.parallel.mesh import MeshConfig
from hetu_tpu.resilience import (
    ElasticSupervisor, FaultInjector, FaultSchedule, Supervisor,
)
from hetu_tpu.train.executor import Executor


def make_executor(seed: int):
    model = layers.Sequential(
        layers.Linear(8, 32), layers.Relu(), layers.Linear(32, 2))

    def loss_fn(params, model_state, batch, rng, train):
        out, new_state = model.apply(
            {"params": params, "state": model_state}, batch["x"],
            train=train, rng=rng)
        loss = jnp.mean(ht.ops.softmax_cross_entropy_sparse(out, batch["y"]))
        return loss, ({}, new_state)

    ex = Executor(loss_fn, optim.AdamOptimizer(0.01), seed=seed)
    state = ex.init_state(model.init(jax.random.PRNGKey(seed)))
    return ex, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--show-schedule", action="store_true")
    args = ap.parse_args()

    if len(jax.devices()) < args.dp:
        print(f"need {args.dp} devices, have {len(jax.devices())} "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return

    g = np.random.default_rng(0)
    X = g.standard_normal((480, 8)).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.int32)
    # global batch divisible by every width the fleet can shrink to
    sched = ElasticBatchSchedule((X, Y), 48, seed=args.seed)

    def batch_fn(i):
        x, y = sched.global_batch(i)
        return {"x": x, "y": y}

    faults = FaultSchedule.generate(
        steps=args.steps, seed=args.seed, worker_losses=1, worker_joins=1,
        n_workers=args.dp)
    if args.show_schedule:
        print("membership schedule:", faults.to_json())

    ex, state = make_executor(args.seed)
    sup = ElasticSupervisor(ex, config=MeshConfig(dp=args.dp),
                            schedule=sched,
                            injector=FaultInjector(faults))
    rep = sup.run(state, batch_fn, args.steps)
    for ev in sup.resizes:
        print(f"step {ev.step}: {ev.kind} (worker {ev.worker}) -> "
              f"width {ev.width} in {ev.downtime_s * 1e3:.1f} ms")
    loss = float(rep.last_metrics["loss"])
    print(f"finished at step {rep.step}, width {sup.width}, "
          f"loss={loss:.4f}")
    assert rep.step == args.steps and len(sup.resizes) == 2

    # the proof: a never-resized run over the SAME schedule lands on the
    # same params
    ex0, state0 = make_executor(args.seed)
    ex0.set_mesh(ht.make_mesh(dp=args.dp))
    rep0 = Supervisor(ex0).run(state0, batch_fn, args.steps)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5),
        rep.state.params, rep0.state.params)
    print("matches the never-resized run: elastic train: OK")


if __name__ == "__main__":
    main()
