"""Fault-tolerant training with the resilience supervisor.

Trains a small classifier under an injected, SEEDED fault schedule —
transient dataloader errors, a NaN-poisoned batch, and a simulated
preemption (SIGTERM) — then auto-resumes from the atomic checkpoint and
finishes, proving the run survives everything the schedule throws at it.

The whole run is TRACED (hetu_tpu.telemetry): it writes a Perfetto-
loadable trace next to the checkpoints, prints the fault → recovery
pairing, and points at `tools/trace_report.py` for the full breakdown
(README "Observability").

Run:  python examples/resilient_train.py [--steps 40] [--seed 7]

The same --seed replays the identical fault sequence (print the schedule
with --show-schedule); see README "Fault tolerance" for the knobs.
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
from hetu_tpu import layers, optim, telemetry
from hetu_tpu.resilience import FaultInjector, FaultSchedule, Supervisor
from hetu_tpu.telemetry import timeline
from hetu_tpu.train.executor import Executor
from hetu_tpu.utils.logger import MetricLogger


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
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: a temp dir)")
    ap.add_argument("--show-schedule", action="store_true")
    args = ap.parse_args()

    ckpt_dir = args.ckpt_dir
    if ckpt_dir is None:
        import tempfile
        ckpt_dir = tempfile.mkdtemp(prefix="resilient_train_")

    g = np.random.default_rng(0)
    X = g.standard_normal((512, 8)).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.int32)

    def batch_fn(i):
        lo = (int(i) * 64) % 448
        return {"x": X[lo:lo + 64], "y": Y[lo:lo + 64]}

    # the chaos: seeded, replayable — plus a preemption mid-run
    schedule = FaultSchedule.generate(
        steps=args.steps, seed=args.seed, data_errors=2, nan_steps=1,
        preempt_at=args.steps // 2)
    if args.show_schedule:
        print("fault schedule:", schedule.to_json())

    # trace the whole run (both supervisor incarnations share the stream)
    trace_jsonl = str(Path(ckpt_dir) / "run.trace.jsonl")
    telemetry.enable(jsonl_path=trace_jsonl)

    logger = MetricLogger()
    ex, state = make_executor(args.seed)
    sup = Supervisor(ex, ckpt_dir=ckpt_dir, ckpt_every=10,
                     injector=FaultInjector(schedule), logger=logger,
                     backoff_base_s=0.01)
    rep = sup.run(state, batch_fn, args.steps)
    assert rep.preempted, "the scheduled SIGTERM should have preempted us"
    print(f"preempted at step {rep.step} -> checkpointed to {ckpt_dir}")

    # a NEW process would do exactly this: same ckpt_dir, auto-resume —
    # the rest of the schedule (faults after the preemption step) still
    # fires, so the resumed run survives chaos too
    ex2, state2 = make_executor(args.seed)
    sup2 = Supervisor(ex2, ckpt_dir=ckpt_dir, ckpt_every=10, logger=logger,
                      injector=FaultInjector(schedule),
                      backoff_base_s=0.01)
    rep2 = sup2.run(state2, batch_fn, args.steps)
    loss = float(rep2.last_metrics["loss"])
    c = {k: rep.counters.get(k, 0) + rep2.counters.get(k, 0)
         for k in set(rep.counters) | set(rep2.counters)}
    print(f"resumed from step {rep2.counters['resumed_from_step']}, "
          f"finished at step {rep2.step}: loss={loss:.4f}")
    print(f"faults survived: {c.get('data_errors_injected', 0)} data, "
          f"{c.get('nan_injected', 0)} nan (skipped "
          f"{c.get('nonfinite_steps_skipped', 0)} steps), "
          f"retries={c.get('retries', 0)}")
    assert rep2.step == args.steps and np.isfinite(loss)

    # the trace: fault -> recovery pairing + a Perfetto export
    tracer = telemetry.disable()
    chrome = tracer.write_chrome(Path(ckpt_dir) / "run.trace.json")
    pairs = timeline.correlate(telemetry.load_jsonl(trace_jsonl))
    paired = sum(1 for p in pairs if p.paired)
    expected = sum(1 for p in pairs if timeline.RECOVERY_FOR.get(p.kind))
    print(f"trace: {len(tracer.events)} events -> {trace_jsonl}")
    print(f"  fault->recovery pairs: {paired}/{expected} "
          f"(report: python tools/trace_report.py {trace_jsonl}; "
          f"Perfetto: {chrome})")
    assert paired == expected, "every injected fault must pair"
    print("resilient train: OK")


if __name__ == "__main__":
    main()
