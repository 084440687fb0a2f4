"""ResNet-18 / CIFAR10 training (reference: examples/cnn/main.py +
scripts/hetu_1gpu.sh / hetu_8gpu.sh — BASELINE configs #1/#2).

Single chip:   python examples/cnn_resnet.py
DP over all:   python examples/cnn_resnet.py --dp $(python -c 'import jax;print(jax.device_count())')
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import numpy as np

import hetu_tpu as ht
from hetu_tpu import lr, models, optim
from hetu_tpu.utils.logger import MetricLogger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--limit-batches", type=int, default=0,
                    help="cap batches per epoch (smoke tests)")
    ap.add_argument("--subset", type=int, default=0,
                    help="train on the first N samples only — the "
                    "documented-synthetic convergence mode: the fallback "
                    "dataset has RANDOM labels, so the measurable learning "
                    "signal is memorization accuracy on a repeated subset "
                    "(with real CIFAR-10 under ~/.hetu_tpu/data this flag "
                    "is unnecessary)")
    args = ap.parse_args()

    train_x, train_y, test_x, test_y = ht.data.datasets.cifar10()
    if args.subset:
        train_x, train_y = train_x[:args.subset], train_y[:args.subset]
    loader = ht.data.Dataloader((train_x, train_y), args.batch, shuffle=True)

    model = models.ResNet18(num_classes=10)
    mesh = ht.make_mesh(dp=args.dp) if args.dp > 1 else None
    steps_per_epoch = loader.num_batches
    sched = lr.CosineScheduler(args.lr, t_max=args.epochs * steps_per_epoch,
                               warmup=steps_per_epoch // 10)
    ex = ht.Executor(model.loss_fn(), optim.MomentumOptimizer(sched, 0.9),
                     mesh=mesh, seed=0)
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))

    logger = MetricLogger()
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        nb = 0
        for batch in loader:
            state, m = ex.run("train", state, batch)
            logger.log(m)
            nb += 1
            if args.limit_batches and nb >= args.limit_batches:
                break
        dt = time.perf_counter() - t0
        means = logger.means(); logger.reset()
        val = ex.run("validate", state, (test_x[:1024], test_y[:1024]))
        print(f"epoch {epoch}: loss={means['loss']:.4f} "
              f"acc={means['acc']:.3f} val_acc={float(val['acc']):.3f} "
              f"({nb * args.batch / dt:.0f} samples/s)")
    ht.checkpoint.save("/tmp/resnet18_ckpt.pkl", state)


if __name__ == "__main__":
    main()
