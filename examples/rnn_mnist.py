"""Recurrent classifiers (RNN/LSTM/GRU) on row-sequence MNIST.

Reference analog: examples/cnn/main.py --model rnn|lstm — the reference's
CNN example family also trains recurrent models on MNIST, reading the
image as a 28-step sequence of 28-pixel rows.  Same task here through the
framework's scan-based cells (hetu_tpu/layers/rnn.py) and the Executor.

Run:  python examples/rnn_mnist.py [--cell lstm] [--epochs 2] [--dp 2]
(synthetic-fallback MNIST without local data; real data under
~/.hetu_tpu/data/mnist trains to real accuracy)
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
import jax.numpy as jnp
import numpy as np

import hetu_tpu as ht
from hetu_tpu import layers, ops, optim
from hetu_tpu.utils.logger import MetricLogger


class RNNClassifier(layers.Module):
    """cell over the 28 image rows -> last hidden state -> linear head."""

    def __init__(self, cell: str, hidden: int = 128, classes: int = 10):
        self.rnn = layers.RNN(28, hidden, cell_type=cell)
        self.head = layers.Linear(hidden, classes)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"params": {"rnn": self.rnn.init(k1)["params"],
                           "head": self.head.init(k2)["params"]},
                "state": {}}

    def loss_fn(self):
        def fn(params, model_state, batch, rng, train):
            x, y = batch
            seq = x.reshape(x.shape[0], 28, 28)  # rows as time steps
            hs, _ = self.rnn.apply({"params": params["rnn"], "state": {}},
                                   seq)
            logits, _ = self.head.apply(
                {"params": params["head"], "state": {}}, hs[:, -1])
            loss = ops.softmax_cross_entropy_sparse(logits, y).mean()
            acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
            return loss, ({"loss": loss, "acc": acc}, model_state)
        return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=["rnn", "lstm", "gru"],
                    default="lstm")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--limit-batches", type=int, default=0)
    args = ap.parse_args()

    train_x, train_y, test_x, test_y = ht.data.datasets.mnist()
    loader = ht.data.Dataloader((train_x, train_y), args.batch,
                                shuffle=True)
    model = RNNClassifier(args.cell)
    mesh = ht.make_mesh(dp=args.dp) if args.dp > 1 else None
    ex = ht.Executor(model.loss_fn(), optim.AdamOptimizer(args.lr),
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
        means = logger.means(); logger.reset()
        val = ex.run("validate", state, (test_x[:1024], test_y[:1024]))
        print(f"epoch {epoch}: loss={means['loss']:.4f} "
              f"acc={means['acc']:.3f} val_acc={float(val['acc']):.3f} "
              f"({nb * args.batch / (time.perf_counter() - t0):.0f} "
              f"samples/s)")


if __name__ == "__main__":
    main()
