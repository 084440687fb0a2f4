"""GCN node classification (reference: examples/gnn run_single.py /
run_dist.py with GraphMix).

Synthetic two-community graph by default; distributed aggregation via
--shards uses the 1.5-D dst-sharded path (ops/distgcn.py).
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
from hetu_tpu import optim
from hetu_tpu.models.gcn import GCN
from hetu_tpu.ops.graph_ops import gcn_norm


def community_graph(n_per=200, n_comm=4, feat=32, intra=8, inter=2, seed=0):
    g = np.random.default_rng(seed)
    N = n_per * n_comm
    edges = []
    for c in range(n_comm):
        base = c * n_per
        for _ in range(n_per * intra):
            a, b = g.integers(0, n_per, 2)
            edges.append((base + a, base + b))
    for _ in range(n_per * inter):
        a, b = g.integers(0, N, 2)
        edges.append((a, b))
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    x = g.standard_normal((N, feat)).astype(np.float32)
    labels = np.repeat(np.arange(n_comm), n_per).astype(np.int32)
    return x, labels, src, dst, N


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=100)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--label-rate", type=float, default=0.1)
    args = ap.parse_args()

    x, labels, src, dst, N = community_graph()
    es, ed, ew = gcn_norm(jnp.asarray(src), jnp.asarray(dst), N)
    mask = (np.random.default_rng(1).random(N) <
            args.label_rate).astype(np.float32)

    model = GCN(x.shape[1], args.hidden, int(labels.max()) + 1)
    ex = ht.Executor(model.loss_fn(es, ed, ew), optim.AdamOptimizer(0.01),
                     seed=0)
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))
    batch = (x, labels, mask)
    t0 = time.perf_counter()
    for epoch in range(args.epochs):
        state, m = ex.run("train", state, batch)
        if (epoch + 1) % 20 == 0:
            logits, _ = model.apply({"params": state.params, "state": {}},
                                    jnp.asarray(x), es, ed, ew)
            acc = float((np.asarray(logits).argmax(-1) == labels).mean())
            print(f"epoch {epoch+1}: loss={float(m['loss']):.4f} "
                  f"labeled_acc={float(m['acc']):.3f} all_acc={acc:.3f} "
                  f"({(epoch+1)/(time.perf_counter()-t0):.1f} ep/s)")


if __name__ == "__main__":
    main()
