"""Memory-compressed embedding training: the EmbeddingMemoryCompression
tool's run_compressed loop on a CTR task.

Reference analog: examples/rec/run_compressed.py — pick a compression
method, train the CTR model with the compressed table, report quality vs
the full table at a fraction of the parameters.

Run:  python examples/rec_compressed.py [--method hash|compo|dpq|tt|robe|
                                         quant|prune|mde|dedup|dhe]
      (default sweeps a representative subset)

CPU-safe via JAX_PLATFORMS=cpu.
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

from hetu_tpu import embedding_compress as ec
from hetu_tpu import optim, ops
from hetu_tpu.models.ctr_common import mlp_tower


def synthetic_ctr(n, fields=8, vocab=5000, seed=0):
    g = np.random.default_rng(seed)
    sparse = g.integers(0, vocab, (n, fields)).astype(np.int64)
    w = g.standard_normal(fields)
    logit = (sparse % 5 - 2) @ w * 0.3
    y = (logit + g.standard_normal(n) > 0).astype(np.float32)
    return sparse, y


def make_table(method, vocab, dim):
    if method == "full":
        from hetu_tpu.layers import Embedding
        return Embedding(vocab, dim)
    if method == "hash":
        return ec.HashEmbedding(vocab, dim, compress_ratio=0.1)
    if method == "compo":
        return ec.CompositionalEmbedding(vocab, dim)
    if method == "dpq":
        return ec.DPQEmbedding(vocab, dim)
    if method == "tt":
        return ec.TensorTrainEmbedding(vocab, dim)
    if method == "robe":
        return ec.ROBEEmbedding(vocab, dim, compress_ratio=0.1)
    if method == "quant":
        return ec.QuantizedEmbedding(vocab, dim)
    if method == "prune":
        return ec.PrunedEmbedding(vocab, dim, rate=0.7)
    if method == "mde":
        return ec.MixedDimEmbedding(vocab, dim)
    if method == "dedup":
        return ec.DedupEmbedding(vocab, dim, compress_ratio=0.2)
    if method == "dhe":
        return ec.DHEEmbedding(vocab, dim)
    raise ValueError(method)


def param_count(params):
    return sum(int(np.prod(np.asarray(p).shape))
               for p in jax.tree_util.tree_leaves(params))


def train_one(method, sparse, y, vocab, dim=8, steps=60, batch=128):
    fields = sparse.shape[1]
    emb = make_table(method, vocab, dim)
    head = mlp_tower(fields * dim, (32,), out_dim=1)
    ke, kh = jax.random.split(jax.random.PRNGKey(0))
    ve, vh = emb.init(ke), head.init(kh)
    params = {"emb": ve["params"], "head": vh["params"]}
    states = {"emb": ve["state"], "head": vh["state"]}
    opt = optim.AdamOptimizer(5e-3)
    ostate = opt.init_state(params)

    @jax.jit
    def step(params, ostate, ids, yy):
        def loss_fn(p):
            rows, _ = emb.apply({"params": p["emb"],
                                 "state": states["emb"]}, ids)
            flat = rows.reshape(rows.shape[0], -1)
            logit, _ = head.apply({"params": p["head"],
                                   "state": states["head"]}, flat)
            return jnp.mean(ops.binary_cross_entropy_with_logits(
                logit[:, 0], yy))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, ostate = opt.update(grads, ostate, params)
        return params, ostate, loss

    first = last = None
    for it in range(steps):
        lo = (it * batch) % (sparse.shape[0] - batch)
        params, ostate, loss = step(params, ostate,
                                    jnp.asarray(sparse[lo:lo + batch]),
                                    jnp.asarray(y[lo:lo + batch]))
        first = first if first is not None else float(loss)
        last = float(loss)
    return first, last, param_count(params["emb"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default=None)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--vocab", type=int, default=5000)
    args = ap.parse_args(argv)

    sparse, y = synthetic_ctr(4096, vocab=args.vocab)
    methods = [args.method] if args.method else \
        ["full", "hash", "compo", "robe", "prune", "mde"]
    full_params = None
    for m in methods:
        first, last, n_params = train_one(m, sparse, y, args.vocab,
                                          steps=args.steps)
        if m == "full":
            full_params = n_params
        ratio = f"{n_params / full_params:6.1%}" if full_params else "   n/a"
        print(f"{m:6s} emb-params {n_params:>8,} ({ratio} of full)  "
              f"loss {first:.3f} -> {last:.3f}")


if __name__ == "__main__":
    main()
