"""Wide&Deep CTR training with PS-backed embeddings + HET cache tier.

Reference analog: examples/ctr/run_hetu.py with comm_mode Hybrid and
cstable_policy LFUOpt (examples/ctr/tests/hybrid_wdl_adult.sh).

Run:  python examples/ctr_wdl.py [--steps 200] [--cache 2048] [--policy lfuopt]

Data: Criteo-shaped synthetic clickstream (no egress in this environment);
drop the real Criteo numpy files into $HETU_TPU_DATA_DIR to train for real.
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
from hetu_tpu import optim
from hetu_tpu.models.wdl import WideDeep
from hetu_tpu.ps import PSEmbedding
from hetu_tpu.utils import metrics
from hetu_tpu.utils.logger import MetricLogger


def synthetic_ctr(n, fields=26, dense=13, vocab=10000, seed=0):
    g = np.random.default_rng(seed)
    sparse = g.integers(0, vocab, (n, fields)).astype(np.int64)
    dense_x = g.standard_normal((n, dense)).astype(np.float32)
    # clicks correlate with a few hidden field embeddings + dense dims
    w_hidden = g.standard_normal(fields)
    logit = (sparse % 7 - 3) @ w_hidden * 0.2 + dense_x[:, :3].sum(-1) * 0.5
    y = (logit + g.standard_normal(n) > 0).astype(np.float32)
    return sparse, dense_x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=10000)
    ap.add_argument("--emb-dim", type=int, default=16)
    ap.add_argument("--cache", type=int, default=0,
                    help="cache capacity (0 = no cache tier)")
    ap.add_argument("--policy", default="lfuopt",
                    choices=["lru", "lfu", "lfuopt"])
    ap.add_argument("--bound", type=int, default=0,
                    help="staleness bound for cache sync")
    args = ap.parse_args()

    fields, dense_dim = 26, 13
    sparse, dense_x, y = synthetic_ctr(args.batch * 64, fields, dense_dim,
                                       args.vocab)

    emb = PSEmbedding(args.vocab, args.emb_dim, optimizer="adagrad", lr=0.05,
                      cache_capacity=args.cache or None, seed=0,
                      cache_policy=args.policy, pull_bound=args.bound)
    model = WideDeep(fields, args.emb_dim, dense_dim)
    opt = optim.AdamOptimizer(1e-3)
    v = model.init(jax.random.PRNGKey(0))
    params, model_state = v["params"], v["state"]
    opt_state = opt.init_state(params)
    step = model.hybrid_step_fn(opt)

    logger = MetricLogger()
    t0 = time.perf_counter()
    n = sparse.shape[0]

    def batch_at(it):
        lo = (it * args.batch) % (n - args.batch)
        return (sparse[lo:lo + args.batch], dense_x[lo:lo + args.batch],
                y[lo:lo + args.batch])

    # prefetch pipeline (reference executor.py:384): batch k+1's pull is
    # submitted AFTER batch k's push (the documented discipline — pulls must
    # see the newest rows), overlapping with metric logging + batching work
    emb.prefetch(batch_at(0)[0])
    for it in range(args.steps):
        ids, dx, yy = batch_at(it)
        rows = emb.pull_prefetched()               # host: PS/cache pull
        params, opt_state, model_state, loss, logit, ge = step(
            params, opt_state, model_state, dx, rows, yy)
        emb.push(ids, np.asarray(ge))              # host: PS/cache push
        if it + 1 < args.steps:
            emb.prefetch(batch_at(it + 1)[0])
        logger.log({"loss": float(loss),
                    "auc": metrics.auc(np.asarray(logit), yy)})
        if (it + 1) % 50 == 0:
            m = logger.means()
            extra = (f" cache_hit={emb.cache.hit_rate:.3f}"
                     if emb.cache else "")
            print(f"step {it+1}: loss={m['loss']:.4f} auc={m['auc']:.4f}"
                  f"{extra} ({time.perf_counter()-t0:.1f}s)")
            logger.reset()
    emb.flush()


if __name__ == "__main__":
    main()
