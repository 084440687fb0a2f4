"""Online CTR recommendation serving while training — the HET loop, live.

A Wide&Deep trainer keeps pushing embedding updates to the PS (the
hybrid plane of examples/ctr_wdl.py) while a 2-member ``RecsysPool``
serves CTR scores CONCURRENTLY from the same tables through
staleness-bounded serving caches (``serve/recsys.py``): every served
row is at most ``--bound`` versions behind the trainer — asserted live
against a version-encoded sentinel row — and hot rows never re-cross
the PS boundary (hit-rate printed).

Run:  python examples/ctr_serve.py [--steps 200] [--requests 64]
                                   [--bound 2] [--cache 2048]
"""

from __future__ import annotations

import argparse
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import numpy as np

from hetu_tpu import optim
from hetu_tpu.models.wdl import WideDeep
from hetu_tpu.ps import PSEmbedding
from hetu_tpu.serve.recsys import RecsysEngine, RecsysPool, \
    ServingEmbeddingCache


def synthetic_ctr(n, fields, dense, vocab, seed=0):
    g = np.random.default_rng(seed)
    sparse = g.integers(0, vocab, (n, fields)).astype(np.int64)
    dense_x = g.standard_normal((n, dense)).astype(np.float32)
    w = g.standard_normal(fields)
    logit = (sparse % 7 - 3) @ w * 0.2 + dense_x[:, :3].sum(-1) * 0.5
    y = (logit + g.standard_normal(n) > 0).astype(np.float32)
    return sparse, dense_x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=4000)
    ap.add_argument("--emb-dim", type=int, default=16)
    ap.add_argument("--bound", type=int, default=2,
                    help="serving staleness bound (versions)")
    ap.add_argument("--cache", type=int, default=2048,
                    help="serving-cache capacity per member")
    args = ap.parse_args()

    fields, dense_dim = 8, 6
    sentinel = args.vocab  # one row past the trainable ids: the trainer
    # writes `step` into it so serving can MEASURE its own staleness
    sparse, dense_x, y = synthetic_ctr(args.batch * 8, fields, dense_dim,
                                       args.vocab)

    emb = PSEmbedding(args.vocab + 1, args.emb_dim, optimizer="adagrad",
                      lr=0.05, seed=0)
    model = WideDeep(fields, args.emb_dim, dense_dim, hidden=(32,))
    opt = optim.AdamOptimizer(1e-3)
    v = model.init(jax.random.PRNGKey(0))
    params, model_state = v["params"], v["state"]
    opt_state = opt.init_state(params)
    step = model.hybrid_step_fn(opt)

    published = [0]
    trainer_exc = []

    def trainer():
        nonlocal params, opt_state, model_state
        try:
            n = sparse.shape[0]
            for it in range(args.steps):
                lo = (it * args.batch) % (n - args.batch)
                ids = sparse[lo:lo + args.batch]
                rows = emb.pull(ids)
                params2, opt_state2, model_state2, loss, logit, ge = step(
                    params, opt_state, model_state,
                    dense_x[lo:lo + args.batch], rows,
                    y[lo:lo + args.batch])
                params, opt_state, model_state = (params2, opt_state2,
                                                  model_state2)
                emb.push(ids, np.asarray(ge))
                # version-encoded sentinel: row == it+1 after this set
                emb.table.sparse_set(
                    [sentinel],
                    np.full((1, args.emb_dim), float(it + 1), np.float32))
                published[0] = it + 1
        except Exception as e:  # pragma: no cover - surfaced below
            trainer_exc.append(e)

    caches = []

    def factory():
        c = ServingEmbeddingCache(emb.table, args.cache,
                                  pull_bound=args.bound)
        caches.append(c)
        return RecsysEngine(model, v, c, max_batch=64, min_bucket=4)

    pool = RecsysPool({"m0": factory, "m1": factory})
    g = np.random.default_rng(1)
    worst_lag = 0
    t0 = time.perf_counter()
    th = threading.Thread(target=trainer, daemon=True)
    th.start()
    try:
        served = 0
        for i in range(args.requests):
            # Zipfian serving traffic: online CTR traffic concentrates on
            # a hot set — exactly what the cache tier banks on
            ids = (g.zipf(1.5, fields) - 1) % args.vocab
            r = pool.score(g.standard_normal(dense_dim).astype(np.float32),
                           ids, timeout_s=60.0)
            assert r["status"] == "ok", r
            served += 1
            # staleness probe: the sentinel row read through a member's
            # cache must be within --bound versions of what the trainer
            # had already published when the lookup started
            c0 = published[0]
            v_read = int(caches[i % len(caches)].lookup([sentinel])[0][0])
            lag = c0 - v_read
            worst_lag = max(worst_lag, lag)
            assert lag <= args.bound, (c0, v_read, args.bound)
        th.join(300)
        if trainer_exc:
            raise trainer_exc[0]
        assert published[0] == args.steps
        dt = time.perf_counter() - t0
        hit = max(c.hit_rate for c in caches)
        print(f"served {served} requests over {len(pool.members)} members "
              f"while training {args.steps} steps ({dt:.1f}s); "
              f"worst observed staleness {worst_lag} <= bound "
              f"{args.bound}; best member hit_rate {hit:.3f}")
        print("ctr serve: OK")
    finally:
        pool.close()
        emb.close()


if __name__ == "__main__":
    main()
