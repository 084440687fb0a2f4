"""Fully-sharded GPT-MoE training over dp/pp/sp/tp/ep — the flagship
(reference analogs: examples/moe + tools/Galvatron hybrid-parallel runs).

    python examples/gpt_sharded_train.py --tp 2 --pp 2 --sp 2   # 8 devices
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
from hetu_tpu.models.gpt_sharded import ShardedGPT, ShardedGPTConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    for ax in ("dp", "tp", "pp", "sp", "ep"):
        ap.add_argument(f"--{ax}", type=int, default=1)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--experts", type=int, default=4)
    args = ap.parse_args()

    cfg = ShardedGPTConfig(
        vocab_size=8192, hidden_size=args.hidden, num_layers=args.layers,
        num_heads=max(4, args.hidden // 64), ffn_size=4 * args.hidden,
        num_experts=args.experts, top_k=2, max_position=args.seq,
        n_microbatches=2)
    mesh = ht.make_mesh(dp=args.dp, tp=args.tp, pp=args.pp, sp=args.sp,
                        ep=args.ep)
    model = ShardedGPT(cfg, mesh)
    params = model.place(model.init(jax.random.PRNGKey(0)))
    opt = optim.AdamOptimizer(3e-4)
    opt_state = opt.init_state(params)
    step = model.make_train_step(opt)

    g = np.random.default_rng(0)
    sh = model.data_sharding()
    t0 = time.perf_counter()
    for it in range(args.steps):
        ids = g.integers(0, cfg.vocab_size,
                         (args.batch, args.seq)).astype(np.int32)
        labels = np.concatenate(
            [ids[:, 1:], np.full((args.batch, 1), -1, np.int32)], axis=1)
        params, opt_state, m = step(params, opt_state,
                                    jax.device_put(ids, sh),
                                    jax.device_put(labels, sh))
        if (it + 1) % 10 == 0:
            print(f"step {it+1}: loss={float(m['loss']):.4f} "
                  f"aux={float(m['aux_loss']):.4f} "
                  f"({10 * args.batch / (time.perf_counter() - t0):.1f} "
                  f"seq/s)")
            t0 = time.perf_counter()
    if args.steps:  # short runs (< 10 steps) still report a result line
        print(f"done: {args.steps} steps, "
              f"final loss={float(m['loss']):.4f}")


if __name__ == "__main__":
    main()
