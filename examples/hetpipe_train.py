"""HetPipe training: two pipelined virtual workers syncing weights through
the parameter server with bounded staleness (reference analog:
gpu_ops/pipedream_subexecutor.py 'hetpipe' mode + HetPipe paper's WSP).

    python examples/hetpipe_train.py --waves 20 --sync-every 2
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

import hetu_tpu as ht
from hetu_tpu.parallel.hetpipe import HetPipeWorker, make_weight_table
from hetu_tpu.parallel.pipedream import PipeDream1F1B
from hetu_tpu.ps import SSPController


def block_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=20)
    ap.add_argument("--sync-every", type=int, default=2)
    ap.add_argument("--staleness", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--dim", type=int, default=32)
    args = ap.parse_args()

    mesh = ht.make_mesh(pp=args.pp)
    ks = jax.random.split(jax.random.PRNGKey(0), args.layers)
    layers = {"w": jnp.stack([jax.random.normal(k, (args.dim, args.dim))
                              * 0.3 for k in ks]),
              "b": jnp.zeros((args.layers, args.dim))}
    pipe = PipeDream1F1B(block_fn, mesh, n_microbatches=4)
    stacked = pipe.stack_params(layers)

    # global weights live on the PS; its server-side optimizer is the
    # global optimizer (DDPushPull)
    table = make_weight_table(stacked, optimizer="momentum", lr=0.05)
    ssp = SSPController(n_workers=2, staleness=args.staleness)
    workers = [
        HetPipeWorker(pipe, stacked, table, publish_init=(i == 0),
                      sync_every=args.sync_every, local_lr=0.05,
                      worker_id=i, ssp=ssp)
        for i in range(2)
    ]
    workers[1].pull_weights()

    data = [jax.random.normal(jax.random.PRNGKey(10 + i), (16, args.dim))
            for i in range(2)]

    def loss_fn(outs):
        return jnp.mean(outs ** 2)

    for wave in range(args.waves):
        losses = [w.step(data[i], loss_fn) for i, w in enumerate(workers)]
        if wave % 5 == 0 or wave == args.waves - 1:
            print(f"wave {wave:3d}  loss A={losses[0]:.5f} "
                  f"B={losses[1]:.5f}  clocks={ssp.clock(0)},{ssp.clock(1)}")
    print("done")


if __name__ == "__main__":
    main()
