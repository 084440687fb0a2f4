"""Long-context training with ring-attention sequence parallelism.

Reference analog: the reference has no long-context story in core (only
Megatron sequence-parallel inside vendored Galvatron code) — SURVEY.md
lists SP long-context as a planned NEW capability.  This example trains a
small causal LM at a sequence length whose full attention matrix would not
fit a single device's memory comfortably: the sequence is sharded over the
'sp' mesh axis, K/V blocks rotate around the ring via ppermute
(hetu_tpu/parallel/ring_attention.py), and each device holds O(S/n)
activations.

Run (CPU, 8 virtual devices):  python examples/long_context_ring.py
Flags:  --seq 8192 --sp 8 --steps 5 --ulysses   (all optional)

The same code runs on a real TPU slice with sp over the ICI ring.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu import ops, optim
from hetu_tpu.parallel.ring_attention import ring_attention
from hetu_tpu.parallel.ulysses import ulysses_attention


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--sp", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--ulysses", action="store_true",
                    help="all-to-all head parallelism instead of the ring")
    args = ap.parse_args()
    B, S, H, NH, V = (args.batch, args.seq, args.hidden, args.heads,
                      args.vocab)
    D = H // NH
    mesh = ht.make_mesh(sp=args.sp)
    attn = ulysses_attention if args.ulysses else ring_attention

    def model(params, ids):
        h = ops.embedding_lookup(params["emb"], ids)          # [B,S,H]
        h = h + params["pos"][None, : h.shape[1]]
        qkv = ops.linear(h, params["qkv"])                    # [B,S,3H]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(x):  # [B,S,H] -> [B,NH,S,D]
            return jnp.moveaxis(x.reshape(B, -1, NH, D), 1, 2)

        o = attn(heads(q), heads(k), heads(v), mesh, causal=True)
        o = jnp.moveaxis(o, 1, 2).reshape(B, -1, H)
        h = h + ops.linear(o, params["out"])
        h = ops.rms_norm(h, params["rms"])
        return ops.linear(h, params["head"])                  # [B,S,V]

    def loss_fn(params, ids):
        logits = model(params, ids)
        per = ops.softmax_cross_entropy_sparse(logits[:, :-1], ids[:, 1:])
        return jnp.mean(per)

    g = np.random.default_rng(0)
    k0 = jax.random.PRNGKey(0)
    ks = jax.random.split(k0, 5)
    params = {
        "emb": jax.random.normal(ks[0], (V, H)) * 0.02,
        "pos": jax.random.normal(ks[1], (S, H)) * 0.02,
        "qkv": jax.random.normal(ks[2], (H, 3 * H)) * 0.02,
        "out": jax.random.normal(ks[3], (H, H)) * 0.02,
        "head": jax.random.normal(ks[4], (H, V)) * 0.02,
        "rms": jnp.ones((H,)),
    }
    # a learnable stream: sticky tokens, so next-token loss can fall
    ids = np.empty((B, S), np.int64)
    ids[:, 0] = g.integers(0, V, B)
    stay = g.random((B, S)) < 0.95
    draws = g.integers(0, V, (B, S))
    for t in range(1, S):
        ids[:, t] = np.where(stay[:, t], ids[:, t - 1], draws[:, t])
    ids = jnp.asarray(ids, jnp.int32)
    ids = jax.device_put(ids, NamedSharding(mesh, P(None, "sp")))

    opt = optim.AdamOptimizer(3e-3)
    ostate = opt.init_state(params)

    @jax.jit
    def step(params, ostate, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        params, ostate = opt.update(grads, ostate, params)
        return params, ostate, loss

    mode = "ulysses" if args.ulysses else "ring"
    print(f"{mode} attention: S={S} over sp={args.sp} "
          f"({S // args.sp} per device), B={B} H={H} heads={NH}")
    losses = []
    for i in range(args.steps):
        t0 = time.perf_counter()
        params, ostate, loss = step(params, ostate, ids)
        losses.append(float(loss))
        print(f"step {i}: loss={losses[-1]:.4f} "
              f"({time.perf_counter() - t0:.1f}s)", flush=True)
    if len(losses) > 1:  # a --steps 1 smoke run has no slope to check
        assert losses[-1] < losses[0], losses
    print(f"long-context {mode} SP: OK ({losses[0]:.4f} -> "
          f"{losses[-1]:.4f})")


if __name__ == "__main__":
    main()
