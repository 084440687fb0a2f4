"""Train a tiny CTR model over an int8 PS gradient wire.

The same logistic-regression-over-pooled-embeddings model trains twice
against one in-process van server on identical data: once over the
legacy f32 gradient wire, once with ``wire="int8"`` (per-row scales on
the wire + client-side error-feedback residuals).  The run asserts the
quantized wire's final loss lands within tolerance of the f32 wire's —
the convergence-parity contract — and prints the wire bytes the int8
encoding did NOT move (from the shared ``van.*.bytes_saved`` telemetry
counters).

    python examples/quant_train.py --steps 150
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import numpy as np


def train(wire, port, *, vocab, dim, fields, batch, steps,
          verbose: bool = True):
    """Train the CTR model over a PS at ``port`` on ``wire``; returns
    ``(final_loss, step_seconds)`` — the mean loss over the last 20
    steps plus per-step pull+push wall times."""
    import time

    from hetu_tpu.ps import van
    teacher = np.random.default_rng(42).normal(0, 1, vocab).astype(
        np.float32)
    emb = van.RemotePSTable("127.0.0.1", port, vocab, dim, seed=7,
                            init="normal", init_b=0.01,
                            optimizer="adagrad", lr=0.1, wire=wire)
    wt = van.RemotePSTable("127.0.0.1", port, 1, dim + 1, seed=8,
                           init="zeros", optimizer="adagrad", lr=0.1,
                           wire=wire)
    rng = np.random.default_rng(3)  # identical stream both arms
    tail = []
    step_s = []
    for step in range(steps):
        ids = rng.integers(0, vocab, (batch, fields))
        y = (teacher[ids].sum(1) > 0).astype(np.float32)
        t0 = time.perf_counter()
        x = emb.sparse_pull(ids.ravel()).reshape(batch, fields, dim).sum(1)
        wb = wt.dense_pull()[0]
        p = 1.0 / (1.0 + np.exp(-(x @ wb[:dim] + wb[dim])))
        dlog = (p - y) / batch
        wt.dense_push(np.concatenate([x.T @ dlog, [dlog.sum()]])[None, :])
        emb.sparse_push(
            ids.ravel(),
            (dlog[:, None] * wb[None, :dim])[:, None, :].repeat(
                fields, axis=1).reshape(batch * fields, dim))
        step_s.append(time.perf_counter() - t0)
        eps = 1e-7
        loss = float(np.mean(-y * np.log(p + eps)
                             - (1 - y) * np.log(1 - p + eps)))
        if step >= steps - 20:
            tail.append(loss)
        if verbose and (step % 50 == 0 or step == steps - 1):
            print(f"  [{wire or 'f32'}] step {step:4d}  loss {loss:.4f}")
    emb.close()
    wt.close()
    return float(np.mean(tail)), step_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--fields", type=int, default=4)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="max relative final-loss delta int8 vs f32")
    args = ap.parse_args()

    from hetu_tpu.ps import van
    from hetu_tpu.telemetry import default_registry as reg
    port = van.serve(0)
    try:
        kw = dict(vocab=args.vocab, dim=args.dim, fields=args.fields,
                  batch=args.batch, steps=args.steps)
        loss_f32, _ = train(None, port, **kw)
        loss_int8, _ = train("int8", port, **kw)
    finally:
        van.stop()

    saved = sum(m.value for name, m in reg.metrics().items()
                if name.startswith("van.") and name.endswith("bytes_saved"))
    wire = sum(m.value for name, m in reg.metrics().items()
               if name.startswith("van.") and name.endswith("bytes_wire"))
    delta = abs(loss_int8 - loss_f32) / max(abs(loss_f32), 1e-9)
    print(f"final loss: f32-wire {loss_f32:.4f}  int8-wire "
          f"{loss_int8:.4f}  (rel delta {delta:.2%})")
    print(f"int8 wire moved {wire / 1024:.0f} KB, saved "
          f"{saved / 1024:.0f} KB vs the f32 encoding")
    assert loss_int8 < 0.65, "int8-wire model failed to learn"
    assert delta <= args.tolerance, (
        f"int8-wire loss {loss_int8:.4f} vs f32 {loss_f32:.4f}: "
        f"delta {delta:.2%} exceeds {args.tolerance:.0%}")
    print("quant train: OK")


if __name__ == "__main__":
    main()
