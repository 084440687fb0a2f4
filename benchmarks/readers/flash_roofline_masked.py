"""``flash_roofline`` for a model whose attention layers differ in their
MASK (a window on some, none on the rest) and whose K and V have fewer heads
than Q: the flash kernels' least time over their device time, as
``readers/flash_roofline.py`` reads it, with each kind of call told from the
others by the ``jax.named_scope`` its custom-calls carry in their name in
the trace (``%hetu.attn.window.12 = (bf16[..], f32[..]) custom-call(..)`` in
a step of recomputed layers, ``%transpose_jvp_hetu.attn.window__.7`` where a
plain gradient is taken), and its operations and bytes counted for that
kind.  The architecture's adapter says which scopes there are and what one
call of each is (``attention_calls``); the three patterns of the metric's
file hold a ``%s`` where the scope goes.

What is counted is what the MASK requires, whatever walks it: a head's live
scores are ``S (S + 1) / 2`` under the diagonal alone and ``W S - W (W - 1)
/ 2`` under a window of ``W`` (a query at ``i`` sees ``min(i + 1, W)``
keys); a live score costs ``2 (d_qk + d_v)`` operations forward (QK^T and
PV) and ``2 (3 d_qk + 2 d_v)`` backward (the recomputed score, dQ and dK over
``d_qk``, dP and dV over ``d_v``), in every query head.  Forward reads Q, K,
V and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV; Q, O,
dO and dQ have the query heads, K, V, dK and dV the KV heads.  A kernel that
walks a tile the mask leaves empty, or reads K once a query head, does work
that counts for nothing here, so the share cannot pass 100%."""

import re

from benchmarks.harness import spec


def live_scores(seq: int, window=None) -> int:
    """Scores one head's mask leaves live over ``seq`` positions."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def call_flops(*, batch, heads, seq, d_qk, d_v, window=None, **_) -> dict:
    unit = 2 * batch * heads * live_scores(seq, window)
    return {"fwd": unit * (d_qk + d_v), "bwd": unit * (3 * d_qk + 2 * d_v)}


def call_bytes(*, batch, heads, kv_heads, seq, d_qk, d_v, itemsize=2,
               **_) -> dict:
    unit = batch * seq * itemsize
    q_side, k_side = heads * (d_qk + d_v), kv_heads * (d_qk + d_v)
    return {"fwd": unit * (q_side + k_side),
            "bwd": unit * 2 * (q_side + k_side)}


def read(ctx, *, fwd: str, bwd: str, bwd_count: str):
    calls = getattr(spec.adapter(ctx.config), "attention_calls", None)
    if ctx.trace is None or ctx.peaks is None or calls is None:
        return None
    seconds = least = 0.0
    for scope, call in calls(ctx.config, ctx.run.values).items():
        f, b, n = (ctx.trace.kernel_events(rx % re.escape(scope))
                   for rx in (fwd, bwd, bwd_count))
        seconds += sum(own for _, own in f + b) / 1e9
        ops, byt = call_flops(**call), call_bytes(**call)
        for kind, count in (("fwd", len(f)), ("bwd", len(n))):
            least += count * max(ops[kind] / ctx.peaks["bf16_flops"],
                                 byt[kind] / ctx.peaks["hbm_bytes_per_s"])
    if not seconds:
        return None
    return 100.0 * least / seconds
