"""``flash_roofline`` for attention whose Q and K have one width and V and O
another (latent attention trains at 192 | 128): the kernels' least time over
their device time, as ``readers/flash_roofline.py`` reads it, with the
operations and bytes of a call counted at the two widths the
architecture's adapter gives (``attention_call_widths``).  Causal, so half
the tiles: forward QK^T over ``d_qk`` and PV over ``d_v``; backward the
recomputed scores, dQ and dK over ``d_qk``, dP and dV over ``d_v``.  Forward
reads Q, K, V and writes O; backward reads Q, K, V, O, dO and writes dQ, dK,
dV; Q, K, dQ, dK are ``d_qk`` wide and V, O, dO, dV ``d_v``."""

from benchmarks.harness import spec


def call_flops(batch: int, heads: int, seq: int, d_qk: int, d_v: int) -> dict:
    unit = batch * heads * seq * seq
    return {"fwd": unit * (d_qk + d_v), "bwd": unit * (3 * d_qk + 2 * d_v)}


def call_bytes(batch: int, heads: int, seq: int, d_qk: int, d_v: int,
               itemsize: int = 2) -> dict:
    unit = batch * heads * seq * itemsize
    return {"fwd": unit * (2 * d_qk + 2 * d_v),
            "bwd": unit * (4 * d_qk + 4 * d_v)}


def read(ctx, *, fwd: str, bwd: str, bwd_count: str):
    if ctx.trace is None or ctx.peaks is None:
        return None
    f = ctx.trace.kernel_events(fwd)
    b = ctx.trace.kernel_events(bwd)
    n_bwd = len(ctx.trace.kernel_events(bwd_count))
    seconds = sum(own for _, own in f + b) / 1e9
    if not seconds:
        return None
    arch = spec.adapter(ctx.config)
    batch, heads, seq, _ = arch.attention_call_shape(ctx.config,
                                                     ctx.run.values)
    widths = arch.attention_call_widths(ctx.config)
    ops = call_flops(batch, heads, seq, *widths)
    byt = call_bytes(batch, heads, seq, *widths)
    least = 0.0
    for kind, calls in (("fwd", len(f)), ("bwd", n_bwd)):
        least += calls * max(ops[kind] / ctx.peaks["bf16_flops"],
                             byt[kind] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
