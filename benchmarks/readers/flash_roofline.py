"""The flash-attention kernels' share of their roofline, from the device
trace of the first chip: for every kernel call the least time the chip could
take (the larger of operations over peak FLOP/s and bytes over peak bytes/s,
from the call's shapes on that chip, which the architecture's adapter
gives), summed, over the kernels' device time.  ``fwd`` matches the forward
kernel's events (remat makes two per layer and step: each is a call),
``bwd`` the kernels of the backward pass, whose calls are counted by
``bwd_count`` (one of them per backward)."""

from benchmarks.harness import flops, spec


def read(ctx, *, fwd: str, bwd: str, bwd_count: str):
    if ctx.trace is None or ctx.peaks is None:
        return None
    f = ctx.trace.kernel_events(fwd)
    b = ctx.trace.kernel_events(bwd)
    n_bwd = len(ctx.trace.kernel_events(bwd_count))
    seconds = sum(own for _, own in f + b) / 1e9
    if not seconds:
        return None
    shape = spec.adapter(ctx.config).attention_call_shape(
        ctx.config, ctx.run.values)
    ops, byt = flops.flash_call_flops(*shape), flops.flash_call_bytes(*shape)
    least = 0.0
    for kind, calls in (("fwd", len(f)), ("bwd", n_bwd)):
        least += calls * max(ops[kind] / ctx.peaks["bf16_flops"],
                             byt[kind] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
