"""The decode program's share of its roofline, from the traced stretch: for
each ``engine.decode`` call the least time the chip could take to read what
a decode step has to read (every matmul weight once in the compute type, and
the cache of every token live in an active slot) or to do its operations,
whichever is larger, over the time the chip was busy during that call."""

from benchmarks.harness import spec


def read(ctx, *, span: str):
    if ctx.trace is None or ctx.peaks is None:
        return None
    busy = ctx.trace.busy_within(span)
    series = ctx.run.values.get("traced_series", {})
    active = series.get("decode_active", [])
    cached = series.get("decode_cached_tokens", [])
    n = min(len(busy), len(active))
    if not n or not sum(busy[:n]):
        return None
    arch = spec.adapter(ctx.config)
    least = 0.0
    for i in range(n):
        least += max(
            arch.decode_step_bytes(ctx.config, cached[i])
            / ctx.peaks["hbm_bytes_per_s"],
            arch.decode_step_flops(ctx.config, active[i], cached[i])
            / ctx.peaks["bf16_flops"])
    return 100.0 * least / sum(busy[:n])
