"""The decode program's share of its roofline, from the traced stretch: for
each decode round the least time the chip could take to read what a decode
step has to read (every matmul weight once in the compute type, and the
cache of every token live in an active slot) or to do its operations,
whichever is larger, over the duration of the round's OWN module run on the
device (``hetu_launches``: the launch followed to its program, wherever the
host's spans lie, so the share reads the same under run-ahead).  Launch i
of the stretch is the round whose ``decode_active`` and
``decode_cached_tokens`` the benchmark's wrapper noted i-th; where the trace
cannot be paired, or the two counts differ, None."""

from benchmarks.harness import spec
from benchmarks.readers import hetu_launches


def read(ctx):
    if ctx.peaks is None:
        return None
    records = hetu_launches.launches(ctx, "serve.decode")
    series = ctx.run.values.get("traced_series", {})
    active = series.get("decode_active", [])
    cached = series.get("decode_cached_tokens", [])
    if records is None or not len(records) == len(active) == len(cached):
        return None
    arch = spec.adapter(ctx.config)
    least = sum(
        max(arch.decode_step_bytes(ctx.config, c)
            / ctx.peaks["hbm_bytes_per_s"],
            arch.decode_step_flops(ctx.config, a, c)
            / ctx.peaks["bf16_flops"])
        for a, c in zip(active, cached))
    return 100.0 * least / (sum(r.program_ns for r in records) / 1e9)
