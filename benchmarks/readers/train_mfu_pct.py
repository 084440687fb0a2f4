"""Model FLOP/s utilization of a training cell: the operations the forward
and backward passes REQUIRE per token (by the architecture's adapter;
recomputation not counted) times tokens per second, over chips times the
published peak."""

from benchmarks.harness import spec


def read(ctx):
    v = ctx.run.values
    if ctx.peaks is None or not v.get("tokens"):
        return None
    need = spec.adapter(ctx.config).train_flops_per_token(
        ctx.config, v["seq"])
    rate = v["tokens"] / v["window_s"]
    return 100.0 * need * rate / (int(ctx.cell["chips"])
                                  * ctx.peaks["bf16_flops"])
