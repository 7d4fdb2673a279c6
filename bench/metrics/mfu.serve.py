"""Model FLOPs of the served tokens (decode at the mean context, plus the
prompt's prefill FLOPs shared over its generated tokens; from the
configuration, `bench.roofline.decoder_flops_per_token`) times the traced
run's tokens per second, over the chip's bf16 peak."""


def read(ctx):
    tps = ctx.metrics.get("tokens_per_s")
    if not tps:
        return None
    return 100.0 * ctx.counters["flops_per_token"] * tps \
        / ctx.peaks["bf16_flops"]
