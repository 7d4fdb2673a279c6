"""Mean host time of `ShardedFabric.quiesce()` after each commit in the
window: BISnp delivery and shard re-extraction on every host."""


def read(ctx):
    d = [b - a for n, a, b in ctx.spans if n == "bench.quiesce"]
    return 1e3 * sum(d) / len(d) if d else None
