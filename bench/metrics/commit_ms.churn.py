"""Mean host time of the FM commit call (revoke or grant) in the window,
from the benchmark's span around it."""


def read(ctx):
    d = [b - a for n, a, b in ctx.spans if n == "bench.fm_commit"]
    return 1e3 * sum(d) / len(d) if d else None
