"""Share of the HBM roofline reached by the decode steps of a model with
held experts: the least bytes they had to read in the window (every held
weight each step, each held expert once for each layer and step that
routes a slot to it, the latent cache up to each step's context;
`bench.roofline_dsv2.decode_bytes`), over the window, over the chip's HBM
bandwidth."""


def read(ctx):
    b = ctx.counters.get("decode_bytes")
    if not b or ctx.window_s <= 0:
        return None
    return 100.0 * b / ctx.window_s / ctx.peaks["hbm_bytes_per_s"]
