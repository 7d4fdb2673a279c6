"""The fused fabric egress kernel's share of its roofline: the least bytes
its calls must move (`bench.roofline.egress_bytes`, over the call's shapes)
at the chip's HBM bandwidth, over the kernel's device time in the trace.
The HBM bound is the binding one (see egress_bytes)."""

# the Mosaic kernel of `repro.kernels.fabric_egress` as the trace names it:
# "_fabric_egress_impl.<n> custom-call:tpu_custom_call"


def is_kernel(name: str, module: str) -> bool:
    return name.startswith("_fabric_egress_impl") and \
        name.endswith("custom-call:tpu_custom_call")


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    calls = t.op_count(is_kernel)
    secs = t.op_time(is_kernel)
    if calls == 0 or secs <= 0:
        return None
    least = ctx.counters["bytes_per_launch"] * calls \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
