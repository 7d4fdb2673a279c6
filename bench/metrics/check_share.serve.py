"""The isolation tax on the device while serving: device time of the
framework checker's jitted modules and of the fused fabric egress kernel's
module, over device busy time."""

# the framework checker's jitted module and the fused kernel's, as the
# trace names them
CHECK_MODULES = ("cached_check_access", "_fabric_egress_impl")


def is_check(name: str, module: str) -> bool:
    return any(m in module or name.startswith(m) for m in CHECK_MODULES)


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.op_time(is_check) / t.busy_s
