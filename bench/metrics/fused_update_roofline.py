"""Share of the fused update's HBM roofline (bytes-bound), in %."""


def read(ctx):
    return ctx.kernel_roofline("fused_update")
