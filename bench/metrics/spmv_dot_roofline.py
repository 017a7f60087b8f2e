"""Share of the SpMV+dot kernel's HBM roofline (bytes-bound), in %."""


def read(ctx):
    return ctx.kernel_roofline("spmv_dot")
