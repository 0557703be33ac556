"""Share of the device's busy time spent in the Pallas attention
kernels (own time of the operations trace_reduce.PALLAS_KERNEL names).
Nothing to read where the trace names no such operation."""


def read(ctx):
    if not ctx.reduced["kernel_calls"]:
        return None
    return 100.0 * ctx.reduced["kernel_s"] / ctx.reduced["busy_s"]
