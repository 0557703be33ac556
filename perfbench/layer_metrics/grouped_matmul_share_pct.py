"""Share of the device's busy time spent in the grouped-matmul kernel
of the expert layers (own time of the trace's ``tpu_custom_call``
operations over the busy union: the only Pallas kernel that the
serving programs of the configurations with expert layers hold).  Nothing to read where the trace
names no such operation, or from a program whose grouped matmuls did
not take the kernel (``/info`` ``grouped_matmul_routes``: XLA's own
``ragged-dot`` is a ``tpu_custom_call`` too)."""


def read(ctx):
    close = ctx.collected.get("trace_close") or {}
    routes = close.get("grouped_matmul_routes") or {}
    if not ctx.reduced["kernel_calls"] or not routes.get("pallas"):
        return None
    return 100.0 * ctx.reduced["kernel_s"] / ctx.reduced["busy_s"]
