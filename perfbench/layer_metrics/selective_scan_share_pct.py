"""Share of the device's busy time spent in the selective-scan kernel
(own time of the trace's ``tpu_custom_call`` operations over the busy
union).  Nothing to read where the trace names no such operation, or
from a program that does not count its scans."""


def read(ctx):
    close = ctx.collected.get("trace_close") or {}
    if not ctx.reduced["kernel_calls"] \
            or "ssm_scan_tokens_total" not in close:
        return None
    return 100.0 * ctx.reduced["kernel_s"] / ctx.reduced["busy_s"]
