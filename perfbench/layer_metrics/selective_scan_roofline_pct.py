"""The selective-scan kernel's share of its roofline: the least time
the chip could take for the traced seconds' kernel calls over their
measured device time (own time of the trace's ``tpu_custom_call``
operations: the only Pallas kernel the serving programs of this
configuration hold).  The least time is the larger of the kernel's
FLOPs over the bf16 peak and its least HBM bytes over the HBM peak
(``perfbench/ssm_flops.py``): positions x state layers from the growth
of ``ssm_scan_tokens_total`` between the ``/info`` reads that bracket
the trace, a state in and out for each call the trace holds.

THE BYTES BIND, and the kernel is not a streaming one: its work is
exponentials and multiplies on the vector units, a position at a time,
and ``peaks.py`` publishes no vector-unit peak.  So this is the share
of the HBM bound and reads LOW; it says how far the kernel is from
being free, not that it is badly written.  The counters' reads
bracket the trace from outside, so the work is over-counted by the
calls of a few hundredths of a second: the share can never pass 100."""

import peaks
import ssm_flops


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    name = "ssm_scan_tokens_total"
    calls = ctx.reduced["kernel_calls"]
    if not calls or not a or not b or name not in a or name not in b:
        return None
    tokens = b[name] - a[name]
    if tokens <= 0 or ctx.reduced["kernel_s"] <= 0:
        return None
    peak = peaks.peaks(ctx.device["kind"])
    least = max(
        ssm_flops.scan_kernel_flops(ctx.config, layer_tokens=tokens)
        / peak["flops"],
        ssm_flops.scan_kernel_bytes(ctx.config, layer_tokens=tokens,
                                    calls=calls) / peak["bytes_per_s"])
    return 100.0 * least / ctx.reduced["kernel_s"]
