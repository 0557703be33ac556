"""The Mamba-2 layers' chunked (SSD) form's share of its roofline: the
least time the chip could take for the traced seconds' prefill pieces
through it over their measured device time, the own time of the part
``scan`` (scope ``ptpu_scan``: ``ops/ssd.ssd_scan``) inside the prefill
programs (``perfbench/device_scopes.py``).  The least time is the
larger of the form's FLOPs over the bf16 peak and its least HBM bytes
over the HBM peak (``perfbench/ssd_flops.py``): positions x Mamba-2
layers from the growth of ``ssm_scan_tokens_total`` between the
``/info`` reads that bracket the trace, a state in and out for each
piece and layer (growth of ``prefill_chunks_total`` x the layers).

The FLOPs bind: the causal half of a 128-position block's products
and the state's update and read-out.  Nothing to read from a program
without the state counters or the scope, or a configuration without a
Mamba-2 pattern."""

import device_scopes
import peaks
import ssd_flops


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    names = ("ssm_scan_tokens_total", "prefill_chunks_total")
    if not a or not b or any(n not in r for n in names for r in (a, b)) \
            or not ssd_flops.has_pattern(ctx.config):
        return None
    found = device_scopes.split(ctx, device_scopes.PREFILL)
    seconds = found[0].get("scan", 0.0) if found else 0.0
    tokens = b[names[0]] - a[names[0]]
    if seconds <= 0 or tokens <= 0:
        return None
    layers = ssd_flops.sizes(ctx.config)["mamba"]
    calls = (b[names[1]] - a[names[1]]) * layers
    peak = peaks.peaks(ctx.device["kind"])
    least = max(
        ssd_flops.scan_flops(ctx.config, layer_tokens=tokens)
        / peak["flops"],
        ssd_flops.scan_bytes(ctx.config, layer_tokens=tokens, calls=calls)
        / peak["bytes_per_s"])
    return 100.0 * least / seconds
