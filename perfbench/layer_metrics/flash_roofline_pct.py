"""The attention kernels' share of their roofline: the least time the
chip could take for one step's kernel calls (perfbench/flops.py: needed
FLOPs over the bf16 peak against least bytes over the HBM peak, the
larger: FLOPs bind at these shapes by two orders of magnitude) over
their measured device time per step and chip."""

import flops
import peaks
from run import load_module


def read(ctx):
    if not ctx.reduced["kernel_calls"]:
        return None
    steps = load_module("layer_metrics", "step_device_ms").traced_steps(ctx)
    peak = peaks.peaks(ctx.device["kind"])
    per_chip = ctx.collected["batch"] // ctx.collected["chips"]
    least = max(
        flops.attention_kernels_flops(ctx.config, per_chip) / peak["flops"],
        flops.attention_kernels_bytes(ctx.config, per_chip)
        / peak["bytes_per_s"])
    return 100.0 * least / (ctx.reduced["kernel_s"] / steps)
