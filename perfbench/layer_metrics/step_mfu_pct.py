"""Model FLOP/s utilisation of the step on the device: model FLOPs of
one step (perfbench/flops.py: forward + backward, no recompute) over the
step's device time and the chips' published bf16 peak."""

import flops
import peaks
from run import load_module


def read(ctx):
    step_s = load_module("layer_metrics", "step_device_ms").read(ctx) / 1e3
    peak = peaks.peaks(ctx.device["kind"])["flops"]
    work = flops.config_train_flops(ctx.config, ctx.collected["batch"])
    return 100.0 * work / step_s / (peak * ctx.collected["chips"])
