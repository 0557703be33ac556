"""Model FLOP/s utilisation of a decode token-step on the device: the
model FLOPs of one token through the weights,
``2 x (layers x (4 h^2 + 2 h intermediate) + h vocab)`` from the
configuration's published sizes, times the slots occupied in the traced
seconds, over `decode_device_ms` and the chip's published bf16 peak.

The attention over the cache is left out, so the share under-reads and
cannot flatter; the count depends on the configuration and the
occupancy alone, not on how the program stores its cache.  A decode
step is bound by bytes: the number is there to bound a claimed gain,
not to be chased."""

import peaks
from run import load_module


def token_flops(published: dict) -> float:
    h = published["hidden_size"]
    per_layer = 4 * h * h + 2 * h * published["intermediate_size"]
    return 2.0 * (published["num_hidden_layers"] * per_layer
                  + h * published["vocab_size"])


def read(ctx):
    step_ms = load_module("layer_metrics", "decode_device_ms").read(ctx)
    if not step_ms:
        return None
    reads = [ctx.collected[k].get("slots_active")
             for k in ("trace_open", "trace_close")]
    if None in reads:
        return None
    occupied = sum(reads) / len(reads)
    peak = peaks.peaks(ctx.device["kind"])["flops"]
    work = token_flops(ctx.config["published"]) * occupied
    return 100.0 * work / (step_ms / 1e3) / peak
