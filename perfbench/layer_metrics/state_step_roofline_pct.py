"""The Mamba-2 layers' one-position update's share of its HBM bound:
the least bytes the traced seconds' decode steps had to move for the
state (``perfbench/ssd_flops.py``: the float32 state read and written,
the convolution's tail, a lane's ``x``, ``B``, ``C``, ``dt`` and
``y``, for each lane and layer the steps rewrote: growth of
``ssm_state_steps_total``, lanes a decode step, between the ``/info``
reads that bracket the trace, times the configuration's Mamba-2
layers) over the HBM peak, divided by the own time of the part
``state`` (scope ``ptpu_state_step``) inside the decode program
(``perfbench/device_scopes.py``).  An update is a few multiply-adds a
state element: the bytes bind.  Nothing to read from a program without
the state counters or the scope, or a configuration without a
Mamba-2 pattern."""

import device_scopes
import peaks
import ssd_flops


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    name = "ssm_state_steps_total"
    if not a or not b or name not in a or name not in b \
            or not ssd_flops.has_pattern(ctx.config):
        return None
    found = device_scopes.split(ctx, device_scopes.DECODE)
    seconds = found[0].get("state", 0.0) if found else 0.0
    lanes = (b[name] - a[name]) * ssd_flops.sizes(ctx.config)["mamba"]
    if seconds <= 0 or lanes <= 0:
        return None
    least = ssd_flops.state_step_bytes(ctx.config, lane_steps=lanes) \
        / peaks.peaks(ctx.device["kind"])["bytes_per_s"]
    return 100.0 * least / seconds
