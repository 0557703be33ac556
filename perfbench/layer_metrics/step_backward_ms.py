"""Own device time per training step, on the first chip, of the
operations traced under ``transpose(``: the backward pass, what it
recomputes of the forward and its attention kernels with it
(perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.step_ms(ctx, "backward", "passes")
