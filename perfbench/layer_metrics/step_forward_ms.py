"""Own device time per training step, on the first chip, of the
operations traced under ``jvp(`` and no ``transpose(``: the forward
pass, its attention kernel with it (perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.step_ms(ctx, "forward", "passes")
