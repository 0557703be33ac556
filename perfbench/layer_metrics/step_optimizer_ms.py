"""Own device time per training step, on the first chip, of
``optimizer.update`` and the parameters' update (``ptpu_optimizer``;
perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.step_ms(ctx, "optimizer", "parts")
