"""The sampler's own device time per decode step: the logits shaped,
the threshold searches, the draw, or the arg-max (``ptpu_sample``;
the head's projection is a module and counts in ``decode_dense_ms``;
perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.decode_ms(ctx, "sample")
