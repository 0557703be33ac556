"""The attention's own device time inside the decode program per
decode step: scores, softmax and values over the keys a step reads,
with the read of the planes (``ptpu_attend``; perfbench/
device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.decode_ms(ctx, "attend")
