"""Own device time per decode step of the recurrent layers'
one-position update with the shift of the convolution's tail
(``ptpu_state_step``; perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.decode_ms(ctx, "state")
