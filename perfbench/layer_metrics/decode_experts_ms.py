"""The expert layers' own device time per decode step: routing
(``ptpu_route``) and the held experts' sort, gather, grouped matmuls
and sum (``ptpu_experts``; perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.decode_ms(ctx, "experts")
