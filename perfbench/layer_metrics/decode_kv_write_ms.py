"""Own device time per decode step of the writes into the pool's
leaves: rows, indices, a ring's table (``ptpu_kv_write``;
perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.decode_ms(ctx, "kv_write")
