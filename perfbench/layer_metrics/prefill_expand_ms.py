"""Own device time per prefill piece of the latent rows' way through
``W_kvb`` to a key and a value a head (``ptpu_latent_expand``, by the
names of the operations' ROOTS: an expansion fused into a consumer
reads under the consumer; perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.prefill_ms(ctx, "expand")
