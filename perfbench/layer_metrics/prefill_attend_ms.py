"""The attention's own device time per prefill piece
(``jit_ptpu_prefill`` + ``jit_ptpu_extend``; ``ptpu_attend``).  A
latent layer's expansion of the rows it reads is NOT in it
(``prefill_expand_ms``; perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.prefill_ms(ctx, "attend")
