"""Own device time per decode step of what a Flax module names and no
scope of the program's does: projections, MLP, norms, embeddings AND
the head's projection (``lm_head``, ``wte.attend``: a module, so it
counts here and not with the sampler; perfbench/device_scopes.py)."""

import device_scopes


def read(ctx):
    return device_scopes.decode_ms(ctx, "dense")
