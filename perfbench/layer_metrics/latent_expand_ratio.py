"""How many times a cached latent row is sent through the expansion
``W_kvb``: growth of ``/info`` ``latent_rows_expanded_total`` (cached
rows x layers that the prefill pieces expanded to keys and values)
over growth of ``prefill_tokens_total`` x the latent layers, from the
window's open to its close.  1 is the model's own (every token's
latent expanded once); a prompt prefilled in pieces re-expands the
rows of the earlier pieces in every later one.  The layers are read off
the pool (``kv_pool_bytes_by_kind`` holds nothing but latent planes of
``held.num_hidden_layers`` layers).  A program without the counter
reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("latent_rows_expanded_total", "prefill_tokens_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    rows, tokens = (b[n] - a[n] for n in names)
    layers = ctx.config["held"]["num_hidden_layers"]
    if tokens <= 0:
        return None
    return rows / (tokens * layers)
