"""Share of the latent layers' causal query-key pairs that took the
absorbed path (attended over the latents as they lie: the decode
steps) rather than the expanded one (the prefill pieces): growth of
``/info`` ``latent_pairs_absorbed_total`` over the growth of both pair
counters, from the window's open to its close.  A program without the
counters reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("latent_pairs_absorbed_total", "latent_pairs_expanded_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    absorbed, expanded = (b[n] - a[n] for n in names)
    if absorbed + expanded <= 0:
        return None
    return 100.0 * absorbed / (absorbed + expanded)
