"""Programs compiled inside the window: growth of `/info`
`compile_cache_misses` from its open to its close.  Should be 0."""


def read(ctx):
    return ctx.collected.get("window_compiles")
