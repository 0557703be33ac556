"""Share of the programs that took the KV pool in the window (decode
dispatches and insertions) that consumed the pool they were handed and
returned it updated in place: growth of `/info`
`kv_pool_in_place_total` over growth of `kv_pool_dispatches_total`
from the window's open to its close.  A program without the counters
reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("kv_pool_in_place_total", "kv_pool_dispatches_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    in_place, dispatches = (b[n] - a[n] for n in names)
    if dispatches <= 0:
        return None
    return 100.0 * in_place / dispatches
