"""90th percentile of a caller's whole wait (see `request_p50_ms`),
withheld where fewer than ten replies lie beyond it."""

import stats


def read(ctx):
    return stats.tail([1e3 * (r["done"] - r["due"])
                       for r in ctx.collected["done"]], 90.0)
