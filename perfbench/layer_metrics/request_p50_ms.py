"""Median of a caller's whole wait, client clock: from when the caller
was free to send (the request was due) to the whole reply, over the
replies that came inside the window.  `/generate` does not stream, so
this is all a caller sees of one request.  Per-layer here because the
cell's loop is closed and saturated: the queue is always full, and the
wait follows from the rate by Little's law (callers / requests a
second)."""

import stats


def read(ctx):
    return stats.median([1e3 * (r["done"] - r["due"])
                         for r in ctx.collected["done"]])
