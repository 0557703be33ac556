"""Share of the window's decode dispatches that were launched before
the dispatch ahead of them was collected: growth of `/info`
`decode_dispatches_ahead_total` over growth of `decode_dispatches_total`
from the window's open to its close.  A dispatch launched ahead is in the
device's queue while the host deals out the tokens of the one before it;
100 is a device whose queue never ran empty under the decode loop (the
others are counted by reason in `decode_serial_reasons`).  A program
without the counters reports nothing."""


def read(ctx):
    a, b = ctx.collected["info_open"], ctx.collected["info_close"]
    names = ("decode_dispatches_ahead_total", "decode_dispatches_total")
    if any(n not in rec for n in names for rec in (a, b)):
        return None
    ahead, launched = (b[n] - a[n] for n in names)
    if launched <= 0:
        return None
    return 100.0 * ahead / launched
