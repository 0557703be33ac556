"""Device time per decode step: the union of the device's operations
in the seconds traced by POST /profile/start|stop right after the
window (the load still on), over the growth of
`decode_steps_total` between the `/info` reads that bracket them
(prefill chunks run in the same seconds and are counted in)."""


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    if not a or not b:
        return None
    steps = b["decode_steps_total"] - a["decode_steps_total"]
    if steps <= 0:
        return None
    return 1e3 * ctx.reduced["busy_s"] / steps
