"""Device idle share: 1 - (union of the device's operations) / (first
operation's start to last operation's end), from the profiler trace
taken in this run."""


def read(ctx):
    return 100.0 * (1.0 - ctx.reduced["busy_s"] / ctx.reduced["window_s"])
