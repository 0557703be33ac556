"""Device time of one training step: the union of the device's
operations over the traced steady steps, divided by their number (the
executions of the step program in the trace; the mix's `profile_steps`
if the trace names no program)."""


def traced_steps(ctx):
    modules = ctx.reduced["modules"]
    return max(modules.values()) if modules \
        else ctx.collected["trace_steps"]


def read(ctx):
    return 1e3 * ctx.reduced["busy_s"] / traced_steps(ctx)
