"""The decode program's own device time per decode step: the summed
durations of ``jit_program`` on the device's ``XLA Modules`` line in
the traced seconds, over the growth of ``decode_steps_total`` between
the ``/info`` reads that bracket them.  ``decode_device_ms`` divides
ALL busy time by the same steps, so it counts the prefill programs in;
where prompts are long that is most of it."""

from run import load_module


def read(ctx):
    times = load_module("layer_metrics", "_serve_programs").times(ctx)
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    if not times or not a or not b or not times["prefill"][1]:
        return None
    steps = b["decode_steps_total"] - a["decode_steps_total"]
    if steps <= 0:
        return None
    return 1e3 * times["decode"][0] / steps
