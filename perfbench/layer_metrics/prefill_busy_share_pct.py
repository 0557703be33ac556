"""Share of the device's busy time in the traced seconds that the
prefill programs took (``jit_ptpu_prefill`` + ``jit_ptpu_extend`` on
the ``XLA Modules`` line over the busy union)."""

from run import load_module


def read(ctx):
    times = load_module("layer_metrics", "_serve_programs").times(ctx)
    if not times or not times["prefill"][1] or not ctx.reduced["busy_s"]:
        return None
    return 100.0 * times["prefill"][0] / ctx.reduced["busy_s"]
