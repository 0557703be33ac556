"""Device time of a traced serving run by PROGRAM, shared by the
readers that split it (``decode_program_ms``,
``prefill_busy_share_pct``): the ``XLA Modules`` line of the first
device plane, one event per executed program.  ``jit_program`` is the
slot manager's decode program, ``jit_ptpu_prefill`` and
``jit_ptpu_extend`` the prefill programs (named so since PR 29; a
program without those names reads as no prefill time and the readers
return nothing), ``jit__insert`` the insertion."""

import os
import re

import trace_reduce


def times(ctx):
    """``{"decode": (seconds, executions), "prefill": ..., "insert":
    ..., "other": ...}`` or None; kept on ``ctx.collected``."""
    if "_program_times" in ctx.collected:
        return ctx.collected["_program_times"]
    out = None
    file = trace_reduce.find_xplane(os.path.join(ctx.out, "profile"))
    if file is not None and not ctx.rehearse:
        planes = trace_reduce.load(file)
        devices = sorted(p for p in planes
                         if trace_reduce.DEVICE_PLANE.match(p))
        if devices:
            out = {k: [0.0, 0] for k in ("decode", "prefill", "insert",
                                         "other")}
            for e in planes[devices[0]].get(trace_reduce.MODULES_LINE,
                                            []):
                name = re.sub(r"\(\d+\)$", "", e.name)
                kind = {"jit_program": "decode",
                        "jit_ptpu_prefill": "prefill",
                        "jit_ptpu_extend": "prefill",
                        "jit__insert": "insert"}.get(name, "other")
                out[kind][0] += (e.end - e.start) / 1e9
                out[kind][1] += 1
    ctx.collected["_program_times"] = out
    return out
