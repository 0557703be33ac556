"""The engine's own time between dispatches: 1 - (sum of the step
records' `device_s`) / (first record's start to last record's end), over
the engine's step records (`GET /trace`) inside the window, as far back as the
telemetry ring reaches.  Admission, prefill chunks, sampling and bookkeeping are
all in the remainder."""


def read(ctx):
    steps = ctx.collected["engine_steps"]
    if len(steps) < 2:
        return None
    span_us = max(e["ts"] + e["dur"] for e in steps) \
        - min(e["ts"] for e in steps)
    return 100.0 * (1.0 - 1e6 * sum(e["args"]["device_s"] for e in steps)
                    / span_us)
