"""Host clock around one decode dispatch plus its `device_get`, per
decode step: the engine's step records (`GET /trace`, `device_s` and
`window` of each dispatch) inside the window, as far back as the
telemetry ring reaches.  A host span, whatever the program calls it;
`/info` carries the matching counters only on a meshed server."""


def read(ctx):
    steps = ctx.collected["engine_steps"]
    n = sum(e["args"]["window"] for e in steps)
    if not n:
        return None
    return 1e3 * sum(e["args"]["device_s"] for e in steps) / n
