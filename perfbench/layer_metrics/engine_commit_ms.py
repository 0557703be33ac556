"""Host seconds committing a dispatch's tokens per decode step
(`ptpu/commit`: tokens out, eviction, completion, the step record):
`commit_s` of the engine's step records (`GET /trace`) inside the
window.  A counter of the program, on in every run; nothing to read
where the records lack it."""

import host_spans


def read(ctx):
    return host_spans.engine_field_ms(ctx, "commit_s")
