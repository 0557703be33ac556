"""Host seconds in admission per decode step (`ptpu/admit`: the first
token from the prefill logits, the insertion of the stream's cache into
the pool): `admit_s` of the engine's step records (`GET /trace`) inside
the window, as far back as the telemetry ring reaches.  A counter of
the program, on in every run; nothing to read where the records lack
it."""

import host_spans


def read(ctx):
    return host_spans.engine_field_ms(ctx, "admit_s")
