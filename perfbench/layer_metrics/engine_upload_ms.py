"""Host seconds handing a dispatch's host operands to the device per
decode step (`ptpu/upload`: tokens, positions, sampling state):
`upload_s` of the engine's step records (`GET /trace`) inside the
window.  A counter of the program, on in every run; nothing to read
where the records lack it."""

import host_spans


def read(ctx):
    return host_spans.engine_field_ms(ctx, "upload_s")
