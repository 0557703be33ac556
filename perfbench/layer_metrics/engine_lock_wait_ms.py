"""Host seconds the engine waited for `device_lock` before a dispatch,
per decode step (`ptpu/lock_wait`): `lock_wait_s` of the engine's step
records (`GET /trace`) inside the window.  A counter of the program, on
in every run; nothing to read where the records lack it."""

import host_spans


def read(ctx):
    return host_spans.engine_field_ms(ctx, "lock_wait_s")
