"""Host seconds a step waits for its batch: `host_data_wait_s` of the
window's logged blocks (the `ptpu/data_wait` sections of `train.py`'s
loop: `next(batches)` and its `device_put`), per step, in ms.  A
counter of the program, on in every run; nothing to read where the job
logs none."""

import host_spans


def read(ctx):
    blocks = host_spans.job_blocks(ctx)
    if blocks is None:
        return None
    return 1e3 * blocks["host_data_wait_s"] / blocks["steps"]
