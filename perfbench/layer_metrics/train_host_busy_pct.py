"""Share of the window's blocks that `train.py`'s loop spent in its own
host sections: (`host_data_wait_s` + `host_enqueue_s` + `host_log_s`) of
the window's logged blocks over their span.  The rest of a block the
host waits for the device (`ptpu/log_sync`), so a long block with a low
share was the device's, one with a high share the host's.  Also has the
traced steps' idle gaps printed by owner (`host_spans.report`)."""

import host_spans


def read(ctx):
    host_spans.report(ctx)
    blocks = host_spans.job_blocks(ctx)
    if blocks is None:
        return None
    busy = sum(blocks[name] for name in host_spans.JOB_COUNTERS)
    return 100.0 * busy / blocks["span_s"]
