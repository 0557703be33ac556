"""Share of the device's idle time, in the seconds traced right after
the window, that lies under a named `ptpu*` span of the engine's thread
(`host_spans.attribute`: the complement of the device's busy union,
each gap given to the innermost span that covers it).  The check on the
tracing itself: what it leaves is idle time nobody owns."""

import host_spans


def read(ctx):
    rep = host_spans.report(ctx)
    if rep is None or rep["idle_s"] <= 0:
        return None
    lost = rep["by_owner"].get(host_spans.UNATTRIBUTED, 0.0)
    return 100.0 * (1.0 - lost / rep["idle_s"])
