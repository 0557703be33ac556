"""90th percentile of the admission-queue wait (`timings.phases`
`queue_wait`, 0 where a request never queued) over the window's
responses."""

import stats


def read(ctx):
    waits = [1e3 * r["response"]["timings"]["phases"]["phases"]
             .get("queue_wait", 0.0)
             for r in ctx.collected["done"]
             if "phases" in r.get("response", {}).get("timings", {})]
    return stats.tail(waits, 90.0)
