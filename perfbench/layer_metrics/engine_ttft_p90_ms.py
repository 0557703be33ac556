"""90th percentile of the server's own admission-anchored time to the
first token (`timings.ttft_ms`) over the window's responses.  Internal
to the server: `/generate` does not stream, so no client sees it."""

import stats


def read(ctx):
    values = [r["response"]["timings"]["ttft_ms"]
              for r in ctx.collected["done"]
              if "ttft_ms" in r.get("response", {}).get("timings", {})]
    return stats.tail(values, 90.0)
