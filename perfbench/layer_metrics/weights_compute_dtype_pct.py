"""Share of the served weights' bytes that rest in the dtype the model
computes in: `/info` `weights_bytes_by_dtype[weights_compute_dtype]`
over `weights_bytes` at the window's close (target and draft trees as
the programs are handed them).  What rests in another dtype is either
kept so on purpose (LayerNorm leaves compute in float32) or converted
inside every program that uses it.  A program without the counters
reports nothing."""


def read(ctx):
    info = ctx.collected.get("info_close") or {}
    total = info.get("weights_bytes")
    by_dtype = info.get("weights_bytes_by_dtype")
    compute = info.get("weights_compute_dtype")
    if not total or by_dtype is None or compute is None:
        return None
    return 100.0 * by_dtype.get(compute, 0) / total
