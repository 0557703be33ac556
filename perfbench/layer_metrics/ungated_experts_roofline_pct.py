"""The grouped-matmul kernel's share of its roofline where the experts
are ungated relu² ones (two matrices an expert): the least time the
chip could take for the traced seconds' kernel calls over their
measured device time (own time of the trace's ``tpu_custom_call``
operations: the only Pallas kernel of these programs, the Mamba-2
layers' chunked form being XLA's).  The least time is the larger of
the kernel's FLOPs over the bf16 peak and its least HBM bytes over the
HBM peak (``perfbench/grouped_matmul_ungated_flops.py``), both from
what the programs counted between the ``/info`` reads that bracket the
trace: the held pairs (growth of ``moe_pairs_held_total``) and the
(layer, held expert) that took at least one pair in a program run
(growth of ``moe_experts_touched_total``).

THE BYTES BIND wherever an expert sees few rows.  Nothing to read from
a program whose grouped matmuls did not take the kernel, or that does
not count the experts touched."""

import grouped_matmul_ungated_flops as gmm
import peaks


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    names = ("moe_pairs_held_total", "moe_experts_touched_total")
    if not a or not b or any(n not in r for n in names for r in (a, b)):
        return None
    routes = b.get("grouped_matmul_routes") or {}
    if not routes.get("pallas") or ctx.reduced["kernel_s"] <= 0:
        return None
    sizes = {"h": ctx.config["hidden_size"],
             "f": ctx.config["moe_intermediate_size"],
             "held_pairs": b[names[0]] - a[names[0]]}
    peak = peaks.peaks(ctx.device["kind"])
    least = max(
        gmm.flops(**sizes) / peak["flops"],
        gmm.least_bytes(**sizes, touched=b[names[1]] - a[names[1]])
        / peak["bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / ctx.reduced["kernel_s"]
