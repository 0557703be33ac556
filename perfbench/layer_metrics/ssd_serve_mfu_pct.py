"""Model FLOP/s utilisation of the whole serving step in the traced
seconds, for the hybrid Mamba-2 decoder: the model FLOPs
(``perfbench/ssd_flops.py``: the Mamba-2 projections, convolution and
recurrence, the router and the shared expert, the attention's
projections, the held pairs' two expert matrices, head rows) of the
tokens prefilled (growth of ``prefill_tokens_total``) and decoded
(growth of ``decode_steps_total`` x the slots occupied) between the
``/info`` reads that bracket the trace, with the token-expert pairs
the held experts really took (growth of ``moe_pairs_held_total``) and
a head row a decoded token and a prefill chunk, over the trace's span
and the chip's published bf16 peak.  The attention over the cache is
left out: the share under-reads.  It is the share of the WHOLE step
(decode and prefill programs, idle time included) that bounds a later
claim.  Nothing to read from a program without the state counters, or
a configuration without a Mamba-2 pattern."""

import peaks
import ssd_flops


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    names = ("prefill_tokens_total", "decode_steps_total",
             "moe_pairs_held_total", "prefill_chunks_total",
             "slots_active", "ssm_scan_tokens_total")
    if not a or not b or any(n not in r for n in names for r in (a, b)) \
            or not ssd_flops.has_pattern(ctx.config):
        return None
    grew = {n: b[n] - a[n] for n in names}
    occupied = (a["slots_active"] + b["slots_active"]) / 2.0
    decoded = grew["decode_steps_total"] * occupied
    work = ssd_flops.serve_flops(
        ctx.config, tokens=grew["prefill_tokens_total"] + decoded,
        held_pairs=grew["moe_pairs_held_total"],
        head_rows=decoded + grew["prefill_chunks_total"])
    seconds = ctx.reduced["window_s"]
    if seconds <= 0 or work <= 0:
        return None
    return 100.0 * work / seconds / peaks.peaks(ctx.device["kind"])["flops"]
