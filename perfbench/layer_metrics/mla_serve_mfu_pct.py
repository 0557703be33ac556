"""Model FLOP/s utilisation of the whole serving step in the traced
seconds, for the latent-attention sparse decoder: the model FLOPs
(``perfbench/mla_flops.py``: projections with ONE expansion a token,
dense and shared SwiGLU, the held pairs counted, head rows, AND the
attention itself at the model's form, from the two pair counters) of
the tokens prefilled (growth of ``prefill_tokens_total``) and decoded
(growth of ``decode_steps_total`` x the slots occupied) between the
``/info`` reads that bracket the trace, with a head row a decoded token
and a prefill chunk, over the trace's span and the chip's published
bf16 peak.  The re-expansion of cached rows and the absorbed path's
wider pairs are not credited.  It is the share of the WHOLE step
(decode and prefill programs, idle time included) that bounds a later
claim.  Nothing to read from a program without the pair counters."""

import mla_flops
import peaks


def read(ctx):
    a, b = ctx.collected["trace_open"], ctx.collected["trace_close"]
    names = ("prefill_tokens_total", "decode_steps_total",
             "moe_pairs_held_total", "prefill_chunks_total",
             "slots_active", "latent_pairs_expanded_total",
             "latent_pairs_absorbed_total")
    if not a or not b or any(n not in r for n in names for r in (a, b)):
        return None
    grew = {n: b[n] - a[n] for n in names}
    occupied = (a["slots_active"] + b["slots_active"]) / 2.0
    decoded = grew["decode_steps_total"] * occupied
    work = mla_flops.serve_flops(
        ctx.config, tokens=grew["prefill_tokens_total"] + decoded,
        held_pairs=grew["moe_pairs_held_total"],
        attention_pairs=grew["latent_pairs_expanded_total"]
        + grew["latent_pairs_absorbed_total"],
        head_rows=decoded + grew["prefill_chunks_total"])
    seconds = ctx.reduced["window_s"]
    if seconds <= 0 or work <= 0:
        return None
    return 100.0 * work / seconds / peaks.peaks(ctx.device["kind"])["flops"]
