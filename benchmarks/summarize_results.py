"""Render benchmarks/results.jsonl as a compact evidence table.

results.jsonl is append-only and heterogeneous (headline rows, MFU
sweeps, decode A/Bs, serving load, offline rooflines, partial
checkpoints...).  This prints the CURRENT evidence state: for every
(bench, model, variant, batch, regime) key, the newest row wins;
superseded and ``partial`` rows are dropped when a newer complete row
for the same key exists.

Usage:
  python benchmarks/summarize_results.py            # markdown table
  python benchmarks/summarize_results.py --tpu-only # hardware rows only
"""

from __future__ import annotations

import argparse
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULTS = os.path.join(HERE, "results.jsonl")


def load_rows(path=RESULTS):
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return rows


def key_of(r):
    return (r.get("bench"), r.get("model"), r.get("variant") or "",
            r.get("batch"), r.get("regime") or "",
            r.get("backend"))


def current_state(rows):
    """Newest row per key; a complete row beats any partial one."""
    best = {}
    for r in rows:
        if r.get("skipped") or r.get("failed"):
            continue
        k = key_of(r)
        prev = best.get(k)
        if prev is None:
            best[k] = r
            continue
        # Completeness first (partial rows are salvage from a cut
        # run), then recency.
        rank = (not r.get("partial"), r.get("ts", 0))
        prev_rank = (not prev.get("partial"), prev.get("ts", 0))
        if rank >= prev_rank:
            best[k] = r
    return sorted(best.values(),
                  key=lambda r: (r.get("bench") or "",
                                 r.get("model") or "",
                                 str(r.get("batch")),
                                 r.get("variant") or ""))


def headline_value(r):
    """The one number a row is 'about', with its unit."""
    for field, unit in (
            ("per_sec_per_chip", None),
            ("tok_per_sec_per_chip", "tok/s/chip"),
            ("roofline_mfu_max", "mfu ceiling"),
            ("hbm_gbps", "GB/s"),
    ):
        v = r.get(field)
        if v is not None:
            return v, (unit or r.get("unit") or "")
    if r.get("load"):
        pts = r["load"]
        last = pts[-1]
        return last.get("agg_tok_per_sec"), \
            f"agg tok/s @ {last.get('clients')} clients"
    return None, ""


def spec_mix_value(r):
    """serving-load rows: the SPEC-MIX leg's headline A/B — engine
    aggregate tok/s over the solo speculative path (coalesce mode),
    with the engine leg's measured draft-acceptance rate.  Empty for
    every other bench."""
    ab = r.get("spec_continuous_vs_coalesce") or {}
    v = ab.get("tok_per_sec_speedup")
    if not v:
        return ""
    eng = next((x for x in r.get("load_spec", [])
                if x.get("mode") == "continuous"), {})
    rate = eng.get("spec_accept_rate")
    return f"{v}x" + (f" (acc {rate})" if rate is not None else "")


def overload_value(r):
    """serving-load rows: the OVERLOAD leg's headline — interactive
    TTFT p99 vs its SLO target (held or blown) and how much batch
    traffic was shed to hold it.  Empty for every other bench."""
    ov = r.get("overload") or {}
    p99 = ov.get("interactive_ttft_p99_ms")
    if p99 is None:
        return ""
    held = "held" if ov.get("slo_held") else "BLOWN"
    shed = (ov.get("shed") or {}).get("batch", 0) \
        + (ov.get("expired") or {}).get("batch", 0)
    return (f"p99 {p99}ms/{ov.get('slo_ttft_ms')}ms {held}, "
            f"batch shed {shed}")


def paged_value(r):
    """serving-load rows: the LONG-TAIL leg's headline — paged vs
    fixed-lane aggregate tok/s at equal KV memory, with the
    steady-state resident-occupancy ratio.  Empty for every other
    bench."""
    ab = (r.get("longtail") or {}).get("paged_vs_fixed") or {}
    v = ab.get("tok_per_sec_speedup")
    if not v:
        return ""
    occ = ab.get("occupancy_ratio")
    return f"{v}x" + (f" (occ {occ}x)" if occ is not None else "")


def lazy_value(r):
    """serving-load rows: the LAZY-GROWTH leg's headline — lazy vs
    full page reservation aggregate tok/s at equal page budget on
    the short-output mix, with the mean-resident ratio and the
    exhaustion-preempt count.  Empty for every other bench."""
    leg = r.get("lazy_longtail") or {}
    ab = leg.get("lazy_vs_full") or {}
    v = ab.get("tok_per_sec_speedup")
    if not v:
        return ""
    occ = ab.get("occupancy_ratio")
    px = (leg.get("lazy") or {}).get("exhaustion_preempts")
    return (f"{v}x" + (f" (occ {occ}x" if occ is not None else "(")
            + (f", {px}px)" if px is not None else ")"))


def spill_value(r):
    """serving-load rows: the PREFIX-SPILL leg's headline — host-
    tier hit-rate vs the drop-on-evict baseline on a population ~4x
    the device pool, with the spilled-hit TTFT p50.  Empty for every
    other bench."""
    leg = r.get("prefix_spill") or {}
    sp = leg.get("spill") or {}
    dr = leg.get("drop") or {}
    if not sp:
        return ""
    return (f"hit {sp.get('hit_rate')} vs {dr.get('hit_rate')}; "
            f"ttft {sp.get('hit_ttft_p50_ms')}ms")


def meshed_value(r):
    """serving-load rows: the MESHED leg's headline — token parity +
    timed-recompile health of the tp=4 arm vs tp=1 (the host-device
    criterion: correctness, not speedup) with the derived
    collective-time share, plus the flight recorder's trace-TRUE
    share when a profiled window landed during the timed arm
    (``collP``; the host-mesh estimate's device-truth check).  Empty
    for every other bench."""
    m = r.get("meshed") or {}
    if not m:
        return ""
    ok = m.get("tokens_equal") and not m.get("compile_misses_timed")
    share = m.get("collective_share_tp4")
    prof = m.get("collective_share_profiled_tp4")
    return (("ok" if ok else "FAIL")
            + f" tp4/tp1 {m.get('agg_ratio_tp4_vs_tp1')}x"
            + (f" coll {share}" if share is not None else "")
            + (f" collP {prof}" if prof is not None else ""))


def _overhead_pct(ov):
    """Render one overhead-leg percentage; a ``!`` suffix marks a
    NOISY-BOX measurement (the harness's same-arm round-to-round
    spread exceeded the ~3% band the leg attests, so the number is
    an honest label, not evidence)."""
    pct = ov.get("overhead_pct")
    if pct is None:
        return ""
    return f"{pct}%" + ("!" if ov.get("noisy_box") else "")


def telemetry_value(r):
    """serving-load rows: the telemetry-overhead A/B column — the
    tracing-on tax in % agg tok/s (contract: <= ~3%).  Empty for
    every other bench."""
    return _overhead_pct(r.get("telemetry_overhead") or {})


def recorder_value(r):
    """serving-load rows: the flight-recorder overhead A/B column —
    the periodic-profiler-window tax in % agg tok/s (same <= ~3%
    contract as telemetry), with the window count.  Empty for every
    other bench."""
    ov = r.get("recorder_overhead") or {}
    pct = _overhead_pct(ov)
    if not pct:
        return ""
    w = ov.get("windows")
    return pct + (f" ({w}w)" if w is not None else "")


def debug_value(r):
    """serving-load rows: the debuggability-overhead A/B column —
    the history-ring + stall-watchdog tax in % agg tok/s with the
    layer fully armed (same <= ~3% contract as telemetry and the
    recorder).  Empty for every other bench."""
    return _overhead_pct(r.get("debug_overhead") or {})


def forensics_value(r):
    """serving-load rows: the forensics-overhead A/B column — the
    phase-ledger + exemplar-capture + anomaly-sentry tax in % agg
    tok/s with the layer armed at defaults (same <= ~3% contract as
    telemetry, the recorder, and the debug ring; both arms carry the
    same history ring so the number isolates the forensics layer).
    Empty for every other bench."""
    return _overhead_pct(r.get("forensics_overhead") or {})


def chaos_value(r):
    """serving-load rows: the chaos-soak column — terminal-status
    accounting under the seeded fault storm (ok / poisoned
    convictions / hung callers), engine restarts, and the armed-
    fault-probe overhead tax.  ``LEAK``/``WEDGED`` flags mean the
    crash-only contract was violated (the bench run itself fails on
    them; a committed flag marks a preserved-evidence row).  Empty
    for every other bench."""
    ch = r.get("chaos") or {}
    if not ch:
        return ""
    out = (f"{ch.get('ok', 0)}ok {ch.get('poisoned', 0)}px "
           f"{ch.get('hung', 0)}hung r{ch.get('engine_restarts', 0)}")
    if ch.get("leaked_slots") or ch.get("leaked_pages"):
        out += " LEAK"
    if ch.get("breaker_wedged"):
        out += " WEDGED"
    probe = _overhead_pct(r.get("faults_overhead") or {})
    if probe:
        out += f" probe {probe}"
    return out


def fleet_value(r):
    """serving-load rows: the FLEET chaos-soak column — terminal
    accounting for the 3-replica router storm (ok / survivor token
    mismatches / hung), failovers + hedges fired/won, and whether
    retry volume stayed under budget.  ``MISMATCH``/``OVERBUDGET``/
    ``RECOMPILED`` flags mean the router-tier contract was violated
    (the bench run itself fails on them; a committed flag marks a
    preserved-evidence row).  Empty for every other bench."""
    fl = r.get("fleet") or {}
    if not fl:
        return ""
    out = (f"{fl.get('ok', 0)}ok {fl.get('hung', 0)}hung "
           f"fo{fl.get('failovers', 0)} "
           f"h{fl.get('hedges_fired', 0)}/"
           f"{fl.get('hedges_won', 0)}w")
    if fl.get("mismatch"):
        out += f" MISMATCH{fl['mismatch']}"
    if not fl.get("retry_under_budget", True):
        out += " OVERBUDGET"
    if any((fl.get("survivor_recompiles") or {}).values()):
        out += " RECOMPILED"
    return out


def fleetobs_value(r):
    """serving-load rows: the FLEET-OBSERVABILITY overhead A/B
    column — router request-span history + SLO accounting + live
    federation scrapes on vs off, in % agg tok/s (same <= ~3%
    contract, noisy-box ``!`` suffix), with the federation scrape
    count and a ``SLO!`` flag when the router's burn gauges
    disagreed with bench-side math.  Empty for every other bench."""
    fo = r.get("fleet_observability") or {}
    pct = _overhead_pct(fo)
    if not pct:
        return ""
    out = pct + f" ({fo.get('federation_scrapes', 0)}sc)"
    if not fo.get("slo_burn_consistent", True):
        out += " SLO!"
    return out


def fleetprefix_value(r):
    """serving-load rows: the FLEET-PREFIX column — through-restart
    hit rate, wire-fetch arm vs per-replica-only arm (the PR 16
    migration tier's headline), plus the wire-fetch TTFT as a
    fraction of the re-prefill cost it replaces (contract: between
    the local spilled-hit ratio and 1.0; ``!`` marks a noisy-box
    ordering the box could not resolve).  ``INEXACT`` flags a
    wire-fetched greedy stream that diverged from the local one —
    the bitwise-identity contract violated (the bench run itself
    fails on it; a committed flag marks a preserved-evidence row).
    Empty for every other bench."""
    fp = r.get("fleet_prefix") or {}
    if not fp:
        return ""
    fleet = (fp.get("fleet") or {}).get("restart_hit_rate")
    local = (fp.get("per_replica") or {}).get("restart_hit_rate")
    out = f"hit {fleet} vs {local}"
    ratio = fp.get("wire_fetch_vs_re_prefill")
    if ratio is not None:
        out += f"; wire {ratio}x"
        if not fp.get("wire_between_bounds"):
            out += "!"
    if not fp.get("exact", True):
        out += " INEXACT"
    return out


def disagg_value(r):
    """serving-load rows: the DISAGG column — interactive TTFT p99
    of the role-split arm (1 prefill + 2 decode) as a fraction of
    the monolithic arm's at equal total KV budget (the PR 17
    headline; < 1.0 is the win), with the agg-tok/s ratio (contract:
    in band) and the measured handoff cost as a fraction of the
    re-prefill it replaces (contract: < 1.0; ``!`` marks a noisy-box
    ordering the box could not resolve).  ``INEXACT`` flags a
    disagg stream that diverged bitwise from the monolithic one;
    ``RECOMPILED`` flags steady-state recompiles on either tier
    (both violate the tentpole contract — the bench run itself
    fails on them; a committed flag marks a preserved-evidence
    row).  Empty for every other bench."""
    dg = r.get("disagg") or {}
    if not dg:
        return ""
    out = f"ttft {dg.get('ttft_p99_vs_mono')}x"
    if dg.get("noisy_box"):
        out += "!"
    agg = dg.get("agg_tok_ratio")
    if agg is not None:
        out += f" agg {agg}x"
    ho = dg.get("handoff_vs_re_prefill")
    if ho is not None:
        out += f" ho {ho}x"
    if not dg.get("exact", True):
        out += " INEXACT"
    if dg.get("steady_recompiles"):
        out += " RECOMPILED"
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tpu-only", action="store_true")
    args = ap.parse_args()
    rows = current_state(load_rows())
    if args.tpu_only:
        rows = [r for r in rows
                if r.get("backend") in ("tpu", "tpu-compile-only")]
    print("| bench | model | variant | batch | backend | value | unit "
          "| spec-mix | paged | lazy | spill | fleetpfx | disagg "
          "| mesh | telemetry | recorder | debug | forensics | chaos "
          "| fleet | fleetobs | overload | mfu | age |")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|---|---|"
          "---|---|---|---|---|---|---|---|---|---|")
    now = time.time()
    for r in rows:
        v, unit = headline_value(r)
        age_h = (now - r.get("ts", now)) / 3600
        flags = []
        if r.get("partial"):
            flags.append("partial")
        if r.get("executed") is False:
            flags.append("predicted")
        if r.get("regime"):
            flags.append(r["regime"])
        print(f"| {r.get('bench')} | {r.get('model')} "
              f"| {r.get('variant') or ''} | {r.get('batch')} "
              f"| {r.get('backend')}{'/' + ','.join(flags) if flags else ''} "
              f"| {v if v is not None else ''} | {unit} "
              f"| {spec_mix_value(r)} "
              f"| {paged_value(r)} "
              f"| {lazy_value(r)} "
              f"| {spill_value(r)} "
              f"| {fleetprefix_value(r)} "
              f"| {disagg_value(r)} "
              f"| {meshed_value(r)} "
              f"| {telemetry_value(r)} "
              f"| {recorder_value(r)} "
              f"| {debug_value(r)} "
              f"| {forensics_value(r)} "
              f"| {chaos_value(r)} "
              f"| {fleet_value(r)} "
              f"| {fleetobs_value(r)} "
              f"| {overload_value(r)} "
              f"| {r.get('mfu', '')} | {age_h:.0f}h |")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
