"""Serving load benchmark: concurrent clients through the HTTP server.

MIXED short/long traffic over SCARCE decode capacity — the workload
continuous batching exists for: N_short clients stream small-budget
requests while N_long clients stream big-budget ones, all sharing one
prompt length (so the seed coalescer merges them maximally — the
fairest possible baseline), with more clients than decode slots.
Under the seed coalescing policy a merged batch decodes to its
LONGEST member's budget: a short request trapped with a long one pays
the long tail, and its row decodes frozen eos tokens the rest of the
way — wasted capacity that oversubscription turns into lost
throughput.  Under the continuous-batching engine (serving/engine.py)
the short request evicts the moment it finishes and its slot admits
the next queued request the same boundary.  The same traffic runs
against all three batching modes —

- ``continuous``: the slot-based engine (default serving path)
- ``coalesce``:   the seed whole-request coalescer (the "before")
- ``off``:        fully serialized (the floor)

— recording per-class p50/p99 latency, aggregate tok/sec, and the
engine/coalescing counters, plus the headline before/after ratios
(``continuous_vs_coalesce``).

A second SAMPLED-MIX leg runs the same client structure with every
other client sampling (varied temperature/top-k/top-p, per-client
seeds) — the workload the per-slot RNG work exists for.  Under
``coalesce``/``off`` a sampled request decodes solo holding the
device lock for its whole decode, so a realistic mixed stream
re-serializes; under the engine sampled streams occupy slots like
greedy ones (position-keyed RNG keeps them schedule-invariant).  The
sampled rows land beside the greedy ones (``load_sampled`` +
``sampled_continuous_vs_coalesce``).

A third SPEC-MIX leg makes EVERY client SPECULATIVE (the same
short/long class mix, greedy-spec and sampled-spec alternating with
per-client seeds) against a weight-perturbed copy of the target
tuned to the realistic ~0.8 draft-acceptance band — the workload PR
3 exists for: under ``coalesce``/``off`` each speculative request
holds the device lock for its whole draft/verify decode, so >= 4
concurrent speculative clients fully serialize; under the engine
their per-round draft/verify work batches across the slot pool with
per-slot variable advance (``load_spec`` +
``spec_continuous_vs_coalesce``; the engine row records the measured
acceptance rate).  Greedy/sampled requests never speculate, so
mixing them into this leg would measure the pool-program tax on
co-tenants, not engine-vs-solo speculative throughput — the greedy
and sampled legs stay the pinned coverage for non-speculative
traffic.  Rows land in benchmarks/results.jsonl as ``{"bench":
"serving-load"}`` with a cpu-smoke regime tag off-TPU.

A fifth OVERLOAD leg drives a 2x-capacity MIXED-PRIORITY burst with
deadlines at one continuous server with the request-lifecycle knobs
armed (interactive short clients + batch long clients, two clients
per slot; ``--slo-ttft-ms`` preemption on, a batch queue deadline,
per-request deadlines): it records per-class admission-anchored TTFT
p50/p99 (from the response ``timings`` block), shed/expired counts
by class (the structured 503/504s), the server's
preempted/resumed/shed counters, and GOODPUT — tokens of completed
requests per second, the number load shedding exists to protect.
The headline check: interactive TTFT p99 held under the SLO target
while batch traffic is shed or deferred (``overload.slo_held``).

A sixth LONG-TAIL leg A/Bs the PAGED KV cache against the fixed-lane
slot cache AT EQUAL KV MEMORY: lognormal-ish prompt/output lengths
(snapped to a pow2 grid so the prefill/window program set stays
bounded; p50 around 32 total tokens, p99 around 512) drive a
16-client stream against (a) a fixed-lane engine whose KV budget is
S_f full-width lanes and (b) a paged engine with the SAME budget in
64-token pages but 3x the logical slots — the workload block-table
paging exists for: short requests no longer pay max_position-wide
lanes, so steady-state resident count (sampled from the occupancy
gauge) and aggregate tok/s rise at identical memory
(``longtail.paged_vs_fixed``).  A SHARED-SYSTEM-PROMPT variant
registers one long prefix and streams suffix requests at both arms;
the paged arm must serve every hit from SHARED pages — the common
prompt is prefilled exactly once, asserted via the
``prefix_hit_tokens`` counter (``longtail_shared``).

A seventh MESHED leg runs the same mixed greedy/sampled load against
a ``--mesh tp=1`` and a ``--mesh tp=4`` engine at EQUAL total KV
budget (same slots, same model — tp shards the pool, never grows it)
on forced host devices.  Criterion is CORRECTNESS AND RECOMPILE
BEHAVIOR, not speedup: a host-platform CPU mesh is one CPU pretending
to be N devices, so the leg pins token-identity between the arms,
zero timed compile misses, and records the per-step device-second
inflation as a collective-time-share estimate (``meshed``) — speedup
claims belong to real multi-chip hardware.

A fourth TELEMETRY-OVERHEAD leg A/Bs the serving telemetry layer
itself: the same greedy mix runs against two fresh continuous-mode
servers back to back — tracing ON (default ring + histograms) vs
tracing OFF (``trace_buffer=0``) — and the row records both
throughputs plus the overhead percentage, asserting the tracing tax
stays under the ~3% agg tok/s contract documented in docs/DESIGN.md
(``telemetry_overhead``; ``summarize_results.py`` surfaces it as its
own column).

A FLEET-OBSERVABILITY leg A/Bs the router tier's observability
layer itself: the same mixed load through two 3-replica fleets —
router request-span history + SLO burn accounting + a live
``GET /fleet/metrics`` federation scraper on vs all off — under a
seeded slow-replica chaos flavor, alternating rounds per the
overhead protocol (``fleet_observability``); the leg also
cross-checks the router's SLO burn-rate gauges against bench-side
math (burn > 0 iff the bench saw violations).

Run: python benchmarks/bench_serving_load.py [--model gpt2-medium]
     [--short-clients 12] [--long-clients 4] [--requests 6]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench as B  # noqa: E402

RESULTS = os.path.join(REPO, "benchmarks", "results.jsonl")

# model -> {"short": (p_len, new), "long": (p_len, new)}.  One shared
# p_len per model so the coalescer merges short and long freely (its
# merge key excludes max_new_tokens) — the tail-latency pathology is
# the budget gap, not a merge failure.
# Sampled-mix leg: every odd-indexed client samples with one of these
# (cycled), plus a per-client seed.  Varied on purpose — the engine
# compiles ONE sampled step program regardless (shaping params are
# run-time inputs), and the solo baselines' "sample_pos" program is
# likewise shape-keyed only, so variety costs the baselines nothing.
SAMPLED_PARAMS = (
    {"temperature": 0.8, "top_k": 64},
    {"temperature": 1.0, "top_p": 0.95},
    {"temperature": 0.7, "top_k": 32, "top_p": 0.9},
    {"temperature": 1.2},
)

SHAPES = {
    "gpt2-medium": {"short": (128, 16), "long": (128, 128)},
    # gpt2-mini is the CPU-smoke default: sized so a decode step's
    # COMPUTE dominates per-dispatch overhead (the regime a real chip
    # is in), so the A/B compares batching policies, not dispatch
    # counts.  gpt2-tiny stays available for a fast functional smoke.
    "gpt2-mini": {"short": (32, 8), "long": (32, 96)},
    # tiny's long budget leaves spec_k slack under its max_position
    # 128 (32 + 88 + 4 - 1 <= 128) so the spec-mix leg's speculative
    # long clients are servable on the functional smoke too.
    "gpt2-tiny": {"short": (32, 8), "long": (32, 88)},
}
DEFAULT_SHAPE = SHAPES["gpt2-medium"]


def _post(base: str, payload, timeout: float = 600,
          path: str = "/generate"):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def percentile(xs, p):
    xs = sorted(xs)
    if not xs:
        return None
    i = min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))
    return xs[i]


def pct_ms(xs, p):
    """Percentile in ms, or None when a client class ran 0 requests
    (e.g. --long-clients 0 for a single-class baseline)."""
    v = percentile(xs, p)
    return None if v is None else round(1e3 * v, 1)


def run_mixed_load(base: str, *, n_short: int, n_long: int,
                   requests: int, shapes, vocab: int,
                   sampled_mix: bool = False,
                   spec_mix: bool = False):
    """N_short + N_long threads x R sequential requests each; returns
    per-class latencies + aggregate wall.  ``sampled_mix`` switches
    every other client to sampling (SAMPLED_PARAMS cycled, per-client
    seed) — the 50/50 greedy/sampled traffic of the sampled leg.
    ``spec_mix`` switches EVERY client to SPECULATIVE requests
    (greedy-spec and sampled-spec alternating, per-client seeds) —
    the all-speculative traffic of the spec leg, where the baselines
    serialize each request's whole draft/verify decode."""
    import numpy as np

    rng = np.random.RandomState(0)
    clients = ("short",) * n_short + ("long",) * n_long
    prompts = []
    for cls in clients:
        p_len, _ = shapes[cls]
        prompts.append(rng.randint(0, vocab, size=p_len).tolist())
    lats = {"short": [], "long": []}
    lat_lock = threading.Lock()
    errors = []

    def client(i):
        cls = clients[i]
        _, new = shapes[cls]
        payload = {"prompt": prompts[i], "max_new_tokens": new}
        if spec_mix:
            payload.update({"speculative": True, "spec_k": 4})
            if i % 2 == 1:
                payload.update({"temperature": 0.9, "top_k": 64,
                                "seed": i})
        elif sampled_mix and i % 2 == 1:
            payload.update(SAMPLED_PARAMS[(i // 2)
                                          % len(SAMPLED_PARAMS)])
            payload["seed"] = i
        for _ in range(requests):
            t0 = time.perf_counter()
            try:
                _post(base, payload)
            except Exception as e:  # noqa: BLE001 - record, don't die
                errors.append(f"{type(e).__name__}: {e}")
                return
            dt = time.perf_counter() - t0
            with lat_lock:
                lats[cls].append(dt)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(clients))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return lats, wall, errors


def bench_serving_load(jax, model_name: str, backend: str, *,
                       n_short: int, n_long: int, requests: int,
                       sanitize: bool = False):
    import numpy as np

    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.serving import ModelServer, make_server

    shapes = SHAPES.get(model_name, DEFAULT_SHAPE)
    spec = get_model(model_name)
    model, variables = spec.init_params(batch_size=1)
    vocab = model.cfg.vocab_size
    # Draft for the SPEC-MIX leg: a weight-perturbed copy of the
    # target.  Random-init models have near-uniform logits, so a
    # *separately initialized* draft proposes garbage (acceptance ~0
    # — speculation pays its overhead and commits one token a round,
    # in any serving system); a deterministic 2e-3 per-element
    # perturbation lands greedy draft/target agreement at the
    # realistic ~0.8 mid-range (measured, recorded per run as
    # spec_accept_rate), exercising BOTH the accept and the
    # reject/rewind lanes.  Every mode gets the same draft, so the
    # A/B compares batching policy only.
    import jax.numpy as jnp

    def _jiggle(x):
        if x.dtype.kind != "f":
            return x
        wave = jnp.cos(jnp.arange(x.size, dtype=jnp.float32))
        return x + 0.002 * wave.reshape(x.shape).astype(x.dtype)

    draft_model = model
    draft_variables = jax.tree.map(_jiggle, variables)
    # Scarce capacity BY DESIGN: ~4 clients per slot, so batching
    # policy (who occupies the physical batch, and for how long)
    # decides throughput — both policies get the same width.
    n_slots = min(16, max(2, (n_short + n_long) // 4))

    rows = []
    rows_sampled = []
    rows_spec = []
    for mode in ("continuous", "coalesce", "off"):
        # SANITIZERS ARE OFF BY DEFAULT IN BENCH RUNS: the lock
        # sanitizer (analysis/locksan.py) adds a recording step to
        # every lock acquire, which is measurement noise the A/B
        # must not carry.  --sanitize exists for a correctness-
        # checked run (same traffic, locks wrapped) — compare its
        # row against a default run to confirm the tax, never
        # publish its numbers as the baseline.
        ms = ModelServer(model, variables, model_name=model_name,
                         max_batch=n_slots,
                         batching=mode, n_slots=n_slots,
                         queue_depth=4 * (n_short + n_long),
                         draft_model=draft_model,
                         draft_variables=draft_variables,
                         sanitize=sanitize)
        srv = make_server("127.0.0.1", 0, ms)
        thread = threading.Thread(target=srv.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            # Warm the compile caches OUTSIDE the timed runs: load
            # latencies must measure decode, not XLA.  Continuous:
            # one long request compiles the prefill piece, the insert
            # program, and every power-of-two decode window; one short
            # covers the short budget's window tail.  Coalesce: each
            # (batch bucket, budget) merged shape is its own program —
            # mixed batches decode to the LONGEST member, so both
            # budgets need every bucket.
            warm_rng = np.random.RandomState(1)
            for cls in ("short", "long"):
                p_len, new = shapes[cls]
                warm = warm_rng.randint(0, vocab, size=p_len).tolist()
                _post(base, {"prompt": warm, "max_new_tokens": new},
                      timeout=900)
                # Sampled warm: one request per shape covers EVERY
                # sampled param combo — the engine's sampled step
                # programs and the solo "sample_pos" program both
                # take the shaping params at run time.
                _post(base, {"prompt": warm, "max_new_tokens": new,
                             "temperature": 0.9, "top_k": 64,
                             "top_p": 0.95, "seed": 1}, timeout=900)
                # Speculative warm: greedy-spec and sampled-spec per
                # shape (the engine's spec round programs per window,
                # or the solo "spec"/"spec_pos" programs).
                _post(base, {"prompt": warm, "max_new_tokens": new,
                             "speculative": True, "spec_k": 4},
                      timeout=900)
                _post(base, {"prompt": warm, "max_new_tokens": new,
                             "speculative": True, "spec_k": 4,
                             "temperature": 0.9, "top_k": 64,
                             "seed": 1}, timeout=900)
                if mode == "coalesce":
                    # every bucket _batch_bucket can land on: powers
                    # of two AND the min(b, max_batch) cap — a
                    # non-pow2 max_batch's top bucket must not compile
                    # inside the timed run.
                    b = 2
                    while b // 2 < ms.max_batch:
                        bb = min(b, ms.max_batch)
                        _post(base, {"prompt": [warm] * bb,
                                     "max_new_tokens": new},
                              timeout=900)
                        b *= 2
            if mode == "continuous":
                # Every power-of-two spec WINDOW program must compile
                # outside the timed runs: a solo warm request's rem
                # walk can skip a window size (high acceptance jumps
                # rem past the [2k, 4k) band), but mixed-residency
                # boundaries in the timed leg will hit it.  A fresh
                # single-resident request's FIRST window is exactly
                # pow2(min(cap, (new - 1) // spec_k)), so budgets
                # 4k*w .. walk every size.
                p_len, _ = shapes["short"]
                warm = warm_rng.randint(0, vocab,
                                        size=p_len).tolist()
                for nb in (12, 20, 40):  # first windows 2, 4, 8
                    _post(base, {"prompt": warm,
                                 "max_new_tokens": nb,
                                 "speculative": True, "spec_k": 4},
                          timeout=900)

            def timed_leg(leg):
                pre = json.loads(urllib.request.urlopen(
                    base + "/info", timeout=30).read())
                lats, wall, errors = run_mixed_load(
                    base, n_short=n_short, n_long=n_long,
                    requests=requests, shapes=shapes, vocab=vocab,
                    sampled_mix=leg == "sampled-mix",
                    spec_mix=leg == "spec-mix")
                if errors:
                    print(f"# load mode={mode} leg={leg} errors: "
                          f"{errors[:3]}", file=sys.stderr)
                    return None
                total_toks = (len(lats["short"]) * shapes["short"][1]
                              + len(lats["long"]) * shapes["long"][1])
                info = json.loads(urllib.request.urlopen(
                    base + "/info", timeout=30).read())
                row = {
                    "mode": mode,
                    "workload": leg,
                    "requests": len(lats["short"])
                    + len(lats["long"]),
                    "short_p50_ms": pct_ms(lats["short"], 50),
                    "short_p99_ms": pct_ms(lats["short"], 99),
                    "long_p50_ms": pct_ms(lats["long"], 50),
                    "long_p99_ms": pct_ms(lats["long"], 99),
                    "agg_tok_per_sec": round(total_toks / wall, 1),
                }
                if mode == "continuous":
                    row["admitted"] = info.get("admitted_total", 0) \
                        - pre.get("admitted_total", 0)
                    row["decode_steps"] = \
                        info.get("decode_steps_total", 0) \
                        - pre.get("decode_steps_total", 0)
                    if leg == "sampled-mix":
                        row["admitted_sampled"] = \
                            info.get("admitted_sampled_total", 0) \
                            - pre.get("admitted_sampled_total", 0)
                    if leg == "spec-mix":
                        row["admitted_spec"] = \
                            info.get("admitted_spec_total", 0) \
                            - pre.get("admitted_spec_total", 0)
                        drafted = info.get("spec_drafted_total", 0) \
                            - pre.get("spec_drafted_total", 0)
                        accepted = \
                            info.get("spec_accepted_total", 0) \
                            - pre.get("spec_accepted_total", 0)
                        row["spec_drafted"] = drafted
                        row["spec_accepted"] = accepted
                        if drafted:
                            row["spec_accept_rate"] = round(
                                accepted / drafted, 4)
                if mode == "coalesce":
                    row["coalesced_batches"] = \
                        info["coalesced_batches"] \
                        - pre["coalesced_batches"]
                    row["coalesced_requests"] = \
                        info["coalesced_requests"] \
                        - pre["coalesced_requests"]
                print(f"# mode={mode} leg={leg}: short "
                      f"p50={row['short_p50_ms']}ms "
                      f"p99={row['short_p99_ms']}ms, long "
                      f"p50={row['long_p50_ms']}ms, "
                      f"agg={row['agg_tok_per_sec']} tok/s",
                      file=sys.stderr)
                return row

            row = timed_leg("greedy")
            if row is not None:
                rows.append(row)
            row = timed_leg("sampled-mix")
            if row is not None:
                rows_sampled.append(row)
            row = timed_leg("spec-mix")
            if row is not None:
                rows_spec.append(row)
        finally:
            srv.shutdown()
            srv.server_close()  # release the listening socket too
            ms.close()
    telemetry = bench_telemetry_overhead(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=4 * (n_short + n_long))
    recorder = bench_recorder_overhead(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=4 * (n_short + n_long))
    debug = bench_debug_overhead(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=4 * (n_short + n_long))
    forensics = bench_forensics_overhead(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=4 * (n_short + n_long))
    faults = bench_faults_overhead(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=4 * (n_short + n_long))
    chaos = bench_chaos_soak(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=4 * (n_short + n_long))
    fleet = bench_fleet_chaos(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, requests=requests)
    fleetobs = bench_fleet_observability(
        model, variables, model_name, vocab, shapes,
        n_slots=n_slots, requests=max(2, requests // 2))
    overload = bench_overload(model, variables, model_name, vocab,
                              shapes, n_slots=n_slots,
                              requests=requests)
    longtail = bench_longtail(model, variables, model_name, vocab,
                              requests=requests)
    lazy = bench_lazy_longtail(model, variables, model_name, vocab,
                               requests=requests)
    spill = bench_prefix_spill(model, variables, model_name, vocab)
    fleet_prefix = bench_fleet_prefix(model, variables, model_name,
                                      vocab, requests=requests)
    disagg = bench_disagg(model, variables, model_name, vocab,
                          requests=requests)
    meshed = bench_meshed(model, variables, model_name, vocab,
                          shapes, n_slots=n_slots, n_short=n_short,
                          n_long=n_long, requests=requests)
    prefix = bench_prefix_cache(model, variables, model_name, vocab)
    return {
        "model": model_name,
        "backend": backend,
        "shapes": {k: list(v) for k, v in shapes.items()},
        "short_clients": n_short,
        "long_clients": n_long,
        "requests_per_client": requests,
        "load": rows,
        "load_sampled": rows_sampled,
        "load_spec": rows_spec,
        # Headline before/after: the engine vs the seed coalescing
        # path (and vs the serialized floor) on the same traffic —
        # once for the all-greedy stream, once for the 50/50
        # greedy/sampled mix (where the baselines decode every
        # sampled request solo), once for the ALL-speculative mix
        # (where the baselines serialize every request's whole
        # draft/verify decode).
        "continuous_vs_coalesce": _ab(rows, "continuous", "coalesce"),
        "continuous_vs_serialized": _ab(rows, "continuous", "off"),
        "sampled_continuous_vs_coalesce":
            _ab(rows_sampled, "continuous", "coalesce"),
        "sampled_continuous_vs_serialized":
            _ab(rows_sampled, "continuous", "off"),
        "spec_continuous_vs_coalesce":
            _ab(rows_spec, "continuous", "coalesce"),
        "spec_continuous_vs_serialized":
            _ab(rows_spec, "continuous", "off"),
        **telemetry,
        **recorder,
        **debug,
        **forensics,
        **faults,
        **chaos,
        **fleet,
        **fleetobs,
        **overload,
        **longtail,
        **lazy,
        **spill,
        **fleet_prefix,
        **disagg,
        **meshed,
        **prefix,
    }


def _ab(rows, a: str, b: str):
    """Speedups of mode ``a`` over mode ``b``: >1 means ``a`` is
    better on that axis (latency ratios invert so bigger is better)."""
    ra = next((r for r in rows if r["mode"] == a), None)
    rb = next((r for r in rows if r["mode"] == b), None)
    if not ra or not rb:
        return None
    out = {}
    if ra.get("short_p50_ms") and rb.get("short_p50_ms"):
        out["short_p50_speedup"] = round(
            rb["short_p50_ms"] / ra["short_p50_ms"], 3)
    if ra.get("agg_tok_per_sec") and rb.get("agg_tok_per_sec"):
        out["tok_per_sec_speedup"] = round(
            ra["agg_tok_per_sec"] / rb["agg_tok_per_sec"], 3)
    return out or None


# The observability-layer overhead contract (docs/DESIGN.md): each
# armed layer (telemetry / flight recorder / debug / fault probes)
# must cost <= ~3% agg tok/s.  Also the NOISE BAND: when a box's
# same-arm round-to-round spread exceeds the contract itself, the
# measurement cannot attest the contract and the row is flagged
# noisy instead of failing the run (the 19.98% "recorder overhead"
# the PR 10 re-anchor flagged was exactly this — drift scored as
# tax by a 2-round max-per-arm harness).
OVERHEAD_CONTRACT_PCT = 3.0
MIN_OVERHEAD_ROUNDS = 3


def _overhead_ab(model, variables, model_name: str, vocab: int,
                 shapes, *, arm_kwargs, n_slots: int, n_short: int,
                 n_long: int, requests: int, queue_depth: int,
                 label: str, rounds: int = 4):
    """Drift-robust overhead A/B harness shared by the telemetry /
    flight-recorder / debug / fault-probe legs: BOTH servers come up
    first (and warm their compile caches), then the same mixed load
    alternates on→off→off→on for one UNSCORED warmup alternation
    plus at least :data:`MIN_OVERHEAD_ROUNDS` PAIRED scored rounds,
    and each arm scores the MEDIAN of its per-round throughputs.  Rationale: this box's
    throughput drifts several percent over a bench run (frequency
    scaling / co-tenancy), so back-to-back single-shot arms hand the
    later arm a systematic win that can dwarf the effect being
    measured (observed: the same config measured 0–4% apart
    depending only on run order, and one 19.98% "recorder overhead"
    reading on a box whose same-build arms spread ±5%).  Alternation
    puts both arms on both sides of the drift; the paired-round
    median (vs the old max-per-arm) keeps one lucky round from
    defining an arm.

    The harness also measures its own NOISE FLOOR: the worst same-
    arm round-to-round spread (``100*(max-min)/median``) — the same
    build measured against itself.  When that spread exceeds the
    effect band the leg is trying to attest (the ~3% contract), the
    leg's row carries a ``noisy_box`` marker so a drifting box
    commits an honestly-labeled row instead of a fake measurement.

    Tradeoff: both arms' slot-KV pools and program sets are resident
    on the device SIMULTANEOUSLY — ~2x the peak device memory of the
    old back-to-back harness.  Fine on the cpu smoke this leg is
    committed from; on real hardware provisioned near HBM capacity,
    run these legs with a smaller ``--slots`` (the overhead contract
    is about the recorder/telemetry tax, not pool size).

    Returns ``(per-arm median tok/s dict, noise dict, per-arm
    ModelServer dict)`` with the servers already closed — or
    ``({}, {}, {})`` on request errors.  The noise dict carries
    ``rounds``, ``noise_pct``, and the raw per-arm ``samples``."""
    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    rounds = max(MIN_OVERHEAD_ROUNDS, int(rounds))
    servers = {}
    try:
        for arm, kw in arm_kwargs.items():
            ms = ModelServer(model, variables,
                             model_name=model_name,
                             max_batch=n_slots,
                             batching="continuous", n_slots=n_slots,
                             queue_depth=queue_depth, **kw)
            srv = make_server("127.0.0.1", 0, ms)
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            servers[arm] = (ms, srv, base)
            warm_rng = np.random.RandomState(2)
            for cls in ("short", "long"):
                p_len, new = shapes[cls]
                warm = warm_rng.randint(0, vocab,
                                        size=p_len).tolist()
                _post(base, {"prompt": warm, "max_new_tokens": new},
                      timeout=900)
        samples = {arm: [] for arm in arm_kwargs}
        # rnd 0 is an UNSCORED warmup alternation: the two warm-up
        # requests above compile the main programs, but the first
        # full mixed round still pays stragglers (window-shape
        # tails, allocator/JIT warm paths, OS frequency ramp) — on
        # this box the first round measured up to ~25% below the
        # steady rounds, which is drift the A/B must not score.
        for rnd in range(rounds + 1):
            order = list(arm_kwargs)
            if rnd % 2:
                # Balance slot position across rounds (on,off then
                # off,on): monotone drift within a round would
                # otherwise hand the same arm the slow slot every
                # time.
                order.reverse()
            for arm in order:
                _, _, base = servers[arm]
                lats, wall, errors = run_mixed_load(
                    base, n_short=n_short, n_long=n_long,
                    requests=requests, shapes=shapes, vocab=vocab)
                if errors:
                    print(f"# {label} arm={arm} errors: "
                          f"{errors[:3]}", file=sys.stderr)
                    return {}, {}, {}
                if rnd == 0:
                    continue        # warmup alternation: unscored
                total_toks = (len(lats["short"])
                              * shapes["short"][1]
                              + len(lats["long"])
                              * shapes["long"][1])
                samples[arm].append(round(total_toks / wall, 1))
        med = {arm: round(percentile(xs, 50), 1)
               for arm, xs in samples.items()}
        noise_pct = max(
            round(100.0 * (max(xs) - min(xs)) / med[arm], 2)
            if med[arm] > 0 else 0.0
            for arm, xs in samples.items())
        noise = {"rounds": rounds, "noise_pct": noise_pct,
                 "samples": samples}
        if noise_pct > OVERHEAD_CONTRACT_PCT:
            print(f"# {label}: NOISY BOX — same-arm spread "
                  f"{noise_pct}% exceeds the "
                  f"{OVERHEAD_CONTRACT_PCT}% band this leg attests; "
                  f"row will carry noisy_box", file=sys.stderr)
        return med, noise, {arm: servers[arm][0] for arm in servers}
    finally:
        for ms, srv, _ in servers.values():
            srv.shutdown()
            srv.server_close()
            ms.close()


def _overhead_row(best, noise) -> dict:
    """The shared overhead-leg row shape: on/off medians, the
    overhead they imply, and the harness's own noise evidence —
    with the honest ``noisy_box`` marker when the box's same-arm
    spread swamps the contract band."""
    overhead_pct = round(
        100.0 * max(0.0, best["off"] - best["on"]) / best["off"], 2)
    return {
        "tok_per_sec_on": best["on"],
        "tok_per_sec_off": best["off"],
        "overhead_pct": overhead_pct,
        "rounds": noise["rounds"],
        "noise_pct": noise["noise_pct"],
        # Raw per-round evidence rides the row: a flagged reading
        # should be re-judgeable without rerunning the box.
        "round_samples": noise["samples"],
        **({"noisy_box": True}
           if noise["noise_pct"] > OVERHEAD_CONTRACT_PCT else {}),
    }


def bench_telemetry_overhead(model, variables, model_name: str,
                             vocab: int, shapes, *, n_slots: int,
                             n_short: int, n_long: int,
                             requests: int, queue_depth: int):
    """Telemetry-overhead A/B: the SAME greedy mix with tracing ON
    (default ring + histograms) vs OFF (``trace_buffer=0``, span
    recording disabled) through the drift-robust alternating harness
    (:func:`_overhead_ab`).  Asserts the tracing tax stays under the
    ~3% agg tok/s overhead contract (docs/DESIGN.md); the
    ring-buffer design note explains why it should be far under it
    (one clock read + one bounded-deque append per span, no IO, no
    device sync)."""
    best, noise, _ = _overhead_ab(
        model, variables, model_name, vocab, shapes,
        arm_kwargs={"on": dict(trace_buffer=4096),
                    "off": dict(trace_buffer=0)},
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=queue_depth,
        label="telemetry-overhead")
    if not best:
        return {}
    row = _overhead_row(best, noise)
    print(f"# telemetry overhead: on={best['on']} "
          f"off={best['off']} tok/s -> {row['overhead_pct']}% "
          f"(noise {noise['noise_pct']}%)", file=sys.stderr)
    return {"telemetry_overhead": row}


def bench_debug_overhead(model, variables, model_name: str,
                         vocab: int, shapes, *, n_slots: int,
                         n_short: int, n_long: int,
                         requests: int, queue_depth: int):
    """Debuggability-overhead A/B: the SAME greedy mix with the
    request-scoped debug layer FULLY ARMED (request-history ring
    recording every terminal causal timeline + the stall watchdog
    polling, ``--request-history 512 --stall-timeout 60``) vs OFF
    (``request_history=0``, no watchdog), through the drift-robust
    alternating harness (:func:`_overhead_ab`).  Asserts the layer
    stays under the same ~3% agg tok/s contract as telemetry and the
    flight recorder (docs/SERVING.md "Debugging") — per-request cost
    is one ID stamp, span-tuple collection the timings path already
    paid, and one dict build at the terminal boundary; the watchdog
    is a 4-Hz reader thread that touches no locks the hot path
    holds.  The 60s stall timeout can never fire inside a round —
    the arm measures the ARMED cost, not a stall's."""
    import tempfile

    best, noise, _ = _overhead_ab(
        model, variables, model_name, vocab, shapes,
        arm_kwargs={"on": dict(request_history=512,
                               stall_timeout_s=60.0,
                               stall_dir=tempfile.gettempdir()),
                    "off": dict(request_history=0)},
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=queue_depth,
        label="debug-overhead")
    if not best:
        return {}
    row = _overhead_row(best, noise)
    print(f"# debug-layer overhead: on={best['on']} "
          f"off={best['off']} tok/s -> {row['overhead_pct']}% "
          f"(noise {noise['noise_pct']}%)", file=sys.stderr)
    return {"debug_overhead": row}


def bench_forensics_overhead(model, variables, model_name: str,
                             vocab: int, shapes, *, n_slots: int,
                             n_short: int, n_long: int,
                             requests: int, queue_depth: int):
    """Forensics-overhead A/B: the SAME greedy mix with the
    tail-latency forensics layer ARMED (per-request phase ledger
    computed at every terminal boundary, histogram exemplar capture
    on every latency observation, anomaly sentry fed per request —
    the defaults) vs OFF (``forensics=False``: no ledger, no
    exemplars, no sentry), through the drift-robust alternating
    harness (:func:`_overhead_ab`).  Both arms carry the same
    ``request_history=512`` so the A/B isolates the forensics tax
    from the history ring the debug leg already prices.  Asserts the
    layer stays under the same ~3% agg tok/s contract
    (docs/SERVING.md "Tail-latency forensics") — the ledger is one
    integer-microsecond sweep over span tuples the timings path
    already collected, exemplar capture is one bounded-deque append
    per histogram observation, and the sentry is dict arithmetic at
    window boundaries; none of it touches the device lock."""
    best, noise, _ = _overhead_ab(
        model, variables, model_name, vocab, shapes,
        arm_kwargs={"on": dict(forensics=True, request_history=512),
                    "off": dict(forensics=False,
                                request_history=512)},
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=queue_depth,
        label="forensics-overhead")
    if not best:
        return {}
    row = _overhead_row(best, noise)
    print(f"# forensics-layer overhead: on={best['on']} "
          f"off={best['off']} tok/s -> {row['overhead_pct']}% "
          f"(noise {noise['noise_pct']}%)", file=sys.stderr)
    return {"forensics_overhead": row}


def bench_faults_overhead(model, variables, model_name: str,
                          vocab: int, shapes, *, n_slots: int,
                          n_short: int, n_long: int,
                          requests: int, queue_depth: int):
    """Fault-probe overhead A/B: the SAME greedy mix with a WORST-
    CASE armed-but-silent fault plan (p=0.0 specs on the hot probe
    sites — every probe pays the full gate walk plus an RNG draw,
    yet nothing ever fires) vs disarmed (``fault_plan=None``: one
    attribute check per site), through the drift-robust alternating
    harness (:func:`_overhead_ab`).  Both arms run supervised (the
    default).  Holding this leg under the same ~3% contract is what
    lets a chaos plan stay armed in a staging tier without
    distorting what it measures — and bounds the disarmed tax from
    above, since disarmed is strictly cheaper than armed-and-
    silent."""
    silent_plan = {"seed": 0, "faults": [
        {"site": "step", "p": 0.0},
        {"site": "engine_death", "p": 0.0},
        {"site": "telemetry", "p": 0.0},
        {"site": "socket_reset", "p": 0.0},
    ]}
    best, noise, _ = _overhead_ab(
        model, variables, model_name, vocab, shapes,
        arm_kwargs={"on": dict(fault_plan=silent_plan),
                    "off": {}},
        n_slots=n_slots, n_short=n_short, n_long=n_long,
        requests=requests, queue_depth=queue_depth,
        label="faults-overhead")
    if not best:
        return {}
    row = _overhead_row(best, noise)
    print(f"# fault-probe overhead: on={best['on']} "
          f"off={best['off']} tok/s -> {row['overhead_pct']}% "
          f"(noise {noise['noise_pct']}%)", file=sys.stderr)
    return {"faults_overhead": row}


def bench_chaos_soak(model, variables, model_name: str, vocab: int,
                     shapes, *, n_slots: int, n_short: int,
                     n_long: int, requests: int, queue_depth: int):
    """Chaos soak: the mixed greedy/sampled load under a SEEDED
    random fault plan — transient step faults, injected stalls,
    telemetry faults, a poisoned request, and two whole-engine
    deaths — on a paged supervised server.  The committed evidence
    is the crash-only liveness contract, not throughput: every
    submitted request reaches a terminal status (zero hung callers),
    zero slots/pages leak once the storm drains, the engine
    restarted and kept serving, and the breaker never wedged the
    healthy engine.  (Token-level determinism under these same fault
    classes is pinned in tests/test_faults.py — the soak exists to
    grind the machinery under real concurrency.)"""
    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    chaos_plan = {"seed": 1234, "faults": [
        {"site": "step", "kind": "transient", "p": 0.03},
        {"site": "slow_step", "p": 0.01, "delay_s": 0.02},
        {"site": "step", "kind": "poisoned", "request_index": 5},
        {"site": "engine_death", "after": 40, "times": 2},
        {"site": "telemetry", "p": 0.05},
    ]}
    ms = ModelServer(model, variables, model_name=model_name,
                     max_batch=n_slots, batching="continuous",
                     n_slots=n_slots, queue_depth=queue_depth,
                     kv_paged=True,
                     fault_plan=chaos_plan)
    srv = make_server("127.0.0.1", 0, ms)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    rng = np.random.RandomState(7)
    clients = ("short",) * n_short + ("long",) * n_long
    counts = {"ok": 0, "poisoned": 0, "shed": 0, "dropped": 0,
              "other_error": 0, "hung": 0}
    count_lock = threading.Lock()

    def bump(k):
        with count_lock:
            counts[k] += 1

    prompts = [rng.randint(0, vocab, size=shapes[c][0]).tolist()
               for c in clients]

    def client(i):
        cls = clients[i]
        _, new = shapes[cls]
        payload = {"prompt": prompts[i], "max_new_tokens": new}
        if i % 2 == 1:
            payload.update(SAMPLED_PARAMS[(i // 2)
                                          % len(SAMPLED_PARAMS)])
            payload["seed"] = i
        for _ in range(requests):
            try:
                _post(base, payload, timeout=120)
                bump("ok")
            except urllib.error.HTTPError as e:
                body = e.read()
                try:
                    reason = json.loads(body).get("reason")
                except Exception:
                    reason = None
                if e.code == 500 and reason == "poisoned_request":
                    bump("poisoned")
                elif e.code in (429, 503):
                    bump("shed")
                else:
                    bump("other_error")
            except (TimeoutError, socket.timeout):
                # the one outcome chaos must never produce
                bump("hung")
            except urllib.error.URLError as e:
                if isinstance(getattr(e, "reason", None),
                              (TimeoutError, socket.timeout)):
                    bump("hung")
                else:
                    # connection death — terminal for the caller,
                    # server-side state already settled
                    bump("dropped")
            except Exception:
                bump("dropped")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(clients))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = round(time.perf_counter() - t0, 1)
    with count_lock:
        counts["hung"] += sum(1 for t in threads if t.is_alive())
    # drain + settle: the breaker must never hold a healthy engine
    # down once the injected deaths are exhausted
    deadline = time.monotonic() + 60
    while ms.engine.down and time.monotonic() < deadline:
        time.sleep(0.1)
    breaker_wedged = bool(ms.engine.down)
    st = ms.engine.stats()
    es = ms.engine
    leaked_pages = 0
    if st.get("kv_pages"):
        leaked_pages = (es.slots.n_pages
                        - es.slots.free_page_count())
    row = {
        "requests_submitted": len(clients) * requests,
        **counts,
        "wall_s": wall,
        "leaked_slots": st["slots_active"],
        "leaked_pages": leaked_pages,
        "queue_len": st["queue_len"],
        "engine_crashes": st["engine_crashes_total"],
        "engine_restarts": st["engine_restarts_total"],
        "step_retries": st["step_retries_total"],
        "requeued": st["requests_requeued_total"],
        "poisoned_convictions": st["poisoned_total"],
        "faults_injected": st["faults_injected"],
        "breaker_state": st["breaker_state"],
        "breaker_wedged": breaker_wedged,
    }
    srv.shutdown()
    srv.server_close()
    ms.close()
    print(f"# chaos soak: {row['requests_submitted']} requests -> "
          f"ok={counts['ok']} poisoned={counts['poisoned']} "
          f"shed={counts['shed']} dropped={counts['dropped']} "
          f"hung={counts['hung']}; crashes={row['engine_crashes']} "
          f"restarts={row['engine_restarts']} "
          f"retries={row['step_retries']} "
          f"requeued={row['requeued']} "
          f"leaked_slots={row['leaked_slots']} "
          f"leaked_pages={row['leaked_pages']}", file=sys.stderr)
    return {"chaos": row}


def bench_fleet_chaos(model, variables, model_name: str, vocab: int,
                      shapes, *, n_slots: int, requests: int):
    """FLEET chaos-soak (serving/router.py): 3 in-process replicas
    behind the router under mixed greedy/sampled load while a SEEDED
    fleet plan kills one replica mid-burst and slow-walks another.
    The committed evidence is the router-tier robustness contract:
    ZERO hung requests, ZERO token mismatches vs the fault-free run
    for surviving requests, retry volume under the budget (spent <=
    burst + ratio x live traffic — the token bucket is never
    overdrawn), hedges cancel their losers (cancelled <= fired, no
    double-completion), and zero steady-state recompiles on the
    SURVIVING replicas (the storm must not perturb their compiled
    program set)."""
    import numpy as np

    from polyaxon_tpu.serving import (LocalReplica, ModelServer,
                                      ReplicaRouter,
                                      make_router_server)

    def factory():
        return ModelServer(model, variables, model_name=model_name,
                           max_batch=n_slots, batching="continuous",
                           n_slots=n_slots, queue_depth=64)

    reps = [LocalReplica(factory, f"r{i}") for i in range(3)]
    # The slow-walk (0.6s/request) sits ABOVE the hedge watermark
    # (0.3s — requests on the slow replica hedge to a healthy one,
    # first winner cancels the loser) but BELOW the probe timeout
    # (1.5s — the replica stays IN rotation, which is exactly the
    # tail pathology hedging exists for; a slower-than-probe replica
    # just drops out like a dead one).
    router = ReplicaRouter(
        reps, probe_interval_s=0.1, probe_timeout_s=1.5,
        cooldown_s=0.3, retry_ratio=0.25, retry_burst=8.0,
        max_attempts=3, request_timeout_s=120.0,
        hedge="0.3", hedge_min_s=0.25,
        # SLO burn-rate cross-check: the router's own availability
        # accounting must agree with the bench-side outcome counts
        # (burn > 0 iff the bench saw typed 5xx sheds); the latency
        # objective is loose enough that nothing under this chaos
        # mix can violate it (burn must stay 0).
        slo="availability=99,latency_p99_ms=60000",
        slo_window=4096,
        fleet_faults={"seed": 97, "faults": [
            # kill r1 a few requests into the burst; slow-walk r2
            {"site": "replica_kill", "replica": 1, "after": 6,
             "times": 1},
            {"site": "replica_slow", "replica": 2, "delay_s": 0.6,
             "after": 2, "times": 1},
        ]})
    srv = make_router_server("127.0.0.1", 0, router)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    rng = np.random.RandomState(23)
    clients = ("short",) * 8 + ("long",) * 4
    payloads = []
    for i, cls in enumerate(clients):
        p_len, new = shapes[cls]
        payload = {"prompt": rng.randint(0, vocab,
                                         size=p_len).tolist(),
                   "max_new_tokens": new}
        if i % 2 == 1:
            payload.update(SAMPLED_PARAMS[(i // 2)
                                          % len(SAMPLED_PARAMS)])
            payload["seed"] = i
        payloads.append(payload)

    # Fault-free references + fleet-wide warmup: every payload runs
    # on EVERY replica directly — r0's answer is the fault-free
    # single-replica reference, the replicas must agree bitwise
    # before any chaos, and every compiled program the burst needs
    # exists everywhere (so the zero-recompile pin below measures
    # the storm, not first compiles).
    refs = []
    for payload in payloads:
        per_rep = [
            _post(rep.url, payload, timeout=900)["tokens"]
            for rep in reps]
        assert per_rep[0] == per_rep[1] == per_rep[2], \
            "replicas disagree before chaos — fleet determinism " \
            "broken at rest"
        refs.append(per_rep[0])
    miss_before = {
        rep.id: rep.ms.recompile.snapshot()["compile_cache_misses"]
        for rep in reps}

    counts = {"ok": 0, "mismatch": 0, "failed": 0, "hung": 0}
    count_lock = threading.Lock()

    def bump(k):
        with count_lock:
            counts[k] += 1

    def client(i):
        for _ in range(requests):
            try:
                r = _post(base, payloads[i], timeout=120)
                if r["tokens"] == refs[i]:
                    bump("ok")
                else:
                    bump("mismatch")
            except (TimeoutError, socket.timeout):
                bump("hung")        # the one outcome the router
                #                     tier exists to prevent
            except urllib.error.URLError as e:
                if isinstance(getattr(e, "reason", None),
                              (TimeoutError, socket.timeout)):
                    bump("hung")
                else:
                    bump("failed")  # fast typed shed: allowed,
                    #                 counted
            except Exception:
                bump("failed")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(clients))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = round(time.perf_counter() - t0, 1)
    with count_lock:
        counts["hung"] += sum(1 for t in threads if t.is_alive())
    st = router.stats()
    # Router-side SLO accounting vs bench-side math: availability
    # burn must be > 0 exactly when the bench counted 5xx sheds, and
    # the loose latency objective must not have burned at all.
    slo_obj = (st.get("slo") or {}).get("objectives", {})
    avail_burn = slo_obj.get("availability", {}).get("burn_rate")
    lat_burn = slo_obj.get("latency_p99_ms", {}).get("burn_rate")
    slo_burn_consistent = (
        avail_burn is not None and lat_burn is not None
        and (avail_burn > 0) == (counts["failed"] > 0)
        and lat_burn == 0.0)
    # Survivors of the storm: every replica the plan did not kill.
    survivor_miss_delta = {
        rep.id: rep.ms.recompile.snapshot()["compile_cache_misses"]
        - miss_before[rep.id]
        for rep in reps if rep.id != "r1"}
    # Re-admit the killed replica: restart + probe back to ready.
    reps[1].restart()
    deadline = time.monotonic() + 60
    while not reps[1].up() and time.monotonic() < deadline:
        time.sleep(0.05)
    row = {
        "replicas": len(reps),
        "requests_submitted": len(clients) * requests,
        **counts,
        "wall_s": wall,
        "failovers": st["failovers_total"],
        "hedges_fired": st["hedges_fired_total"],
        "hedges_won": st["hedges_won_total"],
        "hedges_cancelled": st["hedges_cancelled_total"],
        "retry_budget_spent": st["retry_budget_spent_total"],
        "retry_budget_denied": st["retry_budget_denied_total"],
        "retry_budget_cap": round(
            router.budget.burst
            + router.budget.ratio * st["requests_total"], 1),
        "retry_under_budget": bool(
            st["retry_budget_spent_total"]
            <= router.budget.burst
            + router.budget.ratio * st["requests_total"]),
        "hedges_cancel_losers": bool(
            st["hedges_cancelled_total"] <= st["hedges_fired_total"]
            and st["hedges_won_total"] <= st["hedges_fired_total"]),
        "fleet_faults_applied": st["fleet_faults_applied"],
        "survivor_recompiles": survivor_miss_delta,
        "killed_replica_readmitted": bool(reps[1].up()),
        "slo_availability_burn": avail_burn,
        "slo_latency_burn": lat_burn,
        "slo_burn_consistent": slo_burn_consistent,
    }
    router.close()
    srv.shutdown()
    srv.server_close()
    for rep in reps:
        rep.close()
    print(f"# fleet chaos: {row['requests_submitted']} requests "
          f"over 3 replicas (1 killed, 1 slow-walked) -> "
          f"ok={counts['ok']} mismatch={counts['mismatch']} "
          f"failed={counts['failed']} hung={counts['hung']}; "
          f"failovers={row['failovers']} "
          f"hedges={row['hedges_fired']}/"
          f"{row['hedges_won']}won/"
          f"{row['hedges_cancelled']}cancelled "
          f"budget={row['retry_budget_spent']}/"
          f"{row['retry_budget_cap']} "
          f"survivor_recompiles={survivor_miss_delta} "
          f"readmitted={row['killed_replica_readmitted']} "
          f"slo_burn(avail={row['slo_availability_burn']}, "
          f"lat={row['slo_latency_burn']}, "
          f"consistent={row['slo_burn_consistent']})",
          file=sys.stderr)
    return {"fleet": row}


def bench_fleet_observability(model, variables, model_name: str,
                              vocab: int, shapes, *, n_slots: int,
                              requests: int):
    """FLEET-OBSERVABILITY overhead A/B (serving/router.py fleet
    tier): the SAME mixed greedy/sampled load through two 3-replica
    fleets — ON: router request-span history + SLO burn accounting
    armed AND a live federation scraper hitting ``GET
    /fleet/metrics`` throughout every timed round; OFF: history
    disabled, no SLO, no scrapes — alternating rounds per the PR 11
    protocol (one unscored warmup alternation + >=3 paired rounds
    scored by per-arm MEDIANS, the harness's own noise floor
    measured, rows honestly ``noisy_box``-flagged when the box
    drifts past the band).  Both fleets run the same seeded chaos
    flavor: one replica latches slow above the hedge watermark a few
    requests in, so the hedge/failover machinery the observability
    layer instruments is ACTIVE in both arms (the kill site is
    excluded on purpose — a dead replica's capacity loss compounds
    across rounds and would not be round-symmetric).

    Alongside the overhead contract, the leg cross-checks the SLO
    burn gauges against bench-side math on the ON fleet: the
    impossible ``latency_p99_ms=1`` objective must burn at the
    window maximum (every request's bench-measured latency exceeds
    1ms), the loose ``ttft_p99_ms=30000`` must burn zero (no
    bench-measured latency — an upper bound on TTFT — crossed 30s),
    and ``availability`` burns iff the bench counted 5xx failures."""
    import numpy as np

    from polyaxon_tpu.serving import (LocalReplica, ModelServer,
                                      ReplicaRouter,
                                      make_router_server)

    def factory():
        return ModelServer(model, variables, model_name=model_name,
                           max_batch=n_slots, batching="continuous",
                           n_slots=n_slots, queue_depth=64)

    chaos = {"seed": 11, "faults": [
        {"site": "replica_slow", "replica": 2, "delay_s": 0.3,
         "after": 10, "times": 1}]}
    fleets = {}
    try:
        for arm in ("on", "off"):
            reps = [LocalReplica(factory, f"r{i}")
                    for i in range(3)]
            router = ReplicaRouter(
                reps, probe_interval_s=0.1, probe_timeout_s=1.5,
                cooldown_s=0.3, retry_ratio=0.25, retry_burst=8.0,
                max_attempts=3, request_timeout_s=120.0,
                hedge="0.25", hedge_min_s=0.2,
                fleet_faults=dict(chaos),
                request_history=256 if arm == "on" else 0,
                slo=("availability=99,ttft_p99_ms=30000,"
                     "latency_p99_ms=1") if arm == "on" else None,
                slo_window=4096)
            srv = make_router_server("127.0.0.1", 0, router)
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            fleets[arm] = (reps, router, srv, base)
            # direct warm of both shapes on every replica: round 0
            # is unscored, but a multi-second first compile inside
            # it would starve the alternation of its warmup value
            warm_rng = np.random.RandomState(2)
            for rep in reps:
                for cls in ("short", "long"):
                    p_len, new = shapes[cls]
                    req = urllib.request.Request(
                        rep.url + "/generate",
                        data=json.dumps({
                            "prompt": warm_rng.randint(
                                0, vocab, size=p_len).tolist(),
                            "max_new_tokens": new}).encode(),
                        headers={"Content-Type":
                                 "application/json"})
                    with urllib.request.urlopen(req,
                                                timeout=900) as r:
                        r.read()
        scrapes = [0, 0]                # ok, errors

        def scrape_loop(base_on, stop):
            while not stop.is_set():
                try:
                    with urllib.request.urlopen(
                            base_on + "/fleet/metrics",
                            timeout=10) as r:
                        r.read()
                    scrapes[0] += 1
                except Exception:  # noqa: BLE001 - counted
                    scrapes[1] += 1
                stop.wait(0.25)

        rounds = max(MIN_OVERHEAD_ROUNDS, 3)
        samples = {"on": [], "off": []}
        on_lats = []
        failed_rounds = []
        for rnd in range(rounds + 1):
            order = ["on", "off"] if rnd % 2 == 0 else ["off", "on"]
            for arm in order:
                _, _, _, base = fleets[arm]
                stop = None
                if arm == "on":
                    # the federation scraper runs ONLY during ON
                    # rounds: scraping the on-fleet during an OFF
                    # round would burn CPU the OFF arm pays for
                    stop = threading.Event()
                    threading.Thread(target=scrape_loop,
                                     args=(base, stop),
                                     daemon=True).start()
                lats, wall, errors = run_mixed_load(
                    base, n_short=8, n_long=2, requests=requests,
                    shapes=shapes, vocab=vocab, sampled_mix=True)
                if stop is not None:
                    stop.set()
                if errors:
                    failed_rounds.append(
                        f"rnd{rnd} arm={arm}: {errors[:3]}")
                    continue
                if arm == "on":
                    # EVERY on-arm latency, warmup round included:
                    # the router's SLO window holds all of them, so
                    # the bench-side math below must too (a warmup
                    # straggler that burned the window would
                    # otherwise read as an inconsistency).
                    on_lats += lats["short"] + lats["long"]
                if rnd == 0:
                    continue            # warmup alternation
                total_toks = (len(lats["short"]) * shapes["short"][1]
                              + len(lats["long"])
                              * shapes["long"][1])
                samples[arm].append(round(total_toks / wall, 1))
        if failed_rounds or not samples["on"] or not samples["off"]:
            print(f"# fleet-observability leg errors: "
                  f"{failed_rounds[:3]}", file=sys.stderr)
            return {}
        med = {arm: round(percentile(xs, 50), 1)
               for arm, xs in samples.items()}
        noise_pct = max(
            round(100.0 * (max(xs) - min(xs)) / med[arm], 2)
            if med[arm] > 0 else 0.0
            for arm, xs in samples.items())
        noise = {"rounds": rounds, "noise_pct": noise_pct,
                 "samples": samples}
        if noise_pct > OVERHEAD_CONTRACT_PCT:
            print(f"# fleet-observability: NOISY BOX — same-arm "
                  f"spread {noise_pct}% exceeds the "
                  f"{OVERHEAD_CONTRACT_PCT}% band; row will carry "
                  f"noisy_box", file=sys.stderr)
        # SLO burn gauges vs bench-side math (ON fleet).  burn > 0
        # means ANY violation in the window, so each bench predicate
        # must be the matching any/none form over the SAME request
        # population (every on-arm request, warmup included).
        _, router_on, _, base_on = fleets["on"]
        st = router_on.stats()
        obj = st["slo"]["objectives"]
        bench_any_over_1ms = any(l > 1e-3 for l in on_lats)
        bench_none_over_30s = bool(on_lats) \
            and max(on_lats) < 30.0
        slo_burn_consistent = (
            (obj["latency_p99_ms"]["burn_rate"] > 0)
            == bench_any_over_1ms
            # latency bounds TTFT from above, so a bench run whose
            # every latency stayed under 30s PROVES no TTFT
            # violation; past 30s the bench can't see TTFT directly
            # and asserts nothing
            and ((obj["ttft_p99_ms"]["burn_rate"] == 0.0)
                 if bench_none_over_30s else True)
            # zero bench-side failures reached this point (an
            # errored round returns {} above), so availability must
            # not have burned
            and obj["availability"]["burn_rate"] == 0.0)
        row = {
            "replicas": 3,
            **_overhead_row(med, noise),
            "federation_scrapes": scrapes[0],
            "federation_scrape_errors": scrapes[1],
            "history_records": len(router_on.history),
            "slo_burns": {name: o["burn_rate"]
                          for name, o in obj.items()},
            "slo_burn_consistent": slo_burn_consistent,
            "hedges_fired_on": st["hedges_fired_total"],
            "fleet_faults_applied": st["fleet_faults_applied"],
        }
        print(f"# fleet observability overhead: on={med['on']} "
              f"off={med['off']} tok/s -> {row['overhead_pct']}% "
              f"(noise {noise_pct}%), "
              f"{scrapes[0]} federation scrapes "
              f"({scrapes[1]} errors), "
              f"{row['history_records']} router records, "
              f"slo burns {row['slo_burns']} "
              f"consistent={slo_burn_consistent}", file=sys.stderr)
        return {"fleet_observability": row}
    finally:
        for reps, router, srv, _ in fleets.values():
            router.close()
            srv.shutdown()
            srv.server_close()
            for rep in reps:
                rep.close()


def bench_overload(model, variables, model_name: str, vocab: int,
                   shapes, *, n_slots: int, requests: int):
    """Overload leg: 2x-capacity mixed-priority burst with deadlines
    against ONE continuous server with the lifecycle knobs armed —
    measures whether priority scheduling + preemption hold the
    interactive TTFT SLO while batch traffic absorbs the pain
    (deferred, preempted, or shed), and what goodput survives."""
    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    slo_ttft_ms = 1000          # tight enough that a pool full of
    #                             long batch decodes MUST preempt to
    #                             hold it (a long decode runs ~2s on
    #                             the cpu smoke), loose enough that
    #                             the half-budget preempt trigger
    #                             (fires at slo/2) plus a few decode
    #                             boundaries sits clearly under it
    ms = ModelServer(model, variables, model_name=model_name,
                     max_batch=n_slots, batching="continuous",
                     n_slots=n_slots,
                     queue_depth=16 * n_slots,
                     slo_ttft_s=slo_ttft_ms / 1e3,
                     batch_queue_deadline_s=20.0)
    srv = make_server("127.0.0.1", 0, ms)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    n_int = n_batch = n_slots       # 2x slot capacity in clients
    rng = np.random.RandomState(4)
    ttfts = {"interactive": [], "batch": []}
    completed = {"interactive": 0, "batch": 0}
    shed = {"interactive": 0, "batch": 0}
    expired = {"interactive": 0, "batch": 0}
    tokens_done = [0]
    lock = threading.Lock()
    errors = []

    def client(i):
        cls = "interactive" if i < n_int else "batch"
        p_len, new = shapes["short" if cls == "interactive"
                            else "long"]
        prompt = rng.randint(0, vocab, size=p_len).tolist()
        payload = {"prompt": prompt, "max_new_tokens": new,
                   "priority": cls, "timings": True,
                   # Deadlines sized so a healthy schedule meets
                   # them and a pathological one sheds instead of
                   # rotting: tight-ish for interactive, generous
                   # for batch (which also has the queue deadline).
                   "deadline_ms": 30000 if cls == "interactive"
                   else 120000}
        for r_i in range(requests):
            if cls == "interactive" and r_i:
                # Think time between interactive requests: real
                # interactive traffic arrives in waves, and the gap
                # is what lets batch decodes saturate the pool — the
                # state preempt-or-defer exists for.  Back-to-back
                # interactive requests would hog slots continuously
                # and never let the preemption path engage.
                time.sleep(1.0)
            try:
                r = _post(base, payload)
                with lock:
                    completed[cls] += 1
                    tokens_done[0] += sum(
                        len(row) for row in r["new_tokens"])
                    t = r.get("timings", {}).get("ttft_ms")
                    if t is not None:
                        ttfts[cls].append(t / 1e3)
            except urllib.error.HTTPError as e:
                code = e.code
                e.read()
                with lock:
                    if code == 503:
                        shed[cls] += 1
                    elif code == 504:
                        expired[cls] += 1
                    else:
                        errors.append(f"HTTP {code} ({cls})")
                        return
            except Exception as e:  # noqa: BLE001 - record, don't die
                errors.append(f"{type(e).__name__}: {e}")
                return

    try:
        # Compile warm outside the timed burst (both shapes).
        for cls in ("short", "long"):
            p_len, new = shapes[cls]
            warm = rng.randint(0, vocab, size=p_len).tolist()
            _post(base, {"prompt": warm, "max_new_tokens": new},
                  timeout=900)
        # Warm the PREEMPT/RESUME path too: each preemption's resume
        # re-prefill splits into pow2 pieces, and a cold XLA compile
        # of a piece program runs ON the engine thread — inside the
        # boundary an interactive admission is waiting on.  Driving
        # a few preemption cycles at varied commit points here
        # compiles those shapes outside the timed burst; the row's
        # compile_cache_misses_during then shows the steady state.
        p_len_l, new_l = shapes["long"]
        p_len_s, new_s = shapes["short"]
        for stagger_s in (0.3, 0.8, 1.5):
            warm_ts = []
            for _ in range(n_slots):
                wl = rng.randint(0, vocab, size=p_len_l).tolist()
                t = threading.Thread(target=lambda p=wl: _post(
                    base, {"prompt": p, "max_new_tokens": new_l,
                           "priority": "batch"}, timeout=900))
                t.start()
                warm_ts.append(t)
            time.sleep(stagger_s)
            ws = rng.randint(0, vocab, size=p_len_s).tolist()
            _post(base, {"prompt": ws, "max_new_tokens": new_s,
                         "priority": "interactive"}, timeout=900)
            for t in warm_ts:
                t.join()
        pre = json.loads(urllib.request.urlopen(
            base + "/info", timeout=30).read())
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_int + n_batch)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            print(f"# overload leg errors: {errors[:3]}",
                  file=sys.stderr)
            return {}
        info = json.loads(urllib.request.urlopen(
            base + "/info", timeout=30).read())
        p99_int = pct_ms(ttfts["interactive"], 99)
        row = {
            "slots": n_slots,
            "interactive_clients": n_int,
            "batch_clients": n_batch,
            "slo_ttft_ms": slo_ttft_ms,
            "interactive_ttft_p50_ms": pct_ms(ttfts["interactive"],
                                              50),
            "interactive_ttft_p99_ms": p99_int,
            "batch_ttft_p50_ms": pct_ms(ttfts["batch"], 50),
            "batch_ttft_p99_ms": pct_ms(ttfts["batch"], 99),
            "completed": dict(completed),
            "shed": dict(shed),
            "expired": dict(expired),
            "preempted": info.get("preempted_total", 0)
            - pre.get("preempted_total", 0),
            "resumed": info.get("resumed_total", 0)
            - pre.get("resumed_total", 0),
            "server_shed_total": info.get("shed_total", 0)
            - pre.get("shed_total", 0),
            "goodput_tok_per_sec": round(tokens_done[0] / wall, 1),
            "compile_cache_misses_during": info.get(
                "compile_cache_misses", 0)
            - pre.get("compile_cache_misses", 0),
            "slo_held": p99_int is not None
            and p99_int <= slo_ttft_ms,
        }
        print(f"# overload: interactive TTFT p99="
              f"{row['interactive_ttft_p99_ms']}ms "
              f"(slo {slo_ttft_ms}ms, held={row['slo_held']}), "
              f"preempted={row['preempted']} "
              f"shed={row['shed']} expired={row['expired']} "
              f"goodput={row['goodput_tok_per_sec']} tok/s",
              file=sys.stderr)
        return {"overload": row}
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


def _longtail_schedule(n_clients: int, requests: int, max_pos: int,
                       seed: int = 7):
    """Per-client (prompt_len, new_tokens) lists: lognormal draws
    snapped DOWN to a pow2 grid (16..256 prompt, 8..256 output) so
    the tail is heavy (p99 total ~512) while the prefill/window
    program set stays a handful of shapes.  Deterministic, and the
    SAME schedule drives both arms."""
    import numpy as np

    rng = np.random.RandomState(seed)

    def snap(x, lo, hi):
        g = lo
        while g * 2 <= min(x, hi):
            g *= 2
        return g

    sched = []
    for _ in range(n_clients):
        pairs = []
        for _ in range(requests):
            p = snap(int(rng.lognormal(3.2, 1.0)), 16, 256)
            n = snap(int(rng.lognormal(2.8, 1.2)), 8, 256)
            while p + n > max_pos:          # capacity-safe tail
                n = max(8, n // 2)
            pairs.append((p, n))
        sched.append(pairs)
    return sched


def _run_longtail_clients(base: str, sched, vocab: int,
                          prefix=None):
    """Drive the per-client schedules concurrently; returns
    (completed requests, total NEW tokens, wall seconds, errors).
    ``prefix`` prepends a shared system prompt to every request (the
    shared-prefix variant; prompt lengths then exclude it)."""
    import numpy as np

    rng = np.random.RandomState(11)
    prompts = []
    for pairs in sched:
        row = []
        for p, n in pairs:
            row.append((rng.randint(0, vocab, size=p).tolist(), n))
        prompts.append(row)
    done = [0, 0]
    lock = threading.Lock()
    errors = []

    def client(i):
        for toks, n in prompts[i]:
            body = {"prompt": (prefix + toks) if prefix else toks,
                    "max_new_tokens": n}
            try:
                r = _post(base, body, timeout=900)
            except Exception as e:  # noqa: BLE001 - record, don't die
                errors.append(f"{type(e).__name__}: {e}")
                return
            with lock:
                done[0] += 1
                done[1] += sum(len(x) for x in r["new_tokens"])

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(sched))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return done[0], done[1], time.perf_counter() - t0, errors


def bench_longtail(model, variables, model_name: str, vocab: int, *,
                   requests: int):
    """LONG-TAIL leg: paged vs fixed-lane at EQUAL KV MEMORY.

    Fixed arm: S_f=4 full-width lanes (S_f x max_position tokens of
    KV).  Paged arm: the SAME token budget as 64-token pages, 3x the
    logical slots — occupancy bounded by token usage.  Plus the
    shared-system-prompt variant on both arms (the paged one asserts
    the common prompt is prefilled exactly once via the
    prefix_hit_tokens counter)."""
    import dataclasses

    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    # Serving HEADROOM configuration: real deployments size
    # max_position for the p99.9 request while typical traffic sits
    # far below it — which is exactly where fixed lanes bleed (every
    # slot pays a max_position-wide cache and attention read) and
    # paging wins (a slot pays its own length).  The smoke models'
    # max_position is sized to their tests, so rebuild the bench
    # model with 1024 positions of headroom; traffic tails at ~512.
    cfg = getattr(model, "cfg", None)
    if cfg is not None and getattr(cfg, "max_position", 0) < 1024 \
            and not getattr(cfg, "kv_cache_ring", False) \
            and dataclasses.is_dataclass(cfg):
        import jax
        import jax.numpy as jnp

        cfg = dataclasses.replace(cfg, max_position=1024)
        model = type(model)(cfg=cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    max_pos = getattr(cfg, "max_position", 1024)
    page_tokens = 64
    s_fixed = 4
    pages = s_fixed * (max_pos // page_tokens)   # equal KV budget
    n_clients = 16
    sched = _longtail_schedule(n_clients, requests, max_pos // 2)
    sys_len = min(192, max_pos // 2)
    rng = np.random.RandomState(13)
    system = rng.randint(0, vocab, size=sys_len).tolist()
    shared_sched = [[(16, 16)] * requests for _ in range(n_clients)]

    arms = {
        "fixed": dict(n_slots=s_fixed),
        # 3x the logical slots at the SAME page budget: the pool can
        # hold ~3x the fixed arm's residents on this length mix, and
        # every slot beyond what the pages can back just burns step
        # width on garbage decode.
        "paged": dict(n_slots=3 * s_fixed, kv_paged=True,
                      kv_page_tokens=page_tokens, kv_pages=pages),
    }
    out = {}
    for arm, kw in arms.items():
        ms = ModelServer(model, variables, model_name=model_name,
                         max_batch=4, batching="continuous",
                         queue_depth=16 * n_clients, prefix_cache=4,
                         **kw)
        srv = make_server("127.0.0.1", 0, ms)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        stop_poll = threading.Event()
        occ_samples = []

        def poll(ms=ms, stop=stop_poll, occ=occ_samples):
            while not stop.wait(0.1):
                es = ms.engine.stats()
                occ.append((es["slots_active"],
                            es.get("kv_pages_resident", 0)))

        try:
            # Warm every schedule shape (prefill + window programs,
            # and the paged pad classes) outside the timed run: TWO
            # untimed passes of the same schedule — admission
            # interleavings differ run to run, so one pass can skip
            # a (window, pad-class) combo the timed leg then hits.
            _run_longtail_clients(base, sched, vocab)
            _run_longtail_clients(base, sched, vocab)
            pre = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            n_done, toks, wall, errors = _run_longtail_clients(
                base, sched, vocab)
            stop_poll.set()
            poller.join()
            if errors:
                print(f"# longtail arm={arm} errors: {errors[:3]}",
                      file=sys.stderr)
                return {}
            info = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            mean_res = round(sum(o[0] for o in occ_samples)
                             / max(1, len(occ_samples)), 2)
            row = {
                "requests": n_done,
                "agg_tok_per_sec": round(toks / wall, 1),
                "mean_resident_requests": mean_res,
                "slots": kw["n_slots"],
                "kv_budget_tokens": s_fixed * max_pos,
                "compile_cache_misses_during": info.get(
                    "compile_cache_misses", 0)
                - pre.get("compile_cache_misses", 0),
            }
            if arm == "paged":
                row["mean_pages_resident"] = round(
                    sum(o[1] for o in occ_samples)
                    / max(1, len(occ_samples)), 1)
                row["kv_pages"] = pages
            # SHARED-PREFIX variant: register the system prompt once,
            # then stream suffix requests; hits ride stored prefill.
            req = urllib.request.Request(
                base + "/prefill",
                data=json.dumps({"prompt": system}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=900) as r:
                r.read()
            # warm the suffix shapes untimed, then reset counters
            _run_longtail_clients(base, [[(16, 16)]] * 2, vocab,
                                  prefix=system)
            pre = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            shared_peak = [0]
            stop_shared = threading.Event()

            def poll_shared(ms=ms, stop=stop_shared,
                            peak=shared_peak):
                while not stop.wait(0.05):
                    peak[0] = max(peak[0], ms.engine.stats().get(
                        "kv_pages_shared", 0))

            sp = threading.Thread(target=poll_shared, daemon=True)
            sp.start()
            n_done, toks, wall, errors = _run_longtail_clients(
                base, shared_sched, vocab, prefix=system)
            stop_shared.set()
            sp.join()
            if errors:
                print(f"# longtail-shared arm={arm} errors: "
                      f"{errors[:3]}", file=sys.stderr)
                return {}
            info = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            hit_toks = info.get("prefix_hit_tokens", 0) \
                - pre.get("prefix_hit_tokens", 0)
            shared = {
                "requests": n_done,
                "agg_tok_per_sec": round(toks / wall, 1),
                "system_len": sys_len,
                "hit_tokens": hit_toks,
                # every request served its FULL system prompt from
                # the stored prefill -> the prompt was prefilled
                # exactly once (at /prefill), asserted below for the
                # paged arm
                "prefilled_once": hit_toks >= n_done * sys_len,
            }
            if arm == "paged":
                # Peak of the kv_pages_shared GAUGE sampled DURING
                # the shared-prefix run: live copy-on-write sharing
                # between the stored entry and resident slots.
                shared["kv_pages_shared_peak"] = shared_peak[0]
                assert shared["prefilled_once"], (
                    f"shared-prefix variant: hit_tokens {hit_toks} < "
                    f"{n_done} x {sys_len} — the common prompt was "
                    f"re-prefilled")
            row["shared_prefix"] = shared
            out[arm] = row
        finally:
            stop_poll.set()
            srv.shutdown()
            srv.server_close()
            ms.close()
    if len(out) < 2:
        return {}
    ab = {
        "tok_per_sec_speedup": round(
            out["paged"]["agg_tok_per_sec"]
            / out["fixed"]["agg_tok_per_sec"], 3),
        "occupancy_ratio": round(
            out["paged"]["mean_resident_requests"]
            / max(0.01, out["fixed"]["mean_resident_requests"]), 3),
        "shared_tok_per_sec_speedup": round(
            out["paged"]["shared_prefix"]["agg_tok_per_sec"]
            / out["fixed"]["shared_prefix"]["agg_tok_per_sec"], 3),
    }
    print(f"# longtail: paged {out['paged']['agg_tok_per_sec']} vs "
          f"fixed {out['fixed']['agg_tok_per_sec']} tok/s "
          f"({ab['tok_per_sec_speedup']}x) at equal KV budget; "
          f"mean residents {out['paged']['mean_resident_requests']} "
          f"vs {out['fixed']['mean_resident_requests']} "
          f"({ab['occupancy_ratio']}x); shared-prefix "
          f"{ab['shared_tok_per_sec_speedup']}x, hit_tokens "
          f"{out['paged']['shared_prefix']['hit_tokens']}",
          file=sys.stderr)
    return {"longtail": {**out, "paged_vs_fixed": ab}}


def bench_lazy_longtail(model, variables, model_name: str,
                        vocab: int, *, requests: int):
    """LAZY-GROWTH leg (PR 12 tentpole a): lazy vs full page
    reservation at EQUAL device KV budget on a SHORT-OUTPUT mix.

    Real traffic declares big budgets and stops early; full
    reservation pays the whole budget in pages at admission, so
    reserved-but-dead pages pin concurrency.  The mix here makes
    that explicit: every request declares ``budget`` new tokens but
    carries an ``eos_id`` learned from an untimed PROBE of its own
    greedy continuation (the token at its target output length), so
    it deterministically stops at ~1/3 to ~1/6 of budget — identical
    tokens on both arms, so the A/B compares the RESERVATION POLICY
    only.  Criterion: lazy >= 1.2x mean residents AND >= 1.2x
    aggregate tok/s (decoded tokens, not budget-padded), with ZERO
    timed compile-cache misses on both arms."""
    import dataclasses

    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    # Serving-headroom rebuild, same rationale as bench_longtail.
    cfg = getattr(model, "cfg", None)
    if cfg is not None and getattr(cfg, "max_position", 0) < 1024 \
            and not getattr(cfg, "kv_cache_ring", False) \
            and dataclasses.is_dataclass(cfg):
        import jax
        import jax.numpy as jnp

        cfg = dataclasses.replace(cfg, max_position=1024)
        model = type(model)(cfg=cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    page_tokens = 64
    n_slots = 12
    budget = 192                      # declared (reserved) budget
    pages = 18                        # full reservation: prompt +
    #                                   budget = 4 pages/request ->
    #                                   ~4 concurrent; lazy: usage-
    #                                   bounded -> slot-cap 12
    n_clients = 12
    per_client = max(3, requests // 2)
    rng = np.random.RandomState(23)
    sched = []                        # (prompt tokens, target len)
    for _ in range(n_clients):
        pairs = []
        for _ in range(per_client):
            p = int(rng.choice([32, 64]))
            tgt = int(rng.choice([16, 32, 64]))
            pairs.append((rng.randint(0, vocab, size=p).tolist(),
                          tgt))
        sched.append(pairs)

    def run_clients(base, eos_map, timed):
        done = [0, 0]
        lock = threading.Lock()
        errors = []

        def client(i):
            for j, (toks, tgt) in enumerate(sched[i]):
                if timed:
                    body = {"prompt": toks, "max_new_tokens": budget,
                            "eos_id": eos_map[(i, j)]}
                else:
                    body = {"prompt": toks, "max_new_tokens": tgt}
                try:
                    r = _post(base, body, timeout=900)
                except Exception as e:  # noqa: BLE001
                    errors.append(f"{type(e).__name__}: {e}")
                    return
                if timed:
                    # decoded tokens = up to and incl. the first eos
                    # (the response pads to budget with eos)
                    row = r["new_tokens"][0]
                    eos = eos_map[(i, j)]
                    n = row.index(eos) + 1 if eos in row else len(row)
                else:
                    row = r["new_tokens"][0]
                    eos_map[(i, j)] = row[-1]
                    n = len(row)
                with lock:
                    done[0] += 1
                    done[1] += n

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return done[0], done[1], time.perf_counter() - t0, errors

    out = {}
    for arm in ("full", "lazy"):
        ms = ModelServer(model, variables, model_name=model_name,
                         max_batch=4, batching="continuous",
                         n_slots=n_slots,
                         queue_depth=8 * n_clients, prefix_cache=0,
                         kv_paged=True, kv_page_tokens=page_tokens,
                         kv_pages=pages, kv_lazy=(arm == "lazy"))
        srv = make_server("127.0.0.1", 0, ms)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        stop_poll = threading.Event()
        occ = []

        def poll(ms=ms, stop=stop_poll, occ=occ):
            while not stop.wait(0.1):
                es = ms.engine.stats()
                occ.append((es["slots_active"],
                            es.get("kv_pages_resident", 0)))

        try:
            eos_map = {}
            # PROBE pass (untimed): learns each request's eos AND
            # warms the prompt/window programs.
            _, _, _, errors = run_clients(base, eos_map, False)
            if errors:
                print(f"# lazy-longtail probe arm={arm} errors: "
                      f"{errors[:3]}", file=sys.stderr)
                return {}
            # Warm the preempt-resume program set: pow2 pfill +
            # extend pieces (an exhaustion preempt's re-prefill is a
            # pow2 decomposition whose piece lengths must all be
            # warm before the timed run).
            L = 1
            while 2 * L <= 256:
                warm = np.random.RandomState(L).randint(
                    0, vocab, size=2 * L).tolist()
                _post(base, {"prompt": warm, "max_new_tokens": 1,
                             "prefill_chunk": L}, timeout=900)
                L *= 2
            # TWO untimed passes of the TIMED schedule: warms the
            # lazy pad classes, growth path, and exhaustion-preempt
            # interleavings — two, because admission interleavings
            # differ run to run and one pass can skip a (window,
            # pad-class) combo the timed leg then hits (same
            # rationale as the longtail leg).
            run_clients(base, eos_map, True)
            run_clients(base, eos_map, True)
            pre = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            n_done, toks, wall, errors = run_clients(base, eos_map,
                                                     True)
            stop_poll.set()
            poller.join()
            if errors:
                print(f"# lazy-longtail arm={arm} errors: "
                      f"{errors[:3]}", file=sys.stderr)
                return {}
            info = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            out[arm] = {
                "requests": n_done,
                "agg_tok_per_sec": round(toks / wall, 1),
                "decoded_tokens": toks,
                "declared_budget": budget,
                "mean_resident_requests": round(
                    sum(o[0] for o in occ) / max(1, len(occ)), 2),
                "mean_pages_resident": round(
                    sum(o[1] for o in occ) / max(1, len(occ)), 1),
                "kv_pages": pages,
                "kv_budget_tokens": pages * page_tokens,
                "compile_cache_misses_during": info.get(
                    "compile_cache_misses", 0)
                - pre.get("compile_cache_misses", 0),
                "lazy_growths": info.get(
                    "kv_pages_lazy_growths_total", 0),
                "exhaustion_preempts": info.get(
                    "kv_preempt_exhaustion_total", 0),
            }
        finally:
            stop_poll.set()
            srv.shutdown()
            srv.server_close()
            ms.close()
    if len(out) < 2:
        return {}
    ab = {
        "tok_per_sec_speedup": round(
            out["lazy"]["agg_tok_per_sec"]
            / max(0.01, out["full"]["agg_tok_per_sec"]), 3),
        "occupancy_ratio": round(
            out["lazy"]["mean_resident_requests"]
            / max(0.01, out["full"]["mean_resident_requests"]), 3),
    }
    print(f"# lazy-longtail: lazy {out['lazy']['agg_tok_per_sec']} "
          f"vs full {out['full']['agg_tok_per_sec']} tok/s "
          f"({ab['tok_per_sec_speedup']}x) at equal page budget; "
          f"mean residents "
          f"{out['lazy']['mean_resident_requests']} vs "
          f"{out['full']['mean_resident_requests']} "
          f"({ab['occupancy_ratio']}x); "
          f"{out['lazy']['exhaustion_preempts']} exhaustion "
          f"preempts, {out['lazy']['lazy_growths']} growths",
          file=sys.stderr)
    return {"lazy_longtail": {**out, "lazy_vs_full": ab}}


def bench_prefix_spill(model, variables, model_name: str,
                       vocab: int):
    """SPILL leg (PR 12 tentpole b): hit-rate x TTFT on a prefix
    population sized ~4x the device page pool, host-RAM spill tier
    vs the PR 7 drop-on-evict baseline.

    Each arm registers N prefixes (N x pages-per-prefix >= 4x pool),
    then round-robins hit traffic over all of them.  The drop arm
    retains only the prefixes whose pages still fit the device pool
    (the rest re-prefill from scratch); the spill arm serves the
    whole population — device tier or re-materialized from host RAM
    — so its hit-rate multiplies by the host/HBM ratio while the
    spilled-hit TTFT stays bounded (device_put of the payload vs a
    full prefill forward)."""
    import dataclasses

    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    cfg = getattr(model, "cfg", None)
    if cfg is not None and getattr(cfg, "max_position", 0) < 1024 \
            and not getattr(cfg, "kv_cache_ring", False) \
            and dataclasses.is_dataclass(cfg):
        import jax
        import jax.numpy as jnp

        cfg = dataclasses.replace(cfg, max_position=1024)
        model = type(model)(cfg=cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    page_tokens = 64
    pages = 24                        # device pool: 1536 tokens
    prefix_tokens = 256               # 4 pages per prefix
    n_prefixes = 24                   # population = 96 pages = 4x
    rounds = 2
    rng = np.random.RandomState(31)
    population = [rng.randint(0, vocab,
                              size=prefix_tokens).tolist()
                  for _ in range(n_prefixes)]
    out = {}
    for arm, spill in (("drop", 0), ("spill", 256 << 20)):
        ms = ModelServer(model, variables, model_name=model_name,
                         max_batch=4, batching="continuous",
                         n_slots=4, queue_depth=64,
                         prefix_cache=2 * n_prefixes,
                         kv_paged=True, kv_page_tokens=page_tokens,
                         kv_pages=pages,
                         kv_host_spill_bytes=spill)
        srv = make_server("127.0.0.1", 0, ms)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            # Register the population: page pressure during the
            # later registrations evicts the earlier entries from
            # the device tier (spilling or dropping per arm).
            for p in population:
                req = urllib.request.Request(
                    base + "/prefill",
                    data=json.dumps({"prompt": p}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=900) as r:
                    r.read()
            # Warm the hit path's programs (extend + decode) on one
            # prefix, untimed.
            _post(base, {"prompt": population[0] + [7, 8],
                         "max_new_tokens": 16, "timings": True},
                  timeout=900)
            pre = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            hit_ttfts, miss_ttfts = [], []
            n_req = 0
            t0 = time.perf_counter()
            for _ in range(rounds):
                for i, p in enumerate(population):
                    r = _post(base, {"prompt": p + [11 + i % 7,
                                                    3 + i % 5],
                                     "max_new_tokens": 16,
                                     "timings": True}, timeout=900)
                    n_req += 1
                    ttft = r.get("timings", {}).get("ttft_ms")
                    if r.get("prefix_hit_len", 0) >= prefix_tokens:
                        hit_ttfts.append(ttft)
                    else:
                        miss_ttfts.append(ttft)
            wall = time.perf_counter() - t0
            info = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            hits = info.get("prefix_hits", 0) \
                - pre.get("prefix_hits", 0)
            row = {
                "requests": n_req,
                "population_prefixes": n_prefixes,
                "population_pages": n_prefixes
                * (prefix_tokens // page_tokens),
                "kv_pages": pages,
                "hit_rate": round(len(hit_ttfts) / n_req, 3),
                "prefix_hits": hits,
                "wall_s": round(wall, 3),
                # ttft_ms values are ALREADY milliseconds
                "hit_ttft_p50_ms": round(percentile(hit_ttfts, 50), 3)
                if hit_ttfts else None,
                "hit_ttft_p95_ms": round(percentile(hit_ttfts, 95), 3)
                if hit_ttfts else None,
                "miss_ttft_p50_ms": round(percentile(miss_ttfts, 50),
                                          3)
                if miss_ttfts else None,
                "rematerialize_hits": info.get(
                    "kv_rematerialize_hits_total", 0),
                "rematerialize_mb": round(info.get(
                    "kv_rematerialize_bytes_total", 0) / 2**20, 2),
                "kv_host_entries": info.get("kv_host_entries", 0),
                "kv_host_mb": round(info.get(
                    "kv_host_spill_bytes", 0) / 2**20, 2),
            }
            out[arm] = row
        finally:
            srv.shutdown()
            srv.server_close()
            ms.close()
    if len(out) < 2:
        return {}
    ab = {
        "hit_rate_gain": round(
            out["spill"]["hit_rate"]
            / max(0.001, out["drop"]["hit_rate"]), 2),
        # Spilled-hit TTFT bound: a re-materialized hit must beat a
        # full re-prefill (the drop arm's miss), or the tier buys
        # nothing.
        "spill_hit_ttft_vs_drop_miss": round(
            (out["spill"]["hit_ttft_p50_ms"] or 0)
            / max(0.001, out["drop"]["miss_ttft_p50_ms"] or 0.001),
            3) if out["drop"]["miss_ttft_p50_ms"] else None,
    }
    print(f"# prefix-spill: hit-rate {out['spill']['hit_rate']} "
          f"(spill) vs {out['drop']['hit_rate']} (drop) = "
          f"{ab['hit_rate_gain']}x on a "
          f"{out['spill']['population_pages']}-page population over "
          f"a {pages}-page pool; spilled-hit TTFT p50 "
          f"{out['spill']['hit_ttft_p50_ms']}ms vs drop-miss p50 "
          f"{out['drop']['miss_ttft_p50_ms']}ms "
          f"({out['spill']['rematerialize_hits']} re-"
          f"materializations, {out['spill']['kv_host_mb']} MB host)",
          file=sys.stderr)
    return {"prefix_spill": {**out, "spill_vs_drop": ab}}


def bench_fleet_prefix(model, variables, model_name: str,
                       vocab: int, *, requests: int):
    """FLEET-PREFIX leg (PR 16 tentpole): a session-heavy mix — one
    registered system prompt, distinct per-request suffixes — through
    a 3-replica fleet, wire-fetch arm vs per-replica-only arm,
    straight THROUGH a rolling restart.

    The fleet arm runs the whole migration tier: replicas with
    ``prefix_fetch`` armed (affinity spillover requests carry the
    router's holder hint and pull the prefix over the wire instead of
    re-prefilling) and the router's drain handoff (the drainee pushes
    its entries to a successor before the restart flushes them).  The
    per-replica-only arm is the same paged/spill fleet with both
    switched off — the seed behavior, where every spillover and every
    restart is a re-prefill.

    Scored claims, mirroring the ISSUE's acceptance bar: the fleet
    arm's hit rate through the rolling restart strictly above the
    per-replica arm's; wire-fetch TTFT between the local-hit and
    re-prefill medians (on this box's noise floor, honestly
    ``noisy_box``-flagged when the same-population spread swamps the
    ordering); greedy token streams bitwise-identical across arms for
    the same prompts (wire fetch must not change a single token); and
    zero steady-state recompiles with the fetch path armed."""
    import numpy as np

    from polyaxon_tpu.serving import (LocalReplica, ModelServer,
                                      PrefixFetchPolicy,
                                      ReplicaRouter,
                                      make_router_server)

    sys_len, user_len, new = 192, 8, 16
    max_pos = getattr(getattr(model, "cfg", None), "max_position",
                      None) or 10**9
    if sys_len + user_len + new >= max_pos:
        sys_len = max(16, max_pos - user_len - new - 1)
    page_tokens = 16
    rng = np.random.RandomState(47)
    system = rng.randint(0, vocab, size=sys_len).tolist()
    sfx_rng = np.random.RandomState(48)

    def suffixes(n):
        return [sfx_rng.randint(0, vocab, size=user_len).tolist()
                for _ in range(n)]

    probe_sfx = [np.random.RandomState(49 + i).randint(
        0, vocab, size=user_len).tolist() for i in range(3)]

    def run_batch(base, sfx_list, conc):
        """``conc`` concurrent session requests over the router;
        returns per-request {src, hit, ttft} dicts (errors counted,
        not raised — a failed request is a broken degrade contract
        and fails the leg below)."""
        results, errors = [], []
        lock = threading.Lock()
        it = iter(sfx_list)

        def worker():
            while True:
                with lock:
                    sfx = next(it, None)
                if sfx is None:
                    return
                try:
                    r = _post(base, {"prompt": system + sfx,
                                     "max_new_tokens": new,
                                     "timings": True}, timeout=900)
                except Exception as e:  # noqa: BLE001 - scored
                    with lock:
                        errors.append(str(e))
                    continue
                with lock:
                    results.append({
                        "src": r.get("prefix_source", "re_prefill"),
                        "hit": r.get("prefix_hit_len", 0) >= sys_len,
                        "ttft": (r.get("timings") or {}).get(
                            "ttft_ms")})

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results, errors

    per_round = max(6, requests)
    rounds = 3
    out = {}
    fleets = {}
    leg_errors = []
    try:
        for arm in ("fleet", "local"):
            fetch = arm == "fleet"

            def factory(fetch=fetch):
                return ModelServer(
                    model, variables, model_name=model_name,
                    max_batch=2, batching="continuous", n_slots=2,
                    queue_depth=32, prefix_cache=24, kv_paged=True,
                    kv_page_tokens=page_tokens, kv_pages=96,
                    kv_host_spill_bytes=64 << 20,
                    prefix_fetch=fetch,
                    prefix_fetch_policy=PrefixFetchPolicy(
                        min_tokens=8) if fetch else None)

            reps = [LocalReplica(factory, f"r{i}") for i in range(3)]
            router = ReplicaRouter(
                reps, probe_interval_s=0.1, probe_timeout_s=1.5,
                cooldown_s=0.3, max_attempts=3,
                request_timeout_s=120.0,
                # Saturates at ONE outstanding request: the session
                # burst below spills off the holder every round, so
                # the hint/fetch lane (or the per-replica re-prefill
                # it replaces) carries real traffic.
                affinity_max_outstanding=1,
                prefix_handoff=fetch)
            srv = make_router_server("127.0.0.1", 0, router)
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            fleets[arm] = (reps, router, srv, base)
            # Direct compile warm on EVERY replica: the full-prompt
            # prefill (the re-prefill lane), then a registered
            # prefix + extension (the split prefill/extend lane the
            # hit and wire-fetch paths share).  Throwaway prompts —
            # the measured system prompt is registered after.
            warm_rng = np.random.RandomState(5)
            warm_sys = []
            for rep in reps:
                wfull = warm_rng.randint(
                    0, vocab, size=sys_len + user_len).tolist()
                _post(rep.url, {"prompt": wfull,
                                "max_new_tokens": new}, timeout=900)
                wsys = warm_rng.randint(0, vocab,
                                        size=sys_len).tolist()
                warm_sys.append(wsys)
                req = urllib.request.Request(
                    rep.url + "/prefill",
                    data=json.dumps({"prompt": wsys}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=900) as r:
                    r.read()
                _post(rep.url, {"prompt": wsys + warm_rng.randint(
                    0, vocab, size=user_len).tolist(),
                    "max_new_tokens": new}, timeout=900)
            # Warm the HOST-TIER serve lane on every replica too
            # (pull a neighbor's warm prefix over the wire endpoints
            # and extend it): a wire-fetched or handed-off entry is
            # served via the host->device rematerialize path, whose
            # first use pays one-time jit/scatter warmup a TIMED
            # fetch must not carry.
            for i, rep in enumerate(reps):
                donor = reps[(i + 1) % len(reps)]
                req = urllib.request.Request(
                    donor.url + "/prefix/fetch",
                    data=json.dumps(
                        {"prompt": warm_sys[(i + 1) % len(reps)]}
                    ).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=900) as r:
                    blob = r.read()
                req = urllib.request.Request(
                    rep.url + "/prefix/ingest", data=blob,
                    headers={"Content-Type":
                             "application/octet-stream"})
                with urllib.request.urlopen(req, timeout=900) as r:
                    r.read()
                _post(rep.url, {
                    "prompt": warm_sys[(i + 1) % len(reps)]
                    + warm_rng.randint(0, vocab,
                                       size=user_len).tolist(),
                    "max_new_tokens": new}, timeout=900)
            # Register the measured system prompt through the
            # ROUTER: the routed replica becomes the affinity
            # primary the fetch hints point at.
            req = urllib.request.Request(
                base + "/prefill",
                data=json.dumps({"prompt": system}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=900) as r:
                r.read()

            compiles_pre = {rep.id: rep.ms.recompile.snapshot()[
                "compile_cache_misses"] for rep in reps}
            steady, round_hit_rates = [], []
            for _ in range(rounds):
                got, errs = run_batch(base, suffixes(per_round),
                                      conc=4)
                leg_errors += [f"{arm}: {e}" for e in errs]
                steady += got
                if got:
                    round_hit_rates.append(
                        sum(1 for g in got if g["hit"]) / len(got))
            compiles_steady = {
                rep.id: rep.ms.recompile.snapshot()[
                    "compile_cache_misses"] - compiles_pre[rep.id]
                for rep in reps}
            # Uncontended LANE probes for the cost curve: the
            # concurrent phases above score hit RATES under load
            # (their TTFTs carry queue wait), but the local-hit <=
            # wire-fetch <= re-prefill ordering needs each lane
            # timed alone.  Local hit: the holder serving a fresh
            # session suffix.  Wire fetch: a non-holder pulling a
            # freshly-registered prefix via an explicit holder hint
            # (a new prefix per probe — a fetched entry is stored,
            # so re-probing the same one would time a local hit).
            # Re-prefill: the per-replica arm's non-holder serving
            # the same shape with no fetch tier to lean on.
            by_id = {rep.id: rep for rep in reps}
            holder = by_id.get(
                router._affinity_for(list(system))) or reps[0]
            probe_rng = np.random.RandomState(97)
            lanes = {"local_hit": [], "wire_fetch": [],
                     "re_prefill": []}
            if fetch:
                for _ in range(5):
                    r = _post(holder.url, {
                        "prompt": system + probe_rng.randint(
                            0, vocab, size=user_len).tolist(),
                        "max_new_tokens": new, "timings": True},
                        timeout=900)
                    if r.get("prefix_source") in ("local_hot",
                                                  "local_spilled"):
                        lanes["local_hit"].append(
                            r["timings"]["ttft_ms"])
                fetcher = next(rep for rep in reps
                               if rep is not holder)
                for k in range(4):
                    pk = np.random.RandomState(200 + k).randint(
                        0, vocab, size=sys_len).tolist()
                    req = urllib.request.Request(
                        holder.url + "/prefill",
                        data=json.dumps({"prompt": pk}).encode(),
                        headers={"Content-Type":
                                 "application/json"})
                    with urllib.request.urlopen(req,
                                                timeout=900) as r:
                        r.read()
                    r = _post(fetcher.url, {
                        "prompt": pk + probe_rng.randint(
                            0, vocab, size=user_len).tolist(),
                        "max_new_tokens": new, "timings": True,
                        "prefix_hint": {"host": holder.host,
                                        "port": holder.port}},
                        timeout=900)
                    if r.get("prefix_source") == "wire_fetch":
                        lanes["wire_fetch"].append(
                            r["timings"]["ttft_ms"])
            else:
                cold = next(rep for rep in reps
                            if rep is not holder)
                for _ in range(5):
                    r = _post(cold.url, {
                        "prompt": system + probe_rng.randint(
                            0, vocab, size=user_len).tolist(),
                        "max_new_tokens": new, "timings": True},
                        timeout=900)
                    if r.get("prefix_source") == "re_prefill":
                        lanes["re_prefill"].append(
                            r["timings"]["ttft_ms"])
            # Exactness probes: the SAME three prompts both arms
            # serve — greedy streams must not depend on which lane
            # (local hit / wire fetch / re-prefill) produced the
            # prefix.
            probes = [_post(base, {"prompt": system + s,
                                   "max_new_tokens": new},
                            timeout=900).get("new_tokens")
                      for s in probe_sfx]
            # Rolling restart with the session mix STILL FLOWING:
            # the fleet arm's drain handoff migrates the store ahead
            # of each flush; the local arm restarts are cache
            # massacres.
            with urllib.request.urlopen(urllib.request.Request(
                    base + "/fleet/restart", data=b"",
                    headers={"Content-Type": "application/json"}),
                    timeout=30) as r:
                r.read()
            during = []
            deadline = time.monotonic() + 180.0
            while router.restart_state["in_progress"] \
                    and time.monotonic() < deadline:
                got, errs = run_batch(base, suffixes(4), conc=2)
                leg_errors += [f"{arm} restart: {e}" for e in errs]
                during += got
            post, errs = run_batch(base, suffixes(per_round), conc=4)
            leg_errors += [f"{arm} post: {e}" for e in errs]
            restart_traffic = during + post
            st = router.stats()

            def rate(batch):
                return round(sum(1 for g in batch if g["hit"])
                             / max(1, len(batch)), 3)

            everything = steady + restart_traffic
            out[arm] = {
                "steady": steady, "restart": restart_traffic,
                "round_hit_rates": [round(h, 3)
                                    for h in round_hit_rates],
                "row": {
                    "requests": len(everything),
                    "steady_hit_rate": rate(steady),
                    "restart_hit_rate": rate(restart_traffic),
                    "hit_rate": rate(everything),
                    "sources": {s: sum(1 for g in everything
                                       if g["src"] == s)
                                for s in sorted({g["src"]
                                                 for g in everything})},
                    "steady_recompiles": compiles_steady,
                    "hints_injected": st.get(
                        "kv_fleet_hints_injected_total", 0),
                    "wire_fetches": st.get(
                        "kv_fleet_wire_fetches_total", 0),
                    "handoffs": st.get("kv_fleet_handoffs_total", 0),
                    "handoff_entries": st.get(
                        "kv_fleet_handoff_entries_total", 0),
                    "restart_completed": st["rolling_restart"][
                        "completed"],
                    "restart_error": st["rolling_restart"][
                        "last_error"],
                },
                "probes": probes,
                "lanes": lanes,
            }
    finally:
        for reps, router, srv, _ in fleets.values():
            router.close()
            srv.shutdown()
            srv.server_close()
            for rep in reps:
                rep.close()
    if len(out) < 2 or leg_errors:
        print(f"# fleet-prefix leg errors: {leg_errors[:3]}",
              file=sys.stderr)
        return {}

    fa, la = out["fleet"], out["local"]
    exact = all(
        p is not None and q is not None and p == q
        for p, q in zip(fa["probes"], la["probes"]))
    # The cost curve comes from the UNCONTENDED lane probes (the
    # concurrent phases' TTFTs carry queue wait, not lane cost).
    hot = fa["lanes"]["local_hit"]
    wire = fa["lanes"]["wire_fetch"]
    repre = la["lanes"]["re_prefill"]
    hot_p50 = round(percentile(hot, 50), 3) if hot else None
    wire_p50 = round(percentile(wire, 50), 3) if wire else None
    repre_p50 = round(percentile(repre, 50), 3) if repre else None
    between = (hot_p50 is not None and wire_p50 is not None
               and repre_p50 is not None
               and hot_p50 <= wire_p50 <= repre_p50)
    # Same-lane noise floor: worst within-lane spread as a fraction
    # of that lane's median — the same path timed against itself.
    # When the box spreads a single lane wider than the inter-lane
    # margins, the ordering attests nothing either way.
    noise_pct = 0.0
    for pop in (hot, wire, repre):
        if len(pop) >= 3 and percentile(pop, 50):
            noise_pct = max(noise_pct, round(
                100.0 * (max(pop) - min(pop))
                / percentile(pop, 50), 2))
    noisy = noise_pct > 25.0
    row = {
        "system_tokens": sys_len,
        "fleet": fa["row"],
        "per_replica": la["row"],
        "restart_hit_rate_gain": round(
            fa["row"]["restart_hit_rate"]
            / max(0.001, la["row"]["restart_hit_rate"]), 2),
        "ttft_local_hit_p50_ms": hot_p50,
        "ttft_wire_fetch_p50_ms": wire_p50,
        "ttft_re_prefill_p50_ms": repre_p50,
        "wire_fetch_vs_re_prefill": round(
            wire_p50 / repre_p50, 3)
        if wire_p50 and repre_p50 else None,
        "wire_between_bounds": between,
        "noise_pct": noise_pct,
        **({"noisy_box": True} if noisy else {}),
        "exact": exact,
    }
    print(f"# fleet-prefix: hit rate through restart "
          f"{fa['row']['restart_hit_rate']} (fleet) vs "
          f"{la['row']['restart_hit_rate']} (per-replica), "
          f"{fa['row']['wire_fetches']} wire fetches / "
          f"{fa['row']['handoff_entries']} handed-off entries; "
          f"ttft p50 hit={hot_p50} wire={wire_p50} "
          f"re-prefill={repre_p50} ms (noise {noise_pct}%), "
          f"exact={exact}", file=sys.stderr)
    return {"fleet_prefix": row}


def bench_disagg(model, variables, model_name: str, vocab: int, *,
                 requests: int):
    """DISAGG leg (PR 17 tentpole): role-split serving — 1 prefill +
    2 decode replicas vs 3 monolithic replicas at EQUAL total KV
    budget (identical per-replica paged/spill config; only ``role``
    differs), on mixed interactive traffic: long distinct prompts,
    short outputs.

    The disagg arm runs the whole two-stage schedule: the router
    prefills each prompt on the prefill tier, ships the admit-ready
    KV to the chosen decode replica over the PR 16 wire lane, and
    the decode replica admits it instead of re-prefilling — so long
    prompt prefills never serialize against in-flight decode steps
    on the serving replicas.  The monolithic arm is the seed
    behavior: every replica pays its own prefill inline.

    Scored claims, mirroring the ISSUE's acceptance bar: interactive
    TTFT p99 improves vs monolithic (prefill no longer ahead of
    decode in the same device lock); aggregate tok/s stays in band
    (the decode tier is 2/3 of the fleet but prefill work left with
    the other third); the measured handoff (transfer + admit) costs
    less than the re-prefill it replaces; greedy streams
    bitwise-identical across arms; zero steady-state recompiles on
    BOTH tiers.  The TTFT/cost orderings are noise-bound on a
    drifting box, so they ride the same ``noisy_box`` honesty valve
    as the other legs."""
    import numpy as np

    from polyaxon_tpu.serving import (LocalReplica, ModelServer,
                                      PrefixFetchPolicy,
                                      ReplicaRouter,
                                      make_router_server)

    sys_len, user_len, new = 192, 8, 8
    max_pos = getattr(getattr(model, "cfg", None), "max_position",
                      None) or 10**9
    if sys_len + user_len + new >= max_pos:
        sys_len = max(16, max_pos - user_len - new - 1)
    page_tokens = 16
    prompt_len = sys_len + user_len
    sfx_rng = np.random.RandomState(53)

    def prompts(n):
        # DISTINCT long prompts — interactive traffic, not the
        # shared-system-prompt session mix: every request pays a
        # full-length prefill somewhere, which is exactly the work
        # the split moves off the decode tier.
        return [sfx_rng.randint(0, vocab,
                                size=prompt_len).tolist()
                for _ in range(n)]

    probe_prompts = [np.random.RandomState(300 + i).randint(
        0, vocab, size=prompt_len).tolist() for i in range(3)]
    # Background class: SHORT prompt (below the router's
    # disagg_min_tokens floor, so it goes straight to the decode
    # tier in both arms), LONG decode — the steady decode load the
    # interactive arrivals' prefills barge in on in the monolithic
    # arm and don't in the split.
    bg_len = 8
    page_pool_pages = 96
    pages_per_entry = -(-(prompt_len + new) // page_tokens)
    # Same TOTAL length as the interactive class: the paged step
    # program's pad class is the pow2 of the widest resident page
    # reservation, so classes mixing mid-round would compile a
    # fresh program per mix — equal totals pin every steady-state
    # dispatch into ONE pad class.
    bg_new = max(8, min(prompt_len + new - bg_len,
                        max_pos - bg_len - 1))

    def run_round(base, prompt_list, conc):
        """One mixed round: 2 background long-decode loops running
        for the round's whole duration, ``conc`` interactive workers
        draining ``prompt_list``.  Interactive latency is the CLIENT
        wall of the whole short-output request — the replica-side
        ttft_ms would hide the disagg arm's stage-1 hop, and the
        comparison must charge the split its own overhead."""
        results, errors = [], []
        bg_tokens = [0]
        stop = threading.Event()
        lock = threading.Lock()
        it = iter(prompt_list)

        def bg_worker(seed):
            rng = np.random.RandomState(seed)
            while not stop.is_set():
                try:
                    _post(base, {"prompt": rng.randint(
                        0, vocab, size=bg_len).tolist(),
                        "max_new_tokens": bg_new}, timeout=900)
                except Exception as e:  # noqa: BLE001 - scored
                    with lock:
                        errors.append(f"bg: {e}")
                    return
                with lock:
                    bg_tokens[0] += bg_new

        def worker():
            while True:
                with lock:
                    p = next(it, None)
                if p is None:
                    return
                t0 = time.perf_counter()
                try:
                    # max_new_tokens=1: the client wall IS the
                    # client-perceived TTFT — it charges the disagg
                    # arm its stage-1 prefill hop AND the handoff,
                    # which the replica-side ttft_ms (clock starts
                    # at the decode replica) would hide.  The
                    # decode-capacity axis is the background
                    # class's job, scored by agg tok/s.
                    r = _post(base, {"prompt": p,
                                     "max_new_tokens": 1},
                              timeout=900)
                except Exception as e:  # noqa: BLE001 - scored
                    with lock:
                        errors.append(str(e))
                    continue
                with lock:
                    results.append({
                        "src": r.get("prefix_source", "re_prefill"),
                        "ms": 1e3 * (time.perf_counter() - t0),
                        "fetch_s": r.get("prefix_fetch_s")})

        # One background stream PER REPLICA: every monolithic
        # replica is decoding when an interactive prefill arrives —
        # the interference regime the split exists for.  (Fewer
        # streams leave a free mono replica and measure under-load,
        # where monolithic trivially wins TTFT.)
        bg = [threading.Thread(target=bg_worker, args=(700 + i,),
                               daemon=True) for i in range(3)]
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(conc)]
        for t in bg + threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        for t in bg:
            t.join()
        return results, bg_tokens[0], errors

    per_round = max(6, requests)
    rounds = 3
    out = {}
    fleets = {}
    leg_errors = []
    try:
        for arm, roles in (("disagg", ("prefill", "decode",
                                       "decode")),
                           ("mono", ("both", "both", "both"))):
            def factory(role):
                return ModelServer(
                    model, variables, model_name=model_name,
                    max_batch=2, batching="continuous", n_slots=2,
                    queue_depth=32, prefix_cache=24, kv_paged=True,
                    kv_page_tokens=page_tokens,
                    kv_pages=page_pool_pages,
                    kv_host_spill_bytes=64 << 20, role=role,
                    prefix_fetch=True,
                    # prefill_tok_per_s=1: the cost gate forced OPEN
                    # so the leg MEASURES the handoff lane on every
                    # box — the handoff-vs-re-prefill ratio below is
                    # the honest verdict on whether the calibrated
                    # gate would have chosen it.
                    prefix_fetch_policy=PrefixFetchPolicy(
                        min_tokens=8, prefill_tok_per_s=1.0))

            reps = [LocalReplica(
                lambda role=role: factory(role), f"r{i}")
                for i, role in enumerate(roles)]
            router = ReplicaRouter(
                reps, probe_interval_s=0.1, probe_timeout_s=1.5,
                cooldown_s=0.3, max_attempts=3,
                request_timeout_s=120.0, prefix_handoff=True)
            srv = make_router_server("127.0.0.1", 0, router)
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            fleets[arm] = (reps, router, srv, base)
            # The two-stage schedule only activates once the probes
            # have LEARNED the fleet's roles — routed warmup before
            # that would silently measure the monolithic path.
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if tuple(r.role for r in router.replicas) == roles:
                    break
                time.sleep(0.05)
            # Direct compile warm per replica: decode-capable
            # replicas warm the full-prompt prefill+decode lane;
            # every replica warms the /prefill lane; each decode
            # replica additionally warms the wire-admit lane (pull a
            # fresh prefix off another replica and extend it) so a
            # TIMED handoff never carries one-time jit/scatter
            # warmup.
            warm_rng = np.random.RandomState(7)
            donor = reps[0]
            for rep in reps:
                wsys = warm_rng.randint(0, vocab,
                                        size=prompt_len).tolist()
                _post(rep.url, {"prompt": wsys}, timeout=900,
                      path="/prefill")
                if rep.ms.role != "prefill":
                    _post(rep.url, {"prompt": warm_rng.randint(
                        0, vocab, size=prompt_len).tolist(),
                        "max_new_tokens": new}, timeout=900)
                    # Interactive requests decode exactly ONE token
                    # (client wall == TTFT) — warm that decode
                    # window too.
                    _post(rep.url, {"prompt": warm_rng.randint(
                        0, vocab, size=prompt_len).tolist(),
                        "max_new_tokens": 1}, timeout=900)
                    # The background class's short-prompt prefill
                    # bucket too: its first admission must not
                    # compile mid-round.
                    _post(rep.url, {"prompt": warm_rng.randint(
                        0, vocab, size=bg_len).tolist(),
                        "max_new_tokens": bg_new}, timeout=900)
                    # Overflow the device page pool so the HOST-SPILL
                    # eviction gather compiles now: steady rounds
                    # accumulate stored entries past the pool's
                    # capacity, and the first eviction's
                    # materialize-to-host must not compile mid-round.
                    for _ in range(2 + page_pool_pages
                                   // max(1, pages_per_entry)):
                        _post(rep.url, {"prompt": warm_rng.randint(
                            0, vocab, size=prompt_len).tolist()},
                            timeout=900, path="/prefill")
                    # Full-prompt wire admit — the exact lane a
                    # disagg handoff lands on (stage 1 registers
                    # the WHOLE prompt on the prefill tier).
                    wk = warm_rng.randint(0, vocab,
                                          size=prompt_len).tolist()
                    _post(donor.url, {"prompt": wk}, timeout=900,
                          path="/prefill")
                    _post(rep.url, {
                        "prompt": wk, "max_new_tokens": new,
                        "prefix_hint": {"host": donor.host,
                                        "port": donor.port}},
                        timeout=900)
            # One routed warm through the full two-stage mixed
            # round (background + interactive).
            run_round(base, prompts(3), conc=3)

            compiles_pre = {rep.id: rep.ms.recompile.snapshot()[
                "compile_cache_misses"] for rep in reps}
            steady, round_tok_s = [], []
            for _ in range(rounds):
                batch = prompts(per_round)
                t0 = time.perf_counter()
                got, bgt, errs = run_round(base, batch, conc=3)
                wall = time.perf_counter() - t0
                leg_errors += [f"{arm}: {e}" for e in errs]
                steady += got
                if got:
                    # Interactive requests emit 1 token each; the
                    # background class carries the throughput axis.
                    round_tok_s.append((len(got) + bgt) / wall)
            compiles_steady = {
                rep.id: rep.ms.recompile.snapshot()[
                    "compile_cache_misses"] - compiles_pre[rep.id]
                for rep in reps}
            # Exactness probes: the SAME three prompts both arms
            # serve greedily — the split must not change a token.
            probes = [_post(base, {"prompt": p,
                                   "max_new_tokens": new},
                            timeout=900).get("new_tokens")
                      for p in probe_prompts]
            st = router.stats()
            ttfts = [g["ms"] for g in steady
                     if g["ms"] is not None]
            in_round_fetch = [1e3 * g["fetch_s"] for g in steady
                              if g.get("fetch_s")]
            out[arm] = {
                "steady": steady,
                "round_tok_s": [round(t, 2) for t in round_tok_s],
                "probes": probes,
                "row": {
                    "requests": len(steady),
                    "ttft_p50_ms": round(percentile(ttfts, 50), 3)
                    if ttfts else None,
                    "ttft_p99_ms": round(percentile(ttfts, 99), 3)
                    if ttfts else None,
                    "agg_tok_per_sec": round(
                        sum(round_tok_s) / max(1, len(round_tok_s)),
                        2) if round_tok_s else None,
                    "sources": {s: sum(1 for g in steady
                                       if g["src"] == s)
                                for s in sorted({g["src"]
                                                 for g in steady})},
                    "steady_recompiles": compiles_steady,
                    # Handoff latency AS EXPERIENCED mid-round (the
                    # uncontended cost probe below is the floor;
                    # this is what interactive requests actually
                    # paid while the decode tier was busy).
                    "handoff_in_round_ms_p50": round(
                        percentile(in_round_fetch, 50), 3)
                    if in_round_fetch else None,
                    "disagg_prefills": st.get(
                        "disagg_prefills_total", 0),
                    "disagg_prefill_failed": st.get(
                        "disagg_prefill_failed_total", 0),
                    "handoffs": st.get("disagg_handoffs_total", 0),
                },
            }
        # Uncontended COST probes on the disagg arm: the handoff
        # (transfer + admit, the replica-measured fetch span) vs the
        # full-length re-prefill it replaces (a direct /prefill of
        # the same shape on a decode replica, timed alone).
        reps, router, srv, base = fleets["disagg"]
        handoff_ms, reprefill_ms = [], []
        cost_rng = np.random.RandomState(91)
        for _ in range(4):
            r = _post(base, {"prompt": cost_rng.randint(
                0, vocab, size=prompt_len).tolist(),
                "max_new_tokens": new}, timeout=900)
            if r.get("prefix_source") == "wire_fetch" \
                    and r.get("prefix_fetch_s"):
                handoff_ms.append(1e3 * r["prefix_fetch_s"])
        dec = next(rep for rep in reps if rep.ms.role == "decode")
        for _ in range(4):
            t0 = time.perf_counter()
            _post(dec.url, {"prompt": cost_rng.randint(
                0, vocab, size=prompt_len).tolist()}, timeout=900,
                path="/prefill")
            reprefill_ms.append(1e3 * (time.perf_counter() - t0))
    finally:
        for reps, router, srv, _ in fleets.values():
            router.close()
            srv.shutdown()
            srv.server_close()
            for rep in reps:
                rep.close()
    if len(out) < 2 or leg_errors:
        print(f"# disagg leg errors: {leg_errors[:3]}",
              file=sys.stderr)
        return {}

    da, ma = out["disagg"], out["mono"]
    exact = all(
        p is not None and q is not None and p == q
        for p, q in zip(da["probes"], ma["probes"]))
    d99 = da["row"]["ttft_p99_ms"]
    m99 = ma["row"]["ttft_p99_ms"]
    d_agg = da["row"]["agg_tok_per_sec"]
    m_agg = ma["row"]["agg_tok_per_sec"]
    ho_p50 = round(percentile(handoff_ms, 50), 3) \
        if handoff_ms else None
    rp_p50 = round(percentile(reprefill_ms, 50), 3) \
        if reprefill_ms else None
    # Noise floor: within-population spread of each timed claim's
    # inputs (per-round agg tok/s per arm, the two cost lanes) as a
    # fraction of its median — when the box spreads one population
    # wider than the inter-arm margins, the orderings attest nothing.
    noise_pct = 0.0
    for pop in (da["round_tok_s"], ma["round_tok_s"],
                handoff_ms, reprefill_ms):
        if len(pop) >= 3 and percentile(pop, 50):
            noise_pct = max(noise_pct, round(
                100.0 * (max(pop) - min(pop))
                / percentile(pop, 50), 2))
    noisy = noise_pct > 25.0
    # Violations-only recompile map (the summary column flags any
    # truthy entry): a clean run commits an EMPTY dict.
    recompiled = {
        arm: {rid: n for rid, n in
              out[arm]["row"]["steady_recompiles"].items() if n}
        for arm in out}
    recompiled = {arm: v for arm, v in recompiled.items() if v}
    row = {
        "prompt_tokens": prompt_len,
        "new_tokens": new,
        "disagg_fleet": da["row"],
        "mono_fleet": ma["row"],
        "ttft_p99_vs_mono": round(d99 / m99, 3)
        if d99 and m99 else None,
        "agg_tok_ratio": round(d_agg / m_agg, 3)
        if d_agg and m_agg else None,
        "handoff_ms_p50": ho_p50,
        "re_prefill_ms_p50": rp_p50,
        "handoff_vs_re_prefill": round(ho_p50 / rp_p50, 3)
        if ho_p50 and rp_p50 else None,
        "steady_recompiles": recompiled,
        "noise_pct": noise_pct,
        **({"noisy_box": True} if noisy else {}),
        "exact": exact,
    }
    print(f"# disagg: ttft p99 {d99} ms (1 prefill + 2 decode) vs "
          f"{m99} ms (3 mono) = {row['ttft_p99_vs_mono']}x, "
          f"agg tok/s ratio {row['agg_tok_ratio']}, handoff p50 "
          f"{ho_p50} ms vs re-prefill {rp_p50} ms "
          f"({da['row']['handoffs']} handoffs, "
          f"{da['row']['disagg_prefill_failed']} stage-1 failures; "
          f"noise {noise_pct}%), exact={exact}", file=sys.stderr)
    return {"disagg": row}


def bench_recorder_overhead(model, variables, model_name: str,
                            vocab: int, shapes, *, n_slots: int,
                            n_short: int, n_long: int,
                            requests: int, queue_depth: int):
    """Flight-recorder overhead A/B: the SAME greedy mix with the
    recorder ON (``--profile-every 100 --profile-steps 4``: periodic
    jax.profiler windows + background attribution,
    serving/profiling.py) vs OFF (the default), through the
    drift-robust alternating harness (:func:`_overhead_ab`).
    Asserts the recording tax stays under the same ~3% agg tok/s
    contract as the telemetry layer.  Per-window cost on the cpu
    smoke is ~0.3s of BACKGROUND CPU (async stop/export/parse — the
    engine thread pays a thread spawn), so the CADENCE is the
    budget: every=100 models the production amortization story
    (a window every ~10s of smoke traffic); an every=30
    hyper-cadence was measured >10% — the knob, not the mechanism,
    carries the overhead.  The profiler library's one-time init is
    paid at server construction (the recorder primes it), outside
    the timed rounds."""
    import tempfile

    with tempfile.TemporaryDirectory() as prof_dir:
        best, noise, servers = _overhead_ab(
            model, variables, model_name, vocab, shapes,
            arm_kwargs={"on": dict(profile_dir=prof_dir,
                                   profile_every=100,
                                   profile_steps=4),
                        "off": {}},
            n_slots=n_slots, n_short=n_short, n_long=n_long,
            requests=requests, queue_depth=queue_depth,
            label="recorder-overhead",
            # One extra alternation vs the telemetry leg: the
            # recorder's per-window cost is lumpy (a window fires in
            # some rounds and not others), so a noisy round skewing
            # an arm's score is likelier here — observed a 10.9%
            # and then a 19.98% reading on a box whose same-build
            # arms spread ±5% within one run, against 1.9% on the
            # run before; the paired-round median + noise flag
            # exist because of exactly this leg.
            rounds=5)
        if not best:
            return {}
        rec = servers["on"].recorder
        windows, analyzed = rec.windows_total, rec.windows_analyzed
    row = _overhead_row(best, noise)
    print(f"# recorder overhead: on={best['on']} off={best['off']} "
          f"tok/s ({windows} windows, {analyzed} analyzed) -> "
          f"{row['overhead_pct']}% (noise {noise['noise_pct']}%)",
          file=sys.stderr)
    return {"recorder_overhead": {
        **row, "windows": windows, "windows_analyzed": analyzed,
    }}


def bench_meshed(model, variables, model_name: str, vocab: int,
                 shapes, *, n_slots: int, n_short: int, n_long: int,
                 requests: int):
    """MESHED leg: the same mixed greedy/sampled load against a tp=1
    and a tp=4 engine AT EQUAL TOTAL KV BUDGET (same slot count and
    model — tp shards the same pool over more devices, it never
    grows it), on forced host devices.

    CRITERION — correctness and recompile behavior, NOT speedup: a
    host-platform CPU "mesh" is one physical CPU pretending to be N
    devices, so collectives are memcpy through shared memory and the
    per-device compute shrinkage buys nothing (the devices share the
    same cores).  What this leg pins is (a) the tp=4 arm answers
    TOKEN-IDENTICALLY to the tp=1 arm (the exact-layout contract
    under real concurrent load), (b) ZERO compile-cache misses during
    the timed arm (mesh shapes warm like any other program key), and
    (c) the per-step device-second inflation tp=4/tp=1 — the
    COLLECTIVE-TIME SHARE estimate, derived from the engine's
    last_step_device_s counters: on a host mesh the extra device
    wall per step is collectives + SPMD partition overhead, the
    number a real-hardware deployment would watch shrink as ICI
    replaces memcpy.  Speedup claims belong to real multi-chip runs.

    The FLIGHT RECORDER runs during both timed arms (same config, so
    the A/B stays fair) and its trace-true ``collective_share`` is
    recorded as ``collective_share_profiled`` next to the host-mesh
    inflation estimate — the ROADMAP item 1c residual.  On the host
    mesh the profiled share is ~0 by construction (collectives are
    memcpy, and XLA:CPU runtime events rarely spell them); on real
    hardware it is the number the estimate only approximates.
    """
    import jax as _jax

    from polyaxon_tpu.serving import ModelServer, make_server

    if len(_jax.devices()) < 4:
        print("# meshed leg skipped: needs >= 4 devices (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "for the cpu-smoke arm)", file=sys.stderr)
        return {"meshed_skipped": "needs >= 4 devices"}

    import shutil
    import tempfile

    import numpy as np

    arms = {}
    parity = {}
    rng = np.random.RandomState(11)
    p_len, new = shapes["short"]
    parity_greedy = rng.randint(0, vocab, size=p_len).tolist()
    parity_sampled = rng.randint(0, vocab, size=p_len).tolist()
    prof_root = tempfile.mkdtemp(prefix="ptpu_meshed_prof_")
    try:
        for tp in (1, 4):
            ms = ModelServer(model, variables, model_name=model_name,
                             max_batch=n_slots, batching="continuous",
                             n_slots=n_slots,
                             queue_depth=4 * (n_short + n_long),
                             mesh=f"tp={tp}",
                             # Flight recorder on BOTH arms (fair A/B):
                             # trace-true collective share beside the
                             # host-mesh inflation estimate.
                             profile_dir=os.path.join(prof_root,
                                                      f"tp{tp}"),
                             profile_every=150, profile_steps=4)
            srv = make_server("127.0.0.1", 0, ms)
            thread = threading.Thread(target=srv.serve_forever,
                                      daemon=True)
            thread.start()
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            try:
                warm_rng = np.random.RandomState(1)
                for cls in ("short", "long"):
                    wp, wn = shapes[cls]
                    warm = warm_rng.randint(0, vocab, size=wp).tolist()
                    _post(base, {"prompt": warm, "max_new_tokens": wn},
                          timeout=900)
                    _post(base, {"prompt": warm, "max_new_tokens": wn,
                                 "temperature": 0.9, "top_k": 64,
                                 "top_p": 0.95, "seed": 1}, timeout=900)
                pre = json.loads(urllib.request.urlopen(
                    base + "/info", timeout=30).read())
                # Warm-up dispatches can open a recorder window of
                # their own; only a window opened AFTER this point
                # may stand in for the timed arm's attribution.
                pre_windows = ms.recorder.windows_total
                lats, wall, errors = run_mixed_load(
                    base, n_short=n_short, n_long=n_long,
                    requests=requests, shapes=shapes, vocab=vocab,
                    sampled_mix=True)
                if errors:
                    print(f"# meshed tp={tp} errors: {errors[:3]}",
                          file=sys.stderr)
                    return {}
                info = json.loads(urllib.request.urlopen(
                    base + "/info", timeout=30).read())
                total_toks = (len(lats["short"]) * shapes["short"][1]
                              + len(lats["long"]) * shapes["long"][1])
                steps = info.get("decode_steps_total", 0) \
                    - pre.get("decode_steps_total", 0)
                dev_s = info.get("step_device_seconds_total", 0.0) \
                    - pre.get("step_device_seconds_total", 0.0)
                arms[tp] = {
                    "tp": tp,
                    "agg_tok_per_sec": round(total_toks / wall, 1),
                    "short_p50_ms": pct_ms(lats["short"], 50),
                    "long_p50_ms": pct_ms(lats["long"], 50),
                    "decode_steps": steps,
                    "device_s_per_step":
                        round(dev_s / max(1, steps), 6),
                    "compile_misses_timed":
                        info.get("compile_cache_misses", 0)
                        - pre.get("compile_cache_misses", 0),
                }
                # Profiler-true attribution for this arm (flight
                # recorder).  Only a window OPENED during the timed
                # load counts — the first analyzed window can be a
                # warm-up one, whose shares describe the wrong
                # traffic; the last analysis may still be in flight,
                # so wait briefly for a timed window to publish.
                latest = None
                deadline = time.perf_counter() + 15
                while time.perf_counter() < deadline:
                    cand = ms.recorder.latest()
                    if cand is not None \
                            and cand["window"] > pre_windows:
                        latest = cand
                        break
                    time.sleep(0.2)
                if latest is not None:
                    arms[tp]["collective_share_profiled"] = \
                        latest["collective_share"]
                    arms[tp]["device_busy_profiled"] = \
                        latest["device_busy_share"]
                    arms[tp]["host_gap_profiled"] = \
                        latest["host_gap_share"]
                    arms[tp]["profiled_windows"] = \
                        ms.recorder.windows_analyzed
                # Token-parity probes (fixed seeds): both arms must
                # answer bitwise-identically — the exact-layout contract
                # observed at the HTTP surface.
                parity[tp] = [
                    _post(base, {"prompt": parity_greedy,
                                 "max_new_tokens": new})["new_tokens"],
                    _post(base, {"prompt": parity_sampled,
                                 "max_new_tokens": new,
                                 "temperature": 0.9, "top_k": 64,
                                 "seed": 7})["new_tokens"],
                ]
            finally:
                srv.shutdown()
                srv.server_close()
                ms.close()
    finally:
        # Two arms' xprof sessions are MBs each; never
        # leave them accumulating under /tmp.
        shutil.rmtree(prof_root, ignore_errors=True)
    d1 = arms[1]["device_s_per_step"]
    d4 = arms[4]["device_s_per_step"]
    out = {
        "criterion": "correctness+recompiles (host-device mesh "
                     "measures no speedup)",
        "arms": [arms[1], arms[4]],
        "tokens_equal": parity[1] == parity[4],
        "compile_misses_timed": arms[1]["compile_misses_timed"]
        + arms[4]["compile_misses_timed"],
        "agg_ratio_tp4_vs_tp1": round(
            arms[4]["agg_tok_per_sec"]
            / max(1e-9, arms[1]["agg_tok_per_sec"]), 3),
        # Collective-time share of the tp=4 step's device wall,
        # derived from last_step_device_s (the host-mesh inflation
        # ESTIMATE; see docstring).
        "collective_share_tp4": round(max(0.0, 1 - d1 / d4), 4)
        if d4 > 0 else None,
        # ... and the flight recorder's trace-TRUE share for the
        # same arm (None when no window was analyzed in time).
        "collective_share_profiled_tp4":
            arms[4].get("collective_share_profiled"),
    }
    print(f"# meshed: tp4/tp1 agg {out['agg_ratio_tp4_vs_tp1']}x, "
          f"tokens_equal={out['tokens_equal']}, timed misses "
          f"{out['compile_misses_timed']}, collective share "
          f"{out['collective_share_tp4']} "
          f"(profiled {out['collective_share_profiled_tp4']})",
          file=sys.stderr)
    return {"meshed": out}


def bench_prefix_cache(model, variables, model_name: str, vocab: int):
    """Prefix-cache A/B: a LONG registered system prompt + a short
    user suffix.  The warm timed request repeats a prompt the cache
    has seen (the session-repeat case — first warm request extended
    and stored it), so the latency gap is the whole prefill cost
    saved per request; exactness vs the cold response is asserted."""
    import numpy as np

    from polyaxon_tpu.serving import ModelServer, make_server

    sys_len, user_len, new = 512, 16, 32
    max_pos = getattr(getattr(model, "cfg", None), "max_position",
                      None) or 10**9
    if sys_len + user_len + new >= max_pos:
        sys_len = max(8, max_pos - user_len - new - 1)
    rng = np.random.RandomState(3)
    system = rng.randint(0, vocab, size=sys_len).tolist()
    prompt = system + rng.randint(0, vocab, size=user_len).tolist()

    ms = ModelServer(model, variables, model_name=model_name,
                     max_batch=1)
    srv = make_server("127.0.0.1", 0, ms)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    body = {"prompt": prompt, "max_new_tokens": new}

    def _median_latency(reps=5):
        # median-of-N: single-shot sub-10ms latencies are noise-bound
        # on the CPU smoke config (observed a flipped A/B once).
        # Times the SAME body the compile-warm posts use.
        times = []
        last = None
        for _ in range(reps):
            t0 = time.perf_counter()
            last = _post(base, body)
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2], last

    try:
        _post(base, body, timeout=900)  # compile warm (cold program)
        cold_s, cold = _median_latency()
        req = urllib.request.Request(
            base + "/prefill",
            data=json.dumps({"prompt": system}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=900) as r:
            r.read()
        _post(base, body, timeout=900)  # compile warm (split program)
        warm_s, warm = _median_latency()
        assert warm["new_tokens"] == cold["new_tokens"]  # exactness
        return {
            "prefix_system_len": sys_len,
            "prefix_cold_ms": round(1e3 * cold_s, 1),
            "prefix_warm_ms": round(1e3 * warm_s, 1),
            "prefix_speedup": round(cold_s / warm_s, 3),
        }
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default=None,
                        help="default: gpt2-medium on TPU, gpt2-mini "
                             "smoke otherwise (gpt2-tiny for a "
                             "fast functional check)")
    parser.add_argument("--short-clients", type=int, default=12)
    parser.add_argument("--long-clients", type=int, default=4)
    parser.add_argument("--requests", type=int, default=6)
    parser.add_argument("--sanitize", action="store_true",
                        help="Run the load A/B with the lock-order "
                             "sanitizer wrapping the serving locks "
                             "(analysis/locksan.py). OFF by default: "
                             "bench rows are measured without "
                             "sanitizers; a --sanitize row is a "
                             "correctness check, not a baseline.")
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()

    jax, backend = B.init_backend(args.cpu)
    model = args.model or ("gpt2-medium" if backend == "tpu"
                           else "gpt2-mini")
    r = bench_serving_load(jax, model, backend,
                           n_short=args.short_clients,
                           n_long=args.long_clients,
                           requests=args.requests,
                           sanitize=args.sanitize)
    row = {"bench": "serving-load", "ts": time.time(),
           **({"regime": "cpu-smoke"} if backend != "tpu" else {}),
           **({"sanitize": True} if args.sanitize else {}),
           **r}
    if args.sanitize:
        print("# sanitize run: lock-order sanitizer was ON — row is "
              "a correctness check, not a perf baseline",
              file=sys.stderr)
    # A mode that errored out is missing from load[]/load_sampled[]/
    # load_spec[]: mark the row partial, so that it is never read as
    # a complete one without the headline A/B measurements.
    if len(r.get("load", [])) < 3 or len(r.get("load_sampled", [])) < 3 \
            or len(r.get("load_spec", [])) < 3 \
            or "telemetry_overhead" not in r \
            or "recorder_overhead" not in r \
            or "debug_overhead" not in r \
            or "forensics_overhead" not in r \
            or "faults_overhead" not in r \
            or "chaos" not in r \
            or "fleet" not in r \
            or "fleet_observability" not in r \
            or "overload" not in r \
            or "longtail" not in r \
            or "lazy_longtail" not in r \
            or "prefix_spill" not in r \
            or "fleet_prefix" not in r \
            or "disagg" not in r \
            or ("meshed" not in r and "meshed_skipped" not in r):
        row["partial"] = True
    print(json.dumps(row))
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")
    # The telemetry overhead CONTRACT (docs/DESIGN.md), asserted in
    # the summary AFTER the row is persisted: a telemetry regression
    # (locking on the hot path, unbounded ring, IO in a span) fails
    # the bench run — but a noisy trip never discards the legs'
    # measurements, which are already on disk above.
    # One check per armed layer: telemetry, flight recorder, debug,
    # and the fault-probe sites all ride the same contract.  A row
    # the harness flagged ``noisy_box`` (same-arm round-to-round
    # spread exceeded the contract band) is committed HONESTLY
    # LABELED instead of failing the run — on a drifting box the
    # measurement attests nothing either way, and failing it would
    # just invite a lucky re-roll.
    for leg, what in (("telemetry_overhead", "telemetry-on"),
                      ("recorder_overhead", "flight-recorder"),
                      ("debug_overhead", "debug-layer"),
                      ("forensics_overhead", "forensics-layer"),
                      ("faults_overhead", "fault-probe")):
        sub = r.get(leg, {})
        ov = sub.get("overhead_pct")
        if ov is None:
            # The leg errored out (row already marked partial
            # above) — fail the run, but say what actually happened: the overhead was never
            # MEASURED, which is not the same as exceeding the
            # contract.  Explicit raise, not assert: python -O must
            # not strip the contract check.
            raise SystemExit(
                f"{leg} leg missing from this run (request errors — "
                f"see stderr above); row marked partial")
        if ov > OVERHEAD_CONTRACT_PCT:
            if sub.get("noisy_box"):
                print(f"# {what} overhead {ov}% is above the "
                      f"{OVERHEAD_CONTRACT_PCT}% contract but the "
                      f"box's own noise floor is "
                      f"{sub.get('noise_pct')}% — row committed "
                      f"with noisy_box, not failed", file=sys.stderr)
                continue
            raise SystemExit(
                f"{what} overhead {ov}% exceeds the "
                f"~{OVERHEAD_CONTRACT_PCT}% agg tok/s contract "
                f"(see the {leg} field of the row just written)")
    # The chaos soak's crash-only liveness contract, checked AFTER
    # the row is persisted (the evidence survives the failure):
    # every caller terminal, nothing leaked, breaker not wedged.
    ch = r.get("chaos")
    if ch is None:
        raise SystemExit(
            "chaos soak leg missing from this run (see stderr "
            "above); row marked partial")
    violations = {k: ch[k] for k in ("hung", "leaked_slots",
                                     "leaked_pages",
                                     "breaker_wedged")
                  if ch.get(k)}
    if violations:
        raise SystemExit(
            f"chaos soak violated the crash-only contract: "
            f"{violations} (full evidence in the chaos field of "
            f"the row just written)")
    # The FLEET chaos soak's router-tier contract, same post-persist
    # discipline: zero hung, zero survivor token mismatches, retries
    # under budget, hedges cancel their losers, zero recompiles on
    # surviving replicas, killed replica re-admitted.
    fl = r.get("fleet")
    if fl is None:
        raise SystemExit(
            "fleet chaos leg missing from this run (see stderr "
            "above); row marked partial")
    fleet_violations = {k: fl[k] for k in ("hung", "mismatch")
                        if fl.get(k)}
    if not fl.get("retry_under_budget"):
        fleet_violations["retry_under_budget"] = False
    if not fl.get("hedges_cancel_losers"):
        fleet_violations["hedges_cancel_losers"] = False
    if any(fl.get("survivor_recompiles", {}).values()):
        fleet_violations["survivor_recompiles"] = \
            fl["survivor_recompiles"]
    if not fl.get("killed_replica_readmitted"):
        fleet_violations["killed_replica_readmitted"] = False
    if not fl.get("slo_burn_consistent"):
        # The router's own SLO accounting disagreed with bench-side
        # math — the burn gauges are the thing this leg attests.
        fleet_violations["slo_burn_consistent"] = False
    if fleet_violations:
        raise SystemExit(
            f"fleet chaos soak violated the router-tier contract: "
            f"{fleet_violations} (full evidence in the fleet field "
            f"of the row just written)")
    # The FLEET-OBSERVABILITY leg: same post-persist discipline as
    # the other overhead legs (<=3% contract, noisy_box-aware), plus
    # its own burn-gauge/bench-math and federation-liveness checks.
    fo = r.get("fleet_observability")
    if fo is None:
        raise SystemExit(
            "fleet_observability leg missing from this run (see "
            "stderr above); row marked partial")
    ov = fo.get("overhead_pct")
    if ov is not None and ov > OVERHEAD_CONTRACT_PCT:
        if fo.get("noisy_box"):
            print(f"# fleet-observability overhead {ov}% is above "
                  f"the {OVERHEAD_CONTRACT_PCT}% contract but the "
                  f"box's own noise floor is {fo.get('noise_pct')}% "
                  f"— row committed with noisy_box, not failed",
                  file=sys.stderr)
        else:
            raise SystemExit(
                f"fleet-observability overhead {ov}% exceeds the "
                f"~{OVERHEAD_CONTRACT_PCT}% agg tok/s contract "
                f"(see the fleet_observability field of the row "
                f"just written)")
    fo_violations = {}
    if not fo.get("slo_burn_consistent"):
        fo_violations["slo_burn_consistent"] = False
    if not fo.get("federation_scrapes"):
        fo_violations["federation_scrapes"] = 0
    if fo.get("federation_scrape_errors"):
        fo_violations["federation_scrape_errors"] = \
            fo["federation_scrape_errors"]
    if fo_violations:
        raise SystemExit(
            f"fleet_observability leg violated its contract: "
            f"{fo_violations} (full evidence in the "
            f"fleet_observability field of the row just written)")
    # The FLEET-PREFIX leg (PR 16): same post-persist discipline.
    # Hard claims: the fleet arm's through-restart hit rate strictly
    # above the per-replica arm's (the migration tier's whole point),
    # bitwise-identical greedy streams across arms (wire fetch must
    # not change a token), zero steady-state recompiles with the
    # fetch path armed.  The TTFT ordering (local hit <= wire fetch
    # <= re-prefill) is noise-bound on a drifting box, so it rides
    # the same noisy_box honesty valve as the overhead legs.
    fp = r.get("fleet_prefix")
    if fp is None:
        raise SystemExit(
            "fleet_prefix leg missing from this run (see stderr "
            "above); row marked partial")
    fp_violations = {}
    if not fp.get("exact"):
        fp_violations["exact"] = False
    if fp["fleet"]["restart_hit_rate"] \
            <= fp["per_replica"]["restart_hit_rate"]:
        fp_violations["restart_hit_rate"] = {
            "fleet": fp["fleet"]["restart_hit_rate"],
            "per_replica": fp["per_replica"]["restart_hit_rate"]}
    if any(fp["fleet"]["steady_recompiles"].values()):
        fp_violations["steady_recompiles"] = \
            fp["fleet"]["steady_recompiles"]
    if not fp["fleet"]["wire_fetches"]:
        # Zero wire fetches means the lane under test never ran —
        # the hit-rate delta would be attesting only the handoff.
        fp_violations["wire_fetches"] = 0
    if not fp.get("wire_between_bounds"):
        if fp.get("noisy_box"):
            print(f"# fleet-prefix: TTFT ordering hit<=wire<="
                  f"re-prefill not resolved on this box (noise "
                  f"{fp.get('noise_pct')}%) — row committed with "
                  f"noisy_box, not failed", file=sys.stderr)
        else:
            fp_violations["wire_between_bounds"] = {
                "hit": fp.get("ttft_local_hit_p50_ms"),
                "wire": fp.get("ttft_wire_fetch_p50_ms"),
                "re_prefill": fp.get("ttft_re_prefill_p50_ms")}
    if fp_violations:
        raise SystemExit(
            f"fleet_prefix leg violated its contract: "
            f"{fp_violations} (full evidence in the fleet_prefix "
            f"field of the row just written)")
    # The DISAGG leg (PR 17): same post-persist discipline.  Hard
    # claims: bitwise-identical greedy streams across arms (the
    # split must not change a token), zero steady-state recompiles
    # on BOTH tiers, and the handoff lane actually ran (zero
    # handoffs means the leg attested nothing).  The TTFT-p99 win,
    # the agg-tok/s band, and the handoff-cheaper-than-re-prefill
    # ordering are noise-bound on a drifting box, so they ride the
    # noisy_box honesty valve.
    dg = r.get("disagg")
    if dg is None:
        raise SystemExit(
            "disagg leg missing from this run (see stderr above); "
            "row marked partial")
    dg_violations = {}
    if not dg.get("exact"):
        dg_violations["exact"] = False
    if dg.get("steady_recompiles"):
        dg_violations["steady_recompiles"] = \
            dg["steady_recompiles"]
    if not dg["disagg_fleet"]["handoffs"]:
        dg_violations["handoffs"] = 0
    soft = {}
    t99 = dg.get("ttft_p99_vs_mono")
    if t99 is None or t99 >= 1.0:
        soft["ttft_p99_vs_mono"] = t99
    agg = dg.get("agg_tok_ratio")
    if agg is None or agg < 0.9:
        # "in band": the decode tier is 2/3 of the fleet, so agg
        # throughput within 10% of monolithic counts as held.
        soft["agg_tok_ratio"] = agg
    ho = dg.get("handoff_vs_re_prefill")
    if ho is None or ho >= 1.0:
        soft["handoff_vs_re_prefill"] = ho
    if soft:
        if dg.get("noisy_box"):
            print(f"# disagg: perf orderings {soft} not resolved "
                  f"on this box (noise {dg.get('noise_pct')}%) — "
                  f"row committed with noisy_box, not failed",
                  file=sys.stderr)
        else:
            dg_violations.update(soft)
    if dg_violations:
        raise SystemExit(
            f"disagg leg violated its contract: {dg_violations} "
            f"(full evidence in the disagg field of the row just "
            f"written)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
