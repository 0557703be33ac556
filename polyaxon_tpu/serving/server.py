"""Native model server: the zoo's decode stack behind HTTP.

The reference's serving story is `V1Service` — it schedules an opaque
user container and port-forwards to it (SURVEY.md §2.4); the model
server inside is the user's problem.  Here the framework owns the
decode loop, so it ships the server too: one process, stdlib HTTP
(same no-dependency stance as the control plane), jit-compiled
generate with a shape-bucketed compile cache.

Endpoints:

- ``GET  /healthz``  -> ``{"status": "ok", ...}`` (readiness; also the
  operator's gang-health convention)
- ``GET  /info``     -> model name, config summary, quantization flags
- ``GET  /metrics``  -> Prometheus text: counters, phase summaries,
  and the latency histograms (telemetry.py)
- ``GET  /trace``    -> Chrome trace-event JSON of the telemetry ring
  (request lifecycle spans + the engine step timeline) — load it in
  Perfetto or chrome://tracing
- ``POST /profile/start`` / ``POST /profile/stop`` -> guarded,
  single-flight ``jax.profiler`` trace into the server's
  ``profile_dir`` (400 when started without one)
- ``POST /prefill``  -> register a prompt (prefix) in the PREFIX
  CACHE: its KV prefill is stored on device (LRU, ``prefix_cache``
  entries) and later /generate requests whose prompt starts with it
  skip that prefill — the system-prompt serving win.  Hits extend and
  re-store, so growing sessions stay warm.  Exact by the
  prefill/continue split contract (models/generate.py).
- ``POST /generate`` -> ``{"prompt": [ids] | [[ids], ...],
  "max_new_tokens": N, "temperature": t, "top_k": k, "top_p": p,
  "eos_id": e, "num_beams": B, "speculative": bool, "spec_k": K,
  "seed": s, "prefill_chunk": C}`` -> tokens + timing (speculative
  needs a server-side draft model; greedy by default, and with
  temperature/top_k/top_p it runs rejection speculative sampling —
  exact target-distribution samples for any draft)

Shape discipline: each distinct (batch, prompt_len, max_new_tokens,
decode-mode) compiles once and is cached.  Prompts are NOT padded:
the zoo's decode path has no attention-mask input, so left-padding
would let real tokens attend to pad positions (silently wrong
output).  Clients with ragged traffic should bucket prompt lengths
themselves; rows in one request must share a length (the continuous-
batching engine mixes LENGTHS freely across requests — only rows
within one request body share a shape).

Concurrency — the CONTINUOUS-BATCHING engine (engine.py, default):
greedy AND sampled (non-beam, non-speculative) requests become
per-row decode streams over a fixed pool of decode slots; admission
happens at decode-step boundaries into slots freed by eos/budget
eviction, long prompts prefill in chunks interleaved between decode
steps, and the front-end sheds load with 429 + Retry-After once the
bounded admission queue fills.  Engine responses are exact vs solo
execution: greedy rows never interact (eos-frozen rows pad to
budget), and sampled rows draw through the POSITION-KEYED RNG
contract (models/generate.generate_positional — token i's key is
fold_in(fold_in(PRNGKey(seed), row), i), a function of the request
alone), so co-tenancy never changes a sampled response.
``batching="coalesce"`` selects the legacy whole-request coalescer
(legacy.py — the measured baseline; sampled requests decode solo
there), ``batching="off"`` serializes every request (the A/B floor).
SPECULATIVE decoder-only requests default to the engine too when the
server owns a draft model: spec slots draft/verify/commit a variable
accepted prefix per round under the same position-keyed RNG contract
(engine output == ``generate_speculative``'s seed mode), so a single
speculative client no longer holds the device lock for a whole
decode.  Beam requests always take the solo path (the per-beam cache
schedule would change their outputs if merged); requests that fall
back to solo are counted per kind in /info's routing report.
"""

from __future__ import annotations

import collections
import contextlib
import json
import select
import socket
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

import numpy as np

from ._lru import lru_get
from .debug import (RequestHistory, StallWatchdog, events_to_dicts,
                    new_request_id, sanitize_request_id)
from .engine import DecodeEngine
from .faults import FaultPlan, SocketReset
from .forensics import ForensicsCore, compute_ledger
from .legacy import RequestCoalescer
from .paged import WirePayloadError, pack_spilled, unpack_spilled
from .radix import RadixPrefixIndex
from .recovery import EngineSupervisor
from .scheduler import (DeadlineExceeded, PRIORITIES,
                        PoisonedRequest, QueueFullError,
                        RequestCancelled, SamplingSpec,
                        SchedulerPolicy, ShedError)
from .telemetry import (ProfileSession, Telemetry,
                        render_compile_cache, render_histogram)

BATCHING_MODES = ("continuous", "coalesce", "off")

# Disaggregated-serving roles (docs/SERVING.md "Disaggregated
# serving"): "both" = monolithic (the default, byte-for-byte today's
# behavior), "prefill" = prompt prefill + wire export only (rejects
# /generate), "decode" = full serving expected to ADMIT handed-off
# prefills over the wire-fetch lane.
ROLES = ("prefill", "decode", "both")

# engine._latent_stats: on /info and /metrics where the model has
# latent attention layers.
LATENT_COUNTERS = ("latent_pairs_expanded_total",
                   "latent_pairs_absorbed_total",
                   "latent_rows_expanded_total")


class _PagedPrefix:
    """Radix payload for a PAGE-BACKED prefix entry: the stored
    prompt's KV lives in the engine's page pool (one reference per
    page held by this entry — shared pages referenced, never copied),
    not in a private contiguous cache.  ``logits`` are the last-
    position prefill logits (what a full-length hit seeds decode
    with)."""

    __slots__ = ("pages", "n_tokens", "logits")

    def __init__(self, pages, n_tokens: int, logits):
        self.pages = tuple(int(p) for p in pages)
        self.n_tokens = int(n_tokens)
        self.logits = logits


class _SpilledPrefix:
    """Radix payload for a HOST-TIER prefix entry (the spill tier,
    ``--kv-host-spill-bytes``): the stored prompt's KV lives in host
    RAM — one np array per paged cache leaf, gathered by the
    sanctioned ``PagedSlotKVManager.spill_pages`` helper when page
    pressure evicted the entry from the device pool — instead of
    being dropped.  A hit re-materializes via ``device_put``
    (``manager.rematerialize``) and opportunistically PROMOTES back
    to device pages.  Host buffers reference no device state, so
    spilled entries SURVIVE a crash-recovery pool rebuild (the epoch
    contract extension, docs/DESIGN.md)."""

    __slots__ = ("leaves", "n_tokens", "logits", "nbytes")

    def __init__(self, leaves, n_tokens: int, logits):
        self.leaves = list(leaves)
        self.n_tokens = int(n_tokens)
        self.logits = logits            # host np copy
        self.nbytes = int(sum(a.nbytes for a in leaves
                              if a is not None)) \
            + (int(logits.nbytes) if hasattr(logits, "nbytes") else 0)


PrefixHit = collections.namedtuple(
    "PrefixHit", ["p_cached", "logits", "cache", "pins", "source"],
    defaults=("device",))
"""One prefix-cache lookup result: ``p_cached`` tokens of stored
prefill, the stored last-position ``logits``, a CONTIGUOUS ``cache``
holding them (materialized from pool pages in paged mode), and
``pins`` — still-pinned FULL-page ids the engine path maps read-only
into the admitted slot's table (empty for legacy entries).  The
caller owns the pins until ``engine.submit(shared_pages=pins)``
returns; every other outcome must unpin them.  ``source`` records
which tier served the hit (``"device"`` or ``"host"``) so responses
and history records can attribute the prefix's provenance."""


class PrefixFetchPolicy:
    """The wire-fetch cost curve: fetch a spilled prefix from a
    holder replica only when the expected wire cost beats the local
    re-prefill cost.  A spilled LOCAL hit lands at ~0.26x of a
    re-prefill miss (the PR 12 measurement — ``remat_ratio``); a WIRE
    hit pays that same re-materialization PLUS one round trip and the
    body transfer, so the curve is::

        rtt + nbytes / wire_bytes_per_s + remat_ratio * reprefill
            < reprefill,   where reprefill = n_tokens / prefill_tok_per_s

    plus two hard gates — a minimum match length (tiny prefixes
    re-prefill faster than any network hop) and a byte ceiling (one
    giant payload must not monopolize the fetch path).  Pure and
    deterministic, so the thresholds unit-test without a fleet.  The
    client evaluates it twice: once before dialing (``nbytes=0`` —
    only the token gate can veto yet) and again on the holder's
    Content-Length BEFORE reading the body, so a policy veto costs
    headers, never the transfer."""

    def __init__(self, *, min_tokens: int = 16,
                 max_bytes: int = 1 << 30,
                 wire_bytes_per_s: float = 1e9,
                 rtt_s: float = 2e-3,
                 prefill_tok_per_s: float = 4e3,
                 remat_ratio: float = 0.26):
        if min_tokens < 1:
            raise ValueError(
                f"min_tokens must be >= 1; got {min_tokens}")
        if max_bytes < 1:
            raise ValueError(
                f"max_bytes must be >= 1; got {max_bytes}")
        if wire_bytes_per_s <= 0 or prefill_tok_per_s <= 0:
            raise ValueError(
                "wire_bytes_per_s and prefill_tok_per_s must be > 0")
        if rtt_s < 0 or not 0.0 <= remat_ratio < 1.0:
            raise ValueError(
                "need rtt_s >= 0 and 0 <= remat_ratio < 1")
        self.min_tokens = int(min_tokens)
        self.max_bytes = int(max_bytes)
        self.wire_bytes_per_s = float(wire_bytes_per_s)
        self.rtt_s = float(rtt_s)
        self.prefill_tok_per_s = float(prefill_tok_per_s)
        self.remat_ratio = float(remat_ratio)

    def should_fetch(self, n_tokens: int, nbytes: int, *,
                     wire_bytes_per_s: Optional[float] = None,
                     rtt_s: Optional[float] = None
                     ) -> Tuple[bool, str]:
        """``(ok, reason)`` — ``reason`` is the typed veto (the
        ``prefix_fetch_failed_total{reason=}`` label) or ``"ok"``.

        ``wire_bytes_per_s``/``rtt_s`` override the constructed
        constants for ONE evaluation: the router measures each link
        from completed fetches and handoffs (EWMA) and ships the
        estimates inside the ``prefix_hint``, so the gate runs on
        observed link truth instead of the static defaults whenever
        a measurement exists (ROADMAP item 3's calibration half)."""
        if n_tokens < self.min_tokens:
            return False, "below_min_tokens"
        if nbytes > self.max_bytes:
            return False, "over_max_bytes"
        bw = self.wire_bytes_per_s if wire_bytes_per_s is None \
            or wire_bytes_per_s <= 0 else float(wire_bytes_per_s)
        rtt = self.rtt_s if rtt_s is None or rtt_s < 0 \
            else float(rtt_s)
        reprefill_s = n_tokens / self.prefill_tok_per_s
        wire_s = (rtt + nbytes / bw
                  + self.remat_ratio * reprefill_s)
        if wire_s >= reprefill_s:
            return False, "wire_slower"
        return True, "ok"

    def describe(self) -> Dict[str, Any]:
        return {"min_tokens": self.min_tokens,
                "max_bytes": self.max_bytes,
                "wire_bytes_per_s": self.wire_bytes_per_s,
                "rtt_s": self.rtt_s,
                "prefill_tok_per_s": self.prefill_tok_per_s,
                "remat_ratio": self.remat_ratio}


class PagePins(tuple):
    """Pinned page ids + the pool EPOCH they were pinned under
    (``PagedSlotKVManager.pin`` returns it).  Pins cross thread and
    lock scopes between the lookup and the engine's admission; a
    crash-recovery pool rebuild in between bumps the epoch, which is
    how every consumer (submit, admission, unpin) recognizes the ids
    as dead and drops them BY REFERENCE instead of corrupting the
    fresh refcount accounting."""

    epoch: Optional[int] = None

    def __new__(cls, ids, epoch):
        self = super().__new__(cls, ids)
        self.epoch = epoch
        return self


# The response ``timings`` block and the history record's timeline
# render through the SAME function (docs/DESIGN.md: one source, the
# two surfaces cannot disagree).
_span_dicts = events_to_dicts


# Structural no-drift contract (tests/test_fleet_observability.py):
# EVERY key of engine.stats() must render on the server's /metrics
# under ``ptpu_serving_<key>``, under a rename listed here, or carry
# an explicit exemption reason below — earlier PRs re-pinned this
# counter by counter; the structural walk means a NEW engine counter
# that skips the /metrics surface fails tier-1 instead of shipping
# dark.
ENGINE_STATS_METRIC_RENAMES = {
    "expired_total": "ptpu_serving_deadline_expired_total",
    # The breaker state string renders as the 0/1 open gauge.
    "breaker_state": "ptpu_serving_breaker_open",
    # The per-site dict IS the labeled counter family.
    "faults_injected": "ptpu_serving_faults_injected_total",
    # The acceptance-rate histogram's four stats keys all render
    # through ONE telemetry.render_histogram family.
    "spec_accept_buckets": "ptpu_serving_spec_accept_rate",
    "spec_accept_hist": "ptpu_serving_spec_accept_rate",
    "spec_accept_sum": "ptpu_serving_spec_accept_rate",
    "spec_accept_count": "ptpu_serving_spec_accept_rate",
    # Recompile-sentinel counters (telemetry.render_compile_cache).
    "compile_cache_misses": "ptpu_serving_compile_cache_misses_total",
    "compile_cache_hits": "ptpu_serving_compile_cache_hits_total",
    "compile_cache_evictions":
        "ptpu_serving_compile_cache_evictions_total",
}
ENGINE_STATS_METRIC_EXEMPT = {
    "faults_injected_total":
        "sum of the labeled ptpu_serving_faults_injected_total{site=}"
        " series a scrape can compute",
    "compile_cache_by_kind":
        "per-kind split lives in /info's routing report; the totals "
        "render via render_compile_cache",
    "mesh": "topology dict; renders as ptpu_serving_mesh_devices + "
            "per-axis ptpu_serving_mesh_axis_size{axis=}",
    "kv_pool_shardings":
        "per-leaf placement description (specs and shard shapes), "
        "not a number; /info carries it",
}


def _int_param(v):
    """int() that refuses booleans: int(True) == 1 would silently
    accept {"num_beams": true} / {"prefill_chunk": true}."""
    if isinstance(v, bool):
        raise ValueError("expected an integer, got a boolean")
    return int(v)


def _parse_prompt_rows(req, max_batch: int):
    """Shared /generate + /prefill prompt validation: returns the
    row-wrapped token lists (one shared length, ints-not-bools,
    batch-capped)."""
    if not isinstance(req, dict):
        raise ValueError("request body must be a JSON object")
    rows = req.get("prompt")
    if rows is None:
        raise ValueError("missing 'prompt'")
    if not isinstance(rows, list):
        raise ValueError("'prompt' must be a list of token ids "
                         "or a list of rows")
    if rows and not isinstance(rows[0], list):
        rows = [rows]
    if not rows or not rows[0]:
        raise ValueError("prompt must contain at least one token")
    if len(rows) > max_batch:
        raise ValueError(f"batch {len(rows)} exceeds max_batch "
                         f"{max_batch}")
    if len({len(r) for r in rows}) != 1:
        # No silent padding: the decode path has no attention
        # mask, so padded positions would be attended to.
        raise ValueError(
            "all prompt rows must share one length (the decode "
            "path has no pad mask; bucket lengths client-side)")
    if any(not all(isinstance(t, int) and not isinstance(t, bool)
                   for t in r) for r in rows):
        # bool is an int subclass: [true, false] must not silently
        # decode as tokens [1, 0].
        raise ValueError("prompt rows must be integer token ids")
    return rows


class FairLock:
    """``threading.Lock`` with FIFO-ish handoff — a turnstile guards
    entry, so a releasing thread that immediately re-acquires (the
    continuous-batching engine's step loop does exactly this, every
    boundary) queues BEHIND threads already waiting instead of
    barging past them.

    CPython locks are not fair: release wakes one waiter, but the
    releasing thread can re-acquire before the waiter is scheduled.
    Handler threads doing device work — a wire-fetch admit
    (rematerialize + promote), a direct ``/prefill``, a solo request
    — sit behind an engine loop that holds/releases the device lock
    back-to-back while decodes run, and measured waits reach
    hundreds of milliseconds per acquisition (~30x the actual device
    work).  The turnstile bounds every waiter to roughly one
    in-flight hold: acquire the door, then the inner lock, release
    the door once inside — a barger must first pass the door the
    oldest waiter still holds."""

    def __init__(self):
        self._door = threading.Lock()
        self._inner = threading.Lock()
        self._waiting = 0

    def acquire(self, blocking: bool = True,
                timeout: float = -1) -> bool:
        if not blocking:
            if not self._door.acquire(False):
                return False
            try:
                return self._inner.acquire(False)
            finally:
                self._door.release()
        # Advisory waiter count (GIL-coarse, no extra lock): the
        # engine's window-fuse decision polls it to drop to
        # single-step granularity while external device work waits.
        self._waiting += 1
        try:
            if timeout is None or timeout < 0:
                with self._door:
                    return self._inner.acquire()
            deadline = time.monotonic() + timeout
            if not self._door.acquire(True, timeout):
                return False
            try:
                rem = max(0.0, deadline - time.monotonic())
                return self._inner.acquire(True, rem)
            finally:
                self._door.release()
        finally:
            self._waiting -= 1

    def waiters(self) -> int:
        """Threads currently blocked in :meth:`acquire` — including
        the engine loop itself when it is between holds; callers
        polling this from OFF-thread contexts only ever see their
        own wait excluded."""
        return self._waiting

    def release(self) -> None:
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()


class ModelServer:
    """Wraps one model + params; owns the compile cache, the lock
    serializing device work, and the continuous-batching engine (see
    module docstring)."""

    def __init__(self, model, variables, *, model_name: str = "model",
                 max_batch: int = 8, batching: Optional[str] = None,
                 coalesce: Optional[bool] = None,
                 n_slots: int = 8, queue_depth: int = 64,
                 prefill_chunk: Optional[int] = None,
                 decode_window: int = 8,
                 kv_paged: bool = False,
                 kv_page_tokens: int = 64,
                 kv_pages: Optional[int] = None,
                 kv_lazy: bool = False,
                 kv_host_spill_bytes: int = 0,
                 prefix_fetch: bool = False,
                 prefix_fetch_policy: Optional[
                     "PrefixFetchPolicy"] = None,
                 prefix_fetch_timeout_s: float = 5.0,
                 role: str = "both",
                 default_priority: str = "interactive",
                 batch_queue_depth: Optional[int] = None,
                 queue_deadline_s: Optional[float] = None,
                 batch_queue_deadline_s: Optional[float] = None,
                 slo_ttft_s: Optional[float] = None,
                 request_timeout_s: Optional[float] = 600.0,
                 prefix_cache: int = 4,
                 draft_model=None, draft_variables=None,
                 weights_cast_bytes: int = 0,
                 spec_k: int = 4,
                 mesh=None,
                 trace_buffer: int = 4096,
                 profile_dir: Optional[str] = None,
                 profile_every: int = 0,
                 profile_steps: int = 8,
                 access_log: bool = False,
                 sanitize: bool = False,
                 sanitize_max_hold_s: Optional[float] = None,
                 sanitize_report: Optional[str] = None,
                 request_history: int = 256,
                 stall_timeout_s: Optional[float] = None,
                 stall_dir: str = ".",
                 stall_queue_factor: float = 4.0,
                 forensics: bool = True,
                 exemplar_k: int = 4,
                 forensics_dir: Optional[str] = None,
                 sentry_window: int = 64,
                 sentry_baseline_windows: int = 4,
                 fault_plan=None,
                 supervise: bool = True,
                 info: Optional[Dict[str, Any]] = None):
        self.model = model
        self.variables = variables
        # FAULT INJECTION (serving/faults.py), disarmed by default:
        # ``fault_plan`` (a FaultPlan, a plan dict, or a JSON path —
        # `ptpu serve --fault-plan f.json`) arms the deterministic
        # seeded chaos harness across the engine's step/admission
        # sites, the prefix store, and the HTTP handler.  Disarmed,
        # every probe site is one attribute check.
        self.faults = FaultPlan.load(fault_plan) \
            if fault_plan is not None else None
        # Telemetry core (telemetry.py): ONE ring + histogram set
        # shared with the engine, so request spans and engine step
        # records land in the same /trace timeline.  trace_buffer=0
        # disables span recording (the bench A/B's "telemetry off"
        # arm); the latency histograms stay live — they are the
        # /metrics surface.
        self.telemetry = Telemetry(
            buffer=trace_buffer,
            exemplar_k=(int(exemplar_k) if forensics else 0))
        # Recompile sentinel (analysis/recompile.py): ONE counter set
        # shared by the server's fused/split program LRU, the
        # engine's prefill programs, and the slot pool's step/insert
        # programs — /metrics' compile_cache_misses_total and /info's
        # compile_cache report both read it, and each miss drops a
        # compile_miss instant on the trace's engine track.
        from ..analysis.recompile import RecompileSentinel

        self.recompile = RecompileSentinel(telemetry=self.telemetry)
        # Lock-order sanitizer (analysis/locksan.py), opt-in via
        # ``sanitize`` (the `ptpu serve --sanitize` flag and the
        # engine/serving tests): wraps every serving lock in a
        # recording proxy that raises on lock-order inversion and
        # (when ``sanitize_max_hold_s`` is set) on device_lock holds
        # past the limit.  Off by default — the bench keeps it off
        # and documents why (benchmarks/bench_serving_load.py).
        self.sanitizer = None
        if sanitize:
            from ..analysis.locksan import LockSanitizer

            self.sanitizer = LockSanitizer(
                max_hold_s={"device_lock": sanitize_max_hold_s}
                if sanitize_max_hold_s is not None else None)
        # Machine-readable dump of the observed acquisition graph
        # (the same dict /info reports), written at close() — the
        # offline half of the static ⊆ runtime lock-graph
        # cross-check (analysis/lockgraph.py).
        self.sanitize_report = sanitize_report
        if sanitize_report is not None and self.sanitizer is None:
            raise ValueError("sanitize_report requires sanitize=True")
        # POST /profile/start|stop (single-flight jax.profiler wrap);
        # None keeps the endpoints disabled — profiling writes device
        # traces to disk, so it must be an explicit operator opt-in.
        self.profiler = ProfileSession(profile_dir) \
            if profile_dir else None
        # Structured one-line-per-request access log (off by default:
        # a busy server must not pay per-request stderr IO unasked).
        self.access_log = bool(access_log)
        self._access_log_file = sys.stderr
        # Batching policy: "continuous" (engine, default), "coalesce"
        # (legacy baseline), "off" (serialize — the A/B floor for
        # benchmarks/bench_serving_load.py).  The old boolean kwarg
        # maps onto the modes it used to select.
        if batching is None:
            batching = ("coalesce" if coalesce else "off") \
                if coalesce is not None else "continuous"
        if batching not in BATCHING_MODES:
            raise ValueError(f"batching must be one of "
                             f"{BATCHING_MODES}; got {batching!r}")
        self.batching = batching
        # Optional speculative-decoding draft: requests opt in with
        # {"speculative": true}; greedy by default (output identical
        # to plain greedy decode), rejection-sampled with temperature
        # (models/generate.generate_speculative).  ``spec_k`` is both
        # the default per-request draft length AND the engine's cap:
        # the spec step program's verify chunk is cap+1 wide for
        # EVERY resident, so the cap bounds the end-of-cache slack
        # engine co-tenants must leave (requests that don't fit, or
        # ask for a bigger k, decode solo — see _note_fallback).
        self.draft_model = draft_model
        self.draft_variables = draft_variables
        # The float32 bytes the serving build rounded once to the
        # compute dtype before handing the trees over
        # (serving/weights.py): 0 where they came as declared.
        self.weights_cast_bytes = int(weights_cast_bytes)
        from ..models.generate import _check_spec_k

        _check_spec_k(spec_k)
        self.spec_k_default = int(spec_k)
        self.model_name = model_name
        self.max_batch = int(max_batch)
        self.extra_info = info or {}
        # Request lifecycle: the default priority class for requests
        # that don't declare one (validated by SchedulerPolicy below
        # even in engine-less modes), the bounded front-end wait cap
        # (None = unbounded — NOT the default: a wedged engine must
        # shed its waiters, never collect HTTP workers forever), and
        # the drain latch (/drain flips it; /healthz reports 503).
        if default_priority not in PRIORITIES:
            raise ValueError(
                f"default_priority must be one of {PRIORITIES}; "
                f"got {default_priority!r}")
        self.default_priority = default_priority
        if request_timeout_s is not None and request_timeout_s <= 0:
            raise ValueError(f"request_timeout_s must be > 0; got "
                             f"{request_timeout_s}")
        self.request_timeout_s = request_timeout_s
        self.draining = False
        self.drain_rejected = 0     # 503s shed at the drain gate
        # Fair handoff (FairLock): the engine's step loop re-acquires
        # this lock at every boundary, and an unfair lock starves
        # handler-thread device work (wire-fetch admits, /prefill,
        # solo requests) behind it for hundreds of ms.
        self._lock = FairLock() if self.sanitizer is None \
            else self.sanitizer.wrap("device_lock", FairLock())
        # LRU-bounded: the key includes client-controlled sampling
        # values (temperature must stay trace-static — the greedy
        # branch is Python-level control flow), so unbounded caching
        # would let varied traffic grow compiled programs without
        # limit.
        from collections import OrderedDict

        self._fns: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._fn_cap = 32
        self.requests = 0
        # Continuous-batching engine: decoder-only models only (a
        # seq2seq cache holds computed cross-attention K/V — its
        # decode loop is a different program the slot engine doesn't
        # speak).  Seq2seq falls back to the seed coalescer so
        # concurrent greedy requests still batch, and self.batching
        # (reported by /info) reflects what actually runs.
        self.engine: Optional[DecodeEngine] = None
        if self.batching == "continuous" and hasattr(model, "encode"):
            self.batching = "coalesce"
        if kv_paged and self.batching != "continuous":
            # Paged KV is the engine's storage discipline — there is
            # nothing to page in the coalesce/off solo paths.
            raise ValueError(
                "kv_paged requires the continuous-batching engine "
                f"(batching={self.batching!r}"
                + (" — seq2seq models fall back to coalesce)"
                   if hasattr(model, "encode") else ")"))
        if kv_lazy and not kv_paged:
            raise ValueError(
                "kv_lazy requires kv_paged (lazy growth is a page-"
                "reservation policy; fixed lanes have no pages)")
        if kv_host_spill_bytes < 0:
            raise ValueError(
                f"kv_host_spill_bytes must be >= 0; got "
                f"{kv_host_spill_bytes}")
        if kv_host_spill_bytes and not kv_paged:
            raise ValueError(
                "kv_host_spill_bytes requires kv_paged (the host "
                "tier spills page-pool payloads; legacy prefix "
                "entries already own independent caches)")
        if prefix_fetch and not (kv_paged and kv_host_spill_bytes):
            raise ValueError(
                "prefix_fetch requires kv_paged AND a host spill "
                "budget (--kv-host-spill-bytes): wire-fetched "
                "payloads are host-tier entries — they enter through "
                "the spill machinery and count against its budget")
        if prefix_fetch_timeout_s <= 0:
            raise ValueError(
                f"prefix_fetch_timeout_s must be > 0; got "
                f"{prefix_fetch_timeout_s}")
        # DISAGGREGATED ROLES (docs/SERVING.md "Disaggregated
        # serving"): "both" is today's monolithic replica,
        # byte-for-byte.  "prefill" runs prompt prefill only — it
        # serves /prefill and the /prefix/* wire lanes and rejects
        # /generate with a typed 400, so no decode stream is ever
        # resident and the whole pool/spill budget backs admit-ready
        # prefixes.  "decode" is a full replica expected to pull
        # handed-off KV over the wire-fetch lane (and to degrade to
        # local re-prefill, counted, when a fetch fails).
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}; "
                             f"got {role!r}")
        if role == "prefill" and not (kv_paged and kv_host_spill_bytes):
            raise ValueError(
                "role='prefill' requires kv_paged AND a host spill "
                "budget (--kv-host-spill-bytes): a prefill tier's "
                "only product is admit-ready KV state served over "
                "the /prefix/fetch wire lane, which packs from the "
                "paged pool and the host tier")
        if role == "decode" and not prefix_fetch:
            raise ValueError(
                "role='decode' requires prefix_fetch: a decode tier "
                "admits handed-off prefills through the wire-fetch "
                "lane (it still re-prefills locally, counted, when "
                "a fetch degrades)")
        self.role = role
        # Serving mesh ("tp=4" / MeshSpec / ServingMesh): shard the
        # slot KV pools over the mesh and place params under
        # NamedSharding (serving/meshed.py — the exact layout, so
        # meshed responses are token-bitwise-identical to unmeshed
        # ones per seed).  Params are placed HERE, before the engine
        # and before _split_fns capture self.variables, so every
        # program — engine steps, prefill, solo fallbacks — runs over
        # the same placed tree.
        self.mesh = None
        if mesh is not None:
            from .meshed import MeshError, ServingMesh

            if self.batching != "continuous":
                # MeshError (a ValueError) so the CLI's clean
                # usage-error surface catches it — the seq2seq
                # fallback above can flip batching AFTER the CLI's
                # own pre-check passed.
                raise MeshError(
                    "mesh requires the continuous-batching engine "
                    f"(batching={self.batching!r}"
                    + (" — seq2seq models fall back to coalesce)"
                       if hasattr(model, "encode") else ")"))
            self.mesh = mesh if isinstance(mesh, ServingMesh) \
                else ServingMesh(mesh)
            self.mesh.validate_model(model, "model", n_slots=n_slots)
            if draft_model is not None:
                self.mesh.validate_model(draft_model, "draft model")
            self.variables = variables = \
                self.mesh.place_params(variables)
            if draft_variables is not None:
                self.draft_variables = draft_variables = \
                    self.mesh.place_params(draft_variables)
        if self.batching == "continuous":
            self.engine = DecodeEngine(
                model, variables,
                policy=SchedulerPolicy(
                    n_slots=n_slots, queue_depth=queue_depth,
                    prefill_chunk=prefill_chunk,
                    decode_window=decode_window,
                    default_priority=default_priority,
                    batch_queue_depth=batch_queue_depth,
                    queue_deadline_s=queue_deadline_s,
                    batch_queue_deadline_s=batch_queue_deadline_s,
                    slo_ttft_s=slo_ttft_s,
                    kv_paged=kv_paged,
                    kv_page_tokens=kv_page_tokens,
                    kv_pages=kv_pages,
                    kv_lazy=kv_lazy,
                    spec_k_cap=self.spec_k_default),
                device_lock=self._lock,
                # Engine streams are single-row; share the server's
                # compile cache so a prompt length prefilled via
                # /prefill and via engine admission compiles once.
                prefill_fns=lambda s, first: self._split_fns(
                    1, s, "pfill" if first else "extend", None),
                # Draft model makes speculative requests engine
                # citizens (spec step program, slots.py).
                draft_model=draft_model,
                draft_variables=draft_variables,
                telemetry=self.telemetry,
                sentinel=self.recompile,
                mesh=self.mesh,
                faults=self.faults)
        self._coalescer = RequestCoalescer(self) \
            if self.batching == "coalesce" else None
        self.coalesced_batches = 0
        self.coalesced_requests = 0
        # /metrics counters.  _stats_lock guards every tally mutated
        # from handler threads (requests/hits/errors/latency/tokens) —
        # NEVER the device lock, so bumping a counter can't queue a
        # finished request behind in-flight device work; reads are
        # unlocked, consistent enough for monotonic counters.
        self._stats_lock = threading.Lock() \
            if self.sanitizer is None \
            else self.sanitizer.wrap("_stats_lock")
        self.errors = 0
        # Requests that fell back to the solo path, keyed by request
        # kind: {"reason": ..., "count": n}.  Surfaced in /info's
        # routing report; the reason is logged ONCE per kind.
        self.solo_fallbacks: Dict[str, Dict[str, Any]] = {}
        self._lat_sum = 0.0
        self._lat_count = 0
        self._tokens_out = 0
        # Per-request phase breakdown (queue -> prefill -> decode)
        # summed across engine AND solo requests: solo requests spend
        # their "queue" phase waiting on the device lock and have no
        # separate prefill phase (it is fused into their program).
        self._queue_s_sum = 0.0
        self._prefill_s_sum = 0.0
        self._decode_s_sum = 0.0
        self._breakdown_count = 0
        # PREFIX CACHE: stored prompt prefills in a RADIX index
        # (serving/radix.py — O(prompt) longest-match lookup however
        # many entries, replacing the seed's O(entries) linear scan),
        # LRU-bounded.  A request whose prompt extends a stored entry
        # pays prefill only for the suffix (models/generate.prefill's
        # extension contract).  Entry storage depends on the engine:
        # LEGACY (fixed-lane / engine-less): each entry holds its own
        # contiguous B=1 cache, O(max_position) device memory.
        # PAGED (kv_paged): single-row entries hold POOL PAGES — a
        # stored system prompt is prefilled once and its pages are
        # shared (refcounted, copy-on-write) by every extension entry
        # and every resident slot that hits it, so admission of a hit
        # costs only the divergent suffix.  prefix_cache=0 disables.
        self.prefix_cache_size = int(prefix_cache)
        if not hasattr(model, "encode"):
            self._prefix_enabled = self.prefix_cache_size > 0
        else:
            self._prefix_enabled = False  # seq2seq: encoder != prefix
        # The CONFIGURED state, captured once: the degradation
        # ladder may flip _prefix_enabled off at runtime, and engine
        # recovery restores it to exactly this — never beyond what
        # construction decided.
        self._prefix_configured = self._prefix_enabled
        self._prefix = RadixPrefixIndex(max(1, self.prefix_cache_size))
        self._prefix_lock = threading.Lock() \
            if self.sanitizer is None \
            else self.sanitizer.wrap("_prefix_lock")
        self.prefix_hits = 0
        # Prefix-reuse hit-token counter: prompt tokens served from a
        # stored prefill instead of fresh prefill work — the measure
        # the shared-prefix bench leg asserts on.
        self.prefix_hit_tokens = 0
        self._prefix_store_skips = 0   # paged stores dropped for
        #                                pool pressure (logged once)
        # Degradation ladder (docs/SERVING.md "Fault tolerance"): a
        # prefix-store ERROR (real, or the ``prefix_store`` fault
        # site) disables the store with a counter instead of failing
        # the request — the cache is an optimization, and a broken
        # optimization must cost hit-rate, never availability.
        self._prefix_store_errors = 0
        self.kv_paged = bool(self.engine is not None
                             and self.engine.paged)
        self.kv_lazy = bool(self.kv_paged and kv_lazy)
        # HOST-RAM SPILL TIER for the prefix store (PR 12, tentpole
        # b): a byte budget > 0 makes page-pressure eviction DEMOTE a
        # paged entry — payload gathered to host buffers by the
        # sanctioned spill helper — instead of dropping it, so the
        # shareable-prefix working set is bounded by host RAM, not
        # device pages.  A host-tier hit re-materializes via
        # device_put (+ opportunistic promotion back to pages).  All
        # counters under _stats_lock; _spill_stats() is the ONE dict
        # /metrics and /info render (no drift).
        self.kv_host_spill_bytes = int(kv_host_spill_bytes)
        self._host_bytes = 0
        self._host_entries = 0
        self._host_spills_total = 0
        self._host_dropped_total = 0    # budget evictions + oversize
        self._remat_hits_total = 0
        self._remat_bytes_total = 0
        self._promotions_total = 0
        # FLEET PREFIX CACHE (PR 16): the host tier goes on the wire.
        # ``prefix_fetch`` arms the CLIENT half (an affinity miss
        # with a router-supplied ``prefix_hint`` fetches the holder's
        # spilled payload instead of re-prefilling, gated by the
        # PrefixFetchPolicy cost curve); the SERVING half — the
        # /prefix/fetch|ingest|index|evict|handoff endpoints — is
        # always mounted on paged servers so a drain handoff or a
        # peer's fetch needs no arming on the holder.  All counters
        # under _stats_lock; _spill_stats() renders them on BOTH
        # /metrics and /info (no drift).  Every failure class on
        # these paths degrades to a typed re-prefill — never a
        # request failure.
        self.prefix_fetch = bool(prefix_fetch)
        self.prefix_fetch_timeout_s = float(prefix_fetch_timeout_s)
        self.fetch_policy = prefix_fetch_policy \
            if prefix_fetch_policy is not None else PrefixFetchPolicy()
        self._fetch_attempts_total = 0
        self._fetch_hits_total = 0
        self._fetch_bytes_total = 0
        self._fetch_failed: Dict[str, int] = {}
        self._ingest_total = 0
        self._ingest_rejected_total = 0
        self._handoff_entries_total = 0
        self._handoff_bytes_total = 0
        self._handoff_failed_total = 0
        self._evict_hints_total = 0
        if self.kv_paged:
            # Page-pressure relief: when an admit-ready stream is
            # blocked on free pages, the engine asks us to evict
            # stored-but-idle prefix entries (LRU; pages shared with
            # residents survive via their refcounts) — spilling their
            # payloads to the host tier first when it is enabled.
            self.engine.page_reclaim = self._reclaim_prefix_pages
        # FLIGHT RECORDER (serving/profiling.py), off by default:
        # --profile-every N --profile-steps K periodically wraps K
        # decode-step boundaries in a jax.profiler window, analyzes
        # the dump off-thread (analysis/xprof.py), and publishes
        # trace-TRUE attribution — collective/transfer/host-gap/
        # device-busy shares + serving MFU — as /metrics gauges, the
        # /info "profiling" block, and GET /profile/report.  One
        # published record behind all three surfaces, so they can
        # never drift; shares the manual endpoints' ProfileSession,
        # so recorder windows and POST /profile/start are single-
        # flight against each other.
        self.recorder = None
        if profile_every:
            if profile_every < 0:
                raise ValueError(
                    f"profile_every must be >= 0; got "
                    f"{profile_every}")
            if self.profiler is None:
                raise ValueError(
                    "profile_every needs profile_dir (the flight "
                    "recorder writes jax.profiler windows there)")
            if self.engine is None:
                raise ValueError(
                    "profile_every requires the continuous-batching "
                    f"engine (batching={self.batching!r}) — the "
                    "recorder windows decode-step boundaries")
            from .profiling import (FlightRecorder,
                                    decode_flops_per_token,
                                    detect_peak_flops)

            cfg = getattr(model, "cfg", None)
            peak = detect_peak_flops()
            self.recorder = FlightRecorder(
                self.profiler, every=profile_every,
                steps=profile_steps, telemetry=self.telemetry,
                flops_fn=(lambda pos: decode_flops_per_token(
                    cfg, pos)) if cfg is not None else None,
                peak_flops=peak["peak_flops"],
                peak_flops_source=peak["peak_flops_source"],
                n_devices=self.mesh.n_devices
                if self.mesh is not None else 1,
                position_probe=self.engine.mean_resident_position)
            self.engine.recorder = self.recorder
        # REQUEST-SCOPED DEBUGGABILITY (serving/debug.py).  The
        # history ring answers "what happened to THIS request"
        # (GET /requests/<id>); the engine writes the full causal
        # record on every terminal path, and the front-end writes a
        # minimal one for requests the engine never saw (validation
        # 400s, solo paths, drain 503s) — engine records supersede.
        # request_history=0 disables the whole layer (one attribute
        # check on the engine's terminal paths, same off-switch
        # contract as the trace ring).
        self.history = RequestHistory(request_history)
        if self.engine is not None:
            self.engine.history = self.history
        # TAIL-LATENCY FORENSICS (serving/forensics.py), ON by
        # default: the phase accumulator behind the per-phase
        # /metrics families and the anomaly sentry behind
        # GET /anomalies.  The engine's terminal paths feed it each
        # request's phase ledger; solo paths feed it from the
        # handler.  ``forensics=False`` removes the whole layer (the
        # bench's forensics_overhead off arm); ``forensics_dir``
        # arms on-disk anomaly bundles (StallWatchdog's one-shot
        # discipline).
        self.forensics: Optional[ForensicsCore] = None
        if forensics:
            self.forensics = ForensicsCore(
                window=sentry_window,
                baseline_windows=sentry_baseline_windows,
                out_dir=forensics_dir,
                snapshot_fn=(
                    (lambda: self.engine.build_debug_snapshot(
                        forced=True))
                    if self.engine is not None else None),
                trace_tail_fn=lambda: self.telemetry.events()[-256:],
                record_fn=self.history.get)
            if self.engine is not None:
                self.engine.forensics = self.forensics
        # STALL WATCHDOG (opt-in via --stall-timeout): declares a
        # stall when work exists but no step boundary completes, and
        # writes a one-shot diagnostic bundle (forced state snapshot
        # + trace tail + thread stacks) before anyone restarts the
        # evidence away.  Engine-only: solo paths have no step
        # boundary to watch — their stall surface is the bounded
        # front-end wait (request_timeout_s).
        self.watchdog = None
        if stall_timeout_s is not None:
            if self.engine is None:
                raise ValueError(
                    "stall_timeout_s requires the continuous-"
                    f"batching engine (batching={self.batching!r}) — "
                    "the watchdog monitors decode-step boundaries")
            self.watchdog = StallWatchdog(
                self.engine, self.telemetry,
                timeout_s=stall_timeout_s, out_dir=stall_dir,
                queue_factor=stall_queue_factor,
                extra_state=self._watchdog_extra_state)
            self.watchdog.start()
        # ENGINE SUPERVISOR (serving/recovery.py), ON by default for
        # engine-backed servers: an exception escaping the engine's
        # scheduling layer no longer fails every in-flight request —
        # the supervisor requeues everything for token-identical
        # resume, rebuilds the pools (zero recompiles), and restarts
        # the loop with bounded backoff; a crash STORM trips the
        # circuit breaker instead (healthz 503 ``engine_down``, new
        # submits shed — fail fast, never hang).  ``supervise=False``
        # keeps the legacy fail-everything crash behavior.
        self.supervisor = None
        if self.engine is not None and supervise:
            self.supervisor = EngineSupervisor(self.engine)
            self.supervisor.add_recovery_hook(
                self._on_engine_recovery)

    def close(self) -> None:
        """Stop the engine loop thread (idempotent) and end any
        in-flight profiler trace (recorder window or manual)."""
        if self.watchdog is not None:
            self.watchdog.close()
        if self.engine is not None:
            self.engine.close()
        if self.recorder is not None:
            self.recorder.close()
        if self.profiler is not None:
            self.profiler.close()
        if self.sanitizer is not None \
                and self.sanitize_report is not None:
            # Written LAST: the engine drain above is the final
            # source of acquisitions, so the dump is the complete
            # observed graph for this server's lifetime.
            with open(self.sanitize_report, "w",
                      encoding="utf-8") as fh:
                json.dump(self.sanitizer.stats(), fh, indent=1,
                          sort_keys=True)
                fh.write("\n")

    def _exact(self):
        """Serving-exact trace context for the server's own device
        sections (solo programs and prefill trace over the mesh's
        column-sharded params; the exact constraint mode keeps their
        output bitwise-identical to unmeshed).  No-op unmeshed."""
        return self.mesh.exact() if self.mesh is not None \
            else contextlib.nullcontext()

    # -- request lifecycle ----------------------------------------------

    def drain(self) -> Dict[str, Any]:
        """POST /drain: stop admitting (every path — engine, solo,
        coalesce — sheds new requests with 503 ``draining``), let
        in-flight work finish, and turn /healthz readiness off so a
        router/load-balancer stops sending traffic here.  Idempotent;
        returns the in-flight snapshot so an orchestrator can poll
        until it hits zero before killing the process."""
        self.draining = True
        if self.engine is not None:
            self.engine.drain()
        return self.drain_status()

    def drain_status(self) -> Dict[str, Any]:
        es = self.engine.stats() if self.engine is not None else {}
        return {"draining": self.draining,
                "drain_rejected": self.drain_rejected,
                "slots_active": es.get("slots_active", 0),
                "queue_len": es.get("queue_len", 0)}

    # -- request-scoped debuggability -----------------------------------

    def debug_state(self) -> Dict[str, Any]:
        """``GET /debug/state``: a CONSISTENT snapshot of engine
        internals plus the server-level lifecycle surface.  The
        engine half is the snapshot it published at its most recent
        step boundary (SnapshotBoard — built on the engine thread,
        outside the device lock, so it is internally consistent and
        this handler can never block behind a wedged device call:
        the SNAPSHOT-LOCK contract, docs/DESIGN.md)."""
        now = time.perf_counter()
        out: Dict[str, Any] = {
            "model": self.model_name,
            "batching": self.batching,
            "draining": self.draining,
            "history": self.history.stats(),
        }
        if self.engine is not None:
            snap = self.engine.debug_board.latest()
            if snap is not None:
                snap["age_s"] = round(max(0.0, now - snap["t"]), 3)
                del snap["t"]   # perf_counter origin: meaningless
                #                 outside the process; age_s is the
                #                 consumable form
            out["engine"] = snap
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.status()
        if self.sanitizer is not None:
            # The lock-sanitizer's acquisition graph (edges +
            # violations) when armed — the bundle's deadlock
            # evidence, live.
            out["sanitizer"] = self.sanitizer.stats()
        return out

    def _watchdog_extra_state(self) -> Dict[str, Any]:
        """Server-level state folded into the stall bundle's
        snapshot (the watchdog has no back-reference to us)."""
        return {
            "draining": self.draining,
            "requests": self.requests,
            "errors": self.errors,
            "history": self.history.stats(),
            # Degradation-ladder state: a stall bundle from a
            # recovery storm should show whether the prefix store
            # disabled itself along the way.
            "prefix_enabled": self._prefix_enabled,
            "prefix_store_errors": self._prefix_store_errors,
            **({"sanitizer": self.sanitizer.stats()}
               if self.sanitizer is not None else {}),
        }

    def record_front(self, rid: Optional[str], path: str,
                     status: int, req, resp) -> None:
        """Minimal front-end history record for a request the ENGINE
        never recorded — validation 400s, drain/queue sheds, solo and
        coalesce paths.  Engine-path records are written by the
        engine itself with the full causal timeline; this only fills
        the gap (RequestHistory.record_front never overwrites)."""
        if rid is None or not self.history.enabled:
            return
        # Mirror the handler's error->HTTP mapping back into the
        # engine's terminal-status vocabulary, so GET /requests?
        # status=shed finds queue-full/drain sheds the engine never
        # saw and a record never disagrees with its trace instants.
        front_status = {200: "complete", 429: "shed", 503: "shed",
                        504: "expired", 499: "cancelled"}.get(
                            int(status), "failed")
        rec: Dict[str, Any] = {
            "request_id": rid, "t": round(time.time(), 3),
            "path": path, "http_status": int(status),
            "status": front_status}
        if isinstance(req, dict):
            rec["kind"] = self._request_kind(req, path)
        if isinstance(resp, dict):
            if resp.get("error"):
                rec["error"] = str(resp["error"])[:300]
            if resp.get("reason"):
                rec["reason"] = resp["reason"]
            if "wall_s" in resp:
                rec["wall_s"] = resp["wall_s"]
        self.history.record_front(rec)

    def _check_not_draining(self) -> None:
        if self.draining:
            # Counted HERE (the shed happens at validation, before
            # the engine ever sees the request) so /metrics shows
            # drain-time 503s instead of staying flat while the
            # access log streams them.
            with self._stats_lock:
                self.drain_rejected += 1
            raise ShedError(
                "server is draining: finishing in-flight requests, "
                "admitting none", reason="draining")

    def _wait_group(self, group, cancel_check=None) -> None:
        """Bounded wait for an engine group — the front-end half of
        the lifecycle contract.  Replaces the old untimed
        ``group.event.wait()``, which held an HTTP worker until
        engine drain if the engine ever wedged.  Three give-up paths,
        all delivered to the engine as a boundary cancel first:

        - ``cancel_check`` (client-disconnect probe) fires ->
          :class:`RequestCancelled` (499; nobody is listening);
        - the request's own deadline passes -> the engine sweep
          normally delivers :class:`DeadlineExceeded` itself, but a
          front-end check backstops a wedged engine;
        - ``request_timeout_s`` elapses with no terminal state ->
          :class:`ShedError` (503 ``request_timeout``).

        Raising without waiting for the engine's acknowledgement is
        safe: the group is flagged, its slots free at the engine's
        next boundary, and a late completion writes into state nobody
        reads."""
        cap = None
        if self.request_timeout_s is not None:
            cap = group.t_submit + self.request_timeout_s
        while not group.event.wait(0.1):
            now = time.perf_counter()
            if cancel_check is not None and cancel_check():
                err = RequestCancelled(
                    "client disconnected; request cancelled")
                self.engine.cancel(group, err)
                raise err
            if group.deadline is not None and now > group.deadline:
                err = DeadlineExceeded(
                    f"deadline exceeded after "
                    f"{now - group.t_submit:.3f}s "
                    f"({group.status_phase()})")
                self.engine.cancel(group, err)
                raise err
            if cap is not None and now > cap:
                err = ShedError(
                    f"request exceeded the server request timeout "
                    f"({self.request_timeout_s}s) without reaching a "
                    f"terminal state; shedding the waiter",
                    reason="request_timeout")
                self.engine.cancel(group, err)
                raise err
        if group.error is not None:
            raise group.error

    def log_access(self, method: str, path: str, status: int,
                   req, resp, dt: float,
                   rid: Optional[str] = None) -> None:
        """One structured line per request (the satellite fix for the
        silent ``log_message`` no-op: before this, failed requests
        vanished entirely).  Defensive about ``req`` — it may be
        unparsed garbage on 400s — and writes a single JSON object
        per line so log pipelines need no multi-line stitching."""
        if not self.access_log:
            return
        rec: Dict[str, Any] = {
            "t": round(time.time(), 3), "method": method,
            "path": path, "status": int(status),
            "ms": round(1e3 * dt, 3)}
        if rid is not None:
            # The correlation key: grep the access log, the trace
            # ring, and GET /requests/<id> by the same string.
            rec["request_id"] = rid
        if isinstance(resp, dict):
            # Engine-path provenance (slot id, preempt/resume
            # counts): a resumed request reads differently from a
            # straight-through one in the log.
            for k in ("slot", "preempts", "resumes"):
                if k in resp:
                    rec[k] = resp[k]
        if isinstance(req, dict):
            rec["kind"] = self._request_kind(req, path)
            rows = req.get("prompt")
            if isinstance(rows, list) and rows:
                rec["rows"] = len(rows) \
                    if isinstance(rows[0], list) else 1
        if isinstance(resp, dict):
            if status == 200 and "new_tokens" in resp:
                rec["new_tokens"] = sum(
                    len(r) for r in resp["new_tokens"]
                    if isinstance(r, list))
            err = resp.get("error")
            if err:
                rec["error"] = str(err)[:200]
        try:
            print(json.dumps(rec), file=self._access_log_file,
                  flush=True)
        except Exception:
            pass  # logging must never fail a request

    @staticmethod
    def _request_kind(req: Dict[str, Any], path: str) -> str:
        if path == "/prefill":
            return "prefill"
        if req.get("speculative") is True:
            return "speculative"
        beams = req.get("num_beams")
        if isinstance(beams, int) and not isinstance(beams, bool) \
                and beams > 1:
            return "beam"
        temp = req.get("temperature", 0)
        if isinstance(temp, (int, float)) \
                and not isinstance(temp, bool) and temp > 0:
            return "sampled"
        return "greedy"

    def _note_fallback(self, kind: str, reason: str) -> None:
        """A request class fell back to the solo decode path: count
        it under its kind and log the reason ONCE per kind (a busy
        server must not spam stderr per request).  /info surfaces the
        table, so a silently-solo workload is diagnosable."""
        with self._stats_lock:
            fb = self.solo_fallbacks.get(kind)
            first = fb is None
            if first:
                self.solo_fallbacks[kind] = {"reason": reason,
                                             "count": 1}
            else:
                fb["count"] += 1
        if first:
            print(f"# serving: {kind} requests take the solo path — "
                  f"{reason}", file=sys.stderr)

    def _note_breakdown(self, queue_s: float, prefill_s: float,
                        decode_s: float) -> None:
        with self._stats_lock:
            self._queue_s_sum += queue_s
            self._prefill_s_sum += prefill_s
            self._decode_s_sum += decode_s
            self._breakdown_count += 1

    # -- compile cache --------------------------------------------------

    def _fn(self, key):
        from ..models import generate as G

        def jit(fn):
            # Weights are ARGUMENTS of every program (G.jit_over): w is
            # (target variables, draft variables or None).
            return G.jit_over((self.variables, self.draft_variables), fn)

        def build():
            kind, b, p_len, new, temp, top_k, top_p, eos, beams, \
                chunk = key
            if kind == "beam":
                return jit(lambda w, toks, rng: G.generate_beam(
                    self.model, w[0], toks,
                    max_new_tokens=new, num_beams=beams, eos_id=eos,
                    prefill_chunk=chunk))
            if kind == "sample_pos":
                # Position-keyed sampled solo path: the shaping params
                # are RUN-TIME arguments (traced scalars), so every
                # sampled (temperature, top_k, top_p, seed) combo of
                # one shape shares a single compiled program — and the
                # math is the same _sample_positional_row the engine's
                # slot step runs.
                return jit(
                    lambda w, toks, keys, temp, tk, tp:
                    G.generate_positional(
                        self.model, w[0], toks,
                        max_new_tokens=new, keys=keys,
                        temperature=temp, top_k=tk, top_p=tp,
                        eos_id=eos, prefill_chunk=chunk))
            if kind == "spec":
                k = beams  # slot reused for the draft length
                return jit(lambda w, toks, rng: G.generate_speculative(
                    self.model, w[0], self.draft_model,
                    w[1], toks, max_new_tokens=new,
                    k=k, eos_id=eos, prefill_chunk=chunk,
                    temperature=temp, top_k=top_k, top_p=top_p,
                    rng=rng if temp != 0.0 else None))
            if kind == "spec_pos":
                # sampled speculative solo under the position-keyed
                # schedule — the reference the engine's spec slots
                # are pinned against, so solo and engine agree
                # token-for-token per seed
                k = beams  # slot reused for the draft length
                return jit(
                    lambda w, toks, keys: G.generate_speculative(
                        self.model, w[0], self.draft_model,
                        w[1], toks,
                        max_new_tokens=new, k=k, eos_id=eos,
                        prefill_chunk=chunk, temperature=temp,
                        top_k=top_k, top_p=top_p, keys=keys))
            return jit(lambda w, toks, rng: G.generate(
                self.model, w[0], toks, max_new_tokens=new,
                temperature=temp, top_k=top_k, top_p=top_p,
                eos_id=eos, rng=rng, prefill_chunk=chunk))

        return lru_get(self._fns, key, self._fn_cap, build,
                       sentinel=self.recompile,
                       kind=f"server:{key[0]}")

    # -- prefix cache ----------------------------------------------------

    def _split_fns(self, b: int, p_or_s: int, kind: str, chunk,
                   new=None, temp=None, top_k=None, top_p=None,
                   eos=None):
        """Jitted split programs for the prefix-cache path:
        ``pfill``/``extend`` produce (logits, cache, stats: what the
        model sowed, the engine counts it); ``cont`` decodes
        from a cache.  Cached in the same LRU as the fused programs."""
        from ..models import generate as G

        # "cont"/"cont_pos" do not depend on chunk — keying them would
        # compile duplicate identical decode programs per chunk value.
        key = (kind, b, p_or_s, new, temp, top_k, top_p, eos, None,
               chunk if kind not in ("cont", "cont_pos") else None)

        def jit(fn):
            return G.jit_over(self.variables, fn)

        def build():
            if kind in ("pfill", "extend"):
                return jit(G.prefill_programs(self.model, chunk)[
                    kind == "extend"])
            if kind == "cont_pos":
                # position-keyed sampled continue (prefix-cache hits
                # that stay solo): one program per shape, shaping
                # params at run time — mirrors "sample_pos"
                return jit(
                    lambda w, cache, logits, pos, keys, temp, tk, tp:
                    G.generate_continue_positional(
                        self.model, w, cache, logits,
                        pos, max_new_tokens=new, keys=keys,
                        temperature=temp, top_k=tk, top_p=tp,
                        eos_id=eos, _validated=True))
            return jit(lambda w, cache, logits, pos, rng:
                       G.generate_continue(
                           self.model, w, cache,
                           logits, pos, max_new_tokens=new,
                               temperature=temp, top_k=top_k,
                               top_p=top_p, rng=rng, eos_id=eos,
                               _validated=True))

        return lru_get(self._fns, key, self._fn_cap, build,
                       sentinel=self.recompile,
                       kind=f"server:{kind}")

    # -- fault tolerance: prefix-store degradation + engine recovery ----

    def _note_prefix_error(self, where: str) -> None:
        """One prefix-store failure: count it and DISABLE the store
        (lookups miss, stores skip) — requests keep flowing without
        prefix reuse instead of 500ing on a broken cache.  The
        counter + the disabled flag surface in /info and /metrics so
        the degradation is an alert, not a mystery slowdown."""
        with self._stats_lock:
            self._prefix_store_errors += 1
            first = self._prefix_enabled
            self._prefix_enabled = False
        if first:
            print(f"# serving: prefix store DISABLED after an error "
                  f"in {where} — requests continue without prefix "
                  f"reuse (degradation ladder; counted in /info "
                  f"prefix_store_errors)", file=sys.stderr)

    def _prefix_lookup_safe(self, toks: np.ndarray
                            ) -> Optional[PrefixHit]:
        """Contained prefix lookup: an error (injected via the
        ``prefix_store`` fault site, or real — a corrupt trie, a
        failed page materialization) degrades to a MISS and disables
        the store; the request pays full prefill and succeeds."""
        if not self._prefix_enabled:
            return None
        try:
            if self.faults is not None:
                self.faults.check("prefix_store")
            return self._prefix_lookup(toks)
        except Exception:
            self._note_prefix_error("lookup")
            return None

    def _prefix_store_safe(self, toks, logits, cache, *,
                           hot: bool = True) -> None:
        """Contained prefix store: same degradation contract as the
        lookup — a failing store must never fail the request whose
        prefill it was opportunistically caching."""
        if not self._prefix_enabled:
            return
        try:
            if self.faults is not None:
                self.faults.check("prefix_store")
            self._prefix_store(toks, logits, cache, hot=hot)
        except Exception:
            self._note_prefix_error("store")

    def _on_engine_recovery(self) -> None:
        """EngineSupervisor recovery hook, run after the slot/page
        pool rebuild and before the loop restart.  PAGED prefix
        entries hold page ids into the pool that was just reset —
        their payloads are gone, so the whole index is flushed BY
        REFERENCE (no unpins: the fresh pool's accounting starts
        all-free, and unpinning stale ids into it would corrupt the
        new refcounts).  Legacy contiguous entries survive crashes
        (they own independent caches), so engine-less storage is
        kept."""
        if not self.kv_paged:
            return
        displaced = []
        with self._prefix_lock:
            # HOST-TIER entries SURVIVE the flush: their payloads are
            # host buffers referencing no device state — exactly the
            # epoch contract (stale device ids die with the pool
            # generation; host bytes don't).  Re-stored in eviction
            # order, coldest first, so recency survives too.
            keep = [(t, p) for t, p in self._prefix.entries()
                    if isinstance(p, _SpilledPrefix)]
            self._prefix = RadixPrefixIndex(
                max(1, self.prefix_cache_size))
            for t, p in keep:
                displaced += self._prefix.store(t, p)
        if displaced:
            self._free_displaced(displaced)
        # A store error during the crash window (e.g. a pin racing
        # the pool reset) may have tripped the degradation ladder;
        # the flush just removed whatever was broken, so a
        # config-enabled store comes back up.  (Counted errors stay
        # counted — the episode remains visible in /info.)
        if self._prefix_configured:
            with self._stats_lock:
                self._prefix_enabled = True

    def _prefix_lookup(self, toks: np.ndarray
                       ) -> Optional[PrefixHit]:
        """Longest stored entry whose prompt is a prefix of ``toks``
        (same batch) via one radix walk.  Paged entries are PINNED
        under the prefix lock (so eviction can't free their pages
        mid-flight), materialized into a contiguous cache under the
        device lock, and returned with their still-pinned FULL-page
        ids — the engine path maps those read-only into the admitted
        slot's table; every other outcome must unpin them
        (:class:`PrefixHit`)."""
        with self._prefix_lock:
            hit = self._prefix.lookup(toks)
            if hit is None:
                return None
            ent_toks, payload = hit
            pc = ent_toks.shape[1]
            if isinstance(payload, _SpilledPrefix):
                # Host-tier hit: the payload (immutable host arrays)
                # is safe to carry out of the lock; re-materialize —
                # and opportunistically promote — outside it.
                spilled = payload
            elif not isinstance(payload, _PagedPrefix):
                logits, cache = payload
                return PrefixHit(pc, logits, cache, ())
            else:
                spilled = None
            if spilled is None:
                # Pin while still under the prefix lock: a concurrent
                # eviction between lookup and pin could free the
                # pages.  (Lock order: _prefix_lock > _page_lock.)
                # The returned pool epoch rides the pins to the
                # engine: a crash-recovery rebuild between here and
                # admission invalidates them instead of corrupting
                # fresh counts.
                pin_epoch = self.engine.slots.pin(payload.pages)
        if spilled is not None:
            return self._rematerialize_hit(ent_toks, spilled, pc)
        try:
            with self._lock:
                if self.engine.slots.epoch != pin_epoch:
                    # Pool rebuilt since the pin (recovery holds
                    # this same lock for the rebuild, so the check
                    # cannot race it): the ids are dead — a miss.
                    return None
                cache = self.engine.slots.materialize(payload.pages,
                                                      pc)
        except BaseException:
            if self.engine.slots.epoch != pin_epoch:
                # Crash recovery rebuilt the pool mid-materialize:
                # the failure is the rebuild's, not the store's — a
                # MISS, not an error (counting it would disable the
                # store the recovery hook just flushed clean).  The
                # pins died with the old generation (by reference).
                return None
            # A failed materialization (compile error, device OOM)
            # must not leak the pins — repeated failing hits would
            # otherwise walk the free pool down to permanent
            # kv_pages sheds.
            self.engine.slots.unpin(payload.pages, epoch=pin_epoch)
            raise
        if self.engine.slots.epoch != pin_epoch:
            # Pool rebuilt while we gathered: the gather itself read
            # pre-rebuild content (live device buffers), but the
            # page ids now name OTHER requests' KV in the fresh
            # accounting — drop the hit (miss; pins die by
            # reference) rather than share poisoned pages.
            return None
        # Keep pins only on the FULL pages (the shareable ones — the
        # partial tail page's content rides the materialized cache
        # and is rewritten privately by the admitted slot).
        n_full = pc // self.engine.slots.page_tokens
        pins = PagePins(payload.pages[:n_full], pin_epoch)
        if payload.pages[n_full:]:
            self.engine.slots.unpin(payload.pages[n_full:],
                                    epoch=pin_epoch)
        return PrefixHit(pc, payload.logits, cache, pins)

    def _cache_template(self):
        """ABSTRACT cache pytree (``ShapeDtypeStruct`` leaves) for
        cold-pool shaping — the same shape probe
        ``models.generate.init_cache`` uses, minus the zeros: the
        classifier only reads paths/shapes/dtypes, so nothing is
        allocated or computed here."""
        import jax
        import jax.numpy as jnp

        tokens = jnp.zeros((1, 1), jnp.int32)
        shapes = jax.eval_shape(
            # Shape probe under eval_shape (nothing is ever drawn
            # from this key).  # ptpu: ignore[RNG-DET]
            lambda: self.model.init(jax.random.PRNGKey(0), tokens,
                                    decode=True, decode_position=0))
        return shapes["cache"]

    def _rematerialize_hit(self, ent_toks, payload: "_SpilledPrefix",
                           pc: int) -> PrefixHit:
        """HOST-TIER hit: ``device_put`` the spilled leaves back into
        a contiguous cache (manager.rematerialize — the sanctioned
        host->device helper) and opportunistically PROMOTE the entry
        back to device pages so subsequent hits — and co-resident
        slots — share them copy-on-write again.  Promotion is best-
        effort: a tight pool (the very pressure that spilled the
        entry) just serves the hit from the contiguous cache with no
        shared pages.  Runs on a handler thread with no locks held;
        errors propagate to _prefix_lookup_safe's degradation
        ladder."""
        mgr = self.engine.slots
        with self._lock:
            if not mgr.shaped:
                # Cold pool: this entry arrived over the wire (fetch
                # or drain handoff) BEFORE this replica's first
                # prefill shaped the page pool — a freshly restarted
                # drain successor hits exactly this.  Shape it from
                # an abstract template instead of failing the hit.
                mgr.ensure_shaped(self._cache_template())
            cache = mgr.rematerialize(payload.leaves, pc)
        with self._stats_lock:
            self._remat_hits_total += 1
            self._remat_bytes_total += payload.nbytes
        pins = ()
        ids, ep = mgr.reserve_with_epoch(mgr.pages_needed(pc))
        if ids:
            promoted = False
            try:
                with self._lock:
                    # Epoch re-check INSIDE the device lock, like the
                    # paged store's scatter: recovery rebuilds the
                    # pool under this lock, so a dead-generation
                    # scatter cannot interleave.
                    if mgr.epoch == ep:
                        mgr.scatter_cache(cache, ids)
                        promoted = True
            except Exception:
                promoted = False    # promotion is an optimization
            if promoted:
                new_payload = _PagedPrefix(ids, pc, payload.logits)
                with self._prefix_lock:
                    if self._prefix.set_payload(ent_toks, new_payload,
                                                expect=payload):
                        # This hit maps the promoted FULL pages
                        # read-only, exactly like a device-tier hit;
                        # pin under the prefix lock so an eviction
                        # cannot race the mapping.
                        n_full = pc // mgr.page_tokens
                        pin_epoch = mgr.pin(ids[:n_full]) \
                            if n_full else ep
                        pins = PagePins(ids[:n_full], pin_epoch)
                        promoted_entry = True
                    else:
                        promoted_entry = False
                if promoted_entry:
                    with self._stats_lock:
                        self._host_bytes -= payload.nbytes
                        self._host_entries -= 1
                        self._promotions_total += 1
                else:
                    # Entry changed under us: abandon the promotion
                    # (dead-generation ids drop by reference).
                    mgr.unpin(ids, epoch=ep)
            else:
                mgr.unpin(ids, epoch=ep)
        return PrefixHit(pc, payload.logits, cache, pins,
                         source="host")

    def _unpin_prefix(self, pins) -> None:
        if pins:
            self.engine.slots.unpin(
                pins, epoch=getattr(pins, "epoch", None))

    def _free_displaced(self, displaced) -> None:
        """Release payloads the radix index displaced (overwrites and
        LRU evictions): paged entries drop their page references —
        pages shared by a child entry or a resident slot stay alive
        under the remaining refcounts ("evict leaf pages first, never
        a page with refcount > 1" falls out of the accounting) —
        and host-tier entries leave the spill byte accounting."""
        for _toks, payload in displaced:
            if isinstance(payload, _PagedPrefix):
                self.engine.slots.unpin(payload.pages)
            elif isinstance(payload, _SpilledPrefix):
                with self._stats_lock:
                    self._host_bytes -= payload.nbytes
                    self._host_entries -= 1
                    self._host_dropped_total += 1

    def _spill_entry(self, toks, payload) -> bool:
        """Demote one device-tier entry to the HOST tier: pin its
        pages, gather the payload to host buffers (the sanctioned
        ``spill_pages`` helper, under the device lock), swap the
        entry's payload in place, and release the entry's page
        references — the pages free (to the extent nothing else
        shares them) while the CONTENT survives in host RAM.
        Returns False when the entry must be dropped instead (spill
        failed, over budget, or the entry changed under us)."""
        mgr = self.engine.slots
        with self._prefix_lock:
            # Pin under the prefix lock (same discipline as the
            # lookup): eviction elsewhere cannot free the pages
            # while we gather.  Entry may already be gone/changed —
            # the identity-guarded no-op swap is the O(prompt)
            # presence check (same primitive the drop path uses).
            if not self._prefix.set_payload(toks, payload,
                                            expect=payload):
                return True     # someone else dealt with it
            pin_epoch = mgr.pin(payload.pages)
        try:
            with self._lock:
                if mgr.epoch != pin_epoch:
                    # Pool rebuilt (crash recovery): pins and pages
                    # are dead by reference; the recovery flush owns
                    # the index.
                    return True
                host = mgr.spill_pages(payload.pages,
                                       payload.n_tokens)
                import jax

                logits_host = np.asarray(
                    jax.device_get(payload.logits))
        except Exception:
            mgr.unpin(payload.pages, epoch=pin_epoch)
            return False
        spilled = _SpilledPrefix(host, payload.n_tokens, logits_host)
        if spilled.nbytes > self.kv_host_spill_bytes:
            mgr.unpin(payload.pages, epoch=pin_epoch)
            with self._stats_lock:
                self._host_dropped_total += 1
            return False
        with self._prefix_lock:
            swapped = self._prefix.set_payload(toks, spilled,
                                               expect=payload)
        mgr.unpin(payload.pages, epoch=pin_epoch)   # the gather pin
        if not swapped:
            return True         # entry changed meanwhile: host copy
        #                         discarded, nothing to drop
        # The ENTRY's own page references are released now that its
        # payload lives on the host.
        mgr.unpin(payload.pages, epoch=pin_epoch)
        with self._stats_lock:
            self._host_bytes += spilled.nbytes
            self._host_entries += 1
            self._host_spills_total += 1
        self._enforce_spill_budget()
        return True

    def _enforce_spill_budget(self) -> None:
        """Drop the COLDEST host-tier entries until the spill bytes
        fit the budget (host-tier LRU — the radix recency order
        already is one)."""
        while True:
            with self._stats_lock:
                if self._host_bytes <= self.kv_host_spill_bytes:
                    return
            with self._prefix_lock:
                victim = None
                for t, p in self._prefix.entries():   # coldest first
                    if isinstance(p, _SpilledPrefix):
                        victim = (t, p)
                        break
                if victim is None:
                    return      # accounting drift guard
                self._prefix.remove(victim[0])
            self._free_displaced([victim])

    def _reclaim_prefix_pages(self, n_pages_needed: int) -> bool:
        """Free device pages until ``n_pages_needed`` are free (or no
        page-holding entry remains) — the engine's page-pressure
        hook: stored-but-idle prefixes must never starve admission of
        live traffic.  With the host tier enabled
        (``kv_host_spill_bytes > 0``) evicted entries SPILL their
        payloads to host RAM instead of dropping (tentpole b: the
        shareable-prefix working set multiplies by the host/HBM
        ratio); without it, this is the PR 7 drop-on-evict
        behavior."""
        mgr = self.engine.slots
        while mgr.free_page_count() < n_pages_needed:
            with self._prefix_lock:
                victim = None
                for t, p in self._prefix.entries():   # coldest first
                    if isinstance(p, _PagedPrefix):
                        victim = (t, p)
                        break
            if victim is None:
                return False
            toks, payload = victim
            if self.kv_host_spill_bytes > 0 \
                    and self._spill_entry(toks, payload):
                continue
            # Drop path (spill disabled, failed, or over budget):
            # remove the entry and release its page references —
            # guarded by payload identity, a concurrent overwrite's
            # fresh payload must not be dropped on the old one's
            # verdict.
            with self._prefix_lock:
                if self._prefix.set_payload(toks, payload,
                                            expect=payload):
                    self._prefix.remove(toks)
                else:
                    continue    # entry changed: re-evaluate
            self._free_displaced([(toks, payload)])
        return True

    def _prefix_store(self, toks: np.ndarray, logits, cache, *,
                      hot: bool = True) -> None:
        """Store a prompt's prefill for reuse.  Callers must NOT hold
        the device lock (the paged path scatters pages under it).

        Legacy entries keep the contiguous ``cache``.  Paged entries
        (single-row, paged engine) write the cache into POOL PAGES,
        sharing every page-aligned prefix page with the deepest
        stored ancestor (the radix parent) instead of re-storing it —
        a session extension of an N-page system prompt costs only its
        own suffix pages.

        ``hot=False`` marks a SPECULATIVE store (the per-request
        session store-back): it enters the index's COLD ring, so a
        stream of one-shot suffixes cycles itself out instead of
        flushing explicitly registered system prompts (scan
        resistance — see RadixPrefixIndex.store).  A store that
        could not survive insertion (capacity fully held by hot
        entries) is skipped BEFORE any device/page work."""
        toks = np.asarray(toks, np.int32)
        p_len = toks.shape[1]
        paged = self.kv_paged and toks.shape[0] == 1
        mgr = self.engine.slots if self.engine is not None else None
        shared = ()
        pin_epoch = None
        with self._prefix_lock:
            anc = self._prefix.longest_ancestor(toks)
            if anc is not None and anc[0].shape[1] >= p_len:
                return     # exact prompt already stored
            if not self._prefix.accepts(hot):
                return     # would be displaced in the same call
            if paged and anc is not None \
                    and isinstance(anc[1], _PagedPrefix):
                n_share = min(anc[0].shape[1] // mgr.page_tokens,
                              mgr.pages_needed(p_len))
                shared = tuple(anc[1].pages[:n_share])
                pin_epoch = mgr.pin(shared)
        if paged and pin_epoch is None:
            pin_epoch = mgr.epoch    # no ancestor pins: current gen
        if not paged:
            with self._prefix_lock:
                displaced = self._prefix.store(toks, (logits, cache),
                                               hot=hot)
            self._free_displaced(displaced)
            return
        n_pages = mgr.pages_needed(p_len)
        fresh, reserve_epoch = None, pin_epoch
        for _ in range(8):      # bounded: a reserve/consume race
            #                     must not spin this store forever
            fresh, reserve_epoch = mgr.reserve_with_epoch(
                n_pages - len(shared))
            if fresh is not None:
                break
            if not self._reclaim_prefix_pages(n_pages - len(shared)):
                break
        if fresh is None or reserve_epoch != pin_epoch:
            # Pool too tight to store (live traffic owns the pages)
            # — or rebuilt by crash recovery since the ancestor pins
            # were taken (mixed-generation ids must never enter the
            # index): skip quietly; the prefix cache is an
            # optimization, never back-pressure.  Epoch-guarded
            # unpins release only ids still current; dead-generation
            # ids drop by reference.
            mgr.unpin(shared, epoch=pin_epoch)
            if fresh:
                mgr.unpin(fresh, epoch=reserve_epoch)
            with self._stats_lock:
                self._prefix_store_skips += 1
                first = self._prefix_store_skips == 1
            if first:
                print("# serving: prefix store skipped — page pool "
                      "under live-traffic pressure (counted in "
                      "/info prefix_store_skips)", file=sys.stderr)
            return
        ids = list(shared) + fresh
        try:
            with self._lock:
                # Epoch re-check INSIDE the device lock: crash
                # recovery rebuilds the pool UNDER this lock, so a
                # dead-generation scatter (which would overwrite
                # pages the fresh pool already handed to residents)
                # cannot interleave — it either sees the bump here
                # and drops by reference, or completes before the
                # rebuild (whose recovery flush then wipes the
                # entry).
                if mgr.epoch != pin_epoch:
                    return
                mgr.scatter_cache(cache, ids,
                                  n_shared=len(shared))
        except BaseException:
            mgr.unpin(shared, epoch=pin_epoch)
            mgr.unpin(fresh, epoch=reserve_epoch)
            raise
        payload = _PagedPrefix(ids, p_len, logits)
        with self._prefix_lock:
            if mgr.epoch != pin_epoch:
                # Rebuilt after the scatter: the ids are dead and
                # the recovery flush owns the index — drop the
                # entry by reference.
                return
            displaced = self._prefix.store(toks, payload, hot=hot)
        self._free_displaced(displaced)

    def _store_stream_prefix(self, stream) -> None:
        """Engine ``on_prefilled`` hook for prefix-seeded streams:
        store the extended prompt's prefill back so an exact repeat
        hits at full length (session growth — same contract as the
        solo split path).  Runs on the engine thread, before the
        stream's cache is handed to the slot pool (arrays are
        immutable, so the stored entry and the slot copy never
        alias mutably)."""
        self._prefix_store_safe(np.asarray(stream.toks),
                                stream.logits, stream.cache,
                                hot=False)

    # -- fleet prefix cache (wire fetch / ingest / handoff) --------------

    @staticmethod
    def _prefix_key(toks: np.ndarray) -> str:
        """Stable cross-replica identity of one stored prompt: every
        replica (and the router's rebalance pass) derives the same
        key from the same tokens, so fleet inventory needs no shared
        namespace service."""
        import hashlib

        toks = np.ascontiguousarray(np.asarray(toks, np.int32))
        return hashlib.sha1(
            b"%d|%d|" % toks.shape + toks.tobytes()).hexdigest()

    def _note_fetch_failed(self, reason: str) -> None:
        with self._stats_lock:
            self._fetch_failed[reason] = \
                self._fetch_failed.get(reason, 0) + 1

    def _pack_entry_wire(self, ent_toks, payload) -> Optional[bytes]:
        """Serialize ONE radix entry for the wire.  Host-tier entries
        pack directly (immutable host buffers — no locks needed past
        the lookup that produced them).  Device-tier entries gather
        READ-ONLY: pin under the prefix lock, ``spill_pages`` under
        the device lock, unpin — the entry keeps its pages and its
        payload (unlike ``_spill_entry`` there is NO swap; serving a
        peer must not demote the holder's own hot copy).  Returns
        None when the entry vanished or the gather failed — callers
        treat that as a miss."""
        if isinstance(payload, _SpilledPrefix):
            return pack_spilled(ent_toks, payload.leaves,
                                payload.n_tokens, payload.logits)
        if not isinstance(payload, _PagedPrefix):
            return None     # legacy contiguous entries stay local
        import jax

        mgr = self.engine.slots
        with self._prefix_lock:
            # Identity-guarded presence check + pin under the prefix
            # lock — same discipline as _spill_entry's gather.
            if not self._prefix.set_payload(ent_toks, payload,
                                            expect=payload):
                return None
            pin_epoch = mgr.pin(payload.pages)
        try:
            with self._lock:
                if mgr.epoch != pin_epoch:
                    return None
                host = mgr.spill_pages(payload.pages,
                                       payload.n_tokens)
                logits_host = np.asarray(
                    jax.device_get(payload.logits))
        except Exception:
            return None
        finally:
            mgr.unpin(payload.pages, epoch=pin_epoch)
        return pack_spilled(ent_toks, host, payload.n_tokens,
                            logits_host)

    def prefix_wire_payload(self, req: Dict[str, Any]
                            ) -> Optional[bytes]:
        """POST /prefix/fetch: serve the longest stored entry that
        prefixes the peer's prompt, serialized for the wire.  Served
        even while DRAINING — the drain window is exactly when peers
        come asking.  None -> the handler's 404 (holder miss)."""
        if not self.kv_paged:
            raise ValueError(
                "prefix wire fetch requires a paged engine "
                "(kv_paged)")
        rows = _parse_prompt_rows(req, self.max_batch)
        toks = np.asarray(rows, np.int32)
        with self._prefix_lock:
            # lookup (not longest_ancestor): a fleet hit IS a hit —
            # it should refresh the entry's recency here too.
            hit = self._prefix.lookup(toks)
        if hit is None:
            return None
        return self._pack_entry_wire(hit[0], hit[1])

    def prefix_ingest(self, blob: bytes, *,
                      hot: bool = True) -> Dict[str, Any]:
        """POST /prefix/ingest: verify + store one wire payload as a
        HOST-TIER entry (a drain handoff's push, or a prefetch).  The
        payload is checksummed end to end — a mismatch raises the
        typed :class:`WirePayloadError` (400), and nothing partial is
        ever admitted.  Stored entries enter the spill byte budget
        exactly like locally-spilled ones."""
        if not self.kv_paged or self.kv_host_spill_bytes <= 0:
            with self._stats_lock:
                self._ingest_rejected_total += 1
            raise ValueError(
                "prefix ingest requires a paged engine with a host "
                "spill budget (--kv-host-spill-bytes)")
        if not self._prefix_enabled:
            with self._stats_lock:
                self._ingest_rejected_total += 1
            raise ValueError(
                "prefix cache is disabled on this server")
        try:
            toks, leaves, n_tokens, logits = unpack_spilled(blob)
        except WirePayloadError:
            with self._stats_lock:
                self._ingest_rejected_total += 1
            raise
        spilled = _SpilledPrefix(leaves, n_tokens, logits)
        if spilled.nbytes > self.kv_host_spill_bytes:
            with self._stats_lock:
                self._ingest_rejected_total += 1
            return {"stored": False, "reason": "over_budget",
                    "nbytes": spilled.nbytes,
                    "budget": self.kv_host_spill_bytes}
        with self._prefix_lock:
            anc = self._prefix.longest_ancestor(toks)
            if anc is not None and anc[0].shape[1] >= n_tokens:
                return {"stored": False, "reason": "already_stored"}
            if not self._prefix.accepts(hot):
                with self._stats_lock:
                    self._ingest_rejected_total += 1
                return {"stored": False, "reason": "at_capacity"}
            displaced = self._prefix.store(toks, spilled, hot=hot)
        self._free_displaced(displaced)
        with self._stats_lock:
            self._host_bytes += spilled.nbytes
            self._host_entries += 1
            self._ingest_total += 1
        self._enforce_spill_budget()
        return {"stored": True, "n_tokens": int(n_tokens),
                "nbytes": spilled.nbytes}

    def prefix_index(self) -> Dict[str, Any]:
        """GET /prefix/index: this replica's prefix inventory — the
        fleet eviction policy's input.  Each entry carries its stable
        cross-replica key, tier, recency ring, per-entry hit count,
        and (host tier) byte size, so the router can decide which
        spilled copies are redundant WITHOUT fetching any payload."""
        with self._prefix_lock:
            meta = self._prefix.entries_meta()
        entries = []
        for toks, payload, hits, hot in meta:
            if isinstance(payload, _SpilledPrefix):
                tier: Dict[str, Any] = {"tier": "host",
                                        "nbytes": payload.nbytes}
            elif isinstance(payload, _PagedPrefix):
                tier = {"tier": "device"}
            else:
                tier = {"tier": "legacy"}
            entries.append({"key": self._prefix_key(toks),
                            "rows": int(toks.shape[0]),
                            "tokens": int(toks.shape[1]),
                            "hits": int(hits),
                            "hot": bool(hot), **tier})
        with self._stats_lock:
            host_bytes = self._host_bytes
        return {"entries": entries,
                "host_bytes": host_bytes,
                "host_budget_bytes": self.kv_host_spill_bytes}

    def prefix_evict(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """POST /prefix/evict: apply fleet eviction HINTS — drop the
        named HOST-TIER entries (the redundant cold copies the
        router's one-copy-somewhere policy identified).  Device-tier
        entries never drop on a hint: they are this replica's own
        working set, and fleet policy only governs the spill tier it
        can see through ``kv_host_*``.  Hints are advisory by
        construction — an unknown key is simply skipped."""
        keys = req.get("keys")
        if not isinstance(keys, list) \
                or not all(isinstance(k, str) for k in keys):
            raise ValueError("'keys' must be a list of entry keys "
                             "(GET /prefix/index)")
        want = set(keys)
        dropped = []
        with self._prefix_lock:
            for toks, payload in list(self._prefix.entries()):
                if not isinstance(payload, _SpilledPrefix):
                    continue
                if self._prefix_key(toks) in want:
                    self._prefix.remove(toks)
                    dropped.append((toks, payload))
        self._free_displaced(dropped)
        with self._stats_lock:
            self._evict_hints_total += len(dropped)
        return {"evicted": len(dropped),
                "requested": len(want)}

    def prefix_handoff(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """POST /prefix/handoff: push this replica's prefix entries
        to a successor — the drain workflow's cache half (the router
        posts this between drain-complete and restart, so a rolling
        restart stops being a cache massacre).  Hottest entries
        first; device-tier entries ride too (gathered read-only —
        on a DRAINED replica the device lock is idle).  Serialization
        and every push happen OUTSIDE all locks, each over its own
        bounded connection; per-entry failures are counted and
        skipped, never raised — the restart must proceed whatever the
        successor says."""
        host = req.get("host")
        port = req.get("port")
        if not isinstance(host, str) or not host:
            raise ValueError("'host' must be a non-empty string")
        try:
            port = _int_param(port)
        except (TypeError, ValueError):
            raise ValueError("'port' must be an int")
        max_entries = req.get("max_entries")
        if max_entries is not None:
            max_entries = _int_param(max_entries)
            if max_entries < 1:
                raise ValueError("max_entries must be >= 1")
        include_device = req.get("include_device", True)
        if not isinstance(include_device, bool):
            raise ValueError("'include_device' must be a boolean")
        import http.client

        t0 = time.perf_counter()
        with self._prefix_lock:
            # entries() is coldest-first; the handoff budget should
            # go to the HOTTEST entries, so reverse.
            ents = [(t, p) for t, p in
                    reversed(self._prefix.entries())
                    if isinstance(p, _SpilledPrefix)
                    or (include_device
                        and isinstance(p, _PagedPrefix))]
        if max_entries is not None:
            ents = ents[:max_entries]
        sent = bytes_sent = failed = 0
        for ent_toks, payload in ents:
            blob = self._pack_entry_wire(ent_toks, payload)
            if blob is None:
                failed += 1
                continue
            try:
                conn = http.client.HTTPConnection(
                    host, port, timeout=self.prefix_fetch_timeout_s)
                try:
                    conn.request(
                        "POST", "/prefix/ingest", body=blob,
                        headers={"Content-Type":
                                 "application/octet-stream"})
                    resp = conn.getresponse()
                    body = resp.read()
                finally:
                    conn.close()
                out = json.loads(body or b"{}") \
                    if resp.status == 200 else {}
                if out.get("stored"):
                    sent += 1
                    bytes_sent += len(blob)
                elif out.get("reason") == "already_stored":
                    sent += 1   # the successor already holds it —
                    #             the handoff's goal state
                else:
                    failed += 1
            except (OSError, ValueError,
                    http.client.HTTPException):
                failed += 1
        with self._stats_lock:
            self._handoff_entries_total += sent
            self._handoff_bytes_total += bytes_sent
            self._handoff_failed_total += failed
        t_end = time.perf_counter()
        # The handoff span rides the shared trace ring, so the
        # stitched fleet timeline can attribute the restart's cache
        # migration cost next to the drain/restart spans.
        self._push_solo_events(
            [("prefix_handoff", t0, t_end,
              {"to": f"{host}:{port}", "entries": sent,
               "bytes": bytes_sent, "failed": failed})])
        return {"sent": sent, "bytes": bytes_sent,
                "failed": failed, "considered": len(ents),
                "wall_s": round(t_end - t0, 4)}

    def _prefix_wire_fetch(self, toks: np.ndarray,
                           hint: Dict[str, Any]):
        """Affinity-miss wire fetch (the client half): ask the
        router-designated holder for the spilled payload, verify it,
        admit it through the host tier, and serve THIS request from
        it.  Returns ``(PrefixHit, fetch_span_events)`` or None; every
        failure lands in ``prefix_fetch_failed_total{reason=}`` and
        falls back to re-prefill — the fetch tier is an optimization,
        never a request dependency.  No locks are held across any
        socket work."""
        host, port = hint.get("host"), hint.get("port")
        if not host or not port:
            self._note_fetch_failed("bad_hint")
            return None
        # Router-measured link estimates ride the hint (EWMA over
        # completed fetches/handoffs + probe RTTs): when present the
        # cost gate runs on observed truth for this link instead of
        # the policy's static defaults.
        def _est(key):
            v = hint.get(key)
            try:
                return None if v is None else float(v)
            except (TypeError, ValueError):
                return None

        link_bw = _est("wire_bytes_per_s")
        link_rtt = _est("rtt_s")
        n_tokens = int(toks.shape[1])
        ok, why = self.fetch_policy.should_fetch(
            n_tokens, 0, wire_bytes_per_s=link_bw, rtt_s=link_rtt)
        if not ok:
            self._note_fetch_failed(why)
            return None
        import http.client

        with self._stats_lock:
            self._fetch_attempts_total += 1
        t0 = time.perf_counter()
        blob = None
        try:
            conn = http.client.HTTPConnection(
                str(host), int(port),
                timeout=self.prefix_fetch_timeout_s)
            try:
                conn.request(
                    "POST", "/prefix/fetch",
                    body=json.dumps(
                        {"prompt": toks.tolist()}).encode(),
                    headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                if resp.status != 200:
                    resp.read()
                    self._note_fetch_failed(
                        "holder_miss" if resp.status == 404
                        else f"http_{resp.status}")
                    return None
                nbytes = int(resp.getheader("Content-Length") or 0)
                # The policy's second look, on the TRUE size, before
                # the body transfer: a veto here has paid one RTT and
                # headers, nothing more.
                ok, why = self.fetch_policy.should_fetch(
                    n_tokens, nbytes, wire_bytes_per_s=link_bw,
                    rtt_s=link_rtt)
                if not ok:
                    self._note_fetch_failed(why)
                    return None
                blob = resp.read()
            finally:
                conn.close()
        except (OSError, ValueError, http.client.HTTPException):
            self._note_fetch_failed("wire_error")
            return None
        try:
            ent_toks, leaves, pc, logits = unpack_spilled(blob)
        except WirePayloadError:
            self._note_fetch_failed("integrity")
            return None
        if ent_toks.shape[0] != toks.shape[0] or pc > n_tokens \
                or not np.array_equal(ent_toks, toks[:, :pc]):
            # Verified bytes but the WRONG prefix (a holder bug or a
            # stale hint): admitting it would poison the cache.
            self._note_fetch_failed("wrong_prefix")
            return None
        spilled = _SpilledPrefix(leaves, pc, logits)
        # Admit through the host tier (budget-gated) so later local
        # requests hit it without another wire trip...
        stored = False
        if spilled.nbytes <= self.kv_host_spill_bytes:
            with self._prefix_lock:
                anc = self._prefix.longest_ancestor(ent_toks)
                have = anc is not None \
                    and anc[0].shape[1] >= pc
                displaced = [] if have \
                    else self._prefix.store(ent_toks, spilled)
                stored = not have
            self._free_displaced(displaced)
            if stored:
                with self._stats_lock:
                    self._host_bytes += spilled.nbytes
                    self._host_entries += 1
                self._enforce_spill_budget()
        # ...then serve THIS request: the normal lookup path when the
        # entry landed (promotion and shared pages included), or a
        # direct re-materialization when it didn't — bitwise-
        # identical either way (rematerialize == materialize for the
        # same content).
        try:
            hit = self._prefix_lookup(toks) if stored else None
            if hit is None:
                hit = self._rematerialize_hit(ent_toks, spilled, pc)
        except Exception:
            self._note_fetch_failed("rematerialize")
            self._note_prefix_error("lookup")
            return None
        t_end = time.perf_counter()
        with self._stats_lock:
            self._fetch_hits_total += 1
            self._fetch_bytes_total += len(blob)
        events = [("prefix_wire_fetch", t0, t_end,
                   {"holder": str(hint.get("replica")
                                  or f"{host}:{port}"),
                    "bytes": len(blob), "tokens": int(pc)})]
        return hit, events

    def prefill_prompt(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """POST /prefill: register a prompt (prefix) in the prefix
        cache — the system-prompt workflow.  Later /generate requests
        whose prompt starts with it skip its prefill entirely."""
        self._check_not_draining()
        if not self._prefix_enabled:
            raise ValueError(
                "prefix cache is disabled on this server "
                "(start with --prefix-cache N)"
                + (" — it disabled itself after a store error; see "
                   "/info prefix_store_errors"
                   if self._prefix_store_errors else ""))
        import jax

        rows = _parse_prompt_rows(req, self.max_batch)
        cfg = getattr(self.model, "cfg", None)
        max_pos = getattr(cfg, "max_position", None)
        if max_pos is not None and len(rows[0]) > max_pos \
                and not getattr(cfg, "kv_cache_ring", False):
            # same contract as /generate: doomed requests fail in the
            # cheap validation layer, not at jit-trace time inside
            # the device lock (an over-capacity prefill would clamp
            # the cache write index into garbage).
            raise ValueError(
                f"prompt ({len(rows[0])}) exceeds the model's "
                f"max_position ({max_pos})")
        chunk = req.get("prefill_chunk")
        try:
            chunk = None if chunk is None else _int_param(chunk)
        except (TypeError, ValueError):
            # normalized 400, same contract as /generate (a list or
            # string here must not surface as a 500 TypeError)
            raise ValueError("prefill_chunk must be an int")
        if chunk is not None and chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        toks = np.asarray(rows, np.int32)
        t0 = time.perf_counter()
        with self._lock, self._exact():
            logits, cache, _ = self._split_fns(
                toks.shape[0], toks.shape[1], "pfill", chunk)(toks)
            jax.block_until_ready(logits)
        # Outside the device lock: the paged store re-acquires it for
        # its page scatter (locks never nest device -> prefix).
        self._prefix_store_safe(toks, logits, cache)
        with self._stats_lock:
            self.requests += 1
            self._lat_sum += time.perf_counter() - t0
            self._lat_count += 1
        return {"cached_rows": toks.shape[0],
                "cached_len": toks.shape[1],
                "entries": len(self._prefix)}

    def _generate_prefix_cached(self, toks: np.ndarray, p_len: int,
                                new: int, temp, top_k, top_p, eos,
                                chunk, seed, hit, deadline=None):
        """Solo decode through the split prefill/continue programs on
        a prefix-cache HIT, paying prefill only for the suffix (which
        is stored back, so sessions grow).  Exact: the split is the
        same program as fused generate (generate_continue's contract),
        and extension equals one-shot prefill (chunked-prefill
        contract).  Sampled hits run the position-keyed continue —
        token indices restart at 0 for the new tokens, so a warm hit
        draws the same stream a cold request would."""
        import jax
        import jax.random as jrandom

        from ..models import generate as G

        b = toks.shape[0]
        store_back = None
        try:
            with self._lock, self._exact():
                if deadline is not None \
                        and time.perf_counter() > deadline:
                    # Same contract as the other solo branches: the
                    # split decode is fused dispatches that can't stop
                    # mid-flight, so the deadline is honored up to the
                    # device-lock acquisition.
                    raise DeadlineExceeded(
                        "deadline exceeded waiting for the device "
                        "(prefix-cache solo path)")
                pc, logits, cache = hit.p_cached, hit.logits, hit.cache
                if pc < p_len:  # extend with the suffix, store back
                    suffix = toks[:, pc:]
                    logits, cache, _ = self._split_fns(
                        b, suffix.shape[1], "extend", chunk)(
                            cache, suffix, pc)
                    jax.block_until_ready(logits)
                    store_back = (logits, cache)
                if G.positional_eligible(self.model, temp):
                    keys = np.asarray(G.sample_stream_keys(seed, b))
                    fn = self._split_fns(b, None, "cont_pos", chunk,
                                         new=new, eos=eos)
                    out_new = np.asarray(jax.device_get(fn(
                        cache, logits, p_len, keys, np.float32(temp),
                        np.int32(top_k or 0),
                        np.float32(top_p or 0.0))))
                else:
                    out_new = np.asarray(jax.device_get(
                        self._split_fns(
                            b, None, "cont", chunk, new=new, temp=temp,
                            top_k=top_k, top_p=top_p, eos=eos)(
                            cache, logits, p_len,
                            jrandom.PRNGKey(seed))))
        finally:
            # The solo path never maps shared pages into a slot — the
            # materialized cache is an independent copy.
            self._unpin_prefix(hit.pins)
        if store_back is not None:
            # Outside the device lock: the paged store re-acquires
            # it.  Cold insertion: one speculative store-back per
            # request must never flush a registered system prompt.
            self._prefix_store_safe(toks, *store_back, hot=False)
        with self._stats_lock:
            self.requests += 1
            self.prefix_hits += 1
            self.prefix_hit_tokens += hit.p_cached
        return np.concatenate([toks, out_new], axis=1)

    # -- request handling -----------------------------------------------

    def generate(self, req: Dict[str, Any],
                 cancel_check=None,
                 rid: Optional[str] = None) -> Dict[str, Any]:
        import jax

        # Correlation ID: the HTTP handler passes the inbound (or
        # generated) X-Request-Id; library callers get one here so
        # every request carries an ID into its trace spans and its
        # history record whichever surface submitted it.
        if rid is None:
            rid = new_request_id()
        # Draining sheds BEFORE validation work: the router already
        # saw readiness drop; anything still arriving gets the
        # structured 503 immediately.
        self._check_not_draining()
        if self.role == "prefill":
            # A role-split fleet never routes /generate here (the
            # router's capability filter excludes prefill replicas);
            # a direct caller gets the typed 400 rather than a decode
            # stream quietly competing with the prefill tier.
            raise ValueError(
                "this replica runs role='prefill': it serves "
                "/prefill and /prefix/* only — send /generate to a "
                "decode-capable replica (role 'decode' or 'both')")
        rows = _parse_prompt_rows(req, self.max_batch)
        lens = [len(r) for r in rows]
        _int = _int_param

        def _float(v):
            # float(True) == 1.0: {"temperature": true} must not
            # silently switch greedy to temp-1.0 sampling.
            if isinstance(v, bool):
                raise ValueError("expected a number, got a boolean")
            return float(v)

        try:
            new = _int(req.get("max_new_tokens", 32))
            temp = _float(req.get("temperature", 0.0))
            top_k = req.get("top_k")
            top_k = None if top_k is None else _int(top_k)
            top_p = req.get("top_p")
            top_p = None if top_p is None else _float(top_p)
            eos = req.get("eos_id")
            eos = None if eos is None else _int(eos)
            beams = _int(req.get("num_beams", 1))
            seed = _int(req.get("seed", 0))
        except (TypeError, ValueError):
            raise ValueError(
                "sampling params must be scalars (temperature/top_p "
                "float, max_new_tokens/top_k/eos_id/num_beams/seed "
                "int, not booleans)")
        if new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # Uniform sampling-param validation: ONE message for every
        # path (engine, coalesce, solo, speculative, prefix hits),
        # raised here so doomed requests fail in this cheap layer —
        # never at jit-trace time inside the device lock, and never
        # differently depending on which batching mode fields them.
        from ..models.generate import (SPEC_BEAM_MSG,
                                       _check_spec_k,
                                       _check_temperature,
                                       _check_top_k, _check_top_p)

        _check_top_k(top_k, getattr(getattr(self.model, "cfg", None),
                                    "vocab_size", None))
        _check_top_p(top_p)
        _check_temperature(temp)
        if beams > 1 and (temp != 0.0 or top_k is not None
                          or top_p is not None):
            # Mirror the CLI: beam search is deterministic — dropping
            # sampling params silently would let a client believe it
            # sampled.
            raise ValueError(
                "beam search is deterministic; temperature/top_k/"
                "top_p cannot be combined with num_beams > 1")
        speculative = req.get("speculative", False)
        if not isinstance(speculative, bool):
            # bool("false") is True — a stringified flag must not
            # silently flip the decode mode.
            raise ValueError("'speculative' must be a JSON boolean")
        want_timings = req.get("timings", False)
        if not isinstance(want_timings, bool):
            raise ValueError("'timings' must be a JSON boolean")
        want_logits = req.get("logits", False)
        if not isinstance(want_logits, bool):
            raise ValueError("'logits' must be a JSON boolean")
        # Lifecycle params: the priority class (server default when
        # absent) and an optional relative deadline in ms — expiry
        # evicts the request at the next step boundary (504).
        priority = req.get("priority", self.default_priority)
        if priority not in PRIORITIES:
            raise ValueError(
                f"priority must be one of {list(PRIORITIES)}; got "
                f"{priority!r}")
        deadline_ms = req.get("deadline_ms")
        if deadline_ms is not None:
            try:
                deadline_ms = _int(deadline_ms)
            except (TypeError, ValueError):
                raise ValueError("deadline_ms must be an int")
            if deadline_ms < 1:
                raise ValueError("deadline_ms must be >= 1")
        deadline_s = None if deadline_ms is None \
            else deadline_ms / 1e3
        if speculative:
            if self.draft_model is None:
                raise ValueError(
                    "server has no draft model (start with "
                    "--draft-model to enable speculative decoding)")
            if beams > 1:
                raise ValueError(SPEC_BEAM_MSG)
            if temp == 0.0 and (top_k is not None
                                or top_p is not None):
                # dropping the flags silently would let a client
                # believe it sampled (same contract as num_beams)
                raise ValueError(
                    "speculative top_k/top_p need temperature > 0 "
                    "(temperature=0 is greedy and would ignore them)")
            try:
                spec_k = _int(req.get("spec_k", self.spec_k_default))
            except (TypeError, ValueError):
                raise ValueError("spec_k must be an int")
            _check_spec_k(spec_k)
        chunk = req.get("prefill_chunk")
        try:
            chunk = None if chunk is None else _int(chunk)
        except (TypeError, ValueError):
            raise ValueError("prefill_chunk must be an int")
        if chunk is not None and chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        p_len0 = lens[0]
        if chunk is not None and chunk >= p_len0:
            # a chunk covering the whole prompt IS the single-forward
            # program — normalize so identical programs share one
            # compile-cache slot
            chunk = None

        p_len = lens[0]
        # CROSS-REPLICA RESUME (docs/DESIGN.md): ``resume_tokens: N``
        # declares the trailing N prompt tokens a prior attempt's
        # committed output (a router failover replaying ``prompt ++
        # tokens_received_so_far``).  The engine re-enters the
        # request through the preempt-resume machinery, so sampled
        # draws continue at position key N — token-identical to the
        # uninterrupted run, per seed, on any replica.
        resume_tokens = req.get("resume_tokens", 0)
        try:
            resume_tokens = _int(resume_tokens)
        except (TypeError, ValueError):
            raise ValueError("resume_tokens must be an int")
        if resume_tokens < 0:
            raise ValueError("resume_tokens must be >= 0")
        if resume_tokens:
            if beams > 1:
                raise ValueError(
                    "resume_tokens cannot combine with beam search "
                    "(beam requests replay whole)")
            if self.engine is None:
                raise ValueError(
                    "resume_tokens requires the continuous-batching "
                    f"engine (batching={self.batching!r})")
            if len(rows) != 1:
                raise ValueError(
                    "resume_tokens takes a single-row request")
        # The EFFECTIVE prompt length: what the slot actually holds —
        # a resume replay's original prompt, not the concatenation.
        eff_p_len = p_len - resume_tokens
        if resume_tokens and eff_p_len < 1:
            raise ValueError(
                f"resume_tokens ({resume_tokens}) must leave at "
                f"least one original prompt token (prompt length "
                f"{p_len})")
        # Capacity checks for EVERY model a request will touch, so
        # doomed requests fail in this cheap validation layer instead
        # of inside the locked device section at jit-trace time.
        # Speculative rounds touch k-1 positions past the last
        # committed token (generate_speculative's guards).
        slack = (spec_k - 1) if speculative else 0
        models = [("model", self.model)]
        if speculative:
            models.append(("draft model", self.draft_model))
        for label, m in models:
            cfg = getattr(m, "cfg", None)
            max_pos = getattr(cfg, "max_position", None)
            if getattr(cfg, "kv_cache_ring", False):
                ring_slack = getattr(cfg, "kv_cache_ring_slack", 0)
                if speculative and ring_slack < spec_k - 1:
                    raise ValueError(
                        f"{label} needs kv_cache_ring_slack >= "
                        f"{spec_k - 1} for spec_k={spec_k} "
                        f"(got {ring_slack})")
                continue  # ring caches are position-keyed, unbounded
            if max_pos is not None \
                    and eff_p_len + new + slack > max_pos:
                raise ValueError(
                    f"prompt ({eff_p_len}) + max_new_tokens ({new})"
                    + (f" + spec_k-1 ({slack})" if slack else "")
                    + f" exceeds the {label}'s max_position "
                    f"({max_pos})")
        toks = np.asarray(rows, np.int32)

        t0 = time.perf_counter()
        # Prefix-cache hit (registered via /prefill): engine-eligible
        # B=1 hits (greedy OR sampled) ride the engine seeded with the
        # stored prefill; multi-row and engine-less hits decode from
        # it on the solo split path — beam tiles and speculative rolls
        # back the cache, so they stay cold.
        prefix_hit = None
        fetch_events = None
        if self._prefix_enabled and beams == 1 and not speculative \
                and not resume_tokens:
            # Resume replays skip the prefix store: the replayed
            # tokens ARE the state, and a store hit would re-seed a
            # stream the resume machinery is about to re-prefill.
            prefix_hit = self._prefix_lookup_safe(toks)
            if prefix_hit is None and self.prefix_fetch \
                    and isinstance(req.get("prefix_hint"), dict):
                # Local miss + a router hint naming the holder: try
                # the fleet tier.  Any failure inside lands in
                # prefix_fetch_failed_total{reason=} and leaves
                # prefix_hit None — this request just re-prefills.
                fetched = self._prefix_wire_fetch(
                    toks, req["prefix_hint"])
                if fetched is not None:
                    prefix_hit, fetch_events = fetched
        # Where this request's prefill came from — reported in the
        # response (the router learns holders from it) and in the
        # trace timeline's per-request "prefix source" column.
        if prefix_hit is None:
            prefix_source = "re_prefill"
        elif fetch_events is not None:
            prefix_source = "wire_fetch"
        elif prefix_hit.source == "host":
            prefix_source = "local_spilled"
        else:
            prefix_source = "local_hot"
        # Engine eligibility: any non-beam request on a decoder-only
        # model — greedy, sampled, AND speculative (the engine owns
        # the draft model whenever the server does).  temperature==0
        # streams are greedy (top_k/top_p inert, exactly like solo
        # _sample); temperature>0 streams sample per-slot under the
        # position-keyed RNG contract; speculative streams draft/
        # verify per round under the same contract — co-tenancy never
        # changes tokens on any lane.
        engine_ok = self.engine is not None and beams == 1
        if speculative and self.engine is None:
            # The satellite fix: engine-less modes used to drop
            # speculative requests to solo SILENTLY.
            self._note_fallback(
                "speculative",
                f"batching={self.batching!r} has no decode engine; "
                f"speculative requests hold the device lock for a "
                f"whole solo decode")
        if engine_ok and self.draft_model is not None:
            # A spec-capable pool verifies a cap+1-wide chunk per
            # round for EVERY resident, so every engine request —
            # co-tenants included — must leave cap-1 slack at the
            # cache end; and a spec_k above the cap would widen the
            # pool program past what co-tenants were admitted for.
            cap = self.spec_k_default
            cfg = getattr(self.model, "cfg", None)
            max_pos = getattr(cfg, "max_position", None)
            ring = getattr(cfg, "kv_cache_ring", False)
            if speculative and spec_k > cap:
                engine_ok = False
                self._note_fallback(
                    "speculative (spec_k over cap)",
                    f"request spec_k {spec_k} exceeds the engine cap "
                    f"{cap} (--spec-k); decoding solo")
            elif not ring and max_pos is not None \
                    and eff_p_len + new + cap - 1 > max_pos:
                engine_ok = False
                self._note_fallback(
                    "near-capacity",
                    f"prompt + max_new_tokens within {cap - 1} "
                    f"tokens of max_position ({max_pos}) cannot "
                    f"co-tenant a speculative pool (verify chunks "
                    f"are {cap + 1} wide); decoding solo")
        if resume_tokens and not engine_ok:
            # A request that fell off the engine (spec_k over cap,
            # near-capacity spec pool) replays WHOLE: solo paths have
            # no resume machinery, and silently restarting the RNG at
            # index 0 would break the token-identity contract.
            raise ValueError(
                "resume_tokens requires the engine path for this "
                "request (it fell back solo); replay the request "
                "without resume_tokens instead")
        sampling = None
        if speculative:
            sampling = SamplingSpec(seed, temp, top_k, top_p,
                                    spec_k=spec_k)
        elif temp != 0.0:
            sampling = SamplingSpec(seed, temp, top_k, top_p)
        # The coalescer merges plain greedy requests ONLY — beam and
        # speculative greedy requests must keep their solo programs
        # (a coalesced argmax batch would silently answer a beam
        # request with greedy tokens).
        greedy = temp == 0.0 and beams == 1 and not speculative
        breakdown = None
        # Telemetry anchors: ``group`` (engine paths) carries the
        # stream span lists + the TTFT anchor; solo/coalesce paths
        # collect their coarser spans in ``solo_events``.
        group = None
        solo_events = None
        if prefix_hit is not None and engine_ok \
                and toks.shape[0] == 1:
            # Prefix hit on the engine path: seed a stream with the
            # stored prefill so the request pays only its suffix (or
            # no prefill at all on a full-length hit) and DECODES IN A
            # SLOT like cold traffic — same decode program, and no
            # whole-decode device-lock hold stalling resident streams.
            # Paged engines additionally map the stored prefix's FULL
            # pages read-only into the admitted slot's table
            # (``shared_pages`` — copy-on-write sharing, so N hits of
            # one system prompt hold ONE copy of its KV); the engine
            # owns those pins once submit returns.
            pc, lg, cache = (prefix_hit.p_cached, prefix_hit.logits,
                             prefix_hit.cache)
            try:
                group = self.engine.submit(
                    toks, new, eos, chunk, sampling=sampling,
                    prefix=(pc, lg, cache),
                    on_prefilled=self._store_stream_prefix,
                    record_timings=want_timings,
                    priority=priority, deadline_s=deadline_s,
                    shared_pages=prefix_hit.pins or None,
                    rid=rid,
                    # Hit provenance for the history record: how
                    # many prompt tokens the stored prefill covered
                    # and how many pool pages the slot mapped
                    # read-only instead of refilling.
                    prefix_info={"cached_tokens": pc,
                                 "shared_pages":
                                     len(prefix_hit.pins or ()),
                                 "source": prefix_source},
                    pre_events=fetch_events)
            except BaseException:
                self._unpin_prefix(prefix_hit.pins)
                raise
            if fetch_events:
                # The wire-fetch span also rides the shared trace
                # ring so the stitched fleet timeline shows the
                # holder round-trip next to this request's spans.
                self._push_solo_events(list(fetch_events), rid=rid)
            self._wait_group(group, cancel_check)
            out = group.result()
            breakdown = group.breakdown()
            with self._stats_lock:
                self.requests += 1
                self.prefix_hits += 1
                self.prefix_hit_tokens += pc
        elif prefix_hit is not None:
            out = self._generate_prefix_cached(
                toks, p_len, new, temp, top_k, top_p, eos, chunk,
                seed, prefix_hit,
                deadline=t0 + deadline_s
                if deadline_s is not None else None)
            if fetch_events:
                self._push_solo_events(list(fetch_events), rid=rid)
            solo_events = self._emit_solo(t0, "prefix_solo",
                                          len(rows), rid=rid)
            if fetch_events:
                solo_events = list(fetch_events) + solo_events
        elif engine_ok:
            # CONTINUOUS BATCHING: per-row decode streams through the
            # slot pool.  Greedy streams ignore ``seed`` (greedy
            # decoding never consults the PRNG — identical output in
            # a slot or solo); sampled streams carry (seed,
            # temperature, top_k, top_p) into their slot and draw
            # token i with fold_in(fold_in(PRNGKey(seed), row), i).
            # May raise QueueFullError -> 429.
            group = self.engine.submit(toks, new, eos, chunk,
                                       sampling=sampling,
                                       record_timings=want_timings,
                                       priority=priority,
                                       deadline_s=deadline_s,
                                       rid=rid,
                                       resume_tokens=resume_tokens,
                                       record_logits=want_logits)
            self._wait_group(group, cancel_check)
            out = group.result()
            breakdown = group.breakdown()
            with self._stats_lock:
                self.requests += 1
        elif greedy and self._coalescer is not None:
            # Deadline is honored INSIDE the coalescer, at its one
            # boundary (post-lock, pre-dispatch) — same contract as
            # the solo branch's check under the device lock.
            out = self._coalescer.generate(
                toks, p_len, new, eos, chunk,
                deadline=t0 + deadline_s
                if deadline_s is not None else None)
            # The coalescer's queue wait is its device-lock wait,
            # folded inside generate() — one opaque span, honest
            # about the granularity this path offers.
            solo_events = self._emit_solo(t0, "coalesce_decode",
                                          len(rows), rid=rid)
        else:
            from ..models import generate as G

            positional = (not speculative and beams == 1
                          and G.positional_eligible(self.model, temp))
            # Sampled speculative solo runs the POSITION-KEYED seed
            # mode (generate_speculative keys=...), the same schedule
            # the engine's spec slots run — so a request returns the
            # same tokens whichever batching mode fields it.  Greedy
            # speculative has no randomness (its solo program already
            # equals the engine's greedy-spec commits).
            spec_pos = (speculative and temp != 0.0
                        and not hasattr(self.model, "encode"))
            if speculative:
                # last slot carries the draft length (see _fn)
                key = ("spec_pos" if spec_pos else "spec",
                       len(rows), p_len, new, temp, top_k,
                       top_p, eos, spec_k, chunk)
            elif beams > 1:
                key = ("beam", len(rows), p_len, new, temp, top_k,
                       top_p, eos, beams, chunk)
            elif positional:
                # decoder-only sampled solo (batching off/coalesce):
                # the position-keyed reference program — shaping
                # params fed at RUN TIME, so one compiled program per
                # shape serves every sampled combo, and the tokens
                # equal the engine's for the same request + seed
                key = ("sample_pos", len(rows), p_len, new, None,
                       None, None, eos, 1, chunk)
            else:
                key = ("sample", len(rows), p_len, new, temp, top_k,
                       top_p, eos, beams, chunk)
            t_lock = time.perf_counter()
            # one chip (or one mesh): serialize device work
            with self._lock, self._exact():
                import jax.random as jrandom

                queue_s = time.perf_counter() - t_lock
                if deadline_s is not None \
                        and time.perf_counter() - t0 > deadline_s:
                    # Solo programs are one fused dispatch — the
                    # deadline can only be honored BEFORE it (a
                    # request that expired waiting on the device
                    # lock sheds without burning device time).
                    raise DeadlineExceeded(
                        f"deadline exceeded after {queue_s:.3f}s "
                        f"waiting for the device (solo path)")
                fn = self._fn(key)
                if positional:
                    keys = np.asarray(
                        G.sample_stream_keys(seed, len(rows)))
                    out = np.asarray(jax.device_get(fn(
                        toks, keys, np.float32(temp),
                        np.int32(top_k or 0),
                        np.float32(top_p or 0.0))))
                elif spec_pos:
                    keys = np.asarray(
                        G.sample_stream_keys(seed, len(rows)))
                    out = np.asarray(jax.device_get(fn(toks, keys)))
                else:
                    out = np.asarray(jax.device_get(
                        fn(toks, jrandom.PRNGKey(seed))))
            with self._stats_lock:
                self.requests += 1
            breakdown = (queue_s, 0.0,
                         time.perf_counter() - t_lock - queue_s)
            t_end = time.perf_counter()
            solo_events = [
                ("queue", t_lock, t_lock + queue_s,
                 {"kind": key[0]}),
                ("solo_decode", t_lock + queue_s, t_end,
                 {"kind": key[0], "rows": len(rows)}),
                ("complete", t_end, t_end, {})]
            self._push_solo_events(solo_events, rid=rid)
        dt = time.perf_counter() - t0
        if breakdown is not None:
            self._note_breakdown(*breakdown)
            # Latency histograms (telemetry.py): queue-wait, prefill
            # and decode-per-token come from the phase breakdown;
            # solo requests report prefill 0 (fused into the decode
            # program — documented in docs/SERVING.md).  Per-token
            # divides by tokens actually DECODED: engine streams
            # evict at eos (len(out)), solo programs step the whole
            # budget (eos-frozen rows keep stepping).
            if group is not None:
                tokens_done = sum(len(s.out) for s in group.streams)
            else:
                tokens_done = len(rows) * new
            # Histogram KEY (telemetry.HIST_SPECS), not a ledger
            # phase reference.  # ptpu: ignore[PHASE-ENUM]
            self.telemetry.observe("queue_wait", breakdown[0],
                                   exemplar=rid)
            self.telemetry.observe("prefill", breakdown[1],
                                   exemplar=rid)
            self.telemetry.observe(
                "decode_per_token",
                breakdown[2] / max(1, tokens_done), exemplar=rid)
        # TTFT: the engine samples token 0 at admission; solo paths
        # deliver all tokens at once, so their client-visible TTFT is
        # the full latency.
        ttft = dt
        if group is not None and group.t_first_admit is not None:
            ttft = group.t_first_admit - group.t_submit
        self.telemetry.observe("ttft", ttft, exemplar=rid)
        self.telemetry.observe("total", dt, exemplar=rid)
        # Phase ledger (serving/forensics.py): the SAME function the
        # engine's history record runs, over the SAME events — the
        # timings block and GET /requests/<id> carry identical
        # ledgers by construction.  Solo paths (no engine terminal
        # hook) feed the forensics core from here.
        ledger = None
        if self.forensics is not None or want_timings:
            if group is not None:
                all_events: List = []
                for s in group.streams:
                    if s.events:
                        all_events.extend(s.events)
                t_done = group.t_done \
                    if group.t_done is not None else t0 + dt
                ledger = compute_ledger(all_events, group.t_submit,
                                        t_done)
            elif solo_events is not None:
                ledger = compute_ledger(solo_events, t0, t0 + dt,
                                        solo=True)
                if self.forensics is not None:
                    self.forensics.note(ledger, rid)
        timings = None
        if want_timings:
            timings = {"ttft_ms": round(1e3 * ttft, 3)}
            if group is not None:
                timings["streams"] = [
                    {"row": s.row,
                     "spans": _span_dicts(s.events or [],
                                          group.t_submit)}
                    for s in group.streams]
            elif solo_events is not None:
                timings["spans"] = _span_dicts(solo_events, t0)
            if ledger is not None:
                timings["phases"] = ledger
        with self._stats_lock:
            self._lat_sum += dt
            self._lat_count += 1
            self._tokens_out += len(rows) * new
        # Engine-path provenance for the response AND the access log
        # (log_access copies these fields): which slot(s) served the
        # request, and whether it was preempted/resumed along the way
        # — a resumed request must be distinguishable from a
        # straight-through one in the log.
        eng_fields: Dict[str, Any] = {}
        if group is not None:
            slots_used = [s.last_slot for s in group.streams
                          if s.last_slot is not None]
            if slots_used:
                eng_fields["slot"] = slots_used[0] \
                    if len(slots_used) == 1 else slots_used
            pre = sum(s.preempts for s in group.streams)
            res = sum(s.resumes for s in group.streams)
            if pre or res:
                eng_fields["preempts"] = pre
                eng_fields["resumes"] = res
        return {
            "model": self.model_name,
            "request_id": rid,
            "new_tokens": out[:, p_len:].tolist(),
            "tokens": out.tolist(),
            "wall_s": round(dt, 4),
            "tok_per_sec": round(len(rows) * new / dt, 1),
            **eng_fields,
            **({"queue_ms": round(1e3 * breakdown[0], 3),
                "prefill_ms": round(1e3 * breakdown[1], 3),
                "decode_ms": round(1e3 * breakdown[2], 3)}
               if breakdown is not None else {}),
            **({"prefix_hit_len": prefix_hit.p_cached}
               if prefix_hit is not None else {}),
            # Always present when the prefix store is armed: the
            # router's affinity learner and trace_report's "prefix
            # source" column both read it.
            **({"prefix_source": prefix_source}
               if self._prefix_enabled else {}),
            # Wire-fetch measurement for the router's link
            # calibration (EWMA wire_bytes_per_s): the observed
            # payload size + wall time of the fetch that served this
            # request, straight from its span.
            **({"prefix_fetch_bytes": fetch_events[0][3]["bytes"],
                "prefix_fetch_s": round(
                    fetch_events[0][2] - fetch_events[0][1], 6)}
               if fetch_events else {}),
            **({"timings": timings} if timings is not None else {}),
            **({"logits": self._logits_field(group)}
               if want_logits else {}),
        }

    @staticmethod
    def _logits_field(group) -> Optional[Dict[str, Any]]:
        """{"logits": true}: for row 0, the float32 logits each new
        token was chosen from, ``[tokens, vocab]`` little-endian in
        base64 — the prefill program's for the first, then a row of
        each decode dispatch, the engine's own programs at one step a
        dispatch.  None where the request left the engine (solo
        paths keep no logits) or was resumed after a preemption."""
        import base64

        rows = group.streams[0].step_logits if group is not None \
            else None
        if not rows or len(rows) != len(group.streams[0].out):
            return None
        block = np.ascontiguousarray(np.stack(rows), "<f4")
        return {"dtype": "float32", "shape": list(block.shape),
                "b64": base64.b64encode(block.tobytes()).decode()}

    # -- telemetry helpers ----------------------------------------------

    def _push_solo_events(self, events,
                          rid: Optional[str] = None) -> None:
        """Emit a solo/coalesce request's span tuples onto the shared
        trace ring (one fresh track per request).  ``rid`` is stamped
        into every span's args — solo paths must be as findable by
        request ID as engine paths (the correlation contract in
        docs/SERVING.md)."""
        tid = self.telemetry.new_tid()
        for name, a, b, args in events:
            if rid is not None:
                args.setdefault("rid", rid)
            if a == b:
                self.telemetry.instant(tid, name, a, **args)
            else:
                self.telemetry.span(tid, name, a, b, **args)

    def _emit_solo(self, t0: float, name: str, rows: int,
                   rid: Optional[str] = None):
        """One opaque span for paths whose internal phases are fused
        (coalescer, prefix-cache split decode): arrival -> now."""
        t_end = time.perf_counter()
        events = [(name, t0, t_end, {"rows": rows}),
                  ("complete", t_end, t_end, {})]
        self._push_solo_events(events, rid=rid)
        return events

    def _spill_stats(self) -> Dict[str, Any]:
        """The host-spill tier's counters — ONE dict rendered by
        BOTH /metrics and /info (the no-drift pin, like every prior
        PR's counter families)."""
        with self._stats_lock:
            return {
                "kv_host_spill_bytes": self._host_bytes,
                "kv_host_spill_bytes_budget":
                    self.kv_host_spill_bytes,
                "kv_host_entries": self._host_entries,
                "kv_host_spills_total": self._host_spills_total,
                "kv_host_dropped_total": self._host_dropped_total,
                "kv_rematerialize_hits_total": self._remat_hits_total,
                "kv_rematerialize_bytes_total":
                    self._remat_bytes_total,
                "kv_promotions_total": self._promotions_total,
                "prefix_fetch_total": self._fetch_attempts_total,
                "prefix_fetch_hits_total": self._fetch_hits_total,
                "prefix_fetch_bytes_total": self._fetch_bytes_total,
                "prefix_fetch_failed": dict(self._fetch_failed),
                "prefix_ingest_total": self._ingest_total,
                "prefix_ingest_rejected_total":
                    self._ingest_rejected_total,
                "prefix_handoff_entries_total":
                    self._handoff_entries_total,
                "prefix_handoff_bytes_total":
                    self._handoff_bytes_total,
                "prefix_handoff_failed_total":
                    self._handoff_failed_total,
                "prefix_evict_hints_total": self._evict_hints_total,
            }

    def _weights_stats(self) -> Dict[str, Any]:
        """The trees the programs are handed, target and draft, as
        they rest (serving/weights.py): one dict for /info and
        /metrics."""
        from .weights import weights_report

        return weights_report(
            [self.variables, self.draft_variables],
            getattr(getattr(self.model, "cfg", None), "dtype", None),
            self.weights_cast_bytes)

    def info(self) -> Dict[str, Any]:
        import jax

        from ..ops.attention import route_counts
        from ..ops.grouped_matmul import route_counts as matmul_routes
        from ..ops.selective_scan import route_counts as scan_routes

        cfg = getattr(self.model, "cfg", None)
        summary = {}
        if cfg is not None:
            for f in ("vocab_size", "hidden_size", "d_model",
                      "num_layers", "num_heads", "max_position",
                      "kv_cache_int8"):
                v = getattr(cfg, f, None)
                if v is not None:
                    summary[f] = v
        engine = self.engine.stats() if self.engine is not None else {}
        # Routing report: where each request class decodes on THIS
        # server config, plus the dynamic solo-fallback table (kinds
        # that dropped to solo at request time, with the logged
        # reason and a count).
        if self.engine is not None:
            spec_route = ("engine" if self.draft_model is not None
                          else "unavailable (no draft model)")
            routing = {"greedy": "engine", "sampled": "engine",
                       "speculative": spec_route, "beam": "solo"}
        else:
            routing = {
                "greedy": "coalesce" if self.batching == "coalesce"
                else "solo",
                "sampled": "solo",
                "speculative": "solo" if self.draft_model is not None
                else "unavailable (no draft model)",
                "beam": "solo"}
        with self._stats_lock:
            fallbacks = {k: dict(v)
                         for k, v in self.solo_fallbacks.items()}
        # Recompile sentinel in the routing report: a healthy routing
        # table with a climbing miss count under steady traffic means
        # some request property is leaking into program keys.
        compile_cache = self.recompile.snapshot()
        return {"model": self.model_name, "config": summary,
                "backend": jax.default_backend(),
                # Local attention calls traced so far, by path: the
                # Pallas kernel or the fused-XLA route it drops to
                # (ops/attention.py) — otherwise a silent decision.
                "attention_routes": route_counts(),
                # State-space scans traced so far, by path
                # (ops/selective_scan.py): the Pallas kernel or the
                # ``lax.scan`` it drops to.
                "scan_routes": scan_routes(),
                # The expert layers' grouped matmuls traced so far, by
                # path (ops/grouped_matmul.py): the Pallas kernel or
                # XLA's ``ragged_dot``.
                "grouped_matmul_routes": matmul_routes(),
                "max_batch": self.max_batch,
                "batching": self.batching,
                "role": self.role,
                "spec_k_default": self.spec_k_default,
                "default_priority": self.default_priority,
                # Engine-less modes still drain (solo/coalesce paths
                # shed at validation); the engine passthrough below
                # overwrites with its own latch, which drain() sets
                # in the same call.
                "draining": self.draining,
                "drain_rejected_total": self.drain_rejected,
                "routing": routing,
                "solo_fallbacks": fallbacks,
                "compile_cache_misses":
                    compile_cache["compile_cache_misses"],
                "compile_cache": compile_cache,
                **({"sanitizer": self.sanitizer.stats()}
                   if self.sanitizer is not None else {}),
                # Request-scoped debuggability: the history ring's
                # occupancy (GET /requests) and the stall watchdog's
                # arming/knobs + fire count when enabled.
                "debug": {
                    **self.history.stats(),
                    **({"watchdog": self.watchdog.status()}
                       if self.watchdog is not None else {})},
                # Flight-recorder attribution (serving/profiling.py):
                # summarized from the SAME published record /metrics
                # and GET /profile/report render.
                **({"profiling": self.recorder.info_block()}
                   if self.recorder is not None else {}),
                "compiled_shapes": len(self._fns),
                "requests": self.requests,
                "coalesced_batches": self.coalesced_batches,
                "coalesced_requests": self.coalesced_requests,
                "prefix_entries": len(self._prefix),
                "prefix_hits": self.prefix_hits,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_store_skips": self._prefix_store_skips,
                # Degradation ladder: error count + the live enabled
                # flag (False after a store error OR prefix_cache=0).
                "prefix_store_errors": self._prefix_store_errors,
                "prefix_enabled": self._prefix_enabled,
                # Fault tolerance: the supervisor's full status block
                # and the armed fault plan's counters (the engine
                # passthrough below carries the flat counter keys —
                # same engine.stats() dict /metrics renders).
                **({"supervisor": self.supervisor.status()}
                   if self.supervisor is not None else {}),
                **({"fault_plan": self.faults.stats()}
                   if self.faults is not None else {}),
                "kv_paged": self.kv_paged,
                "kv_lazy": self.kv_lazy,
                **self._weights_stats(),
                # Host-spill tier (tentpole b): bytes/entries/hit
                # counters from the same _spill_stats() dict /metrics
                # renders.
                **(self._spill_stats() if self.kv_paged else {}),
                # Fleet prefix cache: whether the wire-fetch client
                # is armed, and the policy curve it gates on.
                "prefix_fetch": self.prefix_fetch,
                **({"prefix_fetch_policy":
                    self.fetch_policy.describe()}
                   if self.prefix_fetch else {}),
                # The expert layers' pair counts, where the model
                # has expert layers (engine._moe_stats).
                **{k: engine[k] for k in
                   ("moe_pairs_routed_total", "moe_pairs_held_total",
                    "moe_expert_pairs", "moe_experts_touched_total")
                   if k in engine},
                # What the recurrent layers' state went through, where
                # the model keeps one (engine._ssm_stats).
                **{k: engine[k] for k in
                   ("ssm_scan_tokens_total", "ssm_state_steps_total")
                   if k in engine},
                # What the two paths of the latent attention layers
                # took, where the model has any (engine._latent_stats).
                **{k: engine[k] for k in LATENT_COUNTERS
                   if k in engine},
                **{k: engine[k] for k in
                   ("slots", "slots_active", "slot_occupancy",
                    "queue_len", "queue_depth", "admitted_total",
                    "admitted_greedy_total", "admitted_sampled_total",
                    "admitted_spec_total",
                    "evicted_total", "decode_steps_total",
                    "decode_dispatches_total",
                    "decode_dispatches_ahead_total",
                    "decode_serial_reasons",
                    "decode_upload_arrays_total",
                    "kv_pool_dispatches_total",
                    "kv_pool_in_place_total", "kv_pool_bytes",
                    "kv_pool_rest_bytes",
                    "kv_pool_bytes_by_kind", "kv_pool_lost_total",
                    "prefill_chunks_total", "prefill_tokens_total",
                    "kv_plane_rows_read_total",
                    "kv_plane_rows_held_total",
                    "kv_row_writes_total",
                    "completed_total",
                    "completed_greedy_total",
                    "completed_sampled_total",
                    "completed_spec_total",
                    "rejected_total",
                    "cancelled_total", "expired_total", "shed_total",
                    "shed_interactive_total", "shed_batch_total",
                    "preempted_total", "resumed_total",
                    "admitted_interactive_total",
                    "admitted_batch_total",
                    "queue_len_interactive", "queue_len_batch",
                    "draining",
                    "engine_down", "step_retries_total",
                    "requests_requeued_total", "poisoned_total",
                    "telemetry_errors_total",
                    "engine_crashes_total", "engine_restarts_total",
                    "breaker_state", "faults_injected_total",
                    "faults_injected",
                    "shed_kv_pages_total",
                    "kv_pages", "kv_page_tokens", "kv_pages_free",
                    "kv_pages_resident", "kv_pages_shared",
                    "kv_pages_lazy_growths_total",
                    "kv_pages_lazy_grown_total",
                    "kv_preempt_exhaustion_total",
                    "mesh", "mesh_devices", "kv_pool_shardings",
                    "step_device_seconds_total",
                    "step_wall_seconds_total", "step_device_share",
                    "spec_rounds_total", "spec_drafted_total",
                    "spec_accepted_total", "spec_accept_buckets",
                    "spec_accept_hist", "spec_accept_sum",
                    "spec_accept_count") if k in engine},
                **self.extra_info}

    def metrics_text(self) -> str:
        """Prometheus text exposition of the serving counters —
        the observability surface a scraping stack expects from an
        in-cluster `V1Service` (SURVEY §5.5).  Includes the
        per-request queue/prefill/decode phase breakdown (summaries)
        and the continuous-batching engine gauges."""
        # One rejection counter, owned by the admission queue (bumped
        # in submit) — the HTTP 429 path and in-process callers both
        # land there, so /metrics and /info can never disagree.
        from ..ops.selective_scan import route_counts as scan_routes

        es = self.engine.stats() if self.engine is not None else {}
        rejected = es.get("rejected_total", 0)
        stalls = self.watchdog.stalls_total \
            if self.watchdog is not None else 0
        with self._stats_lock:
            lat_sum, lat_count = self._lat_sum, self._lat_count
            toks, errs = self._tokens_out, self.errors
            q_sum, p_sum, d_sum, bd_count = (
                self._queue_s_sum, self._prefill_s_sum,
                self._decode_s_sum, self._breakdown_count)
        lines = [
            "# TYPE ptpu_serving_requests_total counter",
            f"ptpu_serving_requests_total {self.requests}",
            "# TYPE ptpu_serving_errors_total counter",
            f"ptpu_serving_errors_total {errs}",
            "# TYPE ptpu_serving_rejected_total counter",
            f"ptpu_serving_rejected_total {rejected}",
            "# TYPE ptpu_serving_tokens_generated_total counter",
            f"ptpu_serving_tokens_generated_total {toks}",
            "# TYPE ptpu_serving_coalesced_batches_total counter",
            f"ptpu_serving_coalesced_batches_total "
            f"{self.coalesced_batches}",
            "# TYPE ptpu_serving_coalesced_requests_total counter",
            f"ptpu_serving_coalesced_requests_total "
            f"{self.coalesced_requests}",
            "# TYPE ptpu_serving_request_seconds summary",
            f"ptpu_serving_request_seconds_sum {lat_sum:.6f}",
            f"ptpu_serving_request_seconds_count {lat_count}",
            # Phase breakdown: queue (waiting for prefill/device),
            # prefill (prompt consumption), decode (token generation).
            "# TYPE ptpu_serving_queue_seconds summary",
            f"ptpu_serving_queue_seconds_sum {q_sum:.6f}",
            f"ptpu_serving_queue_seconds_count {bd_count}",
            "# TYPE ptpu_serving_prefill_seconds summary",
            f"ptpu_serving_prefill_seconds_sum {p_sum:.6f}",
            f"ptpu_serving_prefill_seconds_count {bd_count}",
            "# TYPE ptpu_serving_decode_seconds summary",
            f"ptpu_serving_decode_seconds_sum {d_sum:.6f}",
            f"ptpu_serving_decode_seconds_count {bd_count}",
            "# TYPE ptpu_serving_compiled_programs gauge",
            f"ptpu_serving_compiled_programs {len(self._fns)}",
            "# TYPE ptpu_serving_prefix_hits_total counter",
            f"ptpu_serving_prefix_hits_total {self.prefix_hits}",
            "# TYPE ptpu_serving_prefix_entries gauge",
            f"ptpu_serving_prefix_entries {len(self._prefix)}",
            # Prefix-reuse in TOKENS: prompt tokens served from a
            # stored prefill instead of fresh prefill work (the
            # shared-prefix bench leg's assertion target).
            "# TYPE ptpu_serving_prefix_hit_tokens_total counter",
            f"ptpu_serving_prefix_hit_tokens_total "
            f"{self.prefix_hit_tokens}",
            # 503s shed at the drain gate (before the engine sees the
            # request) — every batching mode has this path, so it is
            # a server counter, not an engine one.
            "# TYPE ptpu_serving_drain_rejected_total counter",
            f"ptpu_serving_drain_rejected_total "
            f"{self.drain_rejected}",
            # Request-history ring occupancy (GET /requests): how
            # many terminal records are retained vs the capacity
            # knob, and how many have rolled off the ring.
            "# TYPE ptpu_serving_request_records gauge",
            f"ptpu_serving_request_records {len(self.history)}",
            "# TYPE ptpu_serving_request_records_evicted_total "
            "counter",
            f"ptpu_serving_request_records_evicted_total "
            f"{self.history.evicted_total}",
            # Stall-watchdog fires (0 and absent-watchdog both read
            # 0, so dashboards can alert on any increase without
            # caring whether the knob is armed).
            "# TYPE ptpu_serving_stalls_total counter",
            f"ptpu_serving_stalls_total {stalls}",
        ]
        ws = self._weights_stats()
        lines += [
            "# TYPE ptpu_serving_weights_bytes gauge",
            f"ptpu_serving_weights_bytes {ws['weights_bytes']}",
            "# TYPE ptpu_serving_weights_cast_bytes gauge",
            f"ptpu_serving_weights_cast_bytes "
            f"{ws['weights_cast_bytes']}",
            "# TYPE ptpu_serving_weights_bytes_by_dtype gauge",
            *(f'ptpu_serving_weights_bytes_by_dtype{{dtype="{k}"}} {v}'
              for k, v in ws["weights_bytes_by_dtype"].items()),
        ]
        # Recompile sentinel (analysis/recompile.py): ONE counter set
        # across the server/engine/slot program caches, rendered by
        # the shared telemetry helper (same module as the histogram
        # exposition, so /metrics and /info can never drift).
        lines += render_compile_cache(self.recompile.snapshot())
        if self.recorder is not None:
            # Flight-recorder attribution gauges (collective/host-gap/
            # device-busy shares + serving MFU): rendered from the
            # SAME record GET /profile/report returns — one
            # reduction, no drift (serving/profiling.py).
            lines += self.recorder.metrics_lines()
        # Latency histograms (queue-wait, prefill, decode-per-token,
        # TTFT, total) — rendered by the same telemetry helper as the
        # spec-acceptance histogram below, so every histogram on this
        # endpoint shares one exposition path.
        lines += self.telemetry.metrics_lines()
        # Per-phase forensics families (serving/forensics.py):
        # cumulative seconds + wall share per ledger phase, and the
        # sentry's anomaly counter — labeled families whose TYPE
        # lines render unconditionally, so the fleet federation sees
        # them before first traffic.
        if self.forensics is not None:
            lines += self.forensics.metrics_lines("ptpu_serving")
        if self.engine is not None:
            lines += [
                "# TYPE ptpu_serving_slots gauge",
                f"ptpu_serving_slots {es['slots']}",
                "# TYPE ptpu_serving_slots_active gauge",
                f"ptpu_serving_slots_active {es['slots_active']}",
                # resident/total as a ready-made 0..1 ratio, so pool
                # utilization under mixed load needs no PromQL join
                "# TYPE ptpu_serving_slot_occupancy gauge",
                f"ptpu_serving_slot_occupancy {es['slot_occupancy']}",
                "# TYPE ptpu_serving_queue_len gauge",
                f"ptpu_serving_queue_len {es['queue_len']}",
                "# TYPE ptpu_serving_queue_depth gauge",
                f"ptpu_serving_queue_depth {es['queue_depth']}",
                "# TYPE ptpu_serving_admitted_total counter",
                f"ptpu_serving_admitted_total {es['admitted_total']}",
                # admissions/completions split by decode mode: how
                # much of the pool mixed traffic actually gives to
                # sampled streams
                "# TYPE ptpu_serving_admitted_greedy_total counter",
                f"ptpu_serving_admitted_greedy_total "
                f"{es['admitted_greedy_total']}",
                "# TYPE ptpu_serving_admitted_sampled_total counter",
                f"ptpu_serving_admitted_sampled_total "
                f"{es['admitted_sampled_total']}",
                "# TYPE ptpu_serving_admitted_spec_total counter",
                f"ptpu_serving_admitted_spec_total "
                f"{es['admitted_spec_total']}",
                "# TYPE ptpu_serving_completed_total counter",
                f"ptpu_serving_completed_total "
                f"{es['completed_total']}",
                "# TYPE ptpu_serving_completed_greedy_total counter",
                f"ptpu_serving_completed_greedy_total "
                f"{es['completed_greedy_total']}",
                "# TYPE ptpu_serving_completed_sampled_total counter",
                f"ptpu_serving_completed_sampled_total "
                f"{es['completed_sampled_total']}",
                "# TYPE ptpu_serving_completed_spec_total counter",
                f"ptpu_serving_completed_spec_total "
                f"{es['completed_spec_total']}",
                # Request lifecycle: terminal-status counters, the
                # preempt/resume pair, the per-class splits, and the
                # drain latch — all from the same engine.stats()
                # dict /info reports.
                "# TYPE ptpu_serving_cancelled_total counter",
                f"ptpu_serving_cancelled_total "
                f"{es['cancelled_total']}",
                "# TYPE ptpu_serving_deadline_expired_total counter",
                f"ptpu_serving_deadline_expired_total "
                f"{es['expired_total']}",
                "# TYPE ptpu_serving_shed_total counter",
                f"ptpu_serving_shed_total {es['shed_total']}",
                "# TYPE ptpu_serving_shed_interactive_total counter",
                f"ptpu_serving_shed_interactive_total "
                f"{es['shed_interactive_total']}",
                "# TYPE ptpu_serving_shed_batch_total counter",
                f"ptpu_serving_shed_batch_total "
                f"{es['shed_batch_total']}",
                "# TYPE ptpu_serving_preempted_total counter",
                f"ptpu_serving_preempted_total "
                f"{es['preempted_total']}",
                "# TYPE ptpu_serving_resumed_total counter",
                f"ptpu_serving_resumed_total {es['resumed_total']}",
                # Page-shed and exhaustion-preempt counters live in
                # engine.stats() on EVERY layout (0 on fixed lanes),
                # so they render unconditionally — the structural
                # no-drift walk covers fixed-lane servers too.
                "# TYPE ptpu_serving_shed_kv_pages_total counter",
                f"ptpu_serving_shed_kv_pages_total "
                f"{es['shed_kv_pages_total']}",
                "# TYPE ptpu_serving_kv_preempt_exhaustion_total "
                "counter",
                f"ptpu_serving_kv_preempt_exhaustion_total "
                f"{es['kv_preempt_exhaustion_total']}",
                # Fault tolerance (serving/faults.py + recovery.py):
                # step retries, requeue-and-resume events, quarantine
                # convictions, supervised crash/restart totals, the
                # breaker gauge, and the per-site injected-fault
                # split — all from the same engine.stats() dict
                # /info reports (no-drift pin, tests/test_faults.py).
                "# TYPE ptpu_serving_step_retries_total counter",
                f"ptpu_serving_step_retries_total "
                f"{es['step_retries_total']}",
                "# TYPE ptpu_serving_requests_requeued_total counter",
                f"ptpu_serving_requests_requeued_total "
                f"{es['requests_requeued_total']}",
                "# TYPE ptpu_serving_poisoned_total counter",
                f"ptpu_serving_poisoned_total "
                f"{es['poisoned_total']}",
                "# TYPE ptpu_serving_telemetry_errors_total counter",
                f"ptpu_serving_telemetry_errors_total "
                f"{es['telemetry_errors_total']}",
                "# TYPE ptpu_serving_engine_crashes_total counter",
                f"ptpu_serving_engine_crashes_total "
                f"{es['engine_crashes_total']}",
                "# TYPE ptpu_serving_engine_restarts_total counter",
                f"ptpu_serving_engine_restarts_total "
                f"{es['engine_restarts_total']}",
                "# TYPE ptpu_serving_engine_down gauge",
                f"ptpu_serving_engine_down "
                f"{1 if es['engine_down'] else 0}",
                "# TYPE ptpu_serving_breaker_open gauge",
                f"ptpu_serving_breaker_open "
                f"{1 if es['breaker_state'] == 'open' else 0}",
                "# TYPE ptpu_serving_faults_injected_total counter",
                *[f'ptpu_serving_faults_injected_total'
                  f'{{site="{site}"}} {n}'
                  for site, n in sorted(
                      es["faults_injected"].items())],
                "# TYPE ptpu_serving_prefix_store_errors_total "
                "counter",
                f"ptpu_serving_prefix_store_errors_total "
                f"{self._prefix_store_errors}",
                "# TYPE ptpu_serving_admitted_interactive_total "
                "counter",
                f"ptpu_serving_admitted_interactive_total "
                f"{es['admitted_interactive_total']}",
                "# TYPE ptpu_serving_admitted_batch_total counter",
                f"ptpu_serving_admitted_batch_total "
                f"{es['admitted_batch_total']}",
                "# TYPE ptpu_serving_queue_len_interactive gauge",
                f"ptpu_serving_queue_len_interactive "
                f"{es['queue_len_interactive']}",
                "# TYPE ptpu_serving_queue_len_batch gauge",
                f"ptpu_serving_queue_len_batch "
                f"{es['queue_len_batch']}",
                "# TYPE ptpu_serving_draining gauge",
                f"ptpu_serving_draining "
                f"{1 if es['draining'] else 0}",
                "# TYPE ptpu_serving_evicted_total counter",
                f"ptpu_serving_evicted_total {es['evicted_total']}",
                "# TYPE ptpu_serving_decode_steps_total counter",
                f"ptpu_serving_decode_steps_total "
                f"{es['decode_steps_total']}",
                "# TYPE ptpu_serving_decode_dispatches_total counter",
                f"ptpu_serving_decode_dispatches_total "
                f"{es['decode_dispatches_total']}",
                "# TYPE ptpu_serving_decode_dispatches_ahead_total "
                "counter",
                f"ptpu_serving_decode_dispatches_ahead_total "
                f"{es['decode_dispatches_ahead_total']}",
                "# TYPE ptpu_serving_decode_serial_reasons counter",
                *(f'ptpu_serving_decode_serial_reasons'
                  f'{{reason="{reason}"}} {n}' for reason, n in
                  sorted(es["decode_serial_reasons"].items())),
                "# TYPE ptpu_serving_decode_upload_arrays_total counter",
                f"ptpu_serving_decode_upload_arrays_total "
                f"{es['decode_upload_arrays_total']}",
                "# TYPE ptpu_serving_kv_pool_dispatches_total counter",
                f"ptpu_serving_kv_pool_dispatches_total "
                f"{es['kv_pool_dispatches_total']}",
                "# TYPE ptpu_serving_kv_pool_in_place_total counter",
                f"ptpu_serving_kv_pool_in_place_total "
                f"{es['kv_pool_in_place_total']}",
                "# TYPE ptpu_serving_kv_pool_bytes gauge",
                f"ptpu_serving_kv_pool_bytes {es['kv_pool_bytes']}",
                "# TYPE ptpu_serving_kv_pool_rest_bytes gauge",
                f"ptpu_serving_kv_pool_rest_bytes "
                f"{es['kv_pool_rest_bytes']}",
                "# TYPE ptpu_serving_kv_pool_lost_total counter",
                f"ptpu_serving_kv_pool_lost_total "
                f"{es['kv_pool_lost_total']}",
                "# TYPE ptpu_serving_prefill_chunks_total counter",
                f"ptpu_serving_prefill_chunks_total "
                f"{es['prefill_chunks_total']}",
                "# TYPE ptpu_serving_prefill_tokens_total counter",
                f"ptpu_serving_prefill_tokens_total "
                f"{es['prefill_tokens_total']}",
                "# TYPE ptpu_serving_kv_plane_rows_read_total counter",
                f"ptpu_serving_kv_plane_rows_read_total "
                f"{es['kv_plane_rows_read_total']}",
                "# TYPE ptpu_serving_kv_plane_rows_held_total counter",
                f"ptpu_serving_kv_plane_rows_held_total "
                f"{es['kv_plane_rows_held_total']}",
                "# TYPE ptpu_serving_kv_row_writes_total counter",
                f"ptpu_serving_kv_row_writes_total "
                f"{es['kv_row_writes_total']}",
                "# TYPE ptpu_serving_kv_pool_bytes_by_kind gauge",
                *(f'ptpu_serving_kv_pool_bytes_by_kind{{kind="{k}"}} '
                  f"{v}" for k, v in
                  es["kv_pool_bytes_by_kind"].items()),
                *([
                    "# TYPE ptpu_serving_moe_pairs_routed_total counter",
                    f"ptpu_serving_moe_pairs_routed_total "
                    f"{es['moe_pairs_routed_total']}",
                    "# TYPE ptpu_serving_moe_pairs_held_total counter",
                    f"ptpu_serving_moe_pairs_held_total "
                    f"{es['moe_pairs_held_total']}",
                    "# TYPE ptpu_serving_moe_expert_pairs counter",
                    *(f'ptpu_serving_moe_expert_pairs{{expert="{i}"}} '
                      f"{n}" for i, n in
                      enumerate(es["moe_expert_pairs"])),
                    "# TYPE ptpu_serving_moe_experts_touched_total "
                    "counter",
                    f"ptpu_serving_moe_experts_touched_total "
                    f"{es['moe_experts_touched_total']}",
                ] if "moe_pairs_routed_total" in es else []),
                *([
                    "# TYPE ptpu_serving_ssm_scan_tokens_total counter",
                    f"ptpu_serving_ssm_scan_tokens_total "
                    f"{es['ssm_scan_tokens_total']}",
                    "# TYPE ptpu_serving_ssm_state_steps_total counter",
                    f"ptpu_serving_ssm_state_steps_total "
                    f"{es['ssm_state_steps_total']}",
                    "# TYPE ptpu_serving_scan_routes counter",
                    *(f'ptpu_serving_scan_routes{{route="{k}"}} {n}'
                      for k, n in scan_routes().items()),
                ] if "ssm_scan_tokens_total" in es else []),
                *(line for name in LATENT_COUNTERS if name in es
                  for line in (
                      f"# TYPE ptpu_serving_{name} counter",
                      f"ptpu_serving_{name} {es[name]}")),
                # Speculative scheduling counters + the per-request
                # acceptance-rate histogram — rendered from the SAME
                # engine.stats() dict /info reports, so the two
                # endpoints can never drift.
                "# TYPE ptpu_serving_spec_rounds_total counter",
                f"ptpu_serving_spec_rounds_total "
                f"{es['spec_rounds_total']}",
                "# TYPE ptpu_serving_spec_drafted_total counter",
                f"ptpu_serving_spec_drafted_total "
                f"{es['spec_drafted_total']}",
                "# TYPE ptpu_serving_spec_accepted_total counter",
                f"ptpu_serving_spec_accepted_total "
                f"{es['spec_accepted_total']}",
            ]
            if "mesh" in es:
                # Mesh topology (meshed engines only).  Axis sizes
                # render as one labeled gauge per active axis.
                lines += [
                    "# TYPE ptpu_serving_mesh_devices gauge",
                    f"ptpu_serving_mesh_devices {es['mesh_devices']}",
                    "# TYPE ptpu_serving_mesh_axis_size gauge",
                ]
                for axis, size in sorted(es["mesh"]["axes"].items()):
                    lines.append(
                        f'ptpu_serving_mesh_axis_size{{axis="{axis}"}}'
                        f' {size}')
            if "step_device_seconds_total" in es:
                # The per-step device-share counters, meshed or not
                # (host clock around dispatch + sync; on a mesh they
                # feed the bench's tp=1-vs-tpN collective-share
                # derivation, see engine.stats()).
                lines += [
                    "# TYPE ptpu_serving_step_device_seconds_total "
                    "counter",
                    f"ptpu_serving_step_device_seconds_total "
                    f"{es['step_device_seconds_total']}",
                    "# TYPE ptpu_serving_step_wall_seconds_total "
                    "counter",
                    f"ptpu_serving_step_wall_seconds_total "
                    f"{es['step_wall_seconds_total']}",
                    "# TYPE ptpu_serving_step_device_share gauge",
                    f"ptpu_serving_step_device_share "
                    f"{es['step_device_share'] or 0}",
                ]
            if "kv_pages" in es:
                # Paged-KV page-pool gauges (kv_paged engines only):
                # the occupancy surface the block-table refactor
                # exists for, plus the can-never-fit shed split.
                lines += [
                    "# TYPE ptpu_serving_kv_pages gauge",
                    f"ptpu_serving_kv_pages {es['kv_pages']}",
                    "# TYPE ptpu_serving_kv_page_tokens gauge",
                    f"ptpu_serving_kv_page_tokens "
                    f"{es['kv_page_tokens']}",
                    "# TYPE ptpu_serving_kv_pages_free gauge",
                    f"ptpu_serving_kv_pages_free "
                    f"{es['kv_pages_free']}",
                    "# TYPE ptpu_serving_kv_pages_resident gauge",
                    f"ptpu_serving_kv_pages_resident "
                    f"{es['kv_pages_resident']}",
                    "# TYPE ptpu_serving_kv_pages_shared gauge",
                    f"ptpu_serving_kv_pages_shared "
                    f"{es['kv_pages_shared']}",
                    # Tiered KV memory (PR 12): lazy growth/preempt
                    # counters from the same engine.stats() dict, and
                    # the host-spill tier's gauges from ONE
                    # _spill_stats() dict shared with /info.
                    "# TYPE ptpu_serving_kv_lazy gauge",
                    f"ptpu_serving_kv_lazy "
                    f"{1 if es['kv_lazy'] else 0}",
                    "# TYPE ptpu_serving_kv_pages_lazy_growths_total "
                    "counter",
                    f"ptpu_serving_kv_pages_lazy_growths_total "
                    f"{es['kv_pages_lazy_growths_total']}",
                    "# TYPE ptpu_serving_kv_pages_lazy_grown_total "
                    "counter",
                    f"ptpu_serving_kv_pages_lazy_grown_total "
                    f"{es['kv_pages_lazy_grown_total']}",
                ]
                sp = self._spill_stats()
                lines += [
                    "# TYPE ptpu_serving_kv_host_spill_bytes gauge",
                    f"ptpu_serving_kv_host_spill_bytes "
                    f"{sp['kv_host_spill_bytes']}",
                    "# TYPE ptpu_serving_kv_host_entries gauge",
                    f"ptpu_serving_kv_host_entries "
                    f"{sp['kv_host_entries']}",
                    "# TYPE ptpu_serving_kv_host_spills_total counter",
                    f"ptpu_serving_kv_host_spills_total "
                    f"{sp['kv_host_spills_total']}",
                    "# TYPE ptpu_serving_kv_rematerialize_hits_total "
                    "counter",
                    f"ptpu_serving_kv_rematerialize_hits_total "
                    f"{sp['kv_rematerialize_hits_total']}",
                    "# TYPE ptpu_serving_kv_rematerialize_bytes_total "
                    "counter",
                    f"ptpu_serving_kv_rematerialize_bytes_total "
                    f"{sp['kv_rematerialize_bytes_total']}",
                    "# TYPE ptpu_serving_kv_host_dropped_total "
                    "counter",
                    f"ptpu_serving_kv_host_dropped_total "
                    f"{sp['kv_host_dropped_total']}",
                    "# TYPE ptpu_serving_kv_promotions_total counter",
                    f"ptpu_serving_kv_promotions_total "
                    f"{sp['kv_promotions_total']}",
                    "# TYPE ptpu_serving_prefix_fetch_total counter",
                    f"ptpu_serving_prefix_fetch_total "
                    f"{sp['prefix_fetch_total']}",
                    "# TYPE ptpu_serving_prefix_fetch_hits_total "
                    "counter",
                    f"ptpu_serving_prefix_fetch_hits_total "
                    f"{sp['prefix_fetch_hits_total']}",
                    "# TYPE ptpu_serving_prefix_fetch_bytes_total "
                    "counter",
                    f"ptpu_serving_prefix_fetch_bytes_total "
                    f"{sp['prefix_fetch_bytes_total']}",
                    # The TYPE line renders even with no failures yet
                    # — scrapers (and the no-drift walk) see the
                    # family exists before its first labeled sample.
                    "# TYPE ptpu_serving_prefix_fetch_failed_total "
                    "counter",
                ]
                lines += [
                    f"ptpu_serving_prefix_fetch_failed_total"
                    f'{{reason="{r}"}} {n}'
                    for r, n in sorted(
                        sp["prefix_fetch_failed"].items())
                ]
                lines += [
                    "# TYPE ptpu_serving_prefix_ingest_total counter",
                    f"ptpu_serving_prefix_ingest_total "
                    f"{sp['prefix_ingest_total']}",
                    "# TYPE ptpu_serving_prefix_ingest_rejected_total "
                    "counter",
                    f"ptpu_serving_prefix_ingest_rejected_total "
                    f"{sp['prefix_ingest_rejected_total']}",
                    "# TYPE ptpu_serving_prefix_handoff_entries_total "
                    "counter",
                    f"ptpu_serving_prefix_handoff_entries_total "
                    f"{sp['prefix_handoff_entries_total']}",
                    "# TYPE ptpu_serving_prefix_handoff_bytes_total "
                    "counter",
                    f"ptpu_serving_prefix_handoff_bytes_total "
                    f"{sp['prefix_handoff_bytes_total']}",
                    "# TYPE ptpu_serving_prefix_handoff_failed_total "
                    "counter",
                    f"ptpu_serving_prefix_handoff_failed_total "
                    f"{sp['prefix_handoff_failed_total']}",
                    "# TYPE ptpu_serving_prefix_evict_hints_total "
                    "counter",
                    f"ptpu_serving_prefix_evict_hints_total "
                    f"{sp['prefix_evict_hints_total']}",
                ]
            # The acceptance-rate histogram renders through the SAME
            # shared helper as the latency histograms, from the same
            # engine.stats() dict /info reports.
            lines += render_histogram(
                "ptpu_serving_spec_accept_rate",
                es["spec_accept_buckets"], es["spec_accept_hist"],
                es["spec_accept_sum"], es["spec_accept_count"])
        return "\n".join(lines) + "\n"


def _disconnect_probe(conn):
    """A zero-cost poll for "is the client still there?" used while a
    handler thread waits on an engine group: after the request body,
    a well-behaved client sends NOTHING until the response — so a
    readable socket whose peek returns b"" means the peer closed.
    (A pipelined second request also reads as readable; its non-empty
    peek keeps the request alive, which is the conservative side.)

    Known limitation: a client HALF-close (``shutdown(SHUT_WR)``
    after the body, still reading) is indistinguishable from a full
    close at this API — its request is cancelled too.  That matches
    the common async-server convention (an empty read IS "client
    disconnected"), and half-closing writers mid-request are rare
    enough that reclaiming the slot wins; a client that wants the
    response must keep its write side open."""
    def check() -> bool:
        try:
            # poll(), not select(): select is FD_SETSIZE-bound, so
            # at ~1024+ open fds (many waiting clients) it raises
            # ValueError for high-numbered connections — which the
            # except branch would misread as "client gone" and
            # spuriously cancel live requests.  poll has no fd
            # limit; ValueError now only means a genuinely closed
            # socket (fileno() == -1).
            p = select.poll()
            p.register(conn.fileno(), select.POLLIN)
            if not p.poll(0):
                return False
            return conn.recv(1, socket.MSG_PEEK) == b""
        except (OSError, ValueError):
            return True     # probe failed: the socket is gone
    return check


class _ServingHTTPServer(ThreadingHTTPServer):
    # Stdlib default backlog is 5: a burst of concurrent clients
    # beyond it hits kernel SYN retransmits (~1s latency spikes that
    # look like serving stalls).  The admission queue, not the listen
    # backlog, is the intended backpressure surface.
    request_queue_size = 128
    daemon_threads = True


def make_server(host: str, port: int, ms: ModelServer
                ) -> ThreadingHTTPServer:
    return _ServingHTTPServer((host, port), make_handler(ms))


def make_handler(ms: ModelServer):
    """The request-handler CLASS for ``ms`` (what ``make_server``
    binds).  Exposed separately so the router tier's in-process
    replicas (serving/router.py LocalReplica) can mount the same
    handler on their chaos-capable HTTP server."""
    class Handler(BaseHTTPRequestHandler):
        def _req_id(self) -> str:
            """This request's correlation ID: the inbound
            ``X-Request-Id`` when usable, else generated.  Called at
            the top of every do_* (handler instances serve multiple
            keep-alive requests, so the field must refresh per
            request); ``_send_raw`` echoes it on EVERY response —
            success, 4xx, and 5xx alike."""
            rid = sanitize_request_id(
                self.headers.get("X-Request-Id"))
            self._rid = rid or new_request_id()
            return self._rid

        def _send_raw(self, code: int, body: bytes, ctype: str,
                      extra=None) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            rid = getattr(self, "_rid", None)
            if rid is None:
                rid = self._rid = new_request_id()
            self.send_header("X-Request-Id", rid)
            for k, v in (extra or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send(self, code: int, obj: Dict[str, Any],
                  extra=None) -> None:
            self._send_raw(code, json.dumps(obj).encode(),
                           "application/json", extra)

        def log_message(self, fmt, *args):
            # Quiet by default; the structured per-request access log
            # (ms.log_access, --access-log) replaces this — the
            # stdlib's format can't carry status/kind/tokens/latency.
            pass

        def do_GET(self):
            self._req_id()
            path = urlparse(self.path).path
            if path == "/requests" or path.startswith("/requests/") \
                    or path == "/debug/state":
                self._do_debug_get(path)
                return
            if self.path == "/healthz":
                # Readiness doubles as the router's drain signal: a
                # draining server answers 503 so load balancers stop
                # routing here while in-flight work finishes — and a
                # breaker-open engine answers 503 ``engine_down`` so
                # the router sheds AROUND a crash-storming replica
                # instead of feeding it work it will hang.
                # ONE machine-readable schema for every not-ready
                # path: {"status": "unavailable", "reason": ...} —
                # the router probe parses a single contract whether
                # the replica is draining or breaker-open (pinned in
                # tests/test_serving_smoke.py + tests/test_faults.py;
                # extras ride behind the two fixed keys).
                if ms.draining:
                    self._send(503, {"status": "unavailable",
                                     "reason": "draining",
                                     "model": ms.model_name,
                                     **ms.drain_status()})
                elif ms.engine is not None and ms.engine.down:
                    self._send(503, {
                        "status": "unavailable",
                        "reason": "engine_down",
                        "model": ms.model_name,
                        **({"supervisor": ms.supervisor.status()}
                           if ms.supervisor is not None else {})})
                else:
                    # ``role`` rides the 200 body so the router's
                    # probe loop learns the fleet's prefill/decode
                    # split without an extra /info round trip;
                    # ``t`` (host wall clock at response build) is
                    # the router's clock-skew ESTIMATE input — a
                    # host-clock reading, never device truth
                    # (docs/DESIGN.md time-truth discipline).
                    self._send(200, {"status": "ok",
                                     "model": ms.model_name,
                                     "role": ms.role,
                                     "t": time.time()})
            elif self.path == "/info":
                self._send(200, ms.info())
            elif self.path == "/metrics":
                self._send_raw(200, ms.metrics_text().encode(),
                               "text/plain; version=0.0.4")
            elif self.path == "/trace":
                # Chrome trace-event JSON: request spans + the engine
                # step timeline, loadable directly in Perfetto /
                # chrome://tracing (docs/SERVING.md).
                self._send(200, ms.telemetry.chrome_trace())
            elif self.path == "/anomalies":
                # The anomaly sentry's ranked findings + baselines
                # (serving/forensics.py; docs/SERVING.md
                # "Tail-latency forensics").
                if ms.forensics is None:
                    self._send(400, {
                        "error": "forensics disabled (start the "
                                 "server with forensics enabled)"})
                else:
                    self._send(200, ms.forensics.report())
            elif self.path == "/debug/exemplars":
                # Per-bucket request-ID exemplars for every latency
                # histogram — the full K retained per bucket (the
                # /metrics exposition carries only the latest).
                self._send(200, ms.telemetry.exemplars_report())
            elif self.path == "/profile/report":
                # The flight recorder's parsed attribution for the
                # most recent profiled window(s) — the same numbers
                # the /metrics gauges export (one reduction).
                if ms.recorder is None:
                    self._send(400, {
                        "error": "flight recorder disabled (start "
                                 "the server with --profile-every N "
                                 "and --profile-dir)"})
                else:
                    rep = ms.recorder.report()
                    if rep["latest"] is None:
                        self._send(404, {
                            "error": "no profiled window analyzed "
                                     "yet",
                            **{k: rep[k] for k in
                               ("windows_total", "windows_skipped",
                                "windows_deferred", "last_error")}})
                    else:
                        self._send(200, rep)
            elif self.path == "/prefix/index":
                # Fleet inventory: stable entry keys + tier/hits so
                # the router's one-copy-somewhere pass can plan
                # evictions without pulling any payload.
                if not ms.kv_paged:
                    self._send(400, {
                        "error": "prefix index requires a paged "
                                 "engine (--kv-paged)"})
                else:
                    self._send(200, ms.prefix_index())
            else:
                self._send(404, {"error": f"no route {self.path}"})

        def _do_debug_get(self, path: str):
            """The request-scoped debuggability surface:

            - ``GET /debug/state`` — the engine's latest published
              step-boundary snapshot + server lifecycle state.
              Served from the SnapshotBoard, never the device lock
              (SNAPSHOT-LOCK, docs/DESIGN.md), so it answers even
              while the engine is wedged inside a device call.
            - ``GET /requests?status=...&limit=N`` — newest-first
              summaries from the terminal-record retention ring.
            - ``GET /requests/<id>`` — one request's full causal
              record (timeline, preemptions + preemptor IDs, page
              waits, prefix provenance, terminal cause)."""
            if path == "/debug/state":
                self._send(200, ms.debug_state())
                return
            if not ms.history.enabled:
                self._send(400, {
                    "error": "request history disabled (start the "
                             "server with --request-history N)"})
                return
            if path in ("/requests", "/requests/"):
                q = parse_qs(urlparse(self.path).query)
                status = (q.get("status") or [None])[0]
                try:
                    limit = int((q.get("limit") or ["100"])[0])
                except ValueError:
                    self._send(400,
                               {"error": "limit must be an int"})
                    return
                self._send(200, {
                    "requests": ms.history.list(status=status,
                                                limit=limit),
                    **ms.history.stats()})
                return
            want = path[len("/requests/"):]
            rec = ms.history.get(want)
            if rec is None:
                self._send(404, {
                    "error": f"no record for request {want!r} "
                             f"(never seen, or rolled off the "
                             f"{ms.history.capacity}-record "
                             f"retention ring)"})
            else:
                self._send(200, rec)

        def _do_profile(self):
            """POST /profile/start|stop: guarded single-flight
            jax.profiler wrap.  400 when the server was started
            without --profile-dir (profiling writes device traces to
            disk — explicit opt-in); 409 on state conflicts (second
            start, stop with nothing running).  A start's optional
            body ``{"python_tracer": true}`` turns the Python tracer
            on (every call of every thread: for debugging, not for
            timing); without a body it is off."""
            t0 = time.perf_counter()
            if ms.profiler is None:
                code, resp = 400, {
                    "error": "profiling disabled (start the server "
                             "with --profile-dir)"}
            else:
                try:
                    if self.path == "/profile/start":
                        n = int(self.headers.get("Content-Length", 0))
                        body = json.loads(self.rfile.read(n) or b"{}")
                        d = ms.profiler.start(python_tracer=bool(
                            body.get("python_tracer", False)))
                        code, resp = 200, {"profiling": True,
                                           "dir": d}
                    else:
                        d = ms.profiler.stop()
                        code, resp = 200, {"profiling": False,
                                           "dir": d}
                except RuntimeError as e:
                    code, resp = 409, {"error": str(e)}
                except (ValueError, AttributeError) as e:
                    code, resp = 400, {
                        "error": f"bad /profile/start body: {e}"}
                except Exception as e:
                    code, resp = 500, {
                        "error": f"{type(e).__name__}: {e}"}
            try:
                self._send(code, resp)
            except OSError:
                pass
            ms.log_access("POST", self.path, code, None, resp,
                          time.perf_counter() - t0,
                          rid=getattr(self, "_rid", None))

        def _do_prefix(self, rid: str) -> None:
            """The fleet prefix cache's wire surface:

            - ``POST /prefix/fetch``  — serve a stored entry,
              serialized + checksummed (404 = holder miss).
            - ``POST /prefix/ingest`` — verify + admit one wire
              payload into the host tier (drain handoff's push).
            - ``POST /prefix/handoff`` — push this replica's entries
              to a successor (the router posts this mid-drain).
            - ``POST /prefix/evict``  — apply fleet eviction hints
              (host-tier only).

            All answer while DRAINING — the drain window is when the
            fleet needs this surface most."""
            t0 = time.perf_counter()
            req = None
            try:
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n)
                if self.path == "/prefix/fetch":
                    req = json.loads(raw or b"{}")
                    blob = ms.prefix_wire_payload(req)
                    if blob is None:
                        code, resp = 404, {"error": "prefix not held "
                                                    "here"}
                    else:
                        self._send_raw(200, blob,
                                       "application/octet-stream")
                        ms.log_access("POST", self.path, 200, req,
                                      {"nbytes": len(blob)},
                                      time.perf_counter() - t0,
                                      rid=rid)
                        return
                elif self.path == "/prefix/ingest":
                    # Body IS the wire payload (octet-stream, not
                    # JSON) — checksum verified inside.
                    req = {"nbytes": len(raw)}
                    code, resp = 200, ms.prefix_ingest(raw)
                elif self.path == "/prefix/handoff":
                    req = json.loads(raw or b"{}")
                    code, resp = 200, ms.prefix_handoff(req)
                elif self.path == "/prefix/evict":
                    req = json.loads(raw or b"{}")
                    code, resp = 200, ms.prefix_evict(req)
                else:
                    code, resp = 404, {"error":
                                       f"no route {self.path}"}
            except WirePayloadError as e:
                # Typed integrity failure: the payload never touched
                # the cache (counted prefix_ingest_rejected_total).
                code, resp = 400, {"error": str(e),
                                   "reason": "payload_integrity"}
            except ValueError as e:
                code, resp = 400, {"error": str(e)}
            except Exception as e:  # never kill the server thread
                code, resp = 500, {"error":
                                   f"{type(e).__name__}: {e}"}
            if isinstance(resp, dict):
                resp.setdefault("request_id", rid)
            try:
                self._send(code, resp)
            except OSError:
                pass
            ms.log_access("POST", self.path, code, req, resp,
                          time.perf_counter() - t0, rid=rid)

        def do_POST(self):
            rid = self._req_id()
            if self.path in ("/profile/start", "/profile/stop"):
                self._do_profile()
                return
            if self.path == "/drain":
                # Stop admission, finish in-flight, readiness off —
                # idempotent, so an orchestrator can post it again
                # while polling the in-flight snapshot toward zero.
                t0 = time.perf_counter()
                resp = ms.drain()
                try:
                    self._send(200, resp)
                except OSError:
                    pass
                ms.log_access("POST", self.path, 200, None, resp,
                              time.perf_counter() - t0, rid=rid)
                return
            if self.path.startswith("/prefix/"):
                self._do_prefix(rid)
                return
            if self.path not in ("/generate", "/prefill"):
                self._send(404, {"error": f"no route {self.path}"})
                return
            # Generate FIRST, send after: a client hanging up while a
            # successful response streams out must not count as a
            # serving error (nor trigger a doomed second send).
            extra = None
            t0 = time.perf_counter()
            req = None
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                if self.path == "/generate":
                    # The disconnect probe lets a vanished client's
                    # request cancel at the next step boundary
                    # instead of decoding to budget exhaustion.
                    code, resp = 200, ms.generate(
                        req,
                        cancel_check=_disconnect_probe(
                            self.connection),
                        rid=rid)
                else:
                    code, resp = 200, ms.prefill_prompt(req)
            except ShedError as e:
                # Graceful overload: 503 with a machine-readable
                # reason (queue_deadline / draining /
                # request_timeout) so clients and routers can tell
                # shed classes apart from hard failures.
                code = 503
                resp = {"error": str(e), "reason": e.reason}
                if e.retry_after:
                    extra = {"Retry-After": str(e.retry_after)}
            except DeadlineExceeded as e:
                code, resp = 504, {"error": str(e),
                                   "reason": "deadline"}
            except RequestCancelled as e:
                # 499 (client closed request): almost always
                # unsendable — the client is gone — but the access
                # log line is the point.
                code, resp = 499, {"error": str(e),
                                   "reason": "cancelled"}
            except QueueFullError as e:
                # Explicit backpressure, not an error: the bounded
                # admission queue is full — shed load with the
                # standard retry contract instead of letting handler
                # threads pile up behind the engine.  The rejection
                # was already counted by AdmissionQueue.submit.
                code = 429
                resp = {"error": str(e),
                        "retry_after": e.retry_after}
                extra = {"Retry-After": str(e.retry_after)}
            except PoisonedRequest as e:
                # Quarantine conviction: THIS request's computation
                # kept failing the shared decode step, so it alone
                # fails — typed, with the machine-readable reason,
                # while its co-tenants resumed token-identically
                # (engine._quarantine_step).
                with ms._stats_lock:
                    ms.errors += 1
                code, resp = 500, {"error": str(e),
                                   "reason": e.reason}
            except ValueError as e:
                with ms._stats_lock:
                    ms.errors += 1
                code, resp = 400, {"error": str(e)}
            except Exception as e:  # never kill the server thread
                with ms._stats_lock:
                    ms.errors += 1
                code, resp = 500, {"error": f"{type(e).__name__}: {e}"}
            # Error bodies carry the ID too (the header already
            # does): a client that only kept the JSON can still
            # quote the correlation key in a bug report.
            if isinstance(resp, dict):
                resp.setdefault("request_id", rid)
            try:
                if ms.faults is not None:
                    # Injected handler-socket death at the worst
                    # moment — the response write.  The connection
                    # drops with no response; server-side state is
                    # already terminal, which is exactly what the
                    # chaos harness verifies (no leaked slot, no
                    # wedged worker, counters still advance).
                    ms.faults.check("socket_reset")
                self._send(code, resp, extra)
            except SocketReset:
                self.close_connection = True
                try:
                    self.connection.close()
                except OSError:
                    pass
            except OSError:
                pass  # client went away mid-write; nothing to do
            # AFTER the send, so logging latency never delays the
            # response; 4xx/5xx lines are the whole point (failed
            # requests used to vanish into the log_message no-op).
            ms.log_access("POST", self.path, code, req, resp,
                          time.perf_counter() - t0, rid=rid)
            # Front-end history record for requests the engine never
            # recorded (validation 400s, sheds, solo paths) — the
            # engine's full causal record wins when both exist.
            ms.record_front(rid, self.path, code, req, resp)

    return Handler
