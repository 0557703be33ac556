"""Continuous-batching decode engine.

Replaces the request-coalescing path (whole ``generate()`` calls
merged per compile shape) with STEP-LEVEL scheduling: a fixed pool of
decode slots (slots.py) advances one token per tick, and the gaps the
old design wasted are reclaimed at step boundaries —

- a request hitting EOS (or its budget) frees its slot the same step,
  instead of decoding frozen eos tokens until the longest batch
  member finishes;
- a queued request is admitted into a free slot between two decode
  steps, instead of waiting for the whole running batch to drain;
- long prompts prefill in bounded chunks INTERLEAVED between decode
  steps (one chunk per boundary while decodes run), so a 2k-token
  prompt delays resident requests by one chunk forward, not a full
  prefill.

This is the decoupling of logical workload from physical batch that
VirtualFlow (arXiv:2009.09523) argues for, applied to the decode
loop.  Greedy AND sampled (non-beam, non-speculative) requests share
one slot pool and one compiled step program: per-slot greedy argmax
is exact (rows never interact, eos-frozen rows pad to budget —
identical to solo ``generate``, pinned in tests/test_serving.py),
and sampled slots draw through the POSITION-KEYED RNG contract
(models/generate): a stream's i-th token key is
``fold_in(fold_in(PRNGKey(seed), row), i)`` — a function of (seed,
row, token index) only, never of slot id, engine step count, or
co-tenancy — so sampled output is bit-identical to the solo
``generate_positional`` reference under any admission schedule
(pinned in tests/test_sampled_engine.py).  SPECULATIVE requests are
engine citizens too when the engine owns a draft model: spec slots
draft/verify/commit a variable accepted prefix per round through the
spec step program (slots.py), every draft/accept/residual draw
position-keyed per (token index, lane), so speculative output is
bit-identical to ``generate_speculative``'s seed mode under any
co-tenancy (pinned in tests/test_spec_engine.py).  Beam requests
keep the solo path (the per-beam cache tiling/reorder is a layout
the slot pool doesn't speak).

Threading: ``submit`` may be called from any handler thread; all slot
and queue mutation happens on the engine loop thread (or, in tests,
via manual ``tick()`` calls with the loop not started — never both).
Device work (prefill chunks, decode steps) is ENQUEUED under
``device_lock`` shared with the solo path, so engine ticks and solo
requests interleave at step granularity.

THE LOOP THREAD NEVER WAITS FOR WORK IT HAS JUST ENQUEUED.  A prefill
piece is enqueued and not awaited; whatever device work a stream's
first token needs is enqueued right behind its LAST piece and
admission fetches the finished scalar; and the decode dispatch runs
ONE AHEAD: dispatch N+1 is launched before dispatch N's tokens are
fetched, wherever the boundary between them can be decided without
those tokens (``_serial_reason`` names what forbids it).  While
residents exist the device's queue is then never empty: the host
deals out N's tokens, completes streams and enqueues the next
admission while N+1 runs.  The tokens are bitwise the serial order's
— the programs' arithmetic is the same, only WHEN the host reads it
moves (docs/SERVING.md "The tick, one dispatch ahead").
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import traceback
from collections import OrderedDict, deque
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ._lru import lru_get
from .debug import SnapshotBoard, events_to_dicts, new_request_id
from .faults import is_poisoned, is_transient
from .forensics import compute_ledger
from .paged import PageExhausted
from .recovery import RetryPolicy
from .scheduler import (AdmissionQueue, DeadlineExceeded, PRIORITIES,
                        PoisonedRequest, QueueFullError,
                        RequestCancelled, RequestGroup, SamplingSpec,
                        SchedulerPolicy, ShedError, Stream,
                        terminal_status)
from .slots import SlotKVManager, pool_refusal
from ..spans import STEP_MARKER, span, take
from .telemetry import ENGINE_PID, Histogram, Telemetry

__all__ = ["DecodeEngine", "QueueFullError", "SPEC_ACCEPT_BUCKETS"]

# Acceptance-rate histogram bucket upper bounds (le) for completed
# speculative requests; the last implicit bucket is +Inf.
SPEC_ACCEPT_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


class _InFlight:
    """One decode dispatch from its launch to its commit: the
    manager's :class:`~.slots.Flight` and the engine's own snapshot
    of it.  ``streams`` is the dispatch's slot -> (stream, tokens it
    will take) as launched: by the time it is committed a slot may
    hold another stream (a foreseen eviction re-armed it), so tokens
    are dealt by THIS map, never by ``_resident``."""

    __slots__ = ("flight", "k", "streams", "occupancy")

    def __init__(self, flight, k: int, streams: dict):
        self.flight = flight
        self.k = k
        self.streams = streams
        self.occupancy = len(streams)


class DecodeEngine:
    def __init__(self, model, variables, *,
                 policy: Optional[SchedulerPolicy] = None,
                 device_lock: Optional[threading.Lock] = None,
                 autostart: bool = True,
                 prefill_fns=None,
                 draft_model=None, draft_variables=None,
                 telemetry: Optional[Telemetry] = None,
                 sentinel=None, mesh=None, faults=None,
                 retry_policy: Optional[RetryPolicy] = None):
        # Serving mesh (serving/meshed.py): accepts a ServingMesh, a
        # spec string ("tp=4"), a dict, or a MeshSpec.  When set, the
        # slot KV pools shard over the mesh, params are PLACED onto
        # it (library callers who didn't pre-place get the exact
        # layout applied here; ModelServer places before
        # constructing the engine and passes a ServingMesh whose
        # placement this re-application matches, so double placement
        # is a no-op), and every engine-owned trace runs under the
        # serving-exact constraint mode — output stays token-bitwise
        # identical to the unmeshed engine per seed.
        refusal = pool_refusal(
            (model, draft_model),
            paged=bool((policy or SchedulerPolicy()).kv_paged),
            meshed=mesh is not None, speculative=draft_model is not None)
        if refusal:
            raise ValueError(refusal)
        if mesh is not None:
            from .meshed import ServingMesh

            if not isinstance(mesh, ServingMesh):
                mesh = ServingMesh(mesh)
            mesh.validate_model(model, "model",
                                n_slots=(policy or SchedulerPolicy()
                                         ).n_slots)
            if draft_model is not None:
                mesh.validate_model(draft_model, "draft model")
            variables = mesh.place_params(variables)
            if draft_variables is not None:
                draft_variables = mesh.place_params(draft_variables)
        self.mesh = mesh
        self.model = model
        self.variables = variables
        # Telemetry ring shared with the owning server (ModelServer
        # passes its own, so request spans and engine step records
        # land in ONE /trace timeline); a standalone engine defaults
        # to a disabled core — every record call is one attribute
        # check, nothing else.
        self.tel = telemetry if telemetry is not None \
            else Telemetry(buffer=0)
        # Draft model: enables SPECULATIVE streams (spec_k > 0) — the
        # slot pool stacks a second cache for it and the spec step
        # variant drafts/verifies/commits per round.
        self.draft_model = draft_model
        self.draft_variables = draft_variables
        self.policy = policy or SchedulerPolicy()
        self.device_lock = device_lock or threading.Lock()
        # Recompile sentinel (analysis/recompile.py): every program-
        # cache miss across the engine's prefill/step/insert caches is
        # counted (and trace-marked), so the zero-steady-state-
        # recompile contract is testable.  ModelServer passes ITS
        # sentinel so server and engine caches report as one.
        if sentinel is None:
            from ..analysis.recompile import RecompileSentinel

            sentinel = RecompileSentinel(telemetry=self.tel)
        self.sentinel = sentinel
        # autostart=False: no loop thread — the owner drives tick()
        # manually (deterministic tests, offline batch use).
        self.autostart = bool(autostart)
        # KV storage: the fixed-lane stacked pool (slots.py), or —
        # policy.kv_paged — the block-table page pool (paged.py):
        # per-request page reservations instead of max_position
        # lanes, so occupancy under mixed-length traffic is bounded
        # by token usage, not by the widest request.
        self.paged = bool(self.policy.kv_paged)
        if self.paged:
            from .paged import PagedSlotKVManager

            max_pos = getattr(getattr(model, "cfg", None),
                              "max_position", None)
            if max_pos is None or getattr(
                    getattr(model, "cfg", None), "kv_cache_ring",
                    False):
                raise ValueError(
                    "kv_paged needs a decoder-only model with a "
                    "plain/int8 max_position cache (ring caches keep "
                    "the fixed-lane manager)")
            self.slots = PagedSlotKVManager(
                model, variables, self.policy.n_slots,
                page_tokens=self.policy.kv_page_tokens,
                n_pages=self.policy.kv_pages,
                max_position=max_pos,
                decode_window=self.policy.decode_window,
                spec_k_cap=self.policy.spec_k_cap,
                lazy=self.policy.kv_lazy,
                draft_model=draft_model,
                draft_variables=self.draft_variables,
                sentinel=sentinel, mesh=mesh)
        else:
            self.slots = SlotKVManager(model, self.variables,
                                       self.policy.n_slots,
                                       draft_model=draft_model,
                                       draft_variables=self.draft_variables,
                                       sentinel=sentinel, mesh=mesh)
        # Optional page-pressure relief hook (paged mode): called
        # with the page deficit when an admit-ready stream is blocked
        # on free pages; the server wires it to prefix-cache LRU
        # eviction so stored-but-idle prefixes yield to live traffic.
        self.page_reclaim = None
        self.queue = AdmissionQueue(self.policy)
        # streams resident in a slot: slot index -> Stream
        self._resident: Dict[int, Stream] = {}
        # prefill/extend programs keyed by piece length (LRU-bounded:
        # remainder pieces vary with prompt length).  ``prefill_fns``
        # ((s_len, first) -> jitted fn) lets an owner share ONE
        # compile cache — ModelServer passes its _split_fns so engine
        # traffic and /prefill never compile the same program twice.
        self._prefill_fns = prefill_fns
        self._pf_fns: "OrderedDict[Tuple, Any]" = OrderedDict()
        # Draft prefill programs (speculative streams prefill through
        # BOTH models): engine-owned — the server's shared cache only
        # speaks the target model.
        self._pf_fns_draft: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._pf_cap = 16
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._wake = threading.Condition()
        self._stop = False
        # jitted first-token programs (token index 0, from the
        # prefill logits): sampled? -> the positional sampler, or the
        # argmax — each compiled once, shared by every stream
        self._first_fns: Dict[bool, Any] = {}
        # How far prefill may run ahead of the device: pieces enqueued
        # and not yet finished (``_pieces``: their logits, oldest
        # first), each of which holds a lane — a slot's worth of KV —
        # outside the pool.  A sixteenth of the pool, two at least:
        # what the pool's own size says the device can spare.
        self._pieces_cap = max(2, self.policy.n_slots // 16)
        self._pieces: "deque" = deque()
        # The decode dispatch launched and not yet collected (None:
        # nothing in flight), and how the dispatches were ordered:
        # launched before the previous one was collected (ahead), or
        # not, by the name of what forbade it (``_serial_reason``;
        # ``first``: nothing was in flight to run ahead of).
        self._flight: Optional[_InFlight] = None
        self.decode_dispatches_total = 0
        self.decode_dispatches_ahead_total = 0
        self.decode_serial_reasons: Dict[str, int] = {}
        # counters (read unlocked by metrics — monotonic ints);
        # admitted/completed split by mode so pool utilization under
        # mixed greedy/sampled load is observable
        self.admitted_total = 0
        self.admitted_greedy_total = 0
        self.admitted_sampled_total = 0
        self.admitted_spec_total = 0
        self.evicted_total = 0
        self.decode_steps_total = 0
        # Dispatches that failed AFTER consuming the donated KV pool
        # (_recover_lost_pool).
        self.kv_pool_lost_total = 0
        self.prefill_chunks_total = 0
        self.prefill_tokens_total = 0
        self.completed_total = 0
        self.completed_greedy_total = 0
        self.completed_sampled_total = 0
        self.completed_spec_total = 0
        # Speculative scheduling counters + the per-request
        # acceptance-rate histogram (accepted drafts / drafted, bucket
        # upper bounds in SPEC_ACCEPT_BUCKETS; one completed request =
        # one observation).  ONE shared telemetry.Histogram — /metrics
        # and /info both render engine.stats(), so they can never
        # drift, and the exposition goes through the same
        # render_histogram helper as the latency histograms.
        self.spec_rounds_total = 0
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        self.spec_accept = Histogram(SPEC_ACCEPT_BUCKETS)
        # Request-lifecycle counters (one bump per terminal REQUEST,
        # not per stream) + the per-class admission split.  Mostly
        # mutated by the sweep/preemption machinery on the engine
        # thread; the SHED counters are also bumped from submitter
        # threads (the draining gate), so those go under _shed_lock —
        # /metrics reads everything unlocked like the rest.
        self._shed_lock = threading.Lock()
        self.cancelled_total = 0
        self.expired_total = 0
        self.shed_total = 0
        self.shed_by_class = {p: 0 for p in PRIORITIES}
        # Paged-KV shed split: requests whose page budget can never
        # fit the pool (503 reason kv_pages) — a sizing signal, kept
        # separate from queue-deadline/draining sheds.
        self.shed_kv_pages_total = 0
        # LAZY-KV exhaustion preemptions (engine._ensure_lazy_growth):
        # a resident evicted mid-decode because a co-tenant's page
        # growth found the pool empty — the concurrency-vs-memory
        # trade the --kv-lazy mode makes explicit.  ``_exhaust_bars``
        # holds the evictees whose re-admission is barred until the
        # blocked growth completes (the livelock guard).
        self.kv_preempt_exhaustion_total = 0
        self._exhaust_bars: list = []
        self.preempted_total = 0
        self.resumed_total = 0
        self.admitted_by_class = {p: 0 for p in PRIORITIES}
        # Preemption control signal: a SLIDING WINDOW of the most
        # recent interactive admission-anchored TTFTs (the same
        # observations the exported ttft_interactive histogram gets).
        # The controller reads p99 over THIS window, not the
        # cumulative histogram — lifetime bucket counts never decay,
        # so one bad period would otherwise latch aggressive batch
        # preemption until process restart.
        self._ttft_recent: "deque[float]" = deque(maxlen=64)
        # Sweep fast path: the boundary sweep scans residents + the
        # whole queue, which is pure waste for deployments that never
        # touch the lifecycle features.  ``_cancel_pending`` is set
        # by cancel() and consumed by the next sweep;
        # ``_deadline_armed`` goes (and stays) True once ANY
        # deadline-bearing request has been submitted — sticky on
        # purpose: a deployment using deadlines pays the sweep as the
        # feature's cost, one that never does skips it entirely.
        self._cancel_pending = False
        self._deadline_armed = False
        # Draining: stop ADMITTING new requests (submit sheds with
        # 503), finish everything already accepted — the /drain
        # endpoint's engine half.  One-way per engine lifetime.
        self.draining = False
        # Meshed step accounting: cumulative device wall (the step
        # markers' seconds: launch + wait, the records' ``device_s``)
        # vs scheduling wall per decode dispatch — a host-clock
        # ESTIMATE of device time; the flight recorder below is the
        # device-truth counterpart.
        self.step_device_s_total = 0.0
        self.step_wall_s_total = 0.0
        # Seconds in the tick's host sections (spans.span) since the
        # last step record took them: each record says what the host
        # did around its dispatch (_host_fields).
        self._host_s: Dict[str, float] = {}
        # Flight recorder (serving/profiling.py): set by the owning
        # server when --profile-every is armed.  None (the default)
        # keeps the decode loop's cost at one attribute check per
        # dispatch; armed, the recorder periodically wraps
        # profile_steps dispatch boundaries in a jax.profiler window
        # and publishes trace-true attribution (collective/host-gap/
        # busy shares, serving MFU) to /metrics + /profile/report.
        self.recorder = None
        # Request-scoped debuggability (serving/debug.py).
        # ``history``: the terminal-record retention ring behind
        # GET /requests — None (library default) records nothing; the
        # server wires its RequestHistory here before traffic.
        # ``debug_board``: the published step-boundary snapshot
        # behind GET /debug/state; ``last_boundary_t`` is the stall
        # watchdog's progress signal (stamped at the end of every
        # tick).  ``_last_page_free`` attributes a blocked
        # admission's eventual unblock to the eviction that freed
        # capacity — (request id, why) of the most recent release.
        self.history = None
        # ``forensics``: the server's ForensicsCore (phase
        # accumulator + anomaly sentry, serving/forensics.py), or
        # None — terminal paths feed it the request's phase ledger;
        # disarmed it is one attribute check.
        self.forensics = None
        self.debug_board = SnapshotBoard()
        self.last_boundary_t = time.perf_counter()
        self._last_page_free: Optional[Tuple] = None
        # Publishing is throttled to one build per interval: a busy
        # pool crosses hundreds of step boundaries a second, and
        # /debug/state only needs a recent-consistent snapshot, not
        # an every-boundary one — the snapshot build (slot + queue
        # dicts) must not become a per-step tax nobody asked for.
        self.board_interval_s = 0.1
        self._board_t = 0.0
        # FAULT TOLERANCE (serving/faults.py + serving/recovery.py).
        # ``faults``: the armed FaultPlan, or None (the default) —
        # every probe site is one attribute check when disarmed.
        # ``retry_policy``: the bounded jittered-backoff schedule
        # step-level TRANSIENT failures retry under (shared shape
        # with the supervisor's restart backoff).  ``supervisor``:
        # set by recovery.EngineSupervisor — when attached, a crash
        # escaping the scheduling layer restarts the loop and
        # requeues everything for token-identical resume instead of
        # failing every caller; ``down`` latches True while the
        # crash-storm circuit breaker holds the engine offline (new
        # submits shed 503 ``engine_down``; /healthz reports it).
        # ``_suspects``: groups implicated by a poisoned step
        # dispatch, pending exoneration or conviction (the
        # quarantine-bisection state, _quarantine_step).
        self.faults = faults
        self.retry_policy = retry_policy if retry_policy is not None \
            else RetryPolicy()
        self.supervisor = None
        self.down = False
        self._suspects: set = set()
        # Convictions since the last SUCCESSFUL dispatch: a fault
        # that keeps failing across quarantine convictions tracks
        # the ENGINE, not a request — after 2 such convictions the
        # next episode escalates to supervised recovery instead of
        # serially convicting innocents (reset only by a dispatch
        # that works, so a post-restart recurrence escalates
        # immediately).
        self._convictions_without_success = 0
        self.step_retries_total = 0
        self.requests_requeued_total = 0
        self.poisoned_total = 0
        self.telemetry_errors_total = 0
        self.debug_board.publish(self.build_debug_snapshot())

    def _exact(self):
        """Serving-exact trace context for engine-owned device calls
        (prefill pieces trace over column-sharded params); no-op
        unmeshed."""
        return self.mesh.exact() if self.mesh is not None \
            else contextlib.nullcontext()

    # -- submission (any thread) ----------------------------------------

    def submit(self, rows: np.ndarray, new: int,
               eos_id: Optional[int], prefill_chunk: Optional[int],
               *, sampling: Optional[SamplingSpec] = None,
               prefix=None, on_prefilled=None,
               record_timings: bool = False,
               priority: Optional[str] = None,
               deadline_s: Optional[float] = None,
               shared_pages=None,
               rid: Optional[str] = None,
               prefix_info=None,
               pre_events=None,
               resume_tokens: int = 0,
               record_logits: bool = False) -> RequestGroup:
        """Enqueue a request (may raise QueueFullError) and make sure
        the loop is running.  Returns the group; callers block on
        ``group.event``.  ``sampling`` carries the per-request
        (seed, temperature, top_k, top_p) — None (or temperature 0)
        is greedy; sampled streams draw through the position-keyed
        RNG contract, so their tokens are independent of co-tenancy.

        ``prefix=(p_cached, logits, cache)`` seeds a SINGLE-ROW request
        with an existing prefill state (the prefix-cache hit path): the
        stream starts ``p_cached`` tokens in, so it prefills only the
        suffix — or skips prefill entirely on a full-length hit — and
        decodes in a slot like any other request, instead of holding
        the device lock for a whole solo decode.  ``on_prefilled``
        fires on the engine thread once the prompt is fully consumed
        (the cache store-back hook).

        ``sampling.spec_k > 0`` submits a SPECULATIVE request: needs
        the engine's draft model (its prompt prefills through BOTH
        models), and composes with greedy or sampled accept lanes.

        ``priority`` (default: the policy's ``default_priority``)
        picks the request's class queue — ``interactive`` drains
        ahead of ``batch``, and batch residents are preemptible under
        the TTFT SLO.  ``deadline_s`` (relative seconds) arms a
        deadline: expiry evicts the request at the next step boundary
        with :class:`DeadlineExceeded`.  A DRAINING engine sheds
        every new submit with :class:`ShedError` (503).

        PAGED engines additionally shed (503 ``reason: kv_pages``) a
        request whose KV budget can NEVER fit the page pool — waiting
        would deadlock, not resolve — while one that merely doesn't
        fit RIGHT NOW queues until evictions free pages.
        ``shared_pages`` (single-row prefix hits only) are PINNED
        page ids of the stored prefix's full pages: the engine owns
        the pins from here on, maps them read-only into the stream's
        table at admission, and releases them on any pre-admission
        terminal path.

        ``rid`` is the request's correlation ID (the server passes
        the inbound/generated ``X-Request-Id``); None generates one,
        so EVERY group carries an ID into its trace spans and its
        request-history record.  ``prefix_info`` rides the history
        record as prefix-cache hit provenance.  ``pre_events`` are
        span tuples the CALLER paid before submit (a fleet wire
        fetch): prepended to the stream's timeline so the history
        record and the ``timings`` block attribute that cost to this
        request.

        ``resume_tokens=N`` (single-row) declares the trailing N
        prompt tokens a PRIOR attempt's committed output — the
        cross-replica resume contract (docs/DESIGN.md): a router
        failing a request over replays ``prompt ++
        tokens_received_so_far`` and the stream re-enters through
        the SAME preempt-resume machinery PR 6 pinned (re-prefill of
        the committed prefix, re-admission feeding ``out[-1]`` with
        ``next_index == len(out)``), so sampled draws continue at
        position key N exactly as the uninterrupted run — on ANY
        replica — would have drawn them.  ``new`` stays the
        request's ORIGINAL total budget; the group's result is the
        original prompt plus all ``new`` tokens."""
        if priority is None:
            priority = self.policy.default_priority
        if priority not in PRIORITIES:
            # Validate before the draining gate uses it as a counter
            # key (RequestGroup would catch it later anyway; a bad
            # priority must be a ValueError, never a KeyError).
            raise ValueError(f"priority must be one of {PRIORITIES};"
                             f" got {priority!r}")
        if self.draining:
            # Counted here too: the server's drain gate catches HTTP
            # traffic, but a library caller (or a request that raced
            # /drain past the server check) still sheds — and must
            # still show up in the shed metrics.  Under _shed_lock:
            # submit runs on arbitrary threads, unlike the sweep.
            with self._shed_lock:
                self.shed_total += 1
                self.shed_by_class[priority] += 1
            raise ShedError(
                "engine is draining: finishing in-flight requests, "
                "admitting none", reason="draining")
        if self.down:
            # Crash-storm circuit breaker open (recovery.py): shed
            # fast with the machine-readable reason instead of
            # queueing work a dead engine will never drain — the
            # supervisor's cooldown probe flips this back off.
            with self._shed_lock:
                self.shed_total += 1
                self.shed_by_class[priority] += 1
            raise ShedError(
                "decode engine is down (crash-restart circuit "
                "breaker open); retry after the cooldown",
                reason="engine_down",
                retry_after=self.policy.retry_after_s)
        if self.paged:
            # A resume replay carries prior output inside the prompt;
            # the slot only ever holds original-prompt + budget.
            need = self._kv_tokens_needed(
                rows.shape[1] - int(resume_tokens or 0), new)
            if need > self.slots.capacity_tokens:
                # Graceful overload, not deadlock: this request can
                # NEVER fit the pool, so queue-waiting for evictions
                # would hang it forever.  One that fits the pool but
                # not the current free set simply waits admit-ready.
                with self._shed_lock:
                    self.shed_total += 1
                    self.shed_by_class[priority] += 1
                    self.shed_kv_pages_total += 1
                raise ShedError(
                    f"request KV budget ({need} tokens/row) exceeds "
                    f"the page pool ({self.slots.capacity_tokens} "
                    f"tokens = {self.slots.n_pages} x "
                    f"{self.slots.page_tokens}-token pages); shrink "
                    f"the prompt/budget or raise --kv-pages",
                    reason="kv_pages")
            if sampling is not None \
                    and sampling.spec_k > self.policy.spec_k_cap:
                # Paged co-tenants reserved slack for at most
                # spec_k_cap-wide verify chunks; a wider resident
                # would write past their reservations.
                raise ValueError(
                    f"spec_k {sampling.spec_k} exceeds the paged "
                    f"engine's spec_k_cap {self.policy.spec_k_cap}")
        if sampling is not None and sampling.spec_k > 0:
            if self.draft_model is None:
                raise ValueError(
                    "speculative request on an engine without a "
                    "draft model")
            if prefix is not None:
                # The stored prefix holds only the TARGET's prefill;
                # a draft cache seeded from nothing would verify
                # against garbage.  The server keeps speculative
                # requests off the prefix path — enforce it here too.
                raise ValueError(
                    "speculative requests cannot seed from a prefix "
                    "cache entry (the draft cache has no stored "
                    "prefill)")
        if resume_tokens:
            # CROSS-REPLICA RESUME: the trailing N prompt tokens are
            # committed output from a prior attempt (router failover
            # replay).  Split them back out and re-enter through the
            # preempt-resume machinery — prepare_resume re-prefills
            # ``prompt ++ out[:-1]`` in pow2 pieces, and admission
            # feeds ``out[-1]`` at its original absolute position
            # with ``next_index == len(out)``, so token N draws with
            # exactly the position key an uninterrupted run uses.
            rt = int(resume_tokens)
            if prefix is not None:
                raise ValueError(
                    "resume_tokens cannot combine with a prefix-"
                    "cache seed (the replayed prefix IS the state)")
            if rows.shape[0] != 1:
                raise ValueError(
                    f"resume_tokens takes a single-row request (got "
                    f"batch {rows.shape[0]}; multi-row failover "
                    f"replays the whole request instead)")
            if rt >= rows.shape[1]:
                raise ValueError(
                    f"resume_tokens ({rt}) must leave at least one "
                    f"original prompt token (prompt length "
                    f"{rows.shape[1]})")
            if rt >= new:
                raise ValueError(
                    f"resume_tokens ({rt}) >= max_new_tokens "
                    f"({new}): nothing left to generate")
            out_prev = [int(t) for t in rows[0, rows.shape[1] - rt:]]
            if eos_id is not None and eos_id in out_prev:
                raise ValueError(
                    "resume_tokens output already contains eos_id; "
                    "the request is complete — nothing to resume")
            orig = np.ascontiguousarray(rows[:, :rows.shape[1] - rt])
            group = RequestGroup(orig, new, eos_id, [], sampling,
                                 priority=priority)
            stream = group.streams[0]
            stream.out = out_prev
            stream.prepare_resume(SchedulerPolicy.pow2_pieces(
                orig.shape[1] + rt - 1))
        elif prefix is None:
            pieces = self.policy.chunk_plan(rows.shape[1],
                                            prefill_chunk)
            group = RequestGroup(rows, new, eos_id, pieces, sampling,
                                 priority=priority)
        else:
            if rows.shape[0] != 1:
                raise ValueError(
                    "prefix-seeded submit takes a single-row request "
                    f"(got batch {rows.shape[0]})")
            p_cached, logits, cache = prefix
            suffix = rows.shape[1] - p_cached
            pieces = self.policy.chunk_plan(suffix, prefill_chunk) \
                if suffix > 0 else []
            group = RequestGroup(rows, new, eos_id, pieces, sampling,
                                 priority=priority)
            stream = group.streams[0]
            stream.filled = p_cached
            stream.logits = logits
            stream.cache = cache
        if shared_pages:
            # Single-row prefix hits only: the pins ride the stream
            # until admission transfers them into the slot table.
            # The pool epoch they were pinned under rides along —
            # if crash recovery rebuilds the pool before admission,
            # _validate_shared_epoch drops the stale ids by
            # reference instead of feeding them to the fresh
            # accounting.
            group.streams[0].kv_shared = tuple(shared_pages)
            group.streams[0].kv_epoch = getattr(
                shared_pages, "epoch", None)
        if deadline_s is not None:
            group.deadline = group.t_submit + float(deadline_s)
            self._deadline_armed = True
        group.rid = rid if rid is not None else new_request_id()
        if self.faults is not None:
            # Resolve request_index-keyed poisoned fault specs to
            # this request's concrete ID (faults.FaultPlan).
            self.faults.on_submit(group.rid)
        group.prefix_info = prefix_info
        group.on_prefilled = on_prefilled
        group.record_timings = bool(record_timings)
        if record_logits:
            # The logits every token of these streams is chosen from,
            # out of the programs that serve everyone (a reference
            # check reads them): the prefill's at admission, then a
            # row of each decode dispatch's last step, so the window
            # is held to 1 while such a stream is resident.
            for stream in group.streams:
                stream.step_logits = []
        # Streams collect their span tuples when the caller asked for
        # a ``timings`` block, the history ring is armed, OR the
        # forensics core is armed — the same events back all three
        # surfaces, so a record's timeline, a live timings response,
        # and the phase ledger can never disagree (a ledger computed
        # with no events would be pure unattributed wall).
        keep_events = group.record_timings or (
            self.history is not None and self.history.enabled) \
            or self.forensics is not None
        for stream in group.streams:
            stream.sid = self.tel.new_tid()
            if keep_events:
                stream.events = []
        if pre_events and keep_events and group.streams:
            # Caller-paid spans (wire fetch) lead the timeline —
            # they happened before anything the engine records.
            s0 = group.streams[0]
            s0.events = list(pre_events) + (s0.events or [])
        # Idle -> busy transition: re-stamp the watchdog's progress
        # signal, or a server that sat idle past --stall-timeout
        # would read as stalled the moment work arrives (the loop
        # only stamps at tick, and the first tick may be a
        # seconds-long compile).  Only on the transition — submits
        # into an already-busy (possibly wedged) engine must NOT
        # keep resetting staleness.
        if not self._resident and len(self.queue) == 0:
            # ptpu: lockfree[monotonic staleness stamp: torn/lost stamps only shift stall detection by one tick]
            self.last_boundary_t = time.perf_counter()
        # Queue-entry instant: the FIRST trace event a request owns,
        # so even one that never reaches admission (wedged engine,
        # stall bundle) is findable in the ring by its rid.  Emitted
        # BEFORE queue.submit — once the group is in the queue the
        # engine thread can process it immediately, and a later
        # "queued" would land out of order in stream.events.
        for stream in group.streams:
            self._emit_instant(stream, "queued", group.t_submit,
                               row=stream.row, priority=priority)
        try:
            self.queue.submit(group)      # raises when full
        except QueueFullError:
            # Close the causal story for the trace ring: submitted,
            # never queued (429 at the front-end).
            for stream in group.streams:
                self._emit_instant(stream, "shed",
                                   time.perf_counter(),
                                   row=stream.row,
                                   reason="queue_full")
            raise
        if self.autostart:
            self._ensure_thread()
            with self._wake:
                self._wake.notify()
        return group

    def generate(self, rows: np.ndarray, new: int,
                 eos_id: Optional[int],
                 prefill_chunk: Optional[int],
                 sampling: Optional[SamplingSpec] = None) -> np.ndarray:
        """Blocking submit -> [B, p_len + new] tokens (the /generate
        engine path)."""
        group = self.submit(rows, new, eos_id, prefill_chunk,
                            sampling=sampling)
        group.event.wait()
        if group.error is not None:
            raise group.error
        return group.result()

    def cancel(self, group: RequestGroup,
               err: Optional[BaseException] = None) -> None:
        """Request ``group``'s eviction (client disconnect, deadline,
        front-end give-up).  Callable from any thread; the engine
        DELIVERS it at its next step boundary — queued streams drop,
        a mid-prefill stream abandons its partial cache, resident
        streams free their slots — and the group fails with ``err``
        (default :class:`RequestCancelled`)."""
        group.request_cancel(err if err is not None
                             else RequestCancelled(
                                 "request cancelled"))
        # Flag AFTER the cancel is stored: the sweep that sees the
        # flag is guaranteed to see the cancel_error too.  Then wake
        # an idle loop so delivery doesn't wait out the idle sleep;
        # manual-tick owners just call tick().
        # ptpu: lockfree[handoff flag: writers only store True, the engine sweep clears; next boundary re-reads]
        self._cancel_pending = True
        with self._wake:
            self._wake.notify()

    def drain(self) -> None:
        """Stop admission (new submits shed with 503 ``draining``)
        while every already-accepted request — queued, prefilling, or
        resident — runs to completion.  The server half turns
        readiness off so a router stops sending traffic here."""
        self.draining = True

    # -- engine loop ----------------------------------------------------

    def _ensure_thread(self) -> None:
        with self._thread_lock:
            t = self._thread
            if t is not None and t.is_alive():
                if not self._stop:
                    return
                # A concurrent close() is in flight: the exiting loop's
                # final drain may have run before this caller's enqueue
                # landed, which would strand the group with no thread
                # to process or fail it.  Wait the old loop out, then
                # start a fresh one that owns the queue.  (If the old
                # drain DID see the group, it failed it with "decode
                # engine closed" — an error, never a hang.)  Timed:
                # this wait runs under _thread_lock, so an old loop
                # wedged in a device call would otherwise stall every
                # submitter forever (LOCK-HOLD) — and starting a
                # second loop beside a live one would race the slot
                # state, so a timeout is a hard error instead.
                t.join(timeout=30)
                if t.is_alive():
                    raise RuntimeError(
                        "decode engine loop thread did not exit "
                        "within 30s of close(); refusing to start a "
                        "second loop over the same slot pool")
            self._stop = False
            self._thread = threading.Thread(
                target=self._loop, name="decode-engine",
                daemon=True)
            self._thread.start()

    def close(self) -> None:
        # Under _thread_lock so a concurrent submit's _ensure_thread
        # restart serializes against the stop-join-drain sequence:
        # its group is either failed by a drain (error, never a hang)
        # or owned by a loop thread started strictly after close.
        with self._thread_lock:
            self._stop = True
            with self._wake:
                self._wake.notify_all()
            t = self._thread
            if t is not None and t.is_alive():
                t.join(timeout=5)
            # In-flight groups must fail (a generate() caller blocked
            # on group.event has to wake with an error, not wait
            # forever), but _resident and the slot free-list are
            # loop-thread state: a live loop thread (join timed out
            # mid-device-call) drains them itself on exit — see _loop
            # — so only drain here when no loop thread can race us.
            if t is None or not t.is_alive():
                self._fail_all(RuntimeError("decode engine closed"))

    def _fail_all(self, err: BaseException) -> None:
        """Fail every in-flight group (resident and queued) and free
        their slots — shutdown, or last-resort cleanup when a tick
        crashes outside the device-call try blocks that attribute
        errors to their own group."""
        for slot, stream in list(self._resident.items()):
            stream.group.fail(err)
            self._record_history(stream.group)
            try:
                self.slots.release(slot)
            except ValueError:
                pass
        self._resident.clear()
        # Streams that left their slots at a launch and wait for its
        # tokens live in the dispatch in flight alone.
        landing, self._flight = self._flight, None
        for stream, _ in (landing.streams.values() if landing else ()):
            stream.in_flight = 0
            stream.group.fail(err)
            self._record_history(stream.group)
        while True:
            stream = self.queue.pop_head()
            if stream is None:
                break
            self._release_stream_kv(stream)
            stream.group.fail(err)
            self._record_history(stream.group)

    def _loop(self) -> None:
        while not self._stop:
            try:
                if self.faults is not None:
                    # Injected whole-engine death: raised HERE, past
                    # tick's containment, so it exercises exactly the
                    # supervised-restart path a real scheduling-layer
                    # crash takes.
                    self.faults.check("engine_death")
                worked = self.tick()
            except BaseException as e:
                # Device errors inside prefill/admit/decode already
                # failed their own group; anything landing here is a
                # whole-engine crash with no owner.  SUPERVISED
                # engines (recovery.EngineSupervisor) recover: the
                # supervisor requeues every stream for
                # token-identical resume, rebuilds the pools, and
                # starts a replacement loop thread — this thread
                # just exits.  Unsupervised (library) engines keep
                # the legacy crash-never-hang behavior: surface the
                # error and fail everything in flight, since
                # retrying the same tick at 20 Hz would spin forever
                # while the stuck groups' clients hang.
                if self.supervisor is not None \
                        and self.supervisor.handle_crash(e):
                    return
                traceback.print_exc(file=sys.stderr)
                self._fail_all(
                    RuntimeError(f"decode engine error: "
                                 f"{type(e).__name__}: {e}"))
                worked = False
            if worked and self.supervisor is not None:
                self.supervisor.note_progress()
            if not worked:
                with self._wake:
                    if self._stop:
                        break
                    with span("ptpu/idle_wait"):
                        self._wake.wait(timeout=0.05)
        # Shutdown drain on the loop thread itself, where touching
        # _resident and the slot free-list can never race a tick.
        self._fail_all(RuntimeError("decode engine closed"))

    def _restart_loop(self) -> bool:
        """Start a REPLACEMENT loop thread after supervised crash
        recovery (called by the supervisor ON the dying loop thread,
        which exits right after).  Returns False when the engine was
        closed mid-recovery — the caller fails the queue instead of
        restarting."""
        with self._thread_lock:
            if self._stop:
                return False
            self._thread = threading.Thread(
                target=self._loop, name="decode-engine",
                daemon=True)
            self._thread.start()
            return True

    # -- one scheduling round -------------------------------------------

    def tick(self) -> bool:
        """One step boundary: deliver pending lifecycle events
        (cancellations, expired deadlines, queue-deadline sheds),
        preempt a batch resident if the interactive TTFT SLO demands
        it, admit/prefill within the policy budget, then one decode
        dispatch over the resident batch — launched BEFORE the one in
        flight is collected where the boundary allows
        (``_decode_step``), so a prefill piece enqueued here runs
        behind the dispatch in flight and ahead of the next.  Returns
        whether any work was done.  Single-threaded by contract (loop
        thread, or tests driving it manually)."""
        with span("ptpu/sweep"):
            worked = self._sweep_lifecycle()
            if self._maybe_preempt():
                worked = True
        # A piece is enqueued, not awaited, and its lane is allocated
        # when it is enqueued: the budget of a boundary is held to
        # ``_pieces_cap``, which also bounds the prefilled streams
        # that wait a boundary for their first token.
        budget = min(self._pieces_cap, self.policy.prefill_budget(
            bool(self._resident), self.slots.free_slots))
        waiting: list = []      # heads whose first token is on its way
        while budget > 0:
            stream = self._queue_head(waiting)
            if stream is None:
                break
            if stream.group.error is not None:
                self.queue.drop_group(stream.group)
                continue
            if stream.pf_done and not self._can_admit_stream(stream):
                # Prefilled, waiting on a slot / pages: stamp the
                # wait start into its causal timeline (once).
                self._note_blocked(stream)
                break
            with span("ptpu/prefill", self._host_s):
                piece, settled = self._advance_prefill(stream)
            worked = True
            # The budget counts prefill CHUNKS: admitting a stream
            # whose prompt was consumed at an earlier boundary runs
            # none (free slots bound those).
            budget -= piece
            if not settled:
                # The head's first token is on its way behind the
                # piece just enqueued: the next boundary reads it
                # finished.  The budget left goes to those behind it.
                waiting.append(stream)
        if self._resident or self._flight is not None:
            # The tick's sections belong to no request: their stats
            # say what the pool looked like.
            with span("ptpu/decode", occupancy=len(self._resident),
                      batch=self.slots.n_slots):
                self._decode_step()
            worked = True
        if not worked:
            # Going idle: no dispatch is coming to bring the last
            # prefill pieces' pair counts home.
            self.slots.flush_pairs()
        # Step-boundary bookkeeping for the debuggability layer: the
        # watchdog's progress signal and the published /debug/state
        # snapshot (throttled to board_interval_s) — host-side only,
        # never under the device lock.  The progress stamp is
        # PROGRESS-gated: a no-op tick (queue nonempty but nothing
        # admittable, no residents) must let staleness grow, or a
        # livelocked-but-spinning loop could never be declared
        # stalled — "the loop thread is alive" is not "the engine is
        # making progress".
        now = time.perf_counter()
        if worked:
            self.last_boundary_t = now
        if now - self._board_t >= self.board_interval_s:
            self._board_t = now
            with span("ptpu/board"):
                self.debug_board.publish(self.build_debug_snapshot())
        return worked

    # -- paged-KV accounting ---------------------------------------------

    def _kv_tokens_needed(self, p_len: int, new: int) -> int:
        """A stream's FULL KV reservation: prompt + budget, plus the
        speculative write slack every paged co-tenant of a
        spec-capable pool must leave (a spec round's verify chunk
        writes up to spec_k_cap positions past the last committed
        token, for every resident)."""
        slack = self.policy.spec_k_cap \
            if self.draft_model is not None else 0
        return p_len + new + slack

    def _validate_shared_epoch(self, stream: Stream) -> None:
        """Drop shared prefix pins taken under a page-pool generation
        that crash recovery has since rebuilt: the ids mean nothing
        in the fresh accounting (never unpin them into it), and the
        stream's own materialized prefill makes admission without
        the sharing token-identical — the share is an optimization.
        Runs on the engine thread (the only thread recovery
        alternates with), so the check-then-use is race-free."""
        if stream.kv_shared and stream.kv_epoch is not None \
                and stream.kv_epoch != getattr(self.slots, "epoch",
                                               None):
            stream.kv_shared = None
            stream.kv_epoch = None

    def _kv_admit_tokens(self, stream: Stream) -> int:
        """The token span admission must have pages for: the full
        budget (default reservation discipline), or — lazy — the
        stream's current committed length plus one dispatch span
        (serving/paged.py admit_tokens; the rest grows at step
        boundaries)."""
        need = self._kv_tokens_needed(stream.p_len, stream.new)
        if getattr(self.slots, "lazy", False):
            return self.slots.admit_tokens(
                stream.p_len + max(1, len(stream.out)), need)
        return need

    def _stream_barred(self, stream: Stream) -> bool:
        """Lazy-KV livelock guard: an exhaustion evictee is NOT
        admissible while the stream it was evicted for still waits
        for the freed capacity.  ``Stream.evicted_for`` is set by
        _ensure_lazy_growth and cleared the moment a growth pass
        completes (the beneficiary got its pages), so the bar
        normally lasts exactly one boundary — long enough that the
        T+1 admission (which runs BEFORE the T+1 growth) cannot hand
        the freed pages back to the very stream whose eviction freed
        them.  Also cleared when the beneficiary goes terminal, and
        when the pool has NO residents (no growth can be pending
        without a resident, so a lingering bar would deadlock an
        idle engine).  Engine thread only."""
        b = stream.evicted_for
        if b is None:
            return False
        if b.group.event.is_set() or not self._resident:
            stream.evicted_for = None
            return False
        return True

    def _queue_head(self, skip=()) -> Optional[Stream]:
        """Admission head: the class-aware queue head, SKIPPING
        streams under an active exhaustion bar — a barred evictee
        (possibly of a higher class) must never head-of-line-block
        the stream it was evicted for — and those in ``skip``: heads
        the tick has already served, whose first token is on its way
        (they keep their place: the next boundary admits them in this
        order, ahead of whoever was prefilled behind them)."""
        head = self.queue.head()
        if head is None or not (self._stream_barred(head)
                                or head in skip):
            return head
        for s in self.queue.snapshot():
            if not (self._stream_barred(s) or s in skip):
                return s
        return None

    def _admissible_now(self, stream: Stream) -> bool:
        """Pure check (no reclaim side effects — _pick_window calls
        this every boundary): a free slot AND, paged, enough free
        pages for the stream's reservation net of its shared prefix
        pages (and, lazy, no active exhaustion bar)."""
        self._validate_shared_epoch(stream)
        if self._stream_barred(stream):
            return False
        if self.slots.free_slots == 0:
            return False
        if not self.paged:
            return True
        return self.slots.can_admit(
            self._kv_admit_tokens(stream),
            len(stream.kv_shared or ()))

    def _can_admit_stream(self, stream: Stream) -> bool:
        """Admission gate: a free slot AND (paged) enough free pages
        for the stream's full reservation net of its shared prefix
        pages.  When pages are the blocker, ask the owner's reclaim
        hook (prefix-cache LRU eviction) to free some before giving
        up until the next boundary — stored-but-idle prefixes must
        never starve live traffic."""
        if self._admissible_now(stream):
            return True
        if self._stream_barred(stream) or self.slots.free_slots == 0 \
                or not self.paged:
            return False
        need = self._kv_admit_tokens(stream)
        n_shared = len(stream.kv_shared or ())
        if self.page_reclaim is not None:
            # The hook's contract is "make this many pages FREE" (it
            # evicts until the free count reaches the target), so it
            # gets the stream's whole page need — passing only the
            # deficit would stop short and leave admission blocked
            # at every subsequent boundary.
            try:
                self.page_reclaim(
                    self.slots.pages_needed(need) - n_shared)
            except Exception:
                import logging

                logging.getLogger(__name__).debug(
                    "page_reclaim hook failed", exc_info=True)
            ok = self.slots.can_admit(need, n_shared)
            if ok:
                # The unblock came from evicting stored-but-idle
                # prefix entries, not a co-tenant's eviction.
                self._last_page_free = (None, "prefix_reclaim")
            return ok
        return False

    def _release_stream_kv(self, stream: Stream) -> None:
        """Release a stream's still-PINNED shared prefix pages (set
        at submit, consumed at admission) — called on every terminal
        path that can fire before the pins transfer into a slot
        table."""
        ids = stream.kv_shared
        if ids:
            stream.kv_shared = None
            try:
                # Epoch-guarded: pins from a pool generation that
                # crash recovery rebuilt are dropped by reference.
                self.slots.unpin(ids, epoch=stream.kv_epoch)
            except Exception:
                import logging

                logging.getLogger(__name__).debug(
                    "shared-page release failed", exc_info=True)

    # -- debuggability: block/unblock attribution ------------------------

    def _note_blocked(self, stream: Stream) -> None:
        """First boundary a fully-prefilled head could not admit:
        open its wait in the causal timeline, saying WHAT it waits on
        (a slot, or — paged with a free slot — pages).  One instant
        per blocked episode; the matching ``admit_unblocked`` closes
        it with the wait length and what freed the capacity."""
        if stream.blocked_t is not None:
            return
        now = time.perf_counter()
        stream.blocked_t = now
        args: Dict[str, Any] = {"on": "slot"}
        if self.paged and self.slots.free_slots > 0:
            args["on"] = "kv_pages"
            args["pages_free"] = self.slots.free_page_count()
            args["pages_needed"] = self.slots.pages_needed(
                self._kv_admit_tokens(stream)) \
                - len(stream.kv_shared or ())
        self._emit_instant(stream, "admit_blocked", now,
                           row=stream.row, **args)

    def _note_freed(self, stream: Stream, why: str) -> None:
        """Remember who last freed slot/page capacity — the
        attribution a blocked stream's ``admit_unblocked`` instant
        carries ("which eviction unblocked me")."""
        self._last_page_free = (stream.group.rid, why)

    # -- lifecycle: cancel / deadline / shed / preempt -------------------

    def _sweep_lifecycle(self) -> bool:
        """Deliver, at this step boundary, every pending cancel and
        expired deadline (resident AND queued streams — a cancelled
        request frees its slot within ONE boundary, pinned in
        tests/test_lifecycle.py), and shed queued requests that blew
        their class queue deadline before getting any engine
        attention.  Host-side wall-clock only: deadline math never
        enters a compiled step program (JIT-DEADLINE).

        Fast path: with no cancel pending, no deadline ever armed,
        and no class queue deadline configured, there is nothing the
        scan could find — skip the O(resident + queue) walk (and its
        queue-lock snapshot) on this boundary entirely."""
        if not (self._cancel_pending or self._deadline_armed
                or self.policy.queue_deadline_s is not None
                or self.policy.batch_queue_deadline_s is not None):
            return False
        self._cancel_pending = False
        now = time.perf_counter()
        handled = set()          # id(group) -> already terminated
        worked = False
        for stream in ([s for s in self._resident.values()]
                       + self.queue.snapshot()):
            group = stream.group
            if id(group) in handled or group.error is not None:
                continue
            err = group.cancel_error
            if err is None and group.deadline is not None \
                    and now > group.deadline:
                err = DeadlineExceeded(
                    f"deadline exceeded after "
                    f"{now - group.t_submit:.3f}s "
                    f"({group.status_phase()})")
                group.request_cancel(err)
            if err is None and group.t_first_prefill is None \
                    and stream.slot is None:
                # Zero engine attention so far: the class queue
                # deadline decides whether it may keep waiting.
                qd = self.policy.class_queue_deadline(group.priority)
                if qd is not None and now - group.t_submit > qd:
                    err = ShedError(
                        f"{group.priority} request queued "
                        f"{now - group.t_submit:.3f}s without "
                        f"starting (class queue deadline {qd}s); "
                        f"shed unstarted", reason="queue_deadline",
                        retry_after=self.policy.retry_after_s)
                    group.request_cancel(err)
            if err is not None:
                handled.add(id(group))
                self._cancel_group(group, err, now)
                worked = True
        return worked

    def _cancel_group(self, group: RequestGroup, err: BaseException,
                      now: float) -> None:
        """Terminate ``group`` with lifecycle error ``err``: drop its
        queued streams, evict its residents (slots free THIS
        boundary), emit the terminal span, bump the right counter,
        and wake the waiter."""
        status = terminal_status(err)
        self.queue.drop_group(group)
        # With a dispatch in flight the slot is free from HERE, but
        # that dispatch still steps it: the tokens it brings for the
        # group are never dealt (``_forget``).
        self._forget(group.streams)
        for slot, stream in list(self._resident.items()):
            if stream.group is not group:
                continue
            del self._resident[slot]
            self.slots.release(slot)
            self.evicted_total += 1
            self._note_freed(stream, status)
            # Close the decode span at the eviction boundary so the
            # trace shows exactly how much work the cancel discarded.
            self._emit(stream, "decode", stream.t_admit, now,
                       row=stream.row, slot=slot,
                       tokens=len(stream.out), terminal=status)
            stream.slot = None
        for stream in group.streams:
            self._release_stream_kv(stream)
            self._emit_instant(stream, status, now, row=stream.row,
                               tokens=len(stream.out))
        if isinstance(err, ShedError):
            with self._shed_lock:   # submit's draining gate races us
                self.shed_total += 1
                self.shed_by_class[group.priority] += 1
        elif isinstance(err, DeadlineExceeded):
            self.expired_total += 1
        else:
            self.cancelled_total += 1
        group.fail(err)
        self._record_history(group)

    def _recent_ttft_p99(self) -> Optional[float]:
        """p99 of the sliding interactive-TTFT window (None until
        there are observations) — the degraded-class half of the
        preemption trigger."""
        if not self._ttft_recent:
            return None
        xs = sorted(self._ttft_recent)
        return xs[min(len(xs) - 1, int(0.99 * len(xs)))]

    def _maybe_preempt(self) -> bool:
        """Preempt ONE batch resident when the interactive class
        needs its slot: the head of the interactive queue is
        admit-ready (fully prefilled) with no free slot, and the
        interactive admission-anchored TTFT — the p99 of the PR 4
        histogram, or this head's own wait — has degraded past the
        ``slo_ttft_s`` target.  The victim (the batch resident with
        the most remaining budget, i.e. the longest expected hold) is
        evicted through the same path as cancellation and REQUEUED at
        the front of the batch class with its generated-so-far
        prefix: resumption is token-identical (Stream.prepare_resume)
        so preemption costs re-prefill, never correctness."""
        slo = self.policy.slo_ttft_s
        if slo is None or self.slots.free_slots > 0:
            return False
        head = self._queue_head()   # bar-aware: never preempt FOR a
        #                             barred exhaustion evictee
        if head is None or head.group.priority != "interactive" \
                or not head.pf_done:
            return False
        now = time.perf_counter()
        waited = now - head.group.t_submit
        # The control-law reason rides the victim's ``preempted``
        # instant (and so its history record): which trigger fired.
        reason = "head_wait_over_half_slo"
        if waited <= slo / 2:
            # Head-wait trigger acts at HALF the budget: preempting
            # only once the target is already blown would guarantee
            # a TTFT past the SLO by the time the admission it buys
            # lands — a controller has to act with margin.  Under
            # half-budget, consult the class p99 over the RECENT
            # window (self._ttft_recent — same observations the
            # exported ttft_interactive histogram records, but
            # sliding, so a transient bad period stops arming
            # preemption once healthy TTFTs wash it out instead of
            # latching until restart).
            p99 = self._recent_ttft_p99()
            if p99 is None or p99 <= slo:
                return False
            reason = "ttft_p99_degraded"
        victim = None
        for slot, stream in self._resident.items():
            if stream.group.priority != "batch":
                continue
            rem = stream.remaining
            if victim is None or rem > victim[2]:
                victim = (slot, stream, rem)
        if victim is None:
            return False        # all residents interactive: defer only
        slot, stream, _ = victim
        self.preempted_total += 1
        stream.preempts += 1
        # The causal evidence a co-tenancy incident needs: WHO forced
        # this eviction (the preemptor's request ID) and WHY the
        # control law fired.
        self._evict_requeue(slot, stream, "preempted", now,
                            by=head.group.rid, reason=reason,
                            head_waited_ms=round(1e3 * waited, 3))
        return True

    def _evict_requeue(self, slot: int, stream: Stream, why: str,
                       now: float, *, release: bool = True,
                       front: bool = True,
                       **instant_args) -> None:
        """Evict a RESIDENT stream and requeue it for token-identical
        resume — the one path every requeue flavor (SLO preemption,
        quarantine bisection, crash recovery, lazy-KV exhaustion)
        shares, because the safety argument is one argument: resume
        re-prefills ``prompt ++ out[:-1]`` in pow2 pieces (bounded
        program set, steady-state quiet) and re-enters feeding
        ``out[-1]`` with ``next_index == len(out)``, so no token is
        ever resampled (Stream.prepare_resume).

        ``front=True`` (every flavor but exhaustion) requeues at the
        head of the stream's class; exhaustion evictions requeue at
        the BACK (``front=False``) — the freed pages belong to the
        growth-blocked beneficiary and everyone already queued, not
        to the evictee (AdmissionQueue.requeue_back).

        ``release=False`` skips the slot release for crash recovery,
        whose wholesale pool rebuild (slots.reset) makes per-slot
        release both redundant and — paged — unsafe (the page
        accounting it would touch is about to be reset).

        Tokens of the stream in a dispatch in flight are dropped with
        it (``_forget``): they were never committed, and the resume
        draws them again at the same position keys."""
        self._forget((stream,))
        if self._resident.get(slot) is stream:
            del self._resident[slot]
        if release:
            self.slots.release(slot)
        self.evicted_total += 1
        self._note_freed(stream, why)
        self._emit(stream, "decode", stream.t_admit, now,
                   row=stream.row, slot=slot, tokens=len(stream.out),
                   terminal=why)
        self._emit_instant(stream, why, now, row=stream.row,
                           slot=slot, tokens=len(stream.out),
                           **instant_args)
        stream.slot = None
        # pow2 pieces, not chunk_plan: the resume length is
        # data-dependent (prompt + commits at the eviction point),
        # so one-piece prefill would be a fresh compile per
        # eviction — pow2 decomposition keeps the resume program
        # set bounded and steady-state quiet.
        stream.prepare_resume(SchedulerPolicy.pow2_pieces(
            stream.p_len + len(stream.out) - 1))
        if front:
            self.queue.requeue_front(stream)
        else:
            self.queue.requeue_back(stream)
        self.requests_requeued_total += 1

    def mean_resident_position(self) -> float:
        """Mean absolute decode position over resident slots (0.0
        when the pool is empty) — the flight recorder's context-
        length input to the per-token attention-flop term.  Engine
        thread only (it reads the slot arrays the tick mutates)."""
        if not self._resident:
            return 0.0
        return float(np.mean([self.slots.state.positions[s]
                              for s in self._resident]))

    def run_until_idle(self, max_ticks: int = 100000) -> None:
        """Drain queue + slots synchronously (tests/offline use)."""
        for _ in range(max_ticks):
            if not self.tick():
                return
        raise RuntimeError("engine did not go idle within max_ticks")

    # -- crash recovery (recovery.EngineSupervisor) ----------------------

    def recover_from_crash(self) -> int:
        """The engine half of supervised crash recovery — "requeue
        everything and replay" (VirtualFlow's decoupling of request
        state from the device holding it, arXiv:2009.09523).  Called
        by the supervisor with NO loop thread running, so touching
        loop-thread state is race-free by construction.  Returns the
        number of resident streams requeued.

        - Every RESIDENT stream is requeued through the preempt-
          resume path: its committed tokens are host-side state, so
          resumption is token-identical per seed however the engine
          died (pinned in tests/test_faults.py).  So is every stream
          that had left its slot at a launch and was waiting for that
          dispatch's tokens: tokens in flight were never committed,
          and the dispatch in flight is dropped whole.
        - Every PARTIAL PREFILL (and stored-prefix seed) is reset to
          re-prefill from its tokens — the partial cache referenced
          a device state the crash made untrustworthy; chunked
          prefill is position-keyed, so a from-scratch refill equals
          the interrupted one.  pow2 pieces keep the replay program
          set bounded (zero steady-state recompiles after recovery,
          pinned).
        - The slot/page pools rebuild IN PLACE (``slots.reset``):
          fresh storage, SAME compiled step/insert programs.
        - Stale shared-page pins are dropped by reference (never
          unpinned INTO the fresh pool — its accounting starts
          all-free); the owner's recovery hook flushes the prefix
          store whose payloads those pins protected."""
        now = time.perf_counter()
        # Quarantine suspicion dies with the loop that formed it:
        # the fault context behind a pre-crash episode is gone, and
        # a stale suspect re-admitted alone must not be convictable
        # without fresh bisection evidence.  (The conviction-streak
        # counter deliberately SURVIVES recovery — a fault that
        # recurs after restart escalates immediately instead of
        # convicting more innocents; any successful dispatch resets
        # it.)
        self._suspects.clear()
        # Exhaustion bars die with the pool generation: the rebuilt
        # all-free pool has no pending growth to protect.
        self._exhaust_bars.clear()
        self._pieces.clear()
        n = 0
        landing, self._flight = self._flight, None
        departed = [(slot, stream) for slot, (stream, _) in
                    (landing.streams.items() if landing else ())
                    if self._resident.get(slot) is not stream
                    and not stream.group.event.is_set()]
        for slot, stream in sorted(
                list(self._resident.items()) + departed,
                key=lambda at: at[0]):
            self._evict_requeue(slot, stream, "crash_requeued", now,
                                release=False)
            n += 1
        for stream in self.queue.snapshot():
            stream.kv_shared = None
            stream.evicted_for = None
            if stream.filled or stream.cache is not None \
                    or stream.pf_done:
                stream.pieces = SchedulerPolicy.pow2_pieces(
                    stream.pf_toks.shape[1])
                stream.filled = 0
                stream.cache = None
                stream.d_cache = None
                stream.logits = None
                stream.first = None
                stream.pf_done = False
                stream.blocked_t = None
        with self.device_lock:
            # Under the device lock: handler threads scatter/gather
            # prefix pages under this same lock, and their
            # in-device-lock epoch checks are only airtight if the
            # rebuild (which bumps the epoch) cannot interleave.
            # Pure host work — the hold is microseconds.
            self.slots.reset()
        self._last_page_free = None
        self.last_boundary_t = time.perf_counter()
        return n

    # -- telemetry ------------------------------------------------------

    def _emit(self, stream: Stream, name: str, t0: float, t1: float,
              **args) -> None:
        """One lifecycle span for ``stream``: into the shared trace
        ring, and (when a ``timings`` block or the history ring wants
        it) onto the stream's own event list.  Every span carries the
        request ID — the correlation key ``trace_report.py
        --request`` and the /requests records filter on.

        CONTAINED: a telemetry failure (injected via the
        ``telemetry`` fault site, or a real bug in the ring) is
        counted and dropped, never propagated — observability must
        stay strictly isolated from the request path (the
        degradation ladder, docs/SERVING.md)."""
        if stream.group.rid is not None:
            args.setdefault("rid", stream.group.rid)
        try:
            if self.faults is not None:
                self.faults.check("telemetry")
            self.tel.span(stream.sid or 0, name, t0, t1, **args)
        except Exception:
            # ptpu: lockfree[best-effort drop counter: a lost increment under-counts a diagnostic, nothing else]
            self.telemetry_errors_total += 1
        if stream.events is not None:
            stream.events.append((name, t0, t1, args))

    def _emit_instant(self, stream: Stream, name: str, t: float,
                      **args) -> None:
        if stream.group.rid is not None:
            args.setdefault("rid", stream.group.rid)
        try:
            if self.faults is not None:
                self.faults.check("telemetry")
            self.tel.instant(stream.sid or 0, name, t, **args)
        except Exception:
            self.telemetry_errors_total += 1
        if stream.events is not None:
            stream.events.append((name, t, t, args))

    # -- prefill + admission --------------------------------------------

    def _pf_fn(self, s_len: int, first: bool):
        """Jitted prefill (fresh cache) / extend (append at position)
        program for one piece length — the engine-side twin of the
        server's prefix-cache split programs.  Returns ``(logits,
        cache, stats)``: what the model sowed over the piece
        (generate.prefill ``with_stats``; None where nothing)."""
        from ..models import generate as G

        if self._prefill_fns is not None:
            return self._prefill_fns(s_len, first)
        model, variables = self.model, self.variables

        def build():
            return G.jit_over(variables,
                              G.prefill_programs(model)[not first])

        return lru_get(self._pf_fns,
                       ("pfill" if first else "extend", s_len),
                       self._pf_cap, build,
                       sentinel=self.sentinel, kind="engine_prefill")

    def _pf_fn_draft(self, s_len: int, first: bool):
        """Draft-model twin of :meth:`_pf_fn` for speculative
        streams' draft prefill."""
        from ..models import generate as G

        draft, dvars = self.draft_model, self.draft_variables

        def build():
            if first:
                return G.jit_over(
                    dvars, lambda w, toks: G.prefill(draft, w, toks))
            return G.jit_over(
                dvars, lambda w, cache, toks, pos: G.prefill(
                    draft, w, toks, cache=cache, position=pos))

        return lru_get(self._pf_fns_draft,
                       ("pfill" if first else "extend", s_len),
                       self._pf_cap, build,
                       sentinel=self.sentinel, kind="draft_prefill")

    def _advance_prefill(self, stream: Stream) -> Tuple[bool, bool]:
        """ENQUEUE one prefill piece for the head-of-queue stream;
        admit it into a slot when the prompt is fully consumed AND a
        slot is free (prefill works AHEAD while all slots are busy,
        so a freshly evicted slot admits an already-prefilled request
        the same boundary).  Chunked prefill is position-keyed cache
        extension (models/generate._prefill): piecewise equals
        one-shot, so interleaving changes latency, never tokens.

        The piece is not awaited: nothing the host does next needs
        its result but the next piece (a device operand) and the
        first token, whose device work is enqueued right behind the
        LAST piece (``_enqueue_first``).  With a decode dispatch in
        flight the piece runs behind it, so the stream is admitted at
        the NEXT boundary, which reads a finished scalar; with
        nothing in flight the device is idle anyway and admission
        waits for the piece here, as it always did.

        Returns ``(piece, settled)``: whether a piece was enqueued
        (what the tick's prefill budget counts), and whether the
        stream is done with for this boundary as the queue's head
        (False: it waits there for its first token, and the tick
        passes over it)."""
        import jax

        group = stream.group
        if stream.t_prefill_start is None:
            stream.t_prefill_start = time.perf_counter()
            if group.t_first_prefill is None:
                group.t_first_prefill = stream.t_prefill_start
            # Queue span closes the moment the stream first gets
            # engine attention (prefill, or straight admission for
            # full-length prefix hits).
            self._emit(stream, "queue", group.t_submit,
                       stream.t_prefill_start, row=stream.row)
        ran = bool(stream.pieces)
        if ran:                         # full-length prefix hits skip
            piece = stream.pieces[0]
            # pf_toks, not toks: a PREEMPTED stream re-prefills
            # prompt ++ committed[:-1] (Stream.prepare_resume) so its
            # resumption is token-identical; for everyone else the
            # two are the same array.
            toks = stream.pf_toks[:, stream.filled:stream.filled
                                  + piece]
            spec = stream.sampling.spec_k > 0
            t_piece = time.perf_counter()
            try:
                with self.device_lock, self._exact():
                    if stream.cache is None:
                        logits, cache, pairs = self._pf_fn(
                            piece, True)(toks)
                    else:
                        logits, cache, pairs = self._pf_fn(
                            piece, False)(stream.cache, toks,
                                          stream.filled)
                    if spec:
                        # Speculative streams prefill the DRAFT model
                        # too (same pieces — the chunked-prefill
                        # exactness contract holds per model).
                        if stream.d_cache is None:
                            _, d_cache = self._pf_fn_draft(
                                piece, True)(toks)
                        else:
                            _, d_cache = self._pf_fn_draft(
                                piece, False)(stream.d_cache, toks,
                                              stream.filled)
                        stream.d_cache = d_cache
                    stream.logits = logits
                    if len(stream.pieces) == 1:
                        self._enqueue_first(stream)
            except BaseException as e:
                self._fail_group(group, e)
                return True, True
            # Never more than ``_pieces_cap`` pieces ahead of the
            # device: a wait only where the host has run that far
            # ahead, so the device's queue is long, not empty.
            self._pieces.append(logits)
            while len(self._pieces) > self._pieces_cap:
                try:
                    jax.block_until_ready(self._pieces.popleft())
                except Exception:
                    # A failed piece is its own stream's to meet, at
                    # the fetch of its first token (_admit).
                    import logging

                    logging.getLogger(__name__).debug(
                        "an earlier prefill piece failed",
                        exc_info=True)
            # The expert layers' pair counts of the piece ride home
            # with the next dispatch's tokens.
            self.slots.defer_pairs(pairs)
            stream.cache = cache
            stream.filled += piece
            # The chunk's attention read the full-length planes as far
            # as it had written them (generate.prefill_programs).
            self.slots.plane_reads.learn(cache)
            self.slots.plane_reads.count([stream.filled])
            self.slots.plane_reads.count_piece(piece, stream.filled)
            stream.pieces.pop(0)
            self.prefill_chunks_total += 1
            self.prefill_tokens_total += piece
            self._emit(stream, "prefill", t_piece,
                       time.perf_counter(), row=stream.row,
                       piece=piece, filled=stream.filled)
            if stream.pieces:
                return True, True       # more prompt to consume
        if not stream.pf_done:
            stream.pf_done = True
            if stream.first is None:
                # No piece ran here (a full-length prefix hit): the
                # first token comes from the logits the stream
                # brought.
                try:
                    with self.device_lock, self._exact():
                        self._enqueue_first(stream)
                except BaseException as e:
                    self._fail_group(group, e)
                    return ran, True
            # Never on a resumed stream: its pf_toks mix generated
            # tokens into the prefill, which must not be stored back
            # as a prompt prefix.
            if group.on_prefilled is not None and not stream.resume:
                try:
                    group.on_prefilled(stream)
                except Exception:
                    # Cache store-back must not fail the request, but
                    # a broken prefix cache should be diagnosable.
                    import logging

                    logging.getLogger(__name__).debug(
                        "on_prefilled hook failed", exc_info=True)
        if not self._can_admit_stream(stream):
            return ran, True    # wait, fully prefilled, for slot/pages
        if ran and self._flight is not None:
            return ran, False   # its first token: the next boundary
        # Pop THIS stream, never "the head": a concurrent interactive
        # submit can change the class-aware head between the tick's
        # head() and this pop (scheduler.AdmissionQueue.pop_stream).
        self.queue.pop_stream(stream)
        with span("ptpu/admit", self._host_s):
            self._admit(stream)
        return ran, True

    def _enqueue_first(self, stream: Stream) -> None:
        """Enqueue, right behind the stream's LAST prefill piece (the
        caller holds the device lock), whatever device work admission
        needs, and leave the futures on ``stream.first`` as ``(token
        0, base key)``: admission fetches finished scalars instead of
        launching programs it then waits for.

        Token 0 comes from the prefill logits.  Greedy: the argmax
        (np and jnp agree on first-max tie-breaking).  Sampled: the
        SAME position-keyed sampler the slot step program runs
        (``generate._sample_positional_row``), at token index 0, with
        the stream's ``fold_in(PRNGKey(seed), row)`` base key — each
        jitted once.  A RESUMED stream draws none: all its committed
        tokens exist.

        The base key is made for every stream whose slot will want
        one and has none yet: sampled streams, and speculative ones —
        greedy speculative streams never draw token 0 from the PRNG,
        but the spec step program still wants the slot's base key
        operand (the sampled lanes are dead at temperature 0 — zeros
        would work — yet arming the real key keeps one invariant:
        every speculative slot's key is ``fold_in(PRNGKey(seed),
        row)``).  A CROSS-REPLICA resumed sampled stream (submit
        ``resume_tokens=``) drew its token 0 in a prior attempt, so
        its key is made here too: same fold_in, a pure function of
        the request."""
        import jax
        import jax.numpy as jnp

        from ..models import generate as G

        spec = stream.sampling
        key = tok = None
        if stream.base_key is None and (spec.sampled
                                        or spec.speculative):
            key = jax.random.fold_in(jax.random.PRNGKey(spec.seed),
                                     stream.row)
        if not stream.resume:
            fn = self._first_fns.get(spec.sampled)
            if fn is None:
                self.sentinel.miss("admit_sample" if spec.sampled
                                   else "admit_argmax")
                fn = self._first_fns[spec.sampled] = jax.jit(
                    (lambda l, k, t, tk, tp:
                     G._sample_positional_row(l[0], k, 0, t, tk, tp))
                    if spec.sampled else
                    (lambda l: jnp.argmax(l[0]).astype(jnp.int32)))
            tok = fn(stream.logits,
                     stream.base_key if key is None else key,
                     np.float32(spec.temperature),
                     np.int32(spec.top_k), np.float32(spec.top_p)) \
                if spec.sampled else fn(stream.logits)
        stream.first = (tok, key)

    def _admit(self, stream: Stream) -> None:
        """Step-boundary admission: fetch the first token
        (``_enqueue_first`` put its device work behind the last
        prefill piece), cache into a free slot — the insertion is
        ENQUEUED, behind whatever dispatch is in flight: the donated
        pool orders them on the device.  Device failures
        (including the FIRST insert's lazy stacked-pool allocation —
        the engine's largest device buy) release the slot and fail
        the group: a waiter must never hang on an admission that
        silently died.

        A RESUMED (preempted) stream skips token sampling entirely —
        all its committed tokens already exist — and re-enters its
        slot feeding ``out[-1]`` at its original position with
        ``next_index == len(out)``, so the next draw uses exactly the
        position key the uninterrupted run would have."""
        import jax

        slot = self.slots.acquire()
        assert slot is not None, "admission without a free slot"
        stream.last_slot = slot
        stream.evicted_for = None    # an admitted stream carries no
        #                              exhaustion bar
        spec = stream.sampling
        resumed = stream.resume
        try:
            # HOST-SYNC: 4 bytes, and a sampled stream's 8 of key,
            # enqueued behind the last prefill piece
            # (_enqueue_first); a failure of that piece surfaces here.
            first, key = jax.device_get(stream.first)
            if first is not None and stream.step_logits is not None:
                # HOST-SYNC: on request only ({"logits": true}).
                stream.step_logits.append(
                    np.asarray(jax.device_get(stream.logits))[0])
        except BaseException as e:
            self.slots.release(slot)
            self._fail_group(stream.group, e)
            return
        stream.first = None
        if key is not None:
            stream.base_key = np.asarray(key)
        if first is not None:
            stream.out.append(int(first))
        stream.t_admit = time.perf_counter()
        stream.group.t_last_admit = stream.t_admit
        if stream.group.t_first_admit is None:
            # First token of the whole request exists NOW (sampled
            # from the prefill logits) — the TTFT anchor, observed
            # into the request's CLASS histogram (the preemption
            # control signal, docs/SERVING.md).
            stream.group.t_first_admit = stream.t_admit
            ttft = stream.t_admit - stream.group.t_submit
            self.tel.observe("ttft_" + stream.group.priority, ttft,
                             exemplar=stream.group.rid)
            if stream.group.priority == "interactive":
                self._ttft_recent.append(ttft)
        self._emit_instant(stream, "admit", stream.t_admit,
                           row=stream.row, slot=slot,
                           **({"resumed": True} if resumed else {}))
        if stream.blocked_t is not None:
            # Close the admission wait opened by _note_blocked, with
            # the attribution: whose eviction freed the capacity.
            unb = self._last_page_free
            self._emit_instant(
                stream, "admit_unblocked", stream.t_admit,
                row=stream.row, slot=slot,
                wait_ms=round(
                    1e3 * (stream.t_admit - stream.blocked_t), 3),
                **({"unblocked_by": unb[0], "freed_via": unb[1]}
                   if unb is not None else {}))
            stream.blocked_t = None
        stream.logits = None
        if not resumed and stream.done():   # new == 1, or instant eos
            stream.cache = None
            stream.d_cache = None
            self._release_stream_kv(stream)  # never mapped a table
            self.slots.release(slot)
            stream.slot = slot          # zero-length decode span
            self._complete(stream)      # still keys the slot id
            stream.slot = None
            self._count_admitted(spec, stream.group.priority)
            self.evicted_total += 1
            return
        kw = {}
        if self.paged:
            # Ownership of the pinned shared pages passes to insert
            # (it unpins on its own failure paths), so clear the
            # stream's reference FIRST — a later terminal path must
            # not double-release.
            shared = stream.kv_shared or ()
            stream.kv_shared = None
            kw = dict(total_tokens=self._kv_tokens_needed(
                stream.p_len, stream.new), shared_pages=shared)
        try:
            if self.faults is not None:
                # Injected page-pool allocation failure: raises a
                # PageExhausted subclass, so it rides the SAME
                # transient-shortage requeue below that a real
                # admission-gate race takes.
                self.faults.check("page_alloc")
            with self.device_lock:
                # Uniform across fresh and resumed admissions: feed
                # the LAST committed token at its absolute position
                # (fresh: token 0 at p_len), and draw token
                # ``len(out)`` next.
                self.slots.insert(
                    slot, stream.cache, stream.out[-1],
                    stream.p_len + len(stream.out) - 1,
                    base_key=stream.base_key,
                    next_index=len(stream.out),
                    temperature=spec.temperature, top_k=spec.top_k,
                    top_p=spec.top_p, draft_cache=stream.d_cache,
                    spec_k=spec.spec_k, **kw)
        except PageExhausted as pe:
            # A handler thread (prefix store) reserved pages between
            # the admission gate and this insert: a TRANSIENT
            # shortage, not a request failure — put the stream back
            # at the front of its class through the preempt-resume
            # machinery (insert already released its pins/pages), so
            # it re-prefills and admits when pages free.  The
            # fits-but-not-now contract: wait, never 500.
            self.slots.release(slot)
            if kw.get("shared_pages") and getattr(pe, "injected",
                                                  False):
                # An INJECTED exhaustion fires at the probe, BEFORE
                # insert (the pin owner on real failures) ever ran —
                # the transferred pins must be released here or the
                # chaos harness leaks the very pages whose
                # accounting it exists to attest.
                try:
                    self.slots.unpin(kw["shared_pages"],
                                     epoch=stream.kv_epoch)
                except Exception:
                    import logging

                    logging.getLogger(__name__).debug(
                        "injected-fault pin release failed",
                        exc_info=True)
                stream.kv_epoch = None
            self._emit_instant(stream, "page_requeued",
                               time.perf_counter(), row=stream.row,
                               tokens=len(stream.out))
            stream.prepare_resume(SchedulerPolicy.pow2_pieces(
                stream.p_len + len(stream.out) - 1))
            self.queue.requeue_front(stream)
            self.requests_requeued_total += 1
            return
        except BaseException as e:
            self.slots.release(slot)
            self._fail_group(stream.group, e)
            return
        stream.cache = None             # pool owns the KV now
        stream.d_cache = None
        stream.slot = slot
        self._resident[slot] = stream
        if resumed:
            stream.resume = False
            stream.resumes += 1
            self.resumed_total += 1
        else:
            self._count_admitted(spec, stream.group.priority)

    def _count_admitted(self, spec: SamplingSpec,
                        priority: str) -> None:
        self.admitted_total += 1
        self.admitted_by_class[priority] += 1
        if spec.speculative:
            self.admitted_spec_total += 1
        elif spec.sampled:
            self.admitted_sampled_total += 1
        else:
            self.admitted_greedy_total += 1

    # -- decode ---------------------------------------------------------

    def _pick_window(self) -> int:
        """Decode steps to fuse into the next device dispatch.

        Window = 1 whenever a smaller granularity could make forward
        progress sooner: a queued request with a free slot is
        admissible at the very next boundary, an eos-capable resident
        might free one at any step, and a queued prompt still mid-
        prefill earns one chunk per BOUNDARY (prefill_budget) — fusing
        would starve its prefill-ahead and leave the next evicted slot
        waiting on an unfinished prompt.  Otherwise the only capacity
        event is a BUDGET eviction, and ``min(remaining)`` lands the
        window end exactly on the earliest one — so fusing up to
        ``decode_window`` steps (rounded down to a power of two to
        bound compiled programs) saves per-step dispatch + host-sync
        overhead without delaying a single admission."""
        cap = self.policy.decode_window
        if cap <= 1:
            return 1
        if any(s.step_logits is not None
               for s in self._resident.values()):
            return 1    # a dispatch keeps its LAST step's logits
        waiters = getattr(self.device_lock, "waiters", None)
        if waiters is not None and waiters():
            # A handler thread is WAITING on the device lock right
            # now (wire-fetch admit, direct /prefill, solo request):
            # fusing would make it wait out the whole fused hold.
            # Window 1 bounds its wait to one step, exactly like a
            # queued interactive head.
            return 1
        head = self.queue.head()
        if head is not None and (
                not head.pf_done
                # Admissible NEXT BOUNDARY — for paged pools a free
                # slot alone is not admissibility: a head blocked on
                # PAGES can't admit until a budget eviction frees
                # some, so fusing toward that eviction loses nothing
                # (an eos-capable resident still pins the window to 1
                # below, since an eos frees pages mid-window).
                or self._admissible_now(head)
                or any(s.eos_id is not None
                       for s in self._resident.values())
                # An armed TTFT SLO makes every boundary a potential
                # preemption point while an interactive request
                # waits: fusing would delay it by the whole window.
                or (self.policy.slo_ttft_s is not None
                    and head.group.priority == "interactive")):
            return 1
        if any(s.group.deadline is not None
               for s in self._resident.values()):
            # Deadlines are delivered at boundaries only; fusing
            # past one would hold a dead request's slot for the
            # window tail.  Cancels can land at any moment, so only
            # actually-armed deadlines (cheap to check) cost fusion.
            return 1
        # Budget horizon in ROUNDS, advance-aware: a speculative slot
        # may commit up to spec_k tokens per round, so fusing
        # ``rem // spec_k`` rounds can never push any slot past its
        # budget (no wasted rounds, and — because a spec round's
        # verify chunk touches up to position + spec_k — no slot ever
        # writes past the capacity the server validated).
        rem = min(s.remaining //
                  (s.sampling.spec_k if s.sampling.speculative else 1)
                  for s in self._resident.values())
        w, cap = 1, min(cap, max(1, rem))
        while w * 2 <= cap:
            w *= 2
        return w

    # -- step-boundary fault containment ---------------------------------

    def _host_fields(self) -> Dict[str, float]:
        """The step record's host sections: seconds since the last
        record, taken and reset, so that no second is in two records.
        ``device_s`` is the step markers' own time (a host clock
        around launch and wait: one marker in steady state, holding
        the NEXT dispatch's ``upload_s`` and ``enqueue_s`` and this
        one's ``sync_s``; the lock wait lies outside it); ``admit_s``
        and ``prefill_s`` (one ``_advance_prefill`` outside its
        ``_admit``) the admissions and prefill pieces since the last
        record; ``commit_s`` the commit that ended since then, which
        is the previous dispatch's."""
        tick, step = self._host_s, self.slots.host_s
        admit = take(tick, "ptpu/admit")
        return {
            "device_s": take(step, STEP_MARKER),
            "upload_s": take(step, "ptpu/upload"),
            "enqueue_s": take(step, "ptpu/enqueue"),
            "sync_s": take(step, "ptpu/sync"),
            "commit_s": take(tick, "ptpu/commit"),
            "lock_wait_s": take(tick, "ptpu/lock_wait"),
            "admit_s": admit,
            "prefill_s": round(max(
                0.0, take(tick, "ptpu/prefill") - admit), 6),
        }

    def _dispatch_step(self, dispatch):
        """Contained step dispatch — the crash-only containment
        ladder (docs/SERVING.md "Fault tolerance").  Returns the
        dispatch result, or None when containment resolved the
        failure by mutating the resident set (quarantine evictions /
        convictions) — the caller skips this boundary's commit and
        the next tick re-plans.

        Classification of a failing dispatch:

        - POOL LOST (``slots.pool_lost()``): the step programs take
          the KV pool as a DONATED argument and update it in place
          (serving/slots.py), so a failure raised AFTER the program
          consumed the pool leaves no pool to retry on — the live
          tree holds deleted arrays, and there is no second copy.
          Checked first, whatever the error's class:
          :meth:`_recover_lost_pool` requeues every resident for the
          token-identical resume from its committed prefix and
          rebuilds the pool (``recover_from_crash``, the supervisor's
          own path).  A deleted buffer is never dispatched again.
        - TRANSIENT (faults.is_transient — injected TransientFault,
          or any error carrying ``ptpu_transient``), the pool intact:
          retried in place under the shared bounded jittered-backoff
          :class:`~polyaxon_tpu.serving.recovery.RetryPolicy`.
          "Intact" is every failure raised BEFORE the program took
          the pool — every injected fault is (``faults.check`` runs
          ahead of ``dispatch()``), and so are errors in the host
          sections ahead of the call.  A retry re-runs the identical
          dispatch on the unchanged pool — no tokens were committed
          — so retries never change output.
        - POISONED (faults.is_poisoned), or transient with retries
          exhausted, or any other error with residents to protect:
          :meth:`_quarantine_step` — bisect the resident suspects
          until the culprit fails ALONE, requeue everyone else for
          token-identical resume.

        A containment round that cannot converge (a fault tracking
        no single request — e.g. the device itself died) escalates
        by raising: the loop's catch-all hands it to the supervisor
        (restart + requeue) or, unsupervised, fails everything
        visibly.  Either way: bounded, never a hang."""
        attempt = 0
        rounds = 0
        while True:
            rounds += 1
            if rounds > 4 * self.slots.n_slots + 8:
                raise RuntimeError(
                    "step-fault containment did not converge "
                    "(failures outlasted per-request quarantine); "
                    "escalating to engine recovery")
            try:
                if self.faults is not None:
                    # slow_step sleeps OUTSIDE the device lock so an
                    # injected stall wedges the engine loop (what the
                    # stall watchdog watches), not every solo caller.
                    self.faults.check("slow_step")
                    self.faults.check("step", rids=[
                        s.group.rid
                        for s in self._resident.values()])
                out = dispatch()
            except BaseException as e:
                if not self._resident:
                    raise       # nothing to contain: scheduling bug
                if self.slots.pool_lost():
                    self._recover_lost_pool(e)
                    return None
                if is_transient(e) and not is_poisoned(e) \
                        and attempt < self.retry_policy.max_attempts:
                    delay = self.retry_policy.delay_s(attempt)
                    attempt += 1
                    self.step_retries_total += 1
                    try:
                        self.tel.instant(
                            0, "step_retry", time.perf_counter(),
                            pid=ENGINE_PID, error=type(e).__name__,
                            attempt=attempt,
                            backoff_ms=round(1e3 * delay, 3))
                    except Exception:
                        # Same isolation contract as _emit: a broken
                        # ring must never turn a retryable step
                        # fault into an engine crash.
                        self.telemetry_errors_total += 1
                    time.sleep(delay)
                    continue
                self._quarantine_step(e)
                if not self._resident:
                    return None
                continue
            self._convictions_without_success = 0
            if self._suspects:
                # A successful dispatch exonerates every RESIDENT
                # suspect: the deterministic fault did not fire, so
                # the culprit is not among them.
                for s in self._resident.values():
                    self._suspects.discard(s.group)
            return out

    def _recover_lost_pool(self, err: BaseException) -> None:
        """A program consumed the donated KV pool and then failed:
        nothing resident has a cache any more.  Requeue every
        resident (and reset every partial prefill) for the
        token-identical resume and rebuild the pool on the next
        insertion — ``recover_from_crash``, run here on the loop
        thread between two ticks' device sections, with the compiled
        programs kept."""
        self.kv_pool_lost_total += 1
        try:
            self.tel.instant(
                0, "kv_pool_lost", time.perf_counter(),
                pid=ENGINE_PID, error=type(err).__name__,
                residents=len(self._resident))
        except Exception:
            self.telemetry_errors_total += 1
        self.recover_from_crash()

    def _quarantine_step(self, err: BaseException) -> None:
        """One quarantine-bisection round for a poisoned step
        failure: isolate WHICH resident request keeps failing the
        shared dispatch, fail only it, resume everyone else
        token-identically.

        The invariant the machinery rides: a poisoned failure fires
        exactly when its culprit is resident.  So —

        - no resident suspects yet: the failing dispatch implicates
          every resident (fresh episode — mark them all);
        - ONE suspect, and it is the SOLE resident: it just failed
          ALONE — CONVICTED.  It fails with the typed
          :class:`~.scheduler.PoisonedRequest` (500 +
          ``reason: poisoned_request``), and every other suspect is
          exonerated;
        - one suspect among UNMARKED residents (a suspect carried
          over from an earlier episode, sharing the dispatch with
          requests admitted since): the failure implicates everyone
          present — a lone stale suspect must NOT be convicted on
          another request's fault, so every resident is (re)marked
          and bisection continues on fresh evidence;
        - several resident suspects: BISECT — evict half to the
          requeue path (token-identical resume) and let the caller
          re-dispatch with the rest resident.

        A culprit that escapes a bisection round (its half was
        evicted, so the re-dispatch succeeded) stays marked across
        episodes; once bisection leaves it the sole RESIDENT of a
        failing dispatch, it is convicted.  Convergence is bounded
        by the resident count per round (_dispatch_step's round
        guard — and the conviction-streak escalation — handle the
        pathological fault that tracks no request at all)."""
        now = time.perf_counter()
        # Suspects whose group already reached a terminal state
        # (cancelled, expired, completed pre-conviction) leave the
        # pool lazily — the set must stay bounded by live requests.
        for g in [g for g in self._suspects if g.event.is_set()]:
            self._suspects.discard(g)
        suspects = [(slot, s)
                    for slot, s in sorted(self._resident.items())
                    if s.group in self._suspects]
        if not suspects or (len(suspects) == 1
                            and len(self._resident) > 1):
            for s in self._resident.values():
                self._suspects.add(s.group)
            suspects = sorted(self._resident.items())
        if len(suspects) == 1:
            if self._convictions_without_success >= 2:
                # Two convictions with not one working dispatch
                # between them: the failure is not request-tied —
                # convicting a third resident would just 500 another
                # innocent.  Escalate: the raise propagates to the
                # loop's catch-all, where the supervisor restarts
                # the engine (and, if the fault persists, the crash
                # storm trips the breaker into fail-fast shedding).
                raise RuntimeError(
                    "step failures persist across quarantine "
                    "convictions (no successful dispatch between "
                    "episodes) — the fault tracks the engine, not "
                    "a request; escalating to engine recovery"
                ) from err
            slot, stream = suspects[0]
            self._convict(slot, stream, err, now)
            # Culprit found: every other suspect (requeued during
            # bisection) is exonerated.
            self._suspects.clear()
            return
        for slot, stream in suspects[: len(suspects) // 2]:
            self._evict_requeue(slot, stream, "quarantined", now,
                                error=type(err).__name__)

    def _convict(self, slot: int, stream: Stream,
                 err: BaseException, now: float) -> None:
        """Fail the isolated culprit — and ONLY it — with the typed
        PoisonedRequest; its co-tenants keep decoding."""
        group = stream.group
        self.poisoned_total += 1
        self._convictions_without_success += 1
        self._note_freed(stream, "poisoned")
        self._emit(stream, "decode", stream.t_admit, now,
                   row=stream.row, slot=slot, tokens=len(stream.out),
                   terminal="poisoned")
        self._emit_instant(stream, "poisoned", now, row=stream.row,
                           slot=slot, error=type(err).__name__)
        self._fail_group(group, PoisonedRequest(
            f"request {group.rid} poisoned the shared decode step "
            f"and was quarantined (co-tenants resumed unaffected): "
            f"{type(err).__name__}: {err}"))

    def _engine_instant(self, name: str, t: float, **args) -> None:
        """One instant on the ENGINE trace track (growth/preempt
        markers for the trace_report page strip) — same isolation
        contract as _emit: a broken ring is counted, never raised."""
        try:
            self.tel.instant(0, name, t, pid=ENGINE_PID, **args)
        except Exception:
            self.telemetry_errors_total += 1

    def _ensure_lazy_growth(self, span: int) -> bool:
        """LAZY-KV step-boundary growth: before a dispatch that will
        write ``span`` positions per resident slot, make sure every
        resident's page table covers its writes
        (PagedSlotKVManager.grow_slot, capped at each slot's full
        budget).  On POOL EXHAUSTION, preempt the resident with the
        most remaining budget — the longest expected page hold —
        through the shared ``_evict_requeue`` path (token-identical
        resume) and retry, until every survivor can grow.  Returns
        False when the boundary was consumed by evictions (resident
        set mutated or emptied; the next tick re-plans).

        LIVELOCK-FREE by two rules: (1) exhaustion evictees requeue
        at the BACK of their class (never ahead of anything already
        waiting, the blocked beneficiary included), and (2) each
        evictee carries ``evicted_for`` — the growth-blocked stream
        its eviction served — and the admission gate skips it until
        the next EVICTION-FREE growth pass completes, so the freed
        pages cannot be stolen back at the very next boundary's
        admission (which runs before that boundary's growth) by the
        stream whose eviction freed them.  Each failed round evicts exactly one
        resident, so the loop is bounded by the resident count — and
        the submit-time can-never-fit shed guarantees a sole
        resident's growth always fits, so a growth-blocked stream
        eventually wins."""
        evicted_any = False
        while True:
            blocked = None
            for slot, stream in sorted(self._resident.items()):
                budget = self._kv_tokens_needed(stream.p_len,
                                                stream.new)
                need = min(budget,
                           int(self.slots.state.positions[slot]) + span)
                grown = self.slots.grow_slot(slot, need)
                if grown is None and self.page_reclaim is not None:
                    # STORED-BUT-IDLE prefix pages yield before any
                    # LIVE resident does: ask the owner's reclaim
                    # hook (prefix-store spill/eviction) to free the
                    # blocked growth's deficit, exactly as the
                    # admission gate does — preempting a resident
                    # while reclaimable cache pages sit idle would
                    # invert the tier order (and a SOLE resident
                    # could self-evict into a re-prefill spin).
                    try:
                        self.page_reclaim(
                            self.slots.grow_need(slot, need))
                    except Exception:
                        import logging

                        logging.getLogger(__name__).debug(
                            "page_reclaim hook failed during lazy "
                            "growth", exc_info=True)
                    grown = self.slots.grow_slot(slot, need)
                if grown is None:
                    blocked = (slot, stream)
                    break
                if grown:
                    self._engine_instant(
                        "kv_grow", time.perf_counter(), slot=slot,
                        pages=grown, rid=stream.group.rid)
            if blocked is None:
                # Bars clear only on a pass that succeeded WITHOUT
                # evictions: the pass that evicted must leave its
                # bars standing across the next boundary's ADMISSION
                # (which runs before the next growth), or the freed
                # pages could be handed right back to the evictee.
                if not evicted_any and self._exhaust_bars:
                    for v in self._exhaust_bars:
                        v.evicted_for = None
                    self._exhaust_bars.clear()
                return not evicted_any
            now = time.perf_counter()
            _bslot, bstream = blocked
            victim = None
            for slot, stream in self._resident.items():
                rem = stream.remaining
                if victim is None or rem > victim[2]:
                    victim = (slot, stream, rem)
            slot, stream, _rem = victim
            self.kv_preempt_exhaustion_total += 1
            self.preempted_total += 1
            stream.preempts += 1
            self._engine_instant("kv_preempt", now, slot=slot,
                                 rid=stream.group.rid,
                                 blocked_rid=bstream.group.rid)
            self._evict_requeue(slot, stream, "preempted", now,
                                front=False,
                                reason="kv_pages_exhausted",
                                blocked_rid=bstream.group.rid)
            if stream is not bstream:
                # The victim must not re-admit ahead of the stream
                # it was evicted for (a self-eviction has no
                # beneficiary to bar against).
                stream.evicted_for = bstream
                self._exhaust_bars.append(stream)
            evicted_any = True
            if not self._resident:
                return False

    def _serial_reason(self) -> Optional[str]:
        """Why the boundary ahead CANNOT be decided without the
        tokens of the dispatch in flight — the name the dispatch is
        counted under (``decode_serial_reasons``) — or None where it
        can: every eviction ahead is then a budget eviction, which
        the host foresees from ``Stream.remaining`` alone, and
        positions, token indices and sampling operands are the
        host's to compute.  Read off what the engine can observe at
        the boundary, never an option; a serial dispatch is the same
        code collected at once (``_decode_step``).

        - ``drain``: draining or closing — what is in flight is all
          that is left to wait for;
        - ``fault``: an armed fault injector, or quarantine suspects:
          the containment ladder's in-place retry and its bisection
          re-dispatch the SAME boundary, which needs the serial order;
        - ``paged``: ``--kv-lazy`` page growth reads the positions
          the last dispatch committed and may evict on exhaustion;
        - ``lock_waiter``: a handler thread waits on the device lock —
          it gets the device between two dispatches, not behind two;
        - ``spec``: a speculative resident — commit counts are data;
        - ``eos``: a resident that may stop at any token;
        - ``logits``: a resident that keeps each dispatch's LAST
          step's logits;
        - ``deadline``: an armed deadline on a resident, or an
          interactive head under an armed TTFT SLO — every boundary
          is a delivery or preemption point."""
        if self.draining or self._stop:
            return "drain"
        if self.faults is not None or self._suspects:
            return "fault"
        if self.paged and self.slots.lazy:
            return "paged"
        waiters = getattr(self.device_lock, "waiters", None)
        if waiters is not None and waiters():
            return "lock_waiter"
        found = set()
        for s in self._resident.values():
            if s.sampling.speculative:
                found.add("spec")
            if s.eos_id is not None:
                found.add("eos")
            if s.step_logits is not None:
                found.add("logits")
            if s.group.deadline is not None:
                found.add("deadline")
        for reason in ("spec", "eos", "logits", "deadline"):
            if reason in found:
                return reason
        if self.policy.slo_ttft_s is not None:
            head = self.queue.head()
            if head is not None \
                    and head.group.priority == "interactive":
                return "deadline"
        return None

    def _forget(self, streams) -> None:
        """Drop ``streams`` from the dispatch in flight: whatever it
        brings for them is never dealt (a cancelled, failed, expired
        or requeued stream's tokens in flight were never committed)."""
        landing = self._flight
        for stream in streams:
            stream.in_flight = 0
            if landing is not None:
                for slot in [slot for slot, (held, _)
                             in landing.streams.items()
                             if held is stream]:
                    del landing.streams[slot]

    def _decode_step(self) -> None:
        """Launch one fused window of decode steps over the resident
        streams and commit one: the dispatch launched at the LAST
        boundary, collected only now that its successor is in the
        device's queue — or, where the boundary cannot be decided
        without the tokens in flight (``_serial_reason``), today's
        order: collect and commit what is in flight, plan, launch,
        collect and commit.  Within a window a stream stops consuming
        at its own eos/budget (each token depends only on its prefix
        and rows never interact, so the window's later tokens for that
        stream are discardable garbage — exactness is untouched).

        ONE sequence for every kind of step and both orders; the
        kinds differ in the program the manager runs and in how a
        window's output is dealt to the streams (``_take_tokens``,
        ``_take_rounds``), the orders in WHICH dispatch the marker's
        collect half waits for (``SlotManager._dispatch``)."""
        ahead = self._flight
        reason = self._serial_reason() if self._resident else "drain"
        t0 = time.perf_counter()
        if ahead is not None and reason is not None:
            # The boundary waits for the tokens in flight (or nothing
            # is left to launch): the serial order from here on.
            try:
                self.slots.collect(ahead.flight)
            except BaseException as e:
                self._recover_lost_pool(e)
                return
            self._flight = None
            self._commit(ahead, t0)
            ahead, t0 = None, time.perf_counter()
        if not self._resident:
            return
        window = self._pick_window()
        # Program selection is a pool property: any speculative
        # resident switches the pool to the SPEC program of width K,
        # the largest resident spec_k (greedy/sampled co-tenants ride
        # its one-token plain lane, advancing by 1 per round while
        # spec slots advance by accept-count); otherwise one sampled
        # resident selects the sampled program (greedy co-tenants ride
        # its argmax lane); an all-greedy pool keeps the cheapest
        # argmax-only program.
        K = max((s.sampling.spec_k for s in self._resident.values()
                 if s.sampling.speculative), default=0)
        if K:
            kind = "spec"
        else:
            kind = "sampled" if any(
                s.sampling.sampled
                for s in self._resident.values()) else "plain"
        # Positions a slot may write in this dispatch: a window's
        # tokens, or — a spec round's verify chunk — up to window*K+1
        # past the last committed token.
        if self.paged and self.slots.lazy \
                and not self._ensure_lazy_growth(
                    window * K + 1 if K else window):
            # Exhaustion preemptions consumed this boundary (the
            # resident set mutated); the next tick re-plans with the
            # survivors' grown tables.
            return
        if self.recorder is not None:
            self.recorder.on_step_start()
        # What the marker's collect half waits for: this dispatch
        # (serial), the one in flight (ahead), or nothing yet (the
        # first of a run: it stays in flight, the next runs ahead).
        collect = True if reason is not None \
            else ahead.flight if ahead is not None else None

        def dispatch():
            with span("ptpu/lock_wait", self._host_s):
                self.device_lock.acquire()
            held = [self.device_lock]
            try:
                # The lock goes back between the launch and the wait:
                # nothing a collect touches needs it.
                return self.slots.launch(
                    window, sampled=kind == "sampled",
                    cap=self.policy.decode_window, K=K,
                    collect=collect,
                    launched=lambda: held.pop().release())
            finally:
                if held:
                    held.pop().release()

        if ahead is None:
            flight = self._dispatch_step(dispatch)
            if flight is None:
                # Containment resolved the boundary by mutating the
                # resident set (quarantine evictions / a conviction)
                # instead of producing tokens — the next tick
                # re-plans.
                if self.recorder is not None:
                    self.recorder.on_step_end(0)
                return
        else:
            try:
                flight = dispatch()
            except BaseException as e:
                # A failure with a dispatch in flight surfaces after
                # further programs consumed the pool: whatever its
                # class, everything in flight is dropped and every
                # stream resumes from its committed prefix.
                self._recover_lost_pool(e)
                return
        self.decode_dispatches_total += 1
        if ahead is not None:
            self.decode_dispatches_ahead_total += 1
        else:
            name = reason or "first"
            self.decode_serial_reasons[name] = \
                self.decode_serial_reasons.get(name, 0) + 1
        mine = self._launched(flight, K)
        if reason is not None:
            self._commit(mine, t0)
            return
        self._flight = mine
        if ahead is not None:
            self._commit(ahead, t0)
        else:
            # The first of a run commits nothing here: its section
            # still belongs to the dispatch wall.
            self.step_wall_s_total += time.perf_counter() - t0

    def _launched(self, flight, K: int) -> _InFlight:
        """The engine's half of a launch: the dispatch's own slot ->
        stream map, the tokens each stream has in flight, and THE
        FORESEEN EVICTIONS — a stream without an ``eos_id`` whose
        budget ends with this dispatch leaves its slot NOW, whatever
        the tokens turn out to be: the slot is parked, or re-armed by
        an admission whose insertion is enqueued behind this dispatch
        (the donated pool orders them on the device), and the stream
        is completed when the dispatch is committed.  Speculative
        rounds are committed at once and foresee nothing."""
        streams = {}
        for slot, stream in list(self._resident.items()):
            take = 0 if K else min(flight.window, stream.remaining)
            stream.in_flight += take
            streams[slot] = (stream, take)
            if take and stream.eos_id is None \
                    and stream.remaining == 0:
                del self._resident[slot]
                self.slots.release(slot)
                self._note_freed(stream, "complete")
        return _InFlight(flight, K, streams)

    def _commit(self, landed: _InFlight, t0: float) -> None:
        """Deal a collected dispatch's tokens to the streams it was
        launched with, complete the finished ones (evicting those no
        launch foresaw: an eos, a speculative commit) so their slots
        are admissible the SAME boundary, and write its step record.
        ``t0``: when the tick's section that collected it began."""
        t1 = time.perf_counter()
        out, K, window = landed.flight.host, landed.k, \
            landed.flight.window
        with span("ptpu/commit", self._host_s):
            self.decode_steps_total += window
            emitted = accepted = 0
            if K:
                self.spec_rounds_total += window
            for slot, (stream, take) in landed.streams.items():
                stream.in_flight -= take
                if K:
                    n, a = self._take_rounds(stream, slot, *out)
                    accepted += a
                else:
                    n = self._take_tokens(stream, slot, out[0])
                emitted += n
                if stream.done():
                    if self._resident.get(slot) is stream:
                        del self._resident[slot]
                        self.slots.release(slot)
                        self._note_freed(stream, "complete")
                    self.evicted_total += 1
                    self._complete(stream)   # records the slot id
                    stream.slot = None
            fields = self._host_fields()
            self.step_device_s_total += fields["device_s"]
            self.step_wall_s_total += t1 - t0
            if self.recorder is not None:
                self.recorder.on_step_end(emitted)
            self.tel.step("step", t0, t1, kind=landed.flight.kind,
                          window=window, occupancy=landed.occupancy,
                          batch=self.slots.n_slots, tokens=emitted,
                          **({"k": K, "accepted": accepted}
                             if K else {}),
                          **fields,
                          **({"mesh": self.mesh.axes_str()}
                             if self.mesh is not None else {}),
                          **({"pages_free": self.slots.free_page_count(),
                              "pages_total": self.slots.n_pages}
                             if self.paged else {}))

    def _take_tokens(self, stream: Stream, slot: int, toks) -> int:
        """Deal a plain or sampled window's tokens ``[W, S]`` to the
        stream in ``slot``, as far as its own eos/budget; the tokens
        it took."""
        if stream.step_logits is not None:
            stream.step_logits.append(np.asarray(
                self.slots.last_logits[slot]))
        for n, tok in enumerate(toks[:, slot], 1):
            stream.out.append(int(tok))
            if stream.done():
                break
        return n

    def _take_rounds(self, stream: Stream, slot: int, toks, commits,
                     accepts) -> Tuple[int, int]:
        """Deal speculative rounds (tokens ``[W, S, K]``, commits and
        accepts ``[W, S]``) to the stream in ``slot``: a spec slot
        commits its own accepted prefix per round — variable advance —
        a co-tenant exactly one token; budgets are accounted in
        COMMITTED tokens.  Returns the tokens it took and the draft
        tokens accepted (the acceptance-rate metric)."""
        spec = stream.sampling.speculative
        taken = accepted = 0
        for w in range(len(commits)):
            if spec:
                a = int(accepts[w, slot])
                stream.spec_rounds += 1
                stream.spec_drafted += stream.sampling.spec_k
                stream.spec_accepted += a
                self.spec_drafted_total += stream.sampling.spec_k
                self.spec_accepted_total += a
                accepted += a
            for j in range(int(commits[w, slot])):
                stream.out.append(int(toks[w, slot, j]))
                taken += 1
                if stream.done():
                    return taken, accepted
        return taken, accepted

    # -- completion -----------------------------------------------------

    def _complete(self, stream: Stream) -> None:
        group = stream.group
        stream.t_done = time.perf_counter()
        if stream.sampling.speculative and stream.spec_drafted:
            # One acceptance-rate observation per completed stream:
            # accepted draft tokens / drafted (the correction token a
            # rejection commits is not "accepted" work).
            self.spec_accept.observe(
                stream.spec_accepted / stream.spec_drafted)
        # Lifecycle tail: one decode span (admission -> done) plus the
        # completion instant — per-window detail lives on the engine
        # step track, keyed back by the slot id.
        if stream.t_admit is not None:
            args = {"row": stream.row, "slot": stream.slot,
                    "tokens": len(stream.out)}
            if stream.preempts or stream.resumes:
                # A resumed request must be distinguishable from a
                # straight-through one in the trace (the satellite
                # fix — the access log gets the same fields).
                args.update(preempts=stream.preempts,
                            resumes=stream.resumes)
            if stream.sampling.speculative:
                args.update(spec_rounds=stream.spec_rounds,
                            spec_drafted=stream.spec_drafted,
                            spec_accepted=stream.spec_accepted)
            self._emit(stream, "decode", stream.t_admit,
                       stream.t_done, **args)
        self._emit_instant(stream, "complete", stream.t_done,
                           row=stream.row, tokens=len(stream.out))
        group.complete_row(stream)
        if group.event.is_set() and group.error is None:
            self.completed_total += 1
            if group.sampling.speculative:
                self.completed_spec_total += 1
            elif group.sampling.sampled:
                self.completed_sampled_total += 1
            else:
                self.completed_greedy_total += 1
            self._record_history(group)

    def _fail_group(self, group: RequestGroup,
                    err: BaseException) -> None:
        """Deliver ``err`` to every thread waiting on ``group`` and
        reclaim its resources; OTHER groups' streams keep running (a
        stranger's OOM must not kill the batch)."""
        self.queue.drop_group(group)
        self._forget(group.streams)
        for slot, stream in list(self._resident.items()):
            if stream.group is group:
                del self._resident[slot]
                self.slots.release(slot)
                self.evicted_total += 1
        for stream in group.streams:
            self._release_stream_kv(stream)
            # A failed stream's prefilled lanes go NOW, not when the
            # last waiter lets go of the group: a server whose
            # admissions fail would otherwise fill the chip with them
            # (PERF.md section 6, PR 28).
            stream.cache = stream.d_cache = stream.logits = None
            stream.first = None
        if not group.event.is_set():   # fail once, however many
            t = time.perf_counter()    # streams drag the group down
            for stream in group.streams:
                self._emit_instant(stream, "fail", t,
                                   row=stream.row,
                                   error=type(err).__name__)
        group.fail(err)
        self._record_history(group)

    # -- introspection --------------------------------------------------

    @staticmethod
    def _kind_of(sampling: SamplingSpec) -> str:
        if sampling.speculative:
            return "speculative"
        return "sampled" if sampling.sampled else "greedy"

    def _record_history(self, group: RequestGroup) -> None:
        """One terminal record per request into the retention ring —
        the full causal story ``GET /requests/<id>`` serves.  Called
        on every terminal path (complete / cancel / expire / shed /
        fail); re-recording the same request ID replaces the older
        record, so double calls on shutdown races are harmless."""
        h = self.history
        if group.rid is None:
            return
        t_done = group.t_done if group.t_done is not None \
            else time.perf_counter()
        # Phase ledger (serving/forensics.py): ONE computation over
        # the union of the group's stream events feeds the history
        # record, the sentry, and (via the same function at the
        # front-end) the timings block — the partition cannot drift
        # between surfaces.  Computed whenever a consumer is armed,
        # even with the history ring off.
        ledger = None
        if self.forensics is not None or (h is not None
                                          and h.enabled):
            all_events: list = []
            for s in group.streams:
                if s.events:
                    all_events.extend(s.events)
            ledger = compute_ledger(all_events, group.t_submit,
                                    t_done)
            if self.forensics is not None:
                self.forensics.note(ledger, group.rid)
        if h is None or not h.enabled:
            return
        queue_s, prefill_s, decode_s = group.breakdown()
        rec: Dict[str, Any] = {
            "request_id": group.rid,
            "t": round(time.time(), 3),
            "status": group.status,
            "kind": self._kind_of(group.sampling),
            "priority": group.priority,
            "rows": len(group.streams),
            "prompt_tokens": int(group.rows.shape[1]),
            "max_new_tokens": int(group.new),
            "wall_s": round(max(0.0, t_done - group.t_submit), 6),
            "queue_wait_s": round(queue_s, 6),
            "prefill_s": round(prefill_s, 6),
            "decode_s": round(decode_s, 6),
            "preempts": sum(s.preempts for s in group.streams),
            "resumes": sum(s.resumes for s in group.streams),
        }
        if group.t_first_admit is not None:
            rec["ttft_s"] = round(
                group.t_first_admit - group.t_submit, 6)
        if group.error is not None:
            rec["error"] = (f"{type(group.error).__name__}: "
                            f"{group.error}")[:300]
        if group.prefix_info:
            rec["prefix"] = dict(group.prefix_info)
        if group.sampling.speculative:
            rec["spec"] = {
                "rounds": sum(s.spec_rounds for s in group.streams),
                "drafted": sum(s.spec_drafted
                               for s in group.streams),
                "accepted": sum(s.spec_accepted
                                for s in group.streams)}
        if ledger is not None:
            rec["phases"] = ledger
        rec["streams"] = [
            {"row": s.row,
             "tokens_out": len(s.out),
             **({"slot": s.last_slot}
                if s.last_slot is not None else {}),
             **({"preempts": s.preempts, "resumes": s.resumes}
                if (s.preempts or s.resumes) else {}),
             "timeline": events_to_dicts(s.events or [],
                                         group.t_submit)}
            for s in group.streams]
        h.record(rec)

    def build_debug_snapshot(self, forced: bool = False
                             ) -> Dict[str, Any]:
        """The ``/debug/state`` snapshot: slot table, per-class
        queues with entry ages, page pool, lifecycle flags — plain
        host-side dicts, NEVER the device lock (the SNAPSHOT-LOCK
        contract, docs/DESIGN.md).  Normally built on the engine
        thread at a step boundary (tick), so it is internally
        consistent; ``forced=True`` marks a build from another thread
        (the stall watchdog, whose whole premise is that the engine
        thread is stuck) — best-effort, possibly mid-mutation."""
        now = time.perf_counter()
        slots = []
        for slot, s in sorted(list(self._resident.items())):
            slots.append({
                "slot": slot,
                "request_id": s.group.rid,
                "row": s.row,
                "kind": self._kind_of(s.sampling),
                "priority": s.group.priority,
                "position": s.p_len + len(s.out) - 1,
                "tokens_out": len(s.out),
                "remaining": s.new - len(s.out),
                "preempts": s.preempts,
                "resumes": s.resumes,
                "age_s": round(now - s.group.t_submit, 3),
                **({"deadline_in_s": round(
                    s.group.deadline - now, 3)}
                   if s.group.deadline is not None else {}),
            })
        queues: Dict[str, list] = {p: [] for p in PRIORITIES}
        for s in self.queue.snapshot():
            queues[s.group.priority].append({
                "request_id": s.group.rid,
                "row": s.row,
                "age_s": round(now - s.group.t_submit, 3),
                "prefilled": s.filled,
                "prompt_tokens": s.p_len,
                "pf_done": s.pf_done,
                **({"blocked_s": round(now - s.blocked_t, 3)}
                   if s.blocked_t is not None else {}),
            })
        snap: Dict[str, Any] = {
            "t": now,
            "forced": bool(forced),
            "draining": self.draining,
            "n_slots": self.slots.n_slots,
            "free_slots": self.slots.free_slots,
            "slots": slots,
            "queues": queues,
            "queue_len": sum(len(q) for q in queues.values()),
            "last_step_age_s": round(
                max(0.0, now - self.last_boundary_t), 3),
            "decode_steps_total": self.decode_steps_total,
            "dispatch_in_flight": self._flight is not None,
        }
        if self.paged:
            snap["pages"] = {**self.slots.page_stats(),
                             "slot_table_pages":
                                 self.slots.slot_page_counts()}
        if self.mesh is not None:
            snap["mesh"] = self.mesh.axes_str()
        # Fault-tolerance state: the supervisor block (restart
        # count, breaker state, last crash/recovery evidence) and
        # the armed fault plan's injection counters ride every
        # snapshot — so a recovery storm is diagnosable from ONE
        # artifact (/debug/state, or the stall watchdog's bundle,
        # which embeds a forced build of this same snapshot).
        snap["engine_down"] = self.down
        if self.supervisor is not None:
            snap["supervisor"] = self.supervisor.status()
        if self.faults is not None:
            snap["faults"] = self.faults.stats()
        if self._suspects:
            snap["quarantine_suspects"] = sorted(
                g.rid for g in self._suspects if g.rid)
        return snap

    def stats(self) -> Dict[str, Any]:
        # Per-request queue/prefill/decode timing lives in ModelServer
        # (_note_breakdown, fed from group.breakdown()) — one source
        # of truth for /metrics; the engine exposes scheduling
        # counters only.
        fstats = self.faults.stats() if self.faults is not None \
            else None       # one lock-guarded build per scrape
        # A slot whose stream left it at a launch is still being
        # stepped for that stream until the dispatch lands (one
        # reading of the free list: the engine thread moves on).
        landing, free = self._flight, set(self.slots.state.free)
        active = self.slots.n_slots - len(free) + sum(
            slot in free
            for slot in (list(landing.streams) if landing else ()))
        return {
            "slots": self.slots.n_slots,
            "slots_active": active,
            "slot_occupancy": round(active / self.slots.n_slots, 4),
            "queue_len": len(self.queue),
            "queue_depth": self.policy.queue_depth,
            "admitted_total": self.admitted_total,
            "admitted_greedy_total": self.admitted_greedy_total,
            "admitted_sampled_total": self.admitted_sampled_total,
            "admitted_spec_total": self.admitted_spec_total,
            "evicted_total": self.evicted_total,
            "decode_steps_total": self.decode_steps_total,
            # How the decode dispatches were ordered (_decode_step):
            # launched, launched before the previous one was
            # collected, and the others by what forbade it
            # (_serial_reason; ``first``: nothing was in flight).
            "decode_dispatches_total": self.decode_dispatches_total,
            "decode_dispatches_ahead_total":
                self.decode_dispatches_ahead_total,
            "decode_serial_reasons": dict(self.decode_serial_reasons),
            # The KV pool updated in place (serving/slots.py):
            # programs that took the pool, those that consumed the
            # tree they were handed, the live pools' bytes, and the
            # dispatches that failed after consuming it.
            "kv_pool_dispatches_total":
                self.slots.kv_pool_dispatches_total,
            "kv_pool_in_place_total":
                self.slots.kv_pool_in_place_total,
            "kv_pool_bytes": self.slots.kv_pool_bytes,
            "kv_pool_bytes_by_kind": self.slots.kv_pool_bytes_by_kind,
            "kv_pool_lost_total": self.kv_pool_lost_total,
            "prefill_chunks_total": self.prefill_chunks_total,
            "prefill_tokens_total": self.prefill_tokens_total,
            # Rows of the full-length planes the attention was handed,
            # decode steps and prefill chunks, against the rows those
            # planes hold (kv_cache.PlaneReads).
            "kv_plane_rows_read_total": self.slots.plane_reads.read,
            "kv_plane_rows_held_total": self.slots.plane_reads.held,
            # Row writes the decode steps issued into the pool's
            # position-keyed leaves (kv_cache.row_writes_a_step).
            "kv_row_writes_total": self.slots.plane_reads.row_writes,
            **self._moe_stats(),
            **self._ssm_stats(),
            **self._latent_stats(),
            "completed_total": self.completed_total,
            "completed_greedy_total": self.completed_greedy_total,
            "completed_sampled_total": self.completed_sampled_total,
            "completed_spec_total": self.completed_spec_total,
            "rejected_total": self.queue.rejected,
            # Request lifecycle: terminal-status counters, the
            # preempt/resume pair (equal in steady state — every
            # preempted stream resumes unless its group dies first),
            # per-class admission split + queue depths, and the
            # drain latch.
            "cancelled_total": self.cancelled_total,
            "expired_total": self.expired_total,
            "shed_total": self.shed_total,
            "shed_kv_pages_total": self.shed_kv_pages_total,
            "shed_interactive_total":
                self.shed_by_class["interactive"],
            "shed_batch_total": self.shed_by_class["batch"],
            "preempted_total": self.preempted_total,
            "resumed_total": self.resumed_total,
            # Lazy-KV exhaustion preemptions (0 unless --kv-lazy):
            # residents evicted mid-decode because a co-tenant's page
            # growth found the pool empty (engine._ensure_lazy_growth)
            # — a subset of preempted_total.
            "kv_preempt_exhaustion_total":
                self.kv_preempt_exhaustion_total,
            "admitted_interactive_total":
                self.admitted_by_class["interactive"],
            "admitted_batch_total": self.admitted_by_class["batch"],
            "queue_len_interactive":
                self.queue.class_len("interactive"),
            "queue_len_batch": self.queue.class_len("batch"),
            "draining": self.draining,
            # Fault tolerance (serving/faults.py + recovery.py):
            # step-retry / requeue-and-resume / quarantine-conviction
            # counters, the supervisor's crash/restart totals and
            # breaker state, and the armed fault plan's per-site
            # injection counters — ONE dict behind /metrics AND
            # /info (the no-drift pin, tests/test_faults.py).
            "engine_down": self.down,
            "step_retries_total": self.step_retries_total,
            "requests_requeued_total": self.requests_requeued_total,
            "poisoned_total": self.poisoned_total,
            "telemetry_errors_total": self.telemetry_errors_total,
            "engine_crashes_total":
                self.supervisor.crashes_total
                if self.supervisor is not None else 0,
            "engine_restarts_total":
                self.supervisor.restarts_total
                if self.supervisor is not None else 0,
            "breaker_state":
                self.supervisor.breaker.state
                if self.supervisor is not None else "unsupervised",
            "faults_injected_total":
                fstats["faults_injected_total"]
                if fstats is not None else 0,
            "faults_injected":
                fstats["faults_injected"]
                if fstats is not None else {},
            # Speculative scheduling + the per-request acceptance-rate
            # histogram (per-bucket counts, upper bounds in
            # spec_accept_buckets; /metrics cumulates them via
            # telemetry.render_histogram) — ONE structure behind both
            # observability endpoints.
            "spec_rounds_total": self.spec_rounds_total,
            "spec_drafted_total": self.spec_drafted_total,
            "spec_accepted_total": self.spec_accepted_total,
            **self._spec_accept_stats(),
            # Paged-KV page-pool gauges (absent in fixed-lane mode):
            # free/resident/shared page counts — the occupancy story
            # the paged refactor exists for, fed to /metrics + /info
            # from this ONE dict.
            **(self.slots.page_stats() if self.paged else {}),
            # Mesh topology (absent unmeshed): axis names/sizes and
            # device count for /info.  On a mesh the step counters
            # below bundle compute AND collectives, so the
            # tp=1-vs-tpN bench A/B is what isolates the
            # collective-time share (bench_serving_load meshed leg).
            **(self._mesh_stats() if self.mesh is not None else {}),
            # Per-step device share of the dispatch wall, meshed or
            # not: device_s is a HOST clock around launch + wait
            # (the step markers: upload, enqueue and device_get lie
            # inside them); the remainder is host scheduling.  On a mesh the device
            # part bundles per-shard compute + collectives.
            "step_device_seconds_total":
                round(self.step_device_s_total, 6),
            "step_wall_seconds_total":
                round(self.step_wall_s_total, 6),
            "step_device_share":
                round(self.step_device_s_total
                      / self.step_wall_s_total, 4)
                if self.step_wall_s_total > 0 else None,
            # Recompile sentinel: compile_cache_misses must go quiet
            # once traffic has warmed its shapes (the zero-steady-
            # state contract, tests/test_analysis.py); a counter that
            # keeps climbing under same-shaped load is a recompile
            # storm.
            **self.sentinel.snapshot(),
        }

    def _moe_stats(self) -> Dict[str, Any]:
        """The expert layers' token-expert pairs since the start,
        prefill and decode, every expert layer (nothing for a model
        without): pairs routed over ALL experts, pairs that fell on
        the experts held here, the held experts' own counts, and the
        held experts that took at least one pair, a layer a program
        run (a decode step, a prefill piece): whose weights a grouped
        matmul had to read.  An idle slot's dead step counts like a
        live one."""
        pairs = self.slots.moe_pairs
        if pairs is None:
            return {}
        return {"moe_pairs_routed_total": int(pairs[-2]),
                "moe_pairs_held_total": int(pairs[:-2].sum()),
                "moe_expert_pairs": [int(n) for n in pairs[:-2]],
                "moe_experts_touched_total": int(pairs[-1])}

    def _ssm_stats(self) -> Dict[str, Any]:
        """What the recurrent layers' state went through since the
        start (nothing for a model without, or before the first
        prefill shaped the cache): positions x state layers through
        the prefill scan, and sequence-steps of the one-position
        update (kv_cache.PlaneReads)."""
        reads = self.slots.plane_reads
        if not reads.state_layers:
            return {}
        return {"ssm_scan_tokens_total": reads.scan_tokens,
                "ssm_state_steps_total": reads.state_steps}

    def _latent_stats(self) -> Dict[str, Any]:
        """What the latent attention layers' two paths took since the
        start (nothing for a model without, or before the first
        prefill shaped the cache): causal query-key pairs x latent
        layers of the calls that expanded the rows they read and of
        those that attended over them as they lie, and the rows the
        former expanded (kv_cache.PlaneReads)."""
        reads = self.slots.plane_reads
        if not reads.latent_planes:
            return {}
        return {"latent_pairs_expanded_total": reads.pairs_expanded,
                "latent_pairs_absorbed_total": reads.pairs_absorbed,
                "latent_rows_expanded_total": reads.rows_expanded}

    def _mesh_stats(self) -> Dict[str, Any]:
        # Under the device lock: the next dispatch consumes the tree
        # kv_pool() hands out.
        with self.device_lock:
            placement = self.mesh.describe_placement(
                self.slots.kv_pool())
        return {
            "mesh": self.mesh.describe(),
            "mesh_devices": self.mesh.n_devices,
            # Empty until the first prefill has shaped the pool.
            "kv_pool_shardings": placement,
        }

    def _spec_accept_stats(self) -> Dict[str, Any]:
        counts, total, n = self.spec_accept.snapshot()
        return {
            "spec_accept_buckets": list(self.spec_accept.buckets),
            "spec_accept_hist": counts,
            "spec_accept_sum": round(total, 6),
            "spec_accept_count": n,
        }
