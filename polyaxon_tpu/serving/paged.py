"""Paged KV memory for the continuous-batching engine.

The fixed-lane pool (slots.py) stacks one FULL-WIDTH cache lane per
slot, so every resident request pays for ``max_position`` tokens of
KV whatever its actual length: occupancy collapses under mixed
short/long traffic and max concurrency is pinned by the widest
request, not by token usage.  This module replaces that storage with
BLOCK-TABLE PAGING — the VirtualFlow decoupling (arXiv:2009.09523) of
logical slots from physical cache layout:

- every position-indexed cache leaf is stored as a POOL of fixed-size
  pages (``page_tokens`` positions each, leaf shape ``lead +
  (n_pages, page_tokens) + rest``);
- each slot owns a PAGE TABLE (padded int32 page-id list, a RUNTIME
  argument of the step programs, so one compiled program per
  (window, pages-per-slot-pad) shape serves every occupancy pattern
  — the zero-steady-state-recompile contract holds per pad class,
  never per request mix);
- the step programs GATHER a slot's pages into a position-contiguous
  view (``models/kv_cache.gather_pages``), run the SAME decode bodies
  the fixed-lane manager runs (slots.build_step_body /
  build_spec_step_body — one traced body, two storage layouts), and
  SCATTER only the window's dirty pages back;
- pages are REFERENCE-COUNTED and shared COPY-ON-WRITE: a stored
  prefix's pages map read-only into every matching slot's table
  (admission of a prefix hit costs only the divergent suffix), and a
  page is never a scatter target while shared — dirty windows only
  ever cover pages the slot privately owns, enforced by construction
  (decode writes start at the prompt end, which is at or past the
  shared-aligned boundary) rather than by a runtime branch.

Safety argument, same shape as the fixed-lane one: a slot's
materialized view is position-contiguous (page i covers absolute
positions [i*pt, (i+1)*pt)), so the causal-append masking, chunked
prefill, and the speculative rollback contract (stale entries masked
by absolute position) hold verbatim on paged storage — rollback is
still just a ``cache_index`` rewind inside the step body, with NO
page bookkeeping, because each slot's pages are reserved up front for
its full budget (see below).  Idle slots' dead stepping lands in a
per-slot SCRATCH page, and writes redirected away from shared pages
land in a single TRASH page; both hold garbage by definition and are
masked by position before any query could admit them.

RESERVATION DISCIPLINE (two modes):

- FULL (default): admission reserves a request's FULL page need
  (prompt + budget + speculative slack) minus its shared prefix
  pages.  Deadlock-free by construction — a resident can always
  finish — and spec rollback stays pure, because no mid-decode page
  event exists.  Page exhaustion only exists at the edges: a request
  that can NEVER fit the pool sheds 503 ``reason: kv_pages`` at
  submit, and one that doesn't fit RIGHT NOW waits admit-ready in
  the queue until evictions free pages (the admission-resume path,
  tests/test_paged_engine.py).
- LAZY (``lazy=True``, the engine's ``--kv-lazy``): admission
  reserves only ``prompt + one dispatch span`` (the first decode
  window plus spec slack) and slots GROW their page tables at step
  boundaries (:meth:`grow_slot`, through ``reserve_with_epoch`` like
  every other page grab).  On real traffic outputs run short of
  budget, so full reservation leaves reserved-but-dead pages pinning
  concurrency below what the pool could hold; lazy reservation packs
  residents by what they have actually WRITTEN.  The price is a new
  failure mode — mid-decode pool exhaustion — which the engine owns:
  it preempts the resident with the most remaining budget through
  the PR 6/11 ``_evict_requeue`` path (token-identical resume) until
  the blocked growth fits, with a livelock-free re-admission policy
  (engine._ensure_lazy_growth).  The can-NEVER-fit shed at submit is
  unchanged (it is a capacity statement, not a reservation one), so
  a sole resident can always grow to its full budget — lazy mode is
  still deadlock-free.

WHAT THIS MANAGER OWNS is the storage: page accounting, tables, the
pad class of a dispatch, the dirty-page window, gather/scatter, spill
and the wire format.  The slots' host state and the host half of a
decode dispatch are ``slots.SlotManager``'s, shared with the fixed
lanes: ``_program`` hands a launch the program for a key, the pools'
names, the page tables with each slot's first dirty page (uploaded
with the slots' arrays, inside ``device_s``) and the gathered view's
width, and nothing else.  Tables and dirty pages are read off the
slots' positions, which move at a LAUNCH, so a dispatch can be
launched before the one ahead of it is collected wherever the tables
themselves do not wait for its tokens (engine._serial_reason:
``--kv-lazy`` growth does).

Locking: page refcounts and the free list are mutated ONLY under
``_page_lock`` (machine-checked by the PAGE-REF rule in
analysis/rules.py — handler threads pin/unpin prefix pages while the
engine thread admits and releases).  Slot tables and the slots' state
stay engine-thread-only.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .slots import (SlotManager, build_spec_step_body,
                    build_step_body)

__all__ = ["PagedSlotKVManager", "PageExhausted",
           "WirePayloadError", "pack_spilled", "unpack_spilled"]


class PageExhausted(RuntimeError):
    """Page reservation failed.  Engine admission is gated on
    ``can_admit`` so this is a defensive error, not a control path."""


class WirePayloadError(ValueError):
    """A serialized spill payload failed integrity verification
    (truncated body, checksum mismatch, malformed header).  Callers
    on the fetch path treat this as a typed MISS — fall back to
    re-prefill, never admit bytes that don't verify."""


# -- wire serialization (fleet prefix cache) -----------------------------
#
# A host-tier prefix entry is device-independent by construction
# (spill_pages gathered it to plain np arrays), which makes it
# REPLICA-independent too: the same buffers device_put cleanly into
# any replica's pool (rematerialize is byte-identical to materialize
# for the same content).  These helpers turn one spilled entry into a
# single self-describing byte string and back — pure host numpy, no
# device work, so they sit outside the TIER-XFER sanctioned set on
# purpose.  Layout: 4-byte big-endian header length, a JSON header
# (prompt tokens, leaf shapes/dtypes, logits shape/dtype, body
# crc32), then the raw C-order buffers concatenated (logits first).

_WIRE_VERSION = 1


def pack_spilled(toks: np.ndarray,
                 leaves: Sequence[Optional[np.ndarray]],
                 n_tokens: int, logits: np.ndarray) -> bytes:
    """Serialize one host-tier prefix entry for the wire."""
    import json
    import struct
    import zlib

    toks = np.ascontiguousarray(np.asarray(toks, np.int32))
    logits = np.ascontiguousarray(np.asarray(logits))
    parts = [logits.tobytes()]
    leaf_meta = []
    for h in leaves:
        if h is None:
            leaf_meta.append(None)
            continue
        h = np.ascontiguousarray(h)
        leaf_meta.append({"shape": list(h.shape),
                          "dtype": h.dtype.name})
        parts.append(h.tobytes())
    body = b"".join(parts)
    header = json.dumps({
        "v": _WIRE_VERSION,
        "n_tokens": int(n_tokens),
        "prompt": toks.tolist(),
        "logits": {"shape": list(logits.shape),
                   "dtype": logits.dtype.name},
        "leaves": leaf_meta,
        "crc32": zlib.crc32(body) & 0xFFFFFFFF,
    }).encode()
    return struct.pack(">I", len(header)) + header + body


def unpack_spilled(blob: bytes):
    """Parse + VERIFY a :func:`pack_spilled` byte string; returns
    ``(toks, leaves, n_tokens, logits)``.  Raises
    :class:`WirePayloadError` on any truncation, checksum mismatch,
    or malformed header — never a partially-decoded payload."""
    import json
    import struct
    import zlib

    if len(blob) < 4:
        raise WirePayloadError("payload shorter than its own "
                               "header-length field")
    (hlen,) = struct.unpack(">I", blob[:4])
    if len(blob) < 4 + hlen:
        raise WirePayloadError("payload truncated inside the header")
    try:
        header = json.loads(blob[4:4 + hlen].decode())
        version = header["v"]
        n_tokens = int(header["n_tokens"])
        prompt = header["prompt"]
        logits_meta = header["logits"]
        leaf_meta = header["leaves"]
        crc_want = int(header["crc32"])
    except (ValueError, KeyError, TypeError, UnicodeDecodeError):
        raise WirePayloadError("malformed wire header")
    if version != _WIRE_VERSION:
        raise WirePayloadError(
            f"wire version {version!r} != {_WIRE_VERSION} "
            f"(mixed-version fleet; refetch or re-prefill)")
    body = blob[4 + hlen:]
    if (zlib.crc32(body) & 0xFFFFFFFF) != crc_want:
        raise WirePayloadError("payload checksum mismatch")

    def _take(meta):
        nonlocal off
        a = np.empty(meta["shape"], np.dtype(meta["dtype"]))
        n = a.nbytes
        if off + n > len(body):
            raise WirePayloadError("payload truncated inside a "
                                   "buffer (header/body disagree)")
        a = np.frombuffer(body[off:off + n],
                          np.dtype(meta["dtype"])).reshape(
                              meta["shape"]).copy()
        off += n
        return a

    off = 0
    logits = _take(logits_meta)
    leaves: List[Optional[np.ndarray]] = []
    for m in leaf_meta:
        leaves.append(None if m is None else _take(m))
    if off != len(body):
        raise WirePayloadError(
            f"payload has {len(body) - off} trailing bytes past the "
            f"declared buffers")
    toks = np.asarray(prompt, np.int32)
    if toks.ndim != 2 or toks.shape[1] != n_tokens:
        raise WirePayloadError(
            "prompt/n_tokens disagree in the wire header")
    return toks, leaves, n_tokens, logits


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class PagedSlotKVManager(SlotManager):
    """Fixed pool of ``n_slots`` decode slots over a PAGED KV pool.

    Same engine-facing surface as :class:`slots.SlotKVManager`
    (acquire/release/insert/step/step_spec over the shared
    :class:`slots.SlotManager`: the slots' host state and the decode
    dispatch are its), plus the page accounting the engine's
    admission gate and the server's shared-prefix store ride on
    (``can_admit`` /
    ``pin`` / ``unpin`` / ``scatter_cache`` / ``materialize``).
    """

    paged = True

    def __init__(self, model, variables, n_slots: int, *,
                 page_tokens: int = 64, n_pages: Optional[int] = None,
                 max_position: int, decode_window: int = 8,
                 spec_k_cap: int = 4, lazy: bool = False,
                 draft_model=None, draft_variables=None,
                 sentinel=None, mesh=None):
        if mesh is not None and mesh.dp > 1:
            from ..parallel.mesh import MeshError

            raise MeshError(
                "paged KV does not support dp slot parallelism "
                "(pages migrate between slots, so the page axis has "
                "no stable dp decomposition); use tp/ep, or the "
                "fixed-lane manager for dp")
        if page_tokens < 8:
            raise ValueError(
                f"kv_page_tokens must be >= 8; got {page_tokens}")
        if max_position < 1:
            raise ValueError(
                f"paged KV needs the model's max_position; got "
                f"{max_position}")
        super().__init__(model, variables, n_slots, draft_model,
                         draft_variables, sentinel, mesh)
        # Meshed, page pools shard their HEADS axis over tp; page
        # tables/decode state stay host-side and commit replicated
        # through the programs' explicit in_shardings.  Gather/scatter
        # move pages within a head shard — no cross-device math, so
        # paged == fixed-lane byte-identity holds per mesh shape.
        self._pool_sh = None
        self._draft_pool_sh = None
        self.page_tokens = int(page_tokens)
        self.max_position = int(max_position)
        pt = self.page_tokens
        self.max_pages_slot = -(-self.max_position // pt)
        # Default pool = the fixed-lane footprint (n_slots full-width
        # lanes), so `kv_paged=True` alone changes layout, not budget.
        self.n_pages = int(n_pages) if n_pages is not None \
            else self.n_slots * self.max_pages_slot
        if self.n_pages < 1:
            raise ValueError(f"kv_pages must be >= 1; got {n_pages}")
        # Scratch page per slot (dead stepping of idle slots, and the
        # pad target beyond a short slot's real pages) + one TRASH
        # page (the redirected write target for content that must not
        # land on a shared page).  All garbage by definition, masked
        # by absolute position before any read could admit them.
        self.scratch0 = self.n_pages
        self.trash = self.n_pages + self.n_slots
        self.total_pages = self.n_pages + self.n_slots + 1
        # Dirty-window bound: the widest position span one step
        # dispatch can write (a spec round writes K+1 wide per round).
        self._span_cap = max(1, int(decode_window)) \
            * max(1, int(spec_k_cap)) + 1
        # Lazy admission/growth span: the widest span THIS pool's
        # dispatches can actually write — spec rounds only exist
        # when a draft model does, so a plain pool's "first decode
        # window" is decode_window tokens, not the spec worst case
        # (which would front-load most of a short budget and erase
        # the lazy win).
        self._grow_span = max(1, int(decode_window)) \
            * (max(1, int(spec_k_cap))
               if draft_model is not None else 1) + 1
        self._n_dirty_cap = (self._span_cap - 1 + pt - 1) // pt + 1
        # Table width covers the largest possible reservation plus
        # the dirty-window margin (so d0 + n_dirty always lands
        # inside the table and no clamp is ever needed).
        need_cap = (self.max_position + int(spec_k_cap)
                    + pt - 1) // pt
        self.table_width = _pow2ceil(need_cap + self._n_dirty_cap)

        # -- page accounting (under _page_lock) ------------------------
        self._page_lock = threading.Lock()
        with self._page_lock:
            self.refcounts = np.zeros((self.total_pages,), np.int64)
            self.refcounts[self.n_pages:] = 1  # scratch/trash pinned
            self._free_pages: List[int] = list(range(self.n_pages))
            # Pool GENERATION: bumped by the crash-recovery reset().
            # Page ids are only meaningful within one epoch — pin()
            # returns the epoch the pins were taken under, and
            # epoch-tagged unpins/shares from a dead generation are
            # dropped by reference instead of corrupting the fresh
            # accounting.
            self.epoch = 0

        # -- slot state (engine thread only) ---------------------------
        self.page_tables = np.empty((self.n_slots, self.table_width),
                                    np.int32)
        for s in range(self.n_slots):
            self.page_tables[s, :] = self.scratch0 + s
        self._slot_pages: List[Optional[Tuple[List[int], int]]] = \
            [None] * self.n_slots           # (page ids, n shared)
        self._slot_need = np.zeros((self.n_slots,), np.int32)
        # LAZY reservation mode (module docstring): admission
        # reserves one dispatch span past the prompt; the engine
        # grows tables at step boundaries (grow_slot) up to each
        # slot's full budget (_slot_budget, in pages).  The growth
        # counters are monotonic totals (survive reset(), like every
        # other counter behind /metrics).
        self.lazy = bool(lazy)
        self._slot_budget = np.zeros((self.n_slots,), np.int32)
        self.lazy_growths_total = 0
        self.lazy_pages_grown_total = 0

        # -- device pools ---------------------------------------------
        self._pool: Optional[List[Any]] = None       # per paged leaf
        self._meta: Optional[List[Dict[str, Any]]] = None
        self._treedef = None
        self._draft_pool: Optional[List[Any]] = None
        self._draft_meta: Optional[List[Dict[str, Any]]] = None
        self._draft_treedef = None
        self._insert_fns: Dict[Tuple, Any] = {}
        self._gather_fns: Dict[int, Any] = {}
        # First-touch pool shaping is double-checked under this lock:
        # two concurrent handoffs racing a FRESH replica's unshaped
        # pool (ensure_shaped from two wire admissions) must not both
        # allocate — the loser's pool would replace a pool the winner
        # already wrote pages into, silently dropping its KV.
        self._shape_lock = threading.Lock()
        # Of the shared counters (SlotManager): the PAGE pool keeps
        # its own discipline — gather the views, step them (the
        # carried layer loop updates the views in place), scatter the
        # dirty pages into a NEW pool — and donates nothing, so its
        # dispatches count and none counts in place; ``plane_reads``
        # counts a step's reads of the GATHERED view (the resident
        # pages, padded) as far as its furthest stream.

    # -- page accounting ------------------------------------------------

    def pages_needed(self, tokens: int) -> int:
        return max(1, -(-int(tokens) // self.page_tokens))

    def admit_tokens(self, cur_tokens: int, total_tokens: int) -> int:
        """Tokens a new admission must have pages for UP FRONT: the
        full reservation (default — deadlock-free by construction),
        or — lazy — just the request's current length plus one
        dispatch span (the first decode window incl. spec slack),
        the rest growing at step boundaries (grow_slot)."""
        if not self.lazy:
            return int(total_tokens)
        return min(int(total_tokens),
                   int(cur_tokens) + self._grow_span)

    @property
    def capacity_tokens(self) -> int:
        return self.n_pages * self.page_tokens

    def free_page_count(self) -> int:
        with self._page_lock:
            return len(self._free_pages)

    def can_admit(self, tokens: int, shared_pages: int = 0) -> bool:
        """Enough free pages for a ``tokens``-long reservation, of
        which ``shared_pages`` leading pages are already mapped
        (pinned prefix pages)?"""
        need = self.pages_needed(tokens) - int(shared_pages)
        with self._page_lock:
            return len(self._free_pages) >= need

    def pin(self, ids: Sequence[int]) -> int:
        """Take one reference on each page (prefix-cache lookups pin
        an entry's pages so eviction/reuse can't free them while a
        request maps or materializes them).  Returns the pool EPOCH
        the pins were taken under — callers that hold pins across
        their own lock scope (the prefix-hit handler path) carry it
        so a crash-recovery pool rebuild in between invalidates the
        pins instead of corrupting the fresh refcounts."""
        with self._page_lock:
            for i in ids:
                if self.refcounts[i] < 1:
                    raise ValueError(
                        f"pin of a free page {i} (stale page id — "
                        f"the entry holding it was already freed)")
                self.refcounts[i] += 1
            return self.epoch

    def unpin(self, ids: Sequence[int],
              epoch: Optional[int] = None) -> None:
        """Drop one reference per page; pages hitting zero return to
        the free list.  ``epoch`` (when the caller carried one from
        ``pin``) guards the crash-recovery race: pins from a dead
        pool generation are dropped BY REFERENCE — the ids mean
        nothing in the rebuilt accounting."""
        with self._page_lock:
            if epoch is not None and epoch != self.epoch:
                return
            for i in ids:
                if self.refcounts[i] < 1:
                    raise ValueError(f"unpin of a free page {i}")
                self.refcounts[i] -= 1
                if self.refcounts[i] == 0:
                    self._free_pages.append(i)

    def try_reserve(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` free pages (refcount 0 -> 1), or None if fewer
        are free."""
        return self.reserve_with_epoch(n)[0]

    def reserve_with_epoch(self, n: int
                           ) -> Tuple[Optional[List[int]], int]:
        """``try_reserve`` plus the pool epoch the reservation was
        made under, read atomically in one lock hold — for callers
        (the prefix store) that carry the ids across their own lock
        scopes and must recognize a crash-recovery pool rebuild in
        between."""
        with self._page_lock:
            if n <= 0:
                return [], self.epoch
            if len(self._free_pages) < n:
                return None, self.epoch
            ids = [self._free_pages.pop() for _ in range(n)]
            for i in ids:
                self.refcounts[i] = 1
            return ids, self.epoch

    def kv_pool(self):
        """The live main page pool's leaves (None before the first
        page write shaped it)."""
        return self._pool

    @property
    def kv_pool_bytes(self) -> int:
        return sum(leaf.nbytes
                   for pool in (self._pool, self._draft_pool)
                   for leaf in pool or () if leaf is not None)

    @property
    def kv_pool_bytes_by_kind(self) -> Dict[str, int]:
        """All ``full``: neither a ring nor a state ever pages
        (``_classify``, ``slots.pool_refusal``)."""
        return {"window": 0, "full": self.kv_pool_bytes, "state": 0}

    def pool_lost(self) -> bool:
        """Never: no program consumes the page pool (see
        SlotKVManager.pool_lost)."""
        return False

    def page_stats(self) -> Dict[str, int]:
        with self._page_lock:
            free = len(self._free_pages)
            shared = int(np.sum(self.refcounts[:self.n_pages] > 1))
        resident = int(sum(len(p[0]) for p in self._slot_pages
                           if p is not None))
        return {
            "kv_pages": self.n_pages,
            "kv_page_tokens": self.page_tokens,
            "kv_pages_free": free,
            "kv_pages_resident": resident,
            "kv_pages_shared": shared,
            "kv_lazy": self.lazy,
            "kv_pages_lazy_growths_total": self.lazy_growths_total,
            "kv_pages_lazy_grown_total": self.lazy_pages_grown_total,
        }

    def slot_page_counts(self) -> Dict[int, int]:
        """Mapped pool pages per RESIDENT slot (``/debug/state``'s
        per-slot table-size column) — the accounting API's answer so
        introspection never reads pool internals directly
        (PAGE-REF)."""
        out: Dict[int, int] = {}
        for slot, held in enumerate(self._slot_pages):
            if held is not None:
                out[slot] = len(held[0])
        return out

    # -- slot accounting ------------------------------------------------

    def reset(self) -> None:
        """Crash-recovery pool rebuild (recovery.EngineSupervisor):
        every page reference — resident tables, prefix-store pins,
        shared refcounts — is dropped WHOLESALE and the page pool
        returns to all-free, while the compiled step/insert/gather
        programs are KEPT (a supervised restart must add zero
        steady-state recompiles).  Callers own the invalidation
        story: stale page ids must never be unpinned into the fresh
        accounting (the engine clears stream pins by reference; the
        server's recovery hook flushes the prefix store whose
        payloads these pages backed)."""
        with self._page_lock:
            self.refcounts[:] = 0
            self.refcounts[self.n_pages:] = 1  # scratch/trash pinned
            self._free_pages = list(range(self.n_pages))
            self.epoch += 1     # prior-generation page ids are dead
        for s in range(self.n_slots):
            self.page_tables[s, :] = self.scratch0 + s
        self._slot_pages = [None] * self.n_slots
        self._slot_need[:] = 0
        self._slot_budget[:] = 0
        self._pool = None
        self._draft_pool = None
        self._reset_slots()

    def release(self, slot: int) -> None:
        """Evict: park the slot (same contract as the fixed-lane
        release — see SlotKVManager.release) AND return its pages:
        one reference dropped per mapped page, so privately-owned
        pages free immediately while shared prefix pages live on
        under the entries/slots still referencing them."""
        self.state.park(slot)
        held = self._slot_pages[slot]
        if held is not None:
            self._slot_pages[slot] = None
            self.unpin(held[0])
        self.page_tables[slot, :] = self.scratch0 + slot
        self._slot_need[slot] = 0
        self._slot_budget[slot] = 0

    # -- leaf classification / pools ------------------------------------

    def _classify(self, template):
        """Flatten a template cache and classify each leaf: PAGED
        (one axis == max_position — the position axis that splits
        into pages) or INDEX (``cache_index`` leaves, rebuilt from
        the slot position at gather time).  Anything else (e.g. a
        ring cache's position table) is unsupported — the server
        gates paged mode to plain/int8 caches."""
        import jax

        leaves_p, treedef = jax.tree_util.tree_flatten_with_path(
            template)
        metas = []
        for path, leaf in leaves_p:
            key = jax.tree_util.keystr(path)
            if key.endswith("cache_index']"):
                metas.append({"kind": "index", "shape": leaf.shape,
                              "dtype": leaf.dtype})
                continue
            # The standard cache leaves (kv_cache.append_kv_cache)
            # are [..., B, positions, heads, feat]: position is the
            # THIRD-FROM-LAST axis, whatever leading layer-stack axes
            # scan_stack added.  Prefer that known layout — a head
            # count or head dim that coincidentally equals
            # max_position must not confuse the classifier — and fall
            # back to a unique max_position axis for unknown names.
            named = any(key.endswith(f"{n}']") for n in (
                "cached_key", "cached_value", "cached_key_scale",
                "cached_value_scale"))
            if named and leaf.ndim >= 3 \
                    and leaf.shape[leaf.ndim - 3] == self.max_position:
                metas.append({"kind": "paged",
                              "pos_axis": leaf.ndim - 3,
                              # Pool heads axis for mesh sharding:
                              # the position axis splits into
                              # (pages, page_tokens), pushing heads
                              # from leaf ndim-2 to pool ndim-1... +1
                              # overall = pos_axis + 2.
                              "heads_axis": leaf.ndim - 3 + 2,
                              "shape": leaf.shape,
                              "dtype": leaf.dtype})
                continue
            axes = [i for i, d in enumerate(leaf.shape)
                    if d == self.max_position]
            if len(axes) != 1:
                raise ValueError(
                    f"paged KV cannot page cache leaf {key} of shape "
                    f"{leaf.shape}: expected the [..., B, positions, "
                    f"heads, feat] layout or exactly one axis of "
                    f"max_position ({self.max_position}); ring "
                    f"caches and exotic layouts need the fixed-lane "
                    f"manager")
            metas.append({"kind": "paged", "pos_axis": axes[0],
                          "shape": leaf.shape, "dtype": leaf.dtype})
        return metas, treedef

    def _alloc_pool(self, metas):
        """Zero-init pool leaves (None for index leaves); meshed
        pools commit each paged leaf to its heads-over-tp
        NamedSharding at birth.  Returns (pool, shardings)."""
        import jax
        import jax.numpy as jnp

        from ..models.kv_cache import paged_pool_shape

        pool, shardings = [], []
        for m in metas:
            if m["kind"] != "paged":
                pool.append(None)
                shardings.append(None)
                continue
            leaf = jnp.zeros(paged_pool_shape(
                m["shape"], m["pos_axis"], self.total_pages,
                self.page_tokens), m["dtype"])
            if self.mesh is not None:
                sh = self.mesh.pool_leaf_sharding(m, leaf)
                leaf = jax.device_put(leaf, sh)
                shardings.append(sh)
            else:
                shardings.append(None)
            pool.append(leaf)
        return pool, shardings

    def _ensure_pool(self, template_cache) -> None:
        if self._pool is not None:
            return
        with self._shape_lock:
            if self._pool is not None:      # lost the race: done
                return
            meta, treedef = self._classify(template_cache)
            pool, pool_sh = self._alloc_pool(meta)
            # Publish LAST, fully formed: a concurrent ``shaped``
            # reader must never observe meta without its pool.
            self._meta, self._treedef = meta, treedef
            self.plane_reads.learn(template_cache)
            self._pool_sh = pool_sh
            self._pool = pool

    @property
    def shaped(self) -> bool:
        """Whether the main pool's leaf layout is known yet (shaped
        by the first page write, or by :meth:`ensure_shaped`)."""
        return self._meta is not None

    def ensure_shaped(self, template_cache) -> None:
        """Shape the main pool from a template WITHOUT a page write.
        Classification reads only tree paths, shapes and dtypes, so
        an ABSTRACT template (``jax.eval_shape`` pytree of
        ``ShapeDtypeStruct`` leaves) works — no model compute, no
        template allocation.  This is the cold-pool escape hatch for
        the fleet prefix tier: a wire-fetched or handed-off host
        entry can arrive BEFORE this replica's first prefill (a
        freshly restarted drain successor), and its rematerialize
        must not depend on prior traffic.  Safe under concurrent
        first-touch (two handoffs racing a fresh replica's unshaped
        pool): shaping is double-checked under an internal lock, so
        exactly one caller allocates and the rest observe the
        finished pool."""
        self._ensure_pool(template_cache)

    def _ensure_draft_pool(self, template_cache) -> None:
        if self._draft_pool is not None:
            return
        with self._shape_lock:
            if self._draft_pool is not None:
                return
            meta, treedef = self._classify(template_cache)
            pool, pool_sh = self._alloc_pool(meta)
            self._draft_meta, self._draft_treedef = meta, treedef
            self._draft_pool_sh = pool_sh
            self._draft_pool = pool

    def _pad_class(self, n_pages: int) -> int:
        return min(self.table_width, _pow2ceil(max(1, n_pages)))

    # -- gather / scatter program pieces --------------------------------

    def _gather_tree(self, pool, metas, treedef, tables, positions):
        """Stacked [S, ...] cache pytree from the pool: paged leaves
        gather through the page tables into position-contiguous
        views; index leaves rebuild from the slot positions."""
        import jax
        import jax.numpy as jnp

        leaves = []
        for m, p in zip(metas, pool):
            if m["kind"] == "index":
                leaves.append(jax.vmap(
                    lambda pos, m=m: jnp.full(m["shape"], pos,
                                              m["dtype"]))(positions))
                continue
            a = m["pos_axis"]
            v = jnp.take(p, tables, axis=a)
            # lead + (S, P, pt) + rest -> (S,) + lead + (P*pt,) + rest
            v = jnp.moveaxis(v, a, 0)
            shape = v.shape
            leaves.append(v.reshape(
                (shape[0],) + shape[1:a + 1]
                + (shape[a + 1] * shape[a + 2],) + shape[a + 3:]))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _scatter_dirty(self, pool, metas, stacked, tables, d0,
                       n_dirty: int):
        """Write each slot's dirty page window ([d0, d0 + n_dirty)
        local pages — everything this dispatch could have written)
        back to the pool.  Dirty pages are private by construction
        (decode writes start at the prompt end, past any shared
        page), so targets never collide except on scratch/trash
        garbage."""
        import jax
        import jax.numpy as jnp

        from ..models.kv_cache import scatter_pages

        leaves, _ = jax.tree_util.tree_flatten(stacked)
        pt = self.page_tokens
        idx = jax.vmap(lambda t, d: jax.lax.dynamic_slice(
            t, (d,), (n_dirty,)))(tables, d0)       # [S, n_dirty]
        flat_idx = idx.reshape(-1)
        out = []
        for m, p, leaf in zip(metas, pool, leaves):
            if m["kind"] == "index":
                out.append(None)
                continue
            a = m["pos_axis"]

            def slice_one(v, d, a=a):
                return jax.lax.dynamic_slice_in_dim(
                    v, d * pt, n_dirty * pt, axis=a)

            dirty = jax.vmap(slice_one)(leaf, d0)
            s = dirty.shape          # (S,) + lead + (n_dirty*pt,) + rest
            dirty = dirty.reshape(s[:a + 1] + (n_dirty, pt)
                                  + s[a + 2:])
            dirty = jnp.moveaxis(dirty, 0, a)
            s = dirty.shape          # lead + (S, n_dirty, pt) + rest
            dirty = dirty.reshape(s[:a] + (s[a] * s[a + 1],)
                                  + s[a + 2:])
            out.append(scatter_pages(p, dirty, flat_idx, a))
        return out

    def _scatter_cache_leaves(self, pool, metas, cache, targets,
                              P: int):
        """Scatter a contiguous B=1 cache's first ``P * page_tokens``
        positions into pool pages ``targets`` [P] (shared entries are
        pre-munged to the trash page by the host caller)."""
        import jax
        import jax.numpy as jnp

        from ..models.kv_cache import scatter_pages

        leaves, _ = jax.tree_util.tree_flatten(cache)
        pt = self.page_tokens
        width = P * pt
        out = []
        for m, p, leaf in zip(metas, pool, leaves):
            if m["kind"] == "index":
                out.append(None)
                continue
            a = m["pos_axis"]
            have = leaf.shape[a]
            if have < width:
                pad = [(0, 0)] * leaf.ndim
                pad[a] = (0, width - have)
                leaf = jnp.pad(leaf, pad)
            elif have > width:
                leaf = jax.lax.slice_in_dim(leaf, 0, width, axis=a)
            s = leaf.shape
            pages = leaf.reshape(s[:a] + (P, pt) + s[a + 1:])
            out.append(scatter_pages(p, pages, targets, a))
        return out

    # -- insert / prefix-store scatter ----------------------------------

    def _insert_fn(self, P: int, draft: bool):
        import jax

        key = (P, draft)
        fn = self._insert_fns.get(key)
        if fn is None:
            if self.sentinel is not None:
                self.sentinel.miss("page_insert", key)
            metas = self._draft_meta if draft else self._meta

            def ins(pool, cache, targets):
                return self._scatter_cache_leaves(pool, metas, cache,
                                                  targets, P)

            if self.mesh is not None:
                sh = self._draft_pool_sh if draft else self._pool_sh
                fn = jax.jit(ins, in_shardings=(sh, None, None),
                             out_shardings=sh)
            else:
                fn = jax.jit(ins)
            self._insert_fns[key] = fn
        elif self.sentinel is not None:
            self.sentinel.hit("page_insert", key)
        return fn

    def _write_targets(self, ids: List[int], n_shared: int,
                       P: int) -> np.ndarray:
        """Scatter targets for a cache write over pages ``ids``:
        already-populated SHARED pages redirect to the trash page
        (their content is identical by the prefix contract — never
        rewrite a page with refcount > 1), and pad entries past the
        real pages also land in trash."""
        tg = np.full((P,), self.trash, np.int32)
        if len(ids) > n_shared:
            tg[n_shared:len(ids)] = np.asarray(ids[n_shared:],
                                               np.int32)
        return tg

    def scatter_cache(self, cache, ids: List[int],
                      n_shared: int = 0, *, draft: bool = False
                      ) -> None:
        """Write a contiguous B=1 cache into pages ``ids`` (first
        ``n_shared`` already hold the same content and are skipped
        via trash redirect).  Device work — callers hold the device
        lock."""
        if draft:
            self._ensure_draft_pool(cache)
        else:
            self._ensure_pool(cache)
        P = self._pad_class(len(ids))
        tg = self._write_targets(ids, n_shared, P)
        import jax.numpy as jnp

        with self._exact():
            if draft:
                self._draft_pool = self._insert_fn(P, True)(
                    self._draft_pool, cache, jnp.asarray(tg))
            else:
                self._pool = self._insert_fn(P, False)(
                    self._pool, cache, jnp.asarray(tg))
            self.kv_pool_dispatches_total += 1

    def insert(self, slot: int, cache, first_token: int,
               position: int, *, base_key=None, next_index: int = 1,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 0.0, draft_cache=None,
               spec_k: int = 0, total_tokens: Optional[int] = None,
               shared_pages: Sequence[int] = ()) -> None:
        """Admit a prefilled request: reserve its page budget, build
        its table, scatter the prefilled cache into its PRIVATE pages
        (shared prefix pages are mapped, not rewritten), and arm the
        slot's decode state (identical to the fixed-lane insert).

        ``total_tokens`` is the request's full KV budget (prompt +
        new tokens + speculative slack).  FULL mode reserves all of
        it — the reservation that makes mid-decode page exhaustion
        impossible; LAZY mode reserves ``admit_tokens`` (current
        length + one dispatch span) and records the full budget as
        the growth cap (``_slot_budget``).  ``shared_pages`` are
        pinned prefix-page ids whose references this call TAKES
        OWNERSHIP of (released with the rest at slot release)."""
        if total_tokens is None:
            total_tokens = self.max_position
        n_total = self.pages_needed(total_tokens)
        n_need = self.pages_needed(self.admit_tokens(
            position + 1, total_tokens)) if self.lazy else n_total
        shared = list(shared_pages)
        if len(shared) > n_need:       # defensive: over-wide prefix
            self.unpin(shared[n_need:])
            shared = shared[:n_need]
        priv = self.try_reserve(n_need - len(shared))
        if priv is None:
            self.unpin(shared)
            raise PageExhausted(
                f"admission needs {n_need - len(shared)} free pages "
                f"(have {self.free_page_count()}): engine admission "
                f"gate out of sync")
        ids = shared + priv
        try:
            self.scatter_cache(cache, ids, n_shared=len(shared))
            if draft_cache is not None:
                # Mirrored page ids: the draft pool is allocated with
                # the same page geometry, so one table serves both.
                self.scatter_cache(draft_cache, ids,
                                   n_shared=len(shared), draft=True)
        except BaseException:
            self.unpin(ids)
            raise
        self.page_tables[slot, :] = self.scratch0 + slot
        self.page_tables[slot, :len(ids)] = np.asarray(ids, np.int32)
        self._slot_pages[slot] = (ids, len(shared))
        self._slot_need[slot] = n_need
        self._slot_budget[slot] = n_total
        self.state.arm(slot, first_token, position, base_key,
                       next_index, temperature, top_k, top_p, spec_k)

    # -- lazy growth (engine thread, step boundaries) --------------------

    def grow_need(self, slot: int, tokens: int) -> int:
        """Pages a ``grow_slot(slot, tokens)`` would still have to
        reserve (0 = the table already covers it) — what the
        engine's exhaustion path feeds the page-reclaim hook before
        preempting anyone."""
        held = self._slot_pages[slot]
        if held is None:
            raise ValueError(f"grow_need of a free slot {slot}")
        want = min(self.pages_needed(tokens),
                   int(self._slot_budget[slot]))
        return max(0, want - len(held[0]))

    def grow_slot(self, slot: int, tokens: int) -> Optional[int]:
        """LAZY growth at a step boundary: extend ``slot``'s table so
        it holds pages for ``tokens`` positions, capped at the slot's
        full budget.  Returns the number of pages grown (0 = already
        wide enough), or None on POOL EXHAUSTION — the engine's
        preempt-on-exhaustion path owns what happens next.  Engine
        thread only (it mutates the slot table); the reservation
        itself goes through ``reserve_with_epoch`` — one
        ``_page_lock`` hold — like every other page grab, so handler
        threads (prefix pins/stores) interleave safely.

        Freshly grown pages hold garbage until the decode step writes
        them — masked by absolute position before any query could
        admit them, the same argument every reserved-but-unwritten
        page already rides."""
        held = self._slot_pages[slot]
        if held is None:
            raise ValueError(f"grow of a free slot {slot}")
        ids, _n_shared = held
        want = min(self.pages_needed(tokens),
                   int(self._slot_budget[slot]))
        delta = want - len(ids)
        if delta <= 0:
            return 0
        fresh, _epoch = self.reserve_with_epoch(delta)
        if fresh is None:
            return None
        start = len(ids)
        ids.extend(fresh)
        self.page_tables[slot, start:start + delta] = \
            np.asarray(fresh, np.int32)
        self._slot_need[slot] = len(ids)
        self.lazy_growths_total += 1
        self.lazy_pages_grown_total += delta
        return delta

    # -- prefix materialization -----------------------------------------

    def materialize(self, ids: Sequence[int], n_tokens: int):
        """Gather stored prefix pages into a CONTIGUOUS B=1 cache of
        the model's full creation width (``max_position``) — exactly
        the shape the prefill/extend programs expect, so a prefix hit
        reuses every existing compiled program.  Device work — caller
        holds the device lock and a pin on every page in ``ids``."""
        import jax
        import jax.numpy as jnp

        if self._pool is None:
            raise RuntimeError("materialize() before any page write")
        P = self._pad_class(len(ids))
        fn = self._gather_fns.get(P)
        if fn is None:
            if self.sentinel is not None:
                self.sentinel.miss("page_gather", P)
            metas, treedef = self._meta, self._treedef
            pt, width = self.page_tokens, self.max_position

            def gather_cc(pool, table, pos):
                from ..models.kv_cache import gather_pages

                leaves = []
                for m, p in zip(metas, pool):
                    if m["kind"] == "index":
                        leaves.append(jnp.full(m["shape"], pos,
                                               m["dtype"]))
                        continue
                    a = m["pos_axis"]
                    v = gather_pages(p, table, a)
                    have = v.shape[a]
                    if have < width:
                        padw = [(0, 0)] * v.ndim
                        padw[a] = (0, width - have)
                        v = jnp.pad(v, padw)
                    elif have > width:
                        v = jax.lax.slice_in_dim(v, 0, width, axis=a)
                    leaves.append(v)
                return jax.tree_util.tree_unflatten(treedef, leaves)

            if self.mesh is not None:
                # Materialized prefix caches feed the ordinary
                # prefill/extend programs — gather them back to a
                # REPLICATED contiguous cache.
                fn = jax.jit(gather_cc,
                             in_shardings=(self._pool_sh, None, None),
                             out_shardings=self.mesh.replicated)
            else:
                fn = jax.jit(gather_cc)
            self._gather_fns[P] = fn
        elif self.sentinel is not None:
            self.sentinel.hit("page_gather", P)
        table = np.full((P,), self.trash, np.int32)
        table[:len(ids)] = np.asarray(ids, np.int32)
        with self._exact():
            return fn(self._pool, jnp.asarray(table),
                      jnp.asarray(n_tokens, np.int32))

    # -- host-RAM tier (prefix-store spill / re-materialize) -------------
    #
    # The SANCTIONED device<->host transfer helpers for page-pool
    # payloads (the TIER-XFER rule, analysis/rules.py): a prefix
    # entry evicted from the device pool under page pressure spills
    # its payload to host buffers here instead of dropping it, and a
    # later hit re-materializes via ``device_put`` + the existing
    # contiguous-cache plumbing.  Both are device work — callers
    # hold the device lock — and both are OFF the decode step path
    # (spills ride page-pressure reclaim, re-materialization rides a
    # prefix hit's admission, never a step dispatch).

    def spill_pages(self, ids: Sequence[int], n_tokens: int
                    ) -> List[Optional[np.ndarray]]:
        """Gather stored prefix pages into HOST buffers: one np array
        per paged cache leaf (None for index leaves), trimmed to the
        entry's page-aligned span so host bytes track content, not
        ``max_position`` headroom.  Caller holds the device lock and
        a pin on every page in ``ids``."""
        import jax

        cache = self.materialize(ids, n_tokens)
        leaves, _ = jax.tree_util.tree_flatten(cache)
        width = len(ids) * self.page_tokens
        host: List[Optional[np.ndarray]] = []
        for m, leaf in zip(self._meta, leaves):
            if m["kind"] == "index":
                host.append(None)
                continue
            a = m["pos_axis"]
            v = jax.lax.slice_in_dim(
                leaf, 0, min(width, leaf.shape[a]), axis=a)
            host.append(np.asarray(jax.device_get(v)))
        return host

    def rematerialize(self, host_leaves: Sequence[Optional[np.ndarray]],
                      n_tokens: int):
        """Host-tier hit: ``device_put`` the spilled leaves back into
        a CONTIGUOUS B=1 cache of the model's full creation width —
        byte-identical to what :meth:`materialize` returns for the
        same content, so every downstream consumer (extend programs,
        slot insert, page promotion via ``scatter_cache``) is reused
        unchanged.  Caller holds the device lock."""
        import jax
        import jax.numpy as jnp

        if self._meta is None:
            raise RuntimeError("rematerialize() before any page "
                               "write shaped the pool")
        width = self.max_position
        leaves = []
        for m, h in zip(self._meta, host_leaves):
            if m["kind"] == "index":
                leaves.append(jnp.full(m["shape"],
                                       np.int32(n_tokens), m["dtype"]))
                continue
            a = m["pos_axis"]
            have = h.shape[a]
            if have < width:
                pad = [(0, 0)] * h.ndim
                pad[a] = (0, width - have)
                h = np.pad(h, pad)
            elif have > width:
                h = np.take(h, range(width), axis=a)
            h = h.astype(m["dtype"], copy=False)
            # COMMITTED placement both ways (SHARD-LEAK): replicated
            # over the serving mesh, or pinned to the default device.
            sh = self.mesh.replicated if self.mesh is not None \
                else jax.devices()[0]
            leaves.append(jax.device_put(h, sh))
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- decode steps ----------------------------------------------------

    def _resident_pad(self) -> int:
        """Pad class for this dispatch's page tables: pow2 of the
        widest resident reservation, so the compiled program set
        stays bounded and steady-state quiet — and so the gathered
        view (the dispatch's attention width) tracks the RESIDENT
        MIX, not the worst case.  The dirty-window slice clamps its
        start instead of padding the class (see step's d0)."""
        need = int(self._slot_need.max()) if self.n_slots else 1
        return self._pad_class(max(need, self._n_dirty_cap))

    def _n_dirty(self, span: int) -> int:
        pt = self.page_tokens
        return (span - 1 + pt - 1) // pt + 1

    def _dirty_start(self, P: int, n_dirty: int) -> np.ndarray:
        """Per-slot first dirty page for this dispatch, CLAMPED so
        the static-width dirty slice always fits the table.  The
        clamp can shift a window over earlier pages the slot already
        holds — harmless: the gathered view carries their current
        content untouched, so the write-back is byte-identical (for
        the rare boundary case where the earlier page is a SHARED
        prefix page, identical bytes under the serialized device lock
        are benign — content equality is the invariant, and no reader
        can observe a difference)."""
        d0 = self.state.positions // self.page_tokens
        return np.clip(d0, 0, max(0, P - n_dirty)).astype(np.int32)

    def _build_step(self, window: int, sampled: bool, P: int):
        from ..models.generate import jit_over

        model = self.model
        metas, treedef = self._meta, self._treedef
        n_dirty = self._n_dirty(window)

        def step(variables, pool, tables, d0, toks, fed, fresh,
                 positions, *extra):
            # The weights are an ARGUMENT (jit_over), not a closure.
            body = build_step_body(model, variables, window, sampled)
            stacked = self._gather_tree(pool, metas, treedef,
                                        tables, positions)
            outs, extras, stacked = body(stacked, window, toks, fed,
                                         fresh, positions, *extra)
            pool = self._scatter_dirty(pool, metas, stacked, tables,
                                       d0, n_dirty)
            return outs, extras, pool

        if self.mesh is None:
            return jit_over(self.variables, step)
        rep = self.mesh.replicated
        n_extra = 5 if sampled else 0
        in_sh = (self.mesh.shardings_of(self.variables),
                 self._pool_sh) + (rep,) * (6 + n_extra)
        return jit_over(self.variables, step, in_shardings=in_sh,
                        out_shardings=(rep, rep, self._pool_sh))

    def _program(self, window: int, sampled: bool,
                 cap: Optional[int], K: int):
        """What this manager contributes to a launch
        (``SlotManager._launch``): gather views, run the SAME decode
        body as the fixed lanes, scatter dirty pages.  One compiled
        program per (window, sampled or K, pages-per-slot pad class):
        the dirty-page bound is the window's, so ``cap``
        (SlotKVManager._program) buys nothing here and is not used.
        The speculative body's in-program rollback stays a pure
        ``cache_index`` rewind on the gathered view: pages are
        reserved to budget, so rejection never touches the page
        accounting (no truncation, no refcount traffic — the
        full-reservation dividend)."""
        if self._pool is None or (K and self._draft_pool is None):
            raise RuntimeError("step_spec() before a speculative "
                               "insert()" if K
                               else "step() before any insert()")
        P = self._resident_pad()
        tables = self.page_tables[:, :P]
        if K:
            return ((window, "spec", K, P),
                    lambda: self._build_spec_step(window, K, P),
                    ("_pool", "_draft_pool"),
                    (tables, self._dirty_start(
                        P, self._n_dirty(window * K + 1))))
        return ((window, sampled, P),
                lambda: self._build_step(window, sampled, P),
                ("_pool",),
                (tables, self._dirty_start(P, self._n_dirty(window))),
                P * self.page_tokens)

    def _build_spec_step(self, window: int, K: int, P: int):
        from ..models.generate import jit_over

        model, draft = self.model, self.draft_model
        weights = (self.variables, self.draft_variables)
        metas, treedef = self._meta, self._treedef
        d_metas, d_treedef = self._draft_meta, self._draft_treedef
        n_dirty = self._n_dirty(window * K + 1)

        def step(weights, t_pool, d_pool, tables, d0, toks, positions,
                 idxs, keys, temps, tks, tps, sks):
            body = build_spec_step_body(model, weights[0], draft,
                                        weights[1], window, K)
            t_stacked = self._gather_tree(t_pool, metas, treedef,
                                          tables, positions)
            d_stacked = self._gather_tree(d_pool, d_metas, d_treedef,
                                          tables, positions)
            outs, cs, ms, t_stacked, d_stacked = body(
                t_stacked, d_stacked, toks, positions, idxs, keys,
                temps, tks, tps, sks)
            t_pool = self._scatter_dirty(t_pool, metas, t_stacked,
                                         tables, d0, n_dirty)
            d_pool = self._scatter_dirty(d_pool, d_metas, d_stacked,
                                         tables, d0, n_dirty)
            return outs, cs, ms, t_pool, d_pool

        if self.mesh is None:
            return jit_over(weights, step)
        rep = self.mesh.replicated
        in_sh = (self.mesh.shardings_of(weights), self._pool_sh,
                 self._draft_pool_sh) + (rep,) * 10
        return jit_over(weights, step, in_shardings=in_sh,
                        out_shardings=(rep, rep, rep, self._pool_sh,
                                       self._draft_pool_sh))
