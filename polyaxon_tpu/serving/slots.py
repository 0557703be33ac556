"""Slot-indexed KV memory for the continuous-batching engine.

The zoo's decode machinery keys everything off per-cache ``cache_index``
variables and a ``decode_position`` argument — both traceable — so a
pool of S independent per-request caches can be STACKED on one leading
slot axis and stepped under ``jax.vmap``: one compiled program per
model advances every resident request by one token, each at its OWN
position.  This sidesteps the shared-``cache_index`` limitation that
forced the old coalescing path to require a single prompt length per
merged batch: slots are fully independent (ring caches, int8 KV and
scan-stacked layers stack uniformly, because the slot axis is ADDED
rather than reusing the model's internal batch axis — the exact
layout-keying headache beam search has to solve does not exist here).

Speculative decoding rides the same pool: a second stacked cache (the
DRAFT model's) sits alongside the target cache, and a SPECULATIVE
step variant drafts K tokens per slot, verifies them with one
K+1-wide target forward per slot, and commits a per-slot variable
prefix (greedy exact-match lane, or the position-keyed
rejection-sampling lane shared with
``models/generate.generate_speculative``'s seed mode).  Rejection is
a per-slot position REWIND — every slot owns its cache_index, and
the plain/int8/ring caches mask validity by absolute position, so
rewound entries are overwritten before any query can admit them (the
accept/rewind contract, docs/SERVING.md).  Non-speculative co-tenants
ride the same program advancing exactly one token per round: their
token comes from the verify chunk's FIRST logits row through the
shared positional sampler — the same value the plain step programs
produce.

THE HOST HALF OF A DECODE DISPATCH IS WRITTEN ONCE, for this manager
and the paged one (serving/paged.py) and for every kind of step, and
it is CUT IN TWO at the one place where the host waits:
``SlotManager._launch`` — program lookup and the recompile sentinel,
upload (every host operand in ONE packed array, ``LaunchPacking``),
the program's call, rebinding the pool to its successor, the
counters: everything up to the futures, which a ``Flight`` holds —
and ``SlotManager._collect`` — the one ``device_get`` of a flight's
tokens.  ``SlotManager._dispatch`` runs a launch and then a collect
under ONE step marker, and the collect need not be of the dispatch
just launched: the engine launches dispatch N+1 and THEN collects
dispatch N (engine._decode_step), so that the device's queue is not
empty while the host deals N's tokens out.  The serial order is the
same code with the collect of the flight just launched.  What makes
that possible is that the FEEDBACK TOKEN never needs the host: a step
program hands the last token of every slot on as a device array
(``extras["tok"]``), the next launch takes it as an operand
(``SlotState.fed``), and the host's own value goes in only for the
slots it armed or parked since (``SlotState.fresh``, a mask merged
inside the program).  Positions, token indices and sampling operands
stay the host's to compute: they advance at the launch, by the
window, whatever the tokens turn out to be.  The slots' host state
(free list, feedback token, position and sampling operands) is one
object, ``SlotState``, held by either manager as ``state``.  To a
dispatch a manager contributes four things and nothing else
(``_program``): the program for a key not yet compiled, the pool
argument(s) and where their successors are rebound, its own leading
operands, and the width its program sees of the planes.

Device programs, compiled once each per model:

- ``step``:   [S]-stacked cache + toks [S] (the host's, the last
              dispatch's on the device, and the mask that picks
              between them) + positions [S]
              -> next tokens [W, S] + updated stacked cache,
              for a WINDOW of W decode steps fused into one program
              (a loop over the vmapped one-token body, so a window
              costs one dispatch + one host sync instead of W — the
              engine picks W so scheduling granularity is never
              sacrificed, see engine._pick_window).  The step count
              is an OPERAND: one compiled program per capacity (the
              engine's ``decode_window``) runs every window up to
              it.  Two variants: the pure-greedy body (argmax only —
              what an all-greedy pool runs, unchanged from before
              sampling support), and the SAMPLED body, selected
              whenever any resident stream samples: every slot
              additionally
              carries (base PRNG key, next token index, temperature,
              top_k, top_p) and draws its token with
              ``fold_in(base_key, index)`` through the shared
              position-keyed sampler
              (models/generate._sample_positional_row) — greedy
              co-tenants take that sampler's argmax lane, so one
              compiled program serves a mixed pool
- ``insert``: write one finished prefill (a B=1 cache) into slot i
              (``dynamic_update_index_in_dim`` per leaf; the slot
              index is traced, so one program serves every slot)
- the prefill/extend programs live in engine.py (they are keyed by
  chunk length, not slot count)

THE POOL IS UPDATED IN PLACE.  Every program that takes the pool
(``step``, the speculative step's target and draft pools, ``insert``)
takes it as a DONATED argument and returns its successor in the same
buffers: a decode step writes one row a slot and layer
(models/kv_cache.py, the carried layer loop of models/scan_stack.py),
an insertion one lane.  The manager rebinds ``_stacked`` to the
output at once and nothing else may keep the old tree: after a
dispatch its arrays are deleted.  A program that FAILS after it
consumed the pool leaves ``_stacked`` deleted — ``pool_lost()`` says
so, and the engine rebuilds the pool and requeues the residents
(engine._dispatch_step); there is no second copy to fall back on.
The pool's device layout is pinned row-major (``_pool_formats``), the
layout the decode loop works in, so that no program converts the
whole pool on its way in and out.

Idle slots still step (the batch shape is fixed) — they decode garbage
into their own cache (rows of a plane, or a recurrent layer's whole
state: ``kv_cache.leaf_kinds``), which the next ``insert`` overwrites
wholesale.
That is the standard continuous-batching trade: a fixed physical batch
so there is exactly ONE compiled decode program, with logical
occupancy managed above it.

A STEP READS THE PLANES AS FAR AS THE FURTHEST STREAM.  A lane holds
``max_position`` rows a layer; a step's attention is handed the first
``n`` of them, ``n`` the narrowest of a few static widths past ``max(
positions) + 1`` (``build_step_body``, models/kv_cache.attend_kv_cache:
one conditional in the one program).  The extent is ONE scalar for
the pool, computed outside the vmap over the slots: a per-slot bound
would run every branch of the conditional as a select and gather each
slot's rows.  So one long resident makes every lane read to its
length; idle slots, parked at 0, never raise it.  The manager counts
the rows handed over against the rows held (``plane_reads``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import numpy as np

from ..analysis.xprof import STEP_MARKER
from ..models.kv_cache import (KINDS, PlaneReads, cache_kinds,
                               leaf_kinds, read_extent)
from ..spans import scope, span


_KIND_WORDS = {"window": "window rings", "full": "full-length planes",
               "state": "recurrent state without a position axis",
               "latent": "latent planes without a heads axis"}


def pool_refusal(models, *, paged: bool = False, meshed: bool = False,
                 speculative: bool = False) -> Optional[str]:
    """The ONE line with which a server refuses ``models`` (target
    and draft; None entries skipped) at start-up, or None where the
    options in play can hold their caches.  Read off the kinds of leaf
    the models' decode caches hold (``kv_cache.cache_kinds``):
    ``--kv-paged`` (``paged._classify`` cuts ONE position axis into
    pages, of leaves it knows by name) and ``--mesh``
    (``meshed.cache_shardings`` shards heads) know one kind of leaf,
    per-head K and V, and refuse a pool of several, of state, or of
    latent planes (no heads to shard, no K or V to name); a
    speculative slot REWINDS by position (the accept/rewind contract),
    which a state leaf cannot, so a draft model or ``--spec-k`` refuses
    a model that keeps one.  A latent plane rewinds as a K plane does:
    speculative slots serve it."""
    if not (paged or meshed or speculative):
        return None
    for model in models:
        if model is None:
            continue
        kinds = cache_kinds(model)
        stateful = "state" in kinds
        if ((paged or meshed) and (len(kinds) > 1 or stateful
                                   or "latent" in kinds)) \
                or (speculative and stateful):
            count = {1: "one kind", 2: "two kinds",
                     3: "three kinds"}[len(kinds)]
            return (
                f"this model keeps {count} of KV cache in one slot "
                f"pool ({' beside '.join(_KIND_WORDS[k] for k in kinds)}"
                f"): the fixed-lane slot manager on one chip serves it; "
                f"--kv-paged and --mesh know one kind of leaf, K and V "
                f"a head along a position axis, and refuse it"
                + ("; a state has no position to rewind to, so "
                   "speculative decoding (--draft-model, --spec-k) "
                   "refuses it too" if stateful else ""))
    return None


def _rests_as_pinned(shape, fmt) -> bool:
    """Whether the device's default layout for a pool leaf of
    ``shape`` on ``fmt``'s sharding is ``fmt``'s pinned one: where it
    is not, an executable read back from the persistent compilation
    cache would label the leaf wrongly (``config.fresh_compile``)."""
    from jax.experimental.layout import Layout

    sharding = fmt.sharding
    device = min(sharding.device_set, key=lambda d: d.id)
    shard = sharding.shard_shape(shape.shape)
    default = Layout.from_pjrt_layout(
        device.client.get_default_layout(shape.dtype, shard, device))
    return tuple(default.major_to_minor) \
        == tuple(fmt.layout.major_to_minor)


class SlotState:
    """The host's half of the slot pool: which slots are free, and per
    slot what the next step program is fed — feedback token and
    absolute position, the sampled variant's operands (base PRNG key,
    next-token index, shaping params: inert zeros for a greedy or idle
    slot) and the draft length (> 0 marks a SPECULATIVE slot).

    THE FEEDBACK TOKEN HAS TWO HOMES.  ``fed`` is the device array a
    plain or sampled step program handed on: every slot's last token,
    which the next launch takes as an operand without the host ever
    having read it.  ``tokens`` is the host's value, and ``fresh``
    marks the slots where it is the one that counts: armed or parked
    since the last launch, idle, or all of them where the host knows
    every token (after ``reset``, after speculative rounds).  The
    program merges the two by the mask.  ``tokens`` of the other
    slots is a mirror, brought up to date when the newest dispatch is
    collected (``landed``); positions and token indices advance at
    the LAUNCH (``launched``), by the window.

    ONE owner, held by either manager as ``state``: the nine arrays
    are written slot by slot here and nowhere else, and a
    crash-recovery ``reset()`` builds them as construction does, so a
    field added here can never survive a supervised restart carrying
    stale pre-crash state.  A launch hands the ones its kind of step
    takes (``operands``) to the device in ONE int32 buffer
    (:class:`LaunchPacking`: packed in ``SlotManager._launch``,
    unpacked first thing in every step program).  Engine thread
    only."""

    def __init__(self, n_slots: int):
        self.n_slots = int(n_slots)
        self.reset()

    def reset(self) -> None:
        n = self.n_slots
        self.free = list(range(n))
        self.tokens = np.zeros((n,), np.int32)
        self.positions = np.zeros((n,), np.int32)
        self.keys = np.zeros((n, 2), np.uint32)
        self.next_index = np.zeros((n,), np.int32)
        self.temps = np.zeros((n,), np.float32)
        self.top_ks = np.zeros((n,), np.int32)
        self.top_ps = np.zeros((n,), np.float32)
        self.spec_ks = np.zeros((n,), np.int32)
        self.fresh = np.ones((n,), bool)
        self.fed = None

    def acquire(self) -> Optional[int]:
        return self.free.pop(0) if self.free else None

    def arm(self, slot: int, first_token: int, position: int,
            base_key, next_index: int, temperature: float, top_k: int,
            top_p: float, spec_k: int) -> None:
        """``first_token`` at ``position`` is the slot's next step
        input (solo generate's sample-first contract).  A sampled
        stream brings ``base_key`` (its fold_in(PRNGKey(seed), row)
        key) and the index the NEXT step draws; a greedy one leaves
        temperature 0, the sampler's argmax lane."""
        self.tokens[slot] = first_token
        self.fresh[slot] = True
        self.positions[slot] = position
        self.keys[slot] = 0 if base_key is None \
            else np.asarray(base_key, np.uint32)
        self.next_index[slot] = next_index
        self.temps[slot] = temperature
        self.top_ks[slot] = top_k
        self.top_ps[slot] = top_p
        self.spec_ks[slot] = spec_k

    def park(self, slot: int) -> None:
        """Free ``slot`` and park it at position 0, so its dead
        stepping never drifts into out-of-range position-embedding
        lookups, with zeroed sampling state, so it steps through the
        cheap greedy lane of the sampled program."""
        if slot in self.free:
            raise ValueError(f"slot {slot} already free")
        self.free.append(slot)
        self.free.sort()
        self.arm(slot, 0, 0, None, 0, 0.0, 0, 0.0, 0)

    def operands(self, kind: str):
        """The arrays a ``kind`` of step takes, in its body's argument
        order (``build_step_body``, ``build_spec_step_body``): the
        host's travel packed (:class:`LaunchPacking`), ``fed`` is on
        the device already."""
        if kind == "spec":
            return (self.tokens, self.positions, self.next_index,
                    self.keys, self.temps, self.top_ks, self.top_ps,
                    self.spec_ks)
        head = (self.tokens, self.fed, self.fresh, self.positions)
        if kind == "sampled":
            return head + (self.keys, self.next_index, self.temps,
                           self.top_ks, self.top_ps)
        return head

    def launched(self, window: int, fed) -> None:
        """Arm the step after a plain or sampled window, AT ITS
        LAUNCH: every slot feeds back its own last token, ``fed`` on
        the device, at the next position (and token index)."""
        self.fed = fed
        self.fresh[:] = False
        self._move(window)

    def landed(self, last_tokens) -> None:
        """The NEWEST dispatch's last tokens have reached the host:
        the mirror of every slot not armed or parked since."""
        self.tokens = np.where(self.fresh, self.tokens,
                               last_tokens).astype(np.int32)

    def advance_spec(self, outs, commits) -> None:
        """Arm the round after speculative rounds, from each slot's
        LAST commit and by as many positions as it committed: the
        host read every token, so every slot is fresh."""
        rows = np.arange(self.n_slots)
        self.tokens = outs[-1, rows, commits[-1] - 1].astype(np.int32)
        self.fresh[:] = True
        self._move(commits.sum(axis=0).astype(np.int32))

    def _move(self, by) -> None:
        self.positions = self.positions + by
        self.next_index = self.next_index + by
        # Re-park free slots at position 0 so their dead stepping
        # stays bounded by one window and can never drift past
        # max_position on a long-lived resident batch.
        if self.free:
            idle = np.asarray(self.free, np.int32)
            self.tokens[idle] = 0
            self.fresh[idle] = True
            self.positions[idle] = 0
            self.next_index[idle] = 0


# -- one transfer a launch ---------------------------------------------------
#
# What a transfer to the device costs the host is paid per array, not
# per byte: a sampled dispatch's nine arrays of a few dozen words took
# 2.47 ms on a v5e host, the same words in one buffer 0.31 ms
# (benchmarks/bench_upload.py; PERF.md section 6).  So a launch lays
# every host operand's words into ONE int32 buffer and puts that, and
# the program cuts it up again before anything else.
#
# How an operand's elements become int32 words, and come back inside
# the program: "int" as they are, "bits" bit for bit (float32 NaN
# payloads, -0.0 and uint32 keys survive), "bool" as 0/1.  An operand
# of several axes travels column by column: the TPU compiler laid the
# PRNG keys [slots, 2] out slot-minor as a parameter, and cut from
# column order they keep that layout, so the sampler's fusions are
# those it compiled when each operand was a parameter.
_WORDS = {np.dtype(np.int32): "int", np.dtype(np.uint32): "bits",
          np.dtype(np.float32): "bits", np.dtype(np.bool_): "bool"}


def _on_host(a) -> bool:
    return isinstance(a, (np.ndarray, np.generic))


def _words(a) -> np.ndarray:
    a = np.asarray(a)
    how = _WORDS.get(a.dtype)
    if how is None:
        raise TypeError(f"a launch cannot pack {a.dtype} operands")
    a = a.T.reshape(-1)
    return a.astype(np.int32) if how == "bool" else a.view(np.int32)


class LaunchPacking:
    """Where each host operand of a launch lies in the ONE int32 buffer
    the launch transfers, and the way back.

    ``operands`` are a launch's arguments after its pools, in the
    step body's order: the manager's leading ones (the step count;
    paged, page tables and dirty starts), then
    ``SlotState.operands(kind)``.  A numpy array or scalar travels in
    the buffer, its words in that order; anything else (``fed``, on
    the device already; None where it is not yet) is handed to the
    program as it is.  The layout depends on shapes and dtypes alone,
    so a program key has ONE: its program takes ``(weights, *pools,
    words, *device)`` and calls :meth:`unpack` first."""

    def __init__(self, operands):
        fields, size = [], 0
        for a in operands:
            if _on_host(a):
                n = _words(a).size
                fields.append((size, n, np.shape(a), np.asarray(a).dtype))
                size += n
            else:
                fields.append(None)
        self.fields = tuple(fields)
        self.size = size

    @staticmethod
    def pack(operands):
        """``(words, device)``: the host operands' words in one FRESH
        int32 buffer (the slots' arrays are written again before the
        program may have read it), and the device operands in order."""
        return (np.concatenate([_words(a) for a in operands
                                if _on_host(a)]),
                tuple(a for a in operands if not _on_host(a)))

    def unpack(self, words, device):
        """Inside the program: every operand as the step body takes
        it, cut from ``words`` at static offsets and given back its
        dtype, the device operands put back where they stood."""
        import jax

        device, out = iter(device), []
        for field in self.fields:
            if field is None:
                out.append(next(device))
                continue
            at, n, shape, dtype = field
            a = words[at:at + n].reshape(shape[::-1]).T
            how = _WORDS[dtype]
            if how == "bool":
                a = a != 0
            elif how == "bits":
                a = jax.lax.bitcast_convert_type(a, dtype)
            out.append(a)
        return tuple(out)


# -- step-program bodies (shared with the paged manager) --------------------
#
# The scan/vmap decode bodies treat the stacked cache pytree opaquely
# — they only thread it through ``model.apply`` — so the SAME bodies
# serve the fixed-lane manager below (stacked resident cache) and the
# paged manager (serving/paged.py), which wraps them in a page-table
# gather before and a dirty-page scatter after.  Exactness across the
# two storage disciplines is free by construction: one traced body,
# two cache layouts with identical materialized content.


def build_step_body(model, variables, window: int, sampled: bool):
    """Unjitted decode body over a stacked cache: up to ``window``
    fused steps.

    Plain: ``step(stacked, steps, toks, fed, fresh, positions) ->
    (outs [window, S], extras, stacked)``.  Sampled: ``step(stacked,
    steps, toks, fed, fresh, positions, keys, idxs, temps, tks, tps)``
    with the same returns.  A slot's first input token is the host's
    ``toks`` where ``fresh``, else ``fed``: the last token the
    dispatch before this one left on the device (``SlotState``).

    ``extras`` is what the steps computed anyway and nobody kept:
    ``{"tok": [S] the last token of every slot, the next dispatch's
    ``fed``, "logits": [S, V] float32 of the LAST step run, "pairs": what
    the model sowed under generate.STATS summed over steps, layers and
    slots (``generate.stats_total``: the expert layers' token-expert
    pairs and the experts they touched; absent for a model that sows
    nothing)}``.  Both managers return them from every
    program, so the programs a benchmark times are the ones whose
    logits a reference check reads (fetched only on request) and the
    pair counts ride home with the tokens.

    ``steps`` (at most ``window``) is how many steps run; the rows of
    ``outs`` past it stay zero.  A Python int makes the loop one of
    static length (the paged manager, one program a window); TRACED,
    one program serves every window up to its capacity (the
    fixed-lane manager): the body of a step is the same program text
    whatever the count, and a program that takes the pool is compiled
    anew in every process (``SlotKVManager._compiling``), so each one
    not built is seconds of a server's start."""
    import jax
    import jax.numpy as jnp

    from ..models import generate as G

    def logits_for(cache, tok, pos):
        # One decoder step for one slot: tok [] at absolute
        # position pos [].  _params inside the closure keeps int8
        # weights int8 in HBM (generate._params contract).
        out, mut = model.apply(
            {"params": G._params(variables), "cache": cache},
            tok[None, None], decode=True, decode_position=pos,
            mutable=["cache", G.STATS])
        pairs = G.stats_rows(mut.get(G.STATS))      # a row a layer
        if pairs is None:
            pairs = jnp.zeros((0, 0), jnp.int32)
        return (G.extract_logits(out)[:, -1][0], pairs,   # [V]
                mut["cache"])

    def one(cache, tok, pos, *sampling):
        """(next token, logits, pairs, cache) of one slot: argmax in
        the pure-greedy body (all-greedy pools never pay the sampler's
        threshold searches and greedy-only servers compile nothing of
        it), else the shared position-keyed sampler with the slot's
        OWN (key, index, temperature, top_k, top_p); greedy co-tenants
        (temperature 0) take its argmax lane, producing the same
        tokens the greedy body would.  Either way under the scope
        ``ptpu_sample`` (spans.py; the sampler opens its own)."""
        logits, pairs, cache = logits_for(cache, tok, pos)
        if sampled:
            nxt = G._sample_positional_row(logits, *sampling)
        else:
            with scope("ptpu_sample"):
                nxt = jnp.argmax(logits).astype(jnp.int32)
        return nxt, logits, pairs, cache

    def step(stacked, steps, toks, fed, fresh, positions, *sampling):
        # ``sampling``: (keys, idxs, temps, tks, tps), the sampled
        # body's; the token index advances with the step.
        toks = jnp.where(fresh, toks, fed)
        keys, idxs, shaping = sampling[:1], sampling[1:2], sampling[2:]
        like = jax.eval_shape(
            jax.vmap(one), stacked, toks, positions,
            *keys, *idxs, *shaping)

        def counted(rows):
            # One step's rows [S, layers, n], the lanes summed: the
            # experts a LAYER's grouped matmul touches are the pool's.
            return G.stats_total(rows.sum(axis=0)) if rows.size \
                else jnp.zeros((0,), rows.dtype)

        def body(i, carry):
            cache, tok, pos, idx, outs, _, pairs = carry
            # How far this step's attention reads the full-length
            # planes: to the pool's furthest stream (idle slots are
            # parked at 0 and never raise it).  ONE scalar, computed
            # outside the vmap: see kv_cache.read_extent.
            with read_extent(pos.max() + 1, shared=True):
                nxt, logits, new_pairs, cache = jax.vmap(one)(
                    cache, tok, pos, *keys, *idx, *shaping)
            return (cache, nxt, pos + 1, tuple(j + 1 for j in idx),
                    outs.at[i].set(nxt), logits,
                    pairs + counted(new_pairs))

        cache, tok, _, _, outs, logits, pairs = jax.lax.fori_loop(
            0, steps, body,
            (stacked, toks, positions, tuple(idxs),
             jnp.zeros((window,) + toks.shape, jnp.int32),
             jnp.zeros(like[1].shape, like[1].dtype),
             jnp.zeros(jax.eval_shape(counted, like[2]).shape,
                       like[2].dtype)))
        extras = {"tok": tok, "logits": logits}
        if pairs.size:          # nothing sown: nothing to fetch
            extras["pairs"] = pairs
        return outs, extras, cache

    return step


def build_spec_step_body(model, variables, draft, draft_vars,
                         window: int, K: int):
    """Unjitted ``window``-round SPECULATIVE body over a stacked
    target cache + stacked draft cache (the math documented on
    :meth:`SlotKVManager._build_spec_step`):

    ``step(t_stacked, d_stacked, toks, positions, idxs, keys, temps,
    tks, tps, sks) -> (outs [W, S, K], commits [W, S], accepts
    [W, S], t_stacked, d_stacked)``."""
    import jax
    import jax.numpy as jnp

    from ..models import generate as G

    if draft is None:
        raise RuntimeError(
            "speculative step without a draft model (construct the "
            "slot manager with draft_model/draft_variables)")

    def one_round(t_cache, d_cache, tok, pos, idx, key, temp,
                  tk, tp, sk):
        # Draft K proposals (k small steps, its own cache).
        def dstep(carry, _):
            cache, t, p, i = carry
            out, mut = draft.apply(
                {"params": G._params(draft_vars), "cache": cache},
                t[None, None], decode=True, decode_position=p,
                mutable=["cache"])
            logits = G.extract_logits(out)[:, -1][0]
            nxt, q = G._spec_draft_row(logits, key, i, temp, tk,
                                       tp)
            return (mut["cache"], nxt, p + 1, i + 1), (nxt, q)

        (d_cache, _, _, _), (d_toks, q_rows) = jax.lax.scan(
            dstep, (d_cache, tok, pos, idx), None, length=K)

        # Target verifies [tok, d_1..d_K] in ONE forward.
        chunk = jnp.concatenate([tok[None], d_toks])[None, :]
        out, mut = model.apply(
            {"params": G._params(variables), "cache": t_cache},
            chunk, decode=True, decode_position=pos,
            mutable=["cache"])
        t_all = G.extract_logits(out)[0]              # [K+1, V]

        out_toks, c, _m = G._spec_verify_row(
            t_all[:K], d_toks, q_rows, key, idx, temp, tk, tp, sk)
        # Plain lane (sk == 0): one token from the chunk's first
        # logits — identical to the greedy/sampled step programs.
        plain = G._sample_positional_row(t_all[0], key, idx, temp,
                                         tk, tp)
        is_spec = sk > 0
        c = jnp.where(is_spec, c, 1)
        m = jnp.where(is_spec, _m, 0)
        out_toks = jnp.where(is_spec, out_toks,
                             jnp.zeros_like(out_toks).at[0]
                             .set(plain))
        new_pos = pos + c
        t_cache = G._rollback_cache(mut["cache"], new_pos)
        d_cache = G._rollback_cache(d_cache, new_pos)
        nxt = out_toks[c - 1]
        return (t_cache, d_cache, nxt, new_pos, idx + c,
                out_toks, c, m)

    def step(t_stacked, d_stacked, toks, positions, idxs, keys,
             temps, tks, tps, sks):
        def body(carry, _):
            t_c, d_c, tok, pos, idx = carry
            (t_c, d_c, nxt, npos, nidx, outs, cs, ms) = jax.vmap(
                one_round)(t_c, d_c, tok, pos, idx, keys, temps,
                           tks, tps, sks)
            return (t_c, d_c, nxt, npos, nidx), (outs, cs, ms)

        (t_c, d_c, _, _, _), (outs, cs, ms) = jax.lax.scan(
            body, (t_stacked, d_stacked, toks, positions, idxs),
            None, length=window)
        return outs, cs, ms, t_c, d_c   # [W, S, K], [W, S] x2

    return step


class Flight:
    """One decode dispatch between its launch and its collect: what
    the program handed back as futures (``out``: tokens, and after
    speculative rounds commits and accepts; ``pairs``: the pair counts
    that ride home with them, the program's own and those of the
    prefill pieces enqueued ahead of it) and, once collected, the same
    as numpy arrays (``host``; tokens cut to the ``window``)."""

    __slots__ = ("kind", "window", "out", "pairs", "host")

    def __init__(self, kind: str, window: int, out, pairs):
        self.kind = kind
        self.window = window
        self.out = out
        self.pairs = pairs
        self.host = None


class SlotManager:
    """What the two KV managers share: the slots' host state
    (``state``, a :class:`SlotState`) and the host half of ONE decode
    dispatch (:meth:`_dispatch`: :meth:`_launch`, then
    :meth:`_collect`), with everything that half counts and keeps.
    A subclass owns a storage discipline and nothing of the
    dispatch: the stacked pools, their pinned formats and donation
    (:class:`SlotKVManager`); pages, tables, gather and scatter
    (:class:`~.paged.PagedSlotKVManager`).  Device work only — request
    bookkeeping lives in engine.py/scheduler.py."""

    def __init__(self, model, variables, n_slots: int, draft_model,
                 draft_variables, sentinel, mesh):
        self.model = model
        self.variables = variables
        # Draft model for SPECULATIVE slots (optional): its per-slot
        # caches make a second pool stepped by the spec program's
        # draft scan.
        self.draft_model = draft_model
        self.draft_variables = draft_variables
        # Recompile sentinel (analysis/recompile.py): every step/
        # insert program build is a counted compile-cache miss, so a
        # steady-state recompile storm (an unbounded key leaking into
        # the program set) is observable instead of being mystery
        # tail latency.
        self.sentinel = sentinel
        # Serving mesh (serving/meshed.py): when set, the pools live
        # under NamedSharding and every program compiles with EXPLICIT
        # in/out shardings under the serving-exact constraint mode —
        # meshed output is token-bitwise-identical to unmeshed
        # (docs/SERVING.md "Meshed serving").
        self.mesh = mesh
        self.n_slots = int(n_slots)
        self.state = SlotState(self.n_slots)
        self._step_fns = {}           # program key -> jitted step
        # Whether the pools' pinned layout is the device's default
        # (``_compiling``).  A manager that pins none leaves it so.
        self._pin_is_default = True
        # Seconds in the step's host sections (spans.span) since the
        # engine last took them for a step record: upload, enqueue
        # and sync, and the marker that holds them (the record's
        # ``device_s``: a host clock, lock wait excluded).
        self.host_s = {}
        # The last dispatch launched: the one whose last tokens the
        # host's mirror follows (``SlotState.landed``).
        self._newest = None
        # Pair counts of prefill pieces enqueued since the last
        # launch (``defer_pairs``): device arrays nobody waits for.
        self._piece_pairs = []
        # Whether the pool is updated in place, counted where it can
        # be seen: programs that took the pool (decode dispatches and
        # insertions) and how many of them consumed the tree they
        # were handed (``is_deleted()`` of one of its leaves, a host
        # check).  Engine stats / /info, with ``kv_pool_bytes``.
        self.kv_pool_dispatches_total = 0
        self.kv_pool_in_place_total = 0
        # Host arrays the decode launches transferred: one a launch,
        # every host operand packed (``LaunchPacking``).
        self.upload_arrays_total = 0
        # How far the attention read the full-length planes, against
        # what they hold, and what the state leaves went through
        # (kv_cache.PlaneReads): every decode step here, every
        # prefill chunk by the engine.
        self.plane_reads = PlaneReads()
        # What the last decode program left beside its tokens
        # (build_step_body's ``extras``): the last step's logits [S, V],
        # a device array nobody fetches unless a stream asked for its
        # logits (None after a speculative round, whose body keeps
        # none); and the expert layers' token-expert pairs, summed
        # here since the start ([held expert ..., routed]; None until
        # a program of a model that counts them has run).  Prefill
        # programs add theirs through ``count_pairs``.
        self.last_logits = None
        self.moe_pairs = None

    def count_pairs(self, pairs) -> None:
        """Add one program's pair counts (``build_step_body``'s
        ``pairs``, ``generate.prefill``'s stats) to ``moe_pairs``."""
        pairs = np.asarray(pairs, np.int64)
        self.moe_pairs = pairs if self.moe_pairs is None \
            else self.moe_pairs + pairs

    def _reset_slots(self) -> None:
        """The slots' host state as construction left it, and nothing
        of a dispatch launched before (a manager's ``reset``)."""
        self.state.reset()
        self._newest = None
        self._piece_pairs = []

    def defer_pairs(self, pairs) -> None:
        """The pair counts of a prefill piece just ENQUEUED (a device
        array, or None where the model sows nothing): counted when
        the next dispatch launched is collected, the first thing
        behind the piece that the host waits for anyway."""
        if pairs is not None:
            self._piece_pairs.append(pairs)

    def flush_pairs(self) -> None:
        """Count the deferred pair counts now, where no dispatch is
        coming to bring them home (the pool has gone idle)."""
        import jax

        if self._piece_pairs:
            pending, self._piece_pairs = self._piece_pairs, []
            # HOST-SYNC: a few int32, of pieces long finished.
            for pairs in jax.device_get(pending):
                self.count_pairs(pairs)

    @property
    def free_slots(self) -> int:
        return len(self.state.free)

    @property
    def active_slots(self) -> int:
        return self.n_slots - len(self.state.free)

    def acquire(self) -> Optional[int]:
        return self.state.acquire()

    def _fed_sharding(self):
        """Where the feedback token rests between two dispatches
        (None: uncommitted, on the default device): the first launch
        (every slot fresh, ``fed`` never read) makes zeros there, the
        kind of array the step programs hand out, so that a program
        sees ONE signature from its first call on.  A launch's packed
        host operands go there too."""
        return self.mesh.replicated if self.mesh is not None else None

    def _packing(self, kind: str, leading=()) -> LaunchPacking:
        """The layout of a ``kind`` of launch's packed operands, for a
        manager whose leading operands are shaped as ``leading``: what
        a step program unpacks (``_build_step``)."""
        return LaunchPacking((*leading, *self.state.operands(kind)))

    def _exact(self):
        """Serving-exact trace context (no-op unmeshed) — wraps every
        call that can TRACE a program over sharded operands."""
        return self.mesh.exact() if self.mesh is not None \
            else contextlib.nullcontext()

    def _compiling(self, new: bool):
        """Context for the call of a pool program: its FIRST call
        compiles, outside the persistent cache where the pinned
        layout is not the device's default."""
        from ..config import fresh_compile

        return fresh_compile() if new and not self._pin_is_default \
            else contextlib.nullcontext()

    def _count_dispatch(self, *taken) -> None:
        """One program took the pool(s) whose probe leaves are
        ``taken``: count it, and count it in place if every one came
        back consumed."""
        self.kv_pool_dispatches_total += 1
        self.kv_pool_in_place_total += all(
            leaf.is_deleted() for leaf in taken)

    def _dispatch(self, launch=None, collect=None, launched=None,
                  **stats):
        """The host half of ONE decode dispatch, whatever the manager
        and the kind of step, under ONE ``ptpu_step`` marker: the
        LAUNCH of a dispatch (``launch``: what :meth:`_launch` takes;
        None launches nothing) and then the COLLECT of one
        (``collect``: a :class:`Flight` launched earlier, True for
        the one just launched, None for none).  Returns the flight
        launched.

        The engine runs one dispatch ahead: a tick's marker holds the
        launch of dispatch N+1 and then the collect of dispatch N, so
        that N+1 is in the device's queue before the host starts to
        wait for N.  The serial order (``collect=True``) and a drain
        (no ``launch``) are the same code.  ``launched()`` is called
        between the two halves: the caller gives up the device lock
        there, which the wait for tokens does not need.

        ``stats`` (``window``, ``k``: the launched dispatch's, or the
        collected one's in a drain) label the marker.  When a
        ``jax.profiler`` trace is active — a manual ``POST
        /profile/start`` or a flight-recorder window — the trace
        parser (analysis/xprof.py) anchors its attribution window to
        the span of these markers.  Launch and collect BOTH lie
        inside the marker, so the markers of a run of dispatches
        cover it from the first launch to the last collect: a marker
        that closed before the wait would span only the host's
        enqueues and clip the device's execution out of the window.
        A marker no longer brackets the execution of the program it
        launched (that one runs while the host is in its NEXT
        marker's wait, and in the commit between them); it brackets
        the wait for the one before.  Inside it lie ``ptpu/upload``,
        ``ptpu/enqueue`` (the launch) and ``ptpu/sync`` (the
        collect); its own seconds are the step record's
        ``device_s``."""
        with self._exact(), span(STEP_MARKER, self.host_s, **stats):
            flight = None
            if launch is not None:
                flight = self._launch(*launch, **stats)
            if launched is not None:
                launched()
            if collect is True:
                collect = flight
            if collect is not None:
                self._collect(collect)
        return flight

    def _launch(self, kind: str, key, build, pools, leading=(),
                plane_cap=None, **stats) -> Flight:
        """The launch half: everything up to the futures.  The manager
        brings what its storage decides (``_program``): the program's
        ``key`` and ``build()`` for a key not yet compiled; ``pools``,
        the names of the attributes holding the pool argument(s),
        rebound to their successors as soon as the program hands them
        back; its own ``leading`` operands; and the ``plane_cap`` its
        program's view of the planes has (None: the planes' own).
        Every host operand, the manager's leading ones and the
        slots', reaches the device in ONE transfer
        (:class:`LaunchPacking`; the program unpacks it first).
        The slots' host state moves on HERE, by the window
        (``SlotState.launched``): the next launch needs nothing of
        this one's results but ``fed``, which stays on the device.
        Speculative rounds move it at their collect (commit counts
        are data), so they are always collected at once."""
        import jax
        import jax.numpy as jnp

        fn = self._step_fns.get(key)
        new = fn is None
        if new:
            if self.sentinel is not None:
                self.sentinel.miss("slot_step", key)
            fn = self._step_fns[key] = build()
        elif self.sentinel is not None:
            self.sentinel.hit("slot_step", key)
        state, host_s, spec = self.state, self.host_s, kind == "spec"
        window = stats["window"]
        with span("ptpu/upload", host_s):
            if state.fed is None:
                # made on the device: no host array travels for it
                state.fed = jnp.zeros((self.n_slots,), jnp.int32,
                                      device=self._fed_sharding())
            # ONE transfer (LaunchPacking): the buffer is fresh, so
            # the slots' arrays may be written in place before the
            # program runs, however late a backend reads it.
            words, device = LaunchPacking.pack(
                (*leading, *state.operands(kind)))
            words = jax.device_put(words, self._fed_sharding())
            self.upload_arrays_total += 1
        with span("ptpu/enqueue", host_s), self._compiling(new):
            held = [getattr(self, name) for name in pools]
            taken = [jax.tree.leaves(pool)[0] for pool in held]
            out = fn(*held, words, *device)
            for name, pool in zip(pools, out[-len(pools):]):
                setattr(self, name, pool)
            self._count_dispatch(*taken)
            # A plain or sampled program's last host output is the
            # body's ``extras``; the speculative body keeps none.
            host, extras = out[:-len(pools)], {}
            if not spec:
                # The extent each of the window's steps reads the
                # planes to (``build_step_body``): one past the
                # furthest of the pool's positions, which all
                # advance by one a step.
                self.plane_reads.count(
                    int(state.positions.max()) + 1 + np.arange(window),
                    lanes=self.n_slots, cap=plane_cap, shared=True)
                self.plane_reads.count_steps(window, state.positions)
                *host, extras = host
                state.launched(window, extras["tok"])
            self.last_logits = extras.get("logits")  # stays on the device
            pairs, self._piece_pairs = \
                [extras.get("pairs"), *self._piece_pairs], []
        flight = self._newest = Flight(kind, window, tuple(host), pairs)
        return flight

    def _collect(self, flight: Flight) -> None:
        """The collect half: the ONE wait of a dispatch, for its
        tokens (``flight.host``) and the pair counts that ride home
        with them.  The host's mirror of the feedback token follows
        the newest dispatch only; an older one's last tokens are
        already superseded on the device."""
        import jax

        with span("ptpu/sync", self.host_s):
            # HOST-SYNC: the one intentional wait of a dispatch.
            host, pairs = jax.device_get((flight.out, flight.pairs))
        for counted in pairs:
            if counted is not None:
                self.count_pairs(counted)
        flight.out = flight.pairs = None
        if flight.kind == "spec":
            self.state.advance_spec(*host[:2])
        else:
            host = (host[0][:flight.window],)
            if flight is self._newest:
                self.state.landed(host[0][-1])
        flight.host = host

    def launch(self, window: int = 1, *, sampled: bool = False,
               cap: Optional[int] = None, K: int = 0, collect=True,
               launched=None) -> Flight:
        """Launch ``window`` fused decode steps across the whole pool
        (``K`` > 0: speculative rounds of draft width ``K``; else the
        plain or, with ``sampled``, the sampled program) and collect
        ``collect`` in the same marker (:meth:`_dispatch`): True, this
        dispatch, the serial order; an earlier :class:`Flight`, the
        engine one dispatch ahead; None, nothing yet.  Token selection
        and the token feedback run inside one looped program, so a
        window costs ONE dispatch + ONE host round-trip whatever its
        length; the caller (engine._decode_step) passes ``sampled``
        iff any resident stream samples, and engine._pick_window sizes
        the window so no admission or budget-eviction boundary lands
        inside it.

        The pool is DONATED where the manager donates it: the tree
        named before the call is deleted by it, and if the program
        fails after that, ``pool_lost()`` is true and the pool has to
        be rebuilt (engine._dispatch_step)."""
        kind = "spec" if K else "sampled" if sampled else "plain"
        stats = {"window": window, **({"k": K} if K else {})}
        return self._dispatch(
            (kind, *self._program(window, sampled, cap, K)),
            collect, launched, **stats)

    def collect(self, flight: Flight) -> None:
        """Collect a dispatch launched earlier with nothing launched
        behind it: a drain (:meth:`_dispatch`)."""
        self._dispatch(None, flight, window=flight.window)

    def step(self, window: int = 1, sampled: bool = False,
             cap: Optional[int] = None) -> np.ndarray:
        """``window`` fused decode steps, launched and collected:
        the next tokens [window, S] (garbage for idle slots — the
        caller masks by occupancy).  ``cap``: the widest window the
        caller will ever ask for (the engine's ``decode_window``)."""
        return self.launch(window, sampled=sampled, cap=cap).host[0]

    def step_spec(self, window: int, K: int):
        """``window`` fused SPECULATIVE rounds across the whole pool,
        launched and collected.  Returns ``(tokens [window, S, K],
        commits [window, S], accepts [window, S])``: round w commits
        ``tokens[w, s, :commits[w, s]]`` for slot s (1 for
        non-speculative slots, garbage for idle ones — the caller
        masks by occupancy), and ``accepts`` counts the accepted draft
        tokens (the engine's acceptance-rate metric).  ``K`` is the
        program's draft width — the pool max; slots with smaller
        ``spec_k`` commit at most their own k (exactness per slot is
        unchanged, see _spec_verify_row)."""
        return self.launch(window, K=K).host


class SlotKVManager(SlotManager):
    """Fixed pool of ``n_slots`` decode slots over one model.

    Owns the stacked cache pytree (every leaf gains a leading
    ``n_slots`` axis), its pinned formats and the jitted step/insert
    programs, every one of which takes the pool DONATED.
    """

    paged = False

    def __init__(self, model, variables, n_slots: int,
                 draft_model=None, draft_variables=None,
                 sentinel=None, mesh=None):
        super().__init__(model, variables, n_slots, draft_model,
                         draft_variables, sentinel, mesh)
        # Meshed, the stacked pools shard heads over tp and the slot
        # axis over dp.
        self._cache_sh = None         # stacked-pool formats pytree
        self._draft_cache_sh = None
        # ``_pin_is_default`` is read off the first prefilled cache:
        # where the pinned row-major layout differs from the device's
        # default for these leaves, the pool's programs are compiled
        # in this process, never read from the persistent cache
        # (config.fresh_compile says why).
        self._stacked = None          # pytree, leaves [S, ...]
        self._draft_stacked = None    # draft pytree, leaves [S, ...]
        # Bytes the device holds for each pool, read where it is made
        # (``_alloc_stacked``): its arrays are donated afterwards.
        self._rest_bytes = {False: 0, True: 0}     # draft? -> bytes
        self._insert_fns = {}         # draft? -> jitted insert

    def _fed_sharding(self):
        """This manager's pool is COMMITTED to its pinned formats, so
        whatever its programs hand out is committed too, unmeshed as
        well: they pin their host outputs here and the first ``fed``
        is put here.  A second signature of a pool program would be
        compiled at a later call, outside ``_compiling``: into and,
        in the next process, out of the persistent cache, whose
        executables mislabel a pinned layout (config.fresh_compile;
        PERF.md section 6, PR 34)."""
        import jax
        from jax.sharding import SingleDeviceSharding

        return super()._fed_sharding() \
            or SingleDeviceSharding(jax.devices()[0])

    def reset(self) -> None:
        """Crash-recovery pool rebuild (recovery.EngineSupervisor):
        drop ALL resident KV and per-slot decode state while KEEPING
        the compiled step/insert programs — the stacked pools are
        released and lazily re-zeroed by the next insert's
        ``_ensure_stacked``, so a supervised restart adds ZERO
        steady-state recompiles (pinned in tests/test_faults.py)."""
        self._stacked = None
        self._draft_stacked = None
        self._reset_slots()

    def release(self, slot: int) -> None:
        """Evict: the slot is reusable the SAME step — no device work,
        the stale KV is invisible (nothing reads it) until the next
        insert overwrites it.  EVERY eviction flavor goes through
        here — eos/budget completion, engine failure, CANCELLATION,
        deadline expiry, and SLO preemption (engine._cancel_group /
        _maybe_preempt) — because the safety argument is identical:
        the dead slot parks at position 0 with zeroed sampling state
        (``SlotState.park``), its KV is unreachable until an insert
        overwrites it wholesale, and a preempted request re-enters
        through insert() with a freshly prefilled cache rather than
        trusting anything left here."""
        self.state.park(slot)

    # -- device programs ------------------------------------------------

    def kv_pool(self):
        """The live main KV pool pytree (None before the first
        prefill shaped it).  The next dispatch or insertion CONSUMES
        it: read it under the engine's ``device_lock`` and keep no
        reference past the lock."""
        return self._stacked

    @property
    def kv_pool_bytes(self) -> int:
        """The live pools' logical bytes (shapes alone: safe to read
        while a dispatch consumes the tree)."""
        import jax

        return sum(leaf.nbytes
                   for pool in (self._stacked, self._draft_stacked)
                   for leaf in jax.tree.leaves(pool))

    @property
    def kv_pool_rest_bytes(self) -> int:
        """The live pools' bytes as the device holds them: the tiles
        their leaves rest in, padding included (a plane of 64-wide
        heads that does not fold, models/kv_cache.lane_fold, rests in
        twice its ``kv_pool_bytes``)."""
        return sum(self._rest_bytes[draft] for draft, pool in (
            (False, self._stacked), (True, self._draft_stacked))
            if pool is not None)

    @property
    def kv_pool_bytes_by_kind(self) -> dict:
        """``kv_pool_bytes`` split by the kind of cache a leaf belongs
        to (``kv_cache.leaf_kinds``): ``window`` for a ring's leaves,
        ``state`` for a recurrent layer's, ``latent`` for a latent
        attention layer's, ``full`` for everything else."""
        out = dict.fromkeys(KINDS, 0)
        for pool in (self._stacked, self._draft_stacked):
            for _, leaf, kind in leaf_kinds(pool):
                out[kind] += leaf.nbytes
        return out

    def pool_lost(self) -> bool:
        """Whether a program consumed the pool and failed before it
        handed back the successor: the live tree then holds deleted
        arrays and must never be dispatched again (the engine
        rebuilds it, engine._dispatch_step)."""
        import jax

        return any(leaf.is_deleted()
                   for pool in (self._stacked, self._draft_stacked)
                   for leaf in jax.tree.leaves(pool))

    def _pool_formats(self, shapes):
        """The device format of every pool leaf: row-major, on the
        mesh's shardings or on the default device.  Row-major is the
        layout the decode loop works in (a row written at a traced
        position wants the position axis outside the tiled minor
        two).  Left to itself the TPU gives a ``[..., positions,
        heads, 64]`` array a position-minor layout at rest, and every
        program that took the pool converted all of it on the way in
        and again on the way out (PERF.md section 6, PR 28); such a
        plane now rests folded, ``[..., positions, heads * 64]``
        (kv_cache.lane_fold), whose default is row-major already.  Every
        program pins its pool arguments and results to these formats,
        so a mismatch is an error at the call, never a silent copy."""
        import jax
        from jax.experimental.layout import Format, Layout
        from jax.sharding import SingleDeviceSharding

        if self.mesh is not None:
            sh = self.mesh.cache_shardings(shapes, slot_axis=True)
        else:
            one = SingleDeviceSharding(jax.devices()[0])
            sh = jax.tree.map(lambda _: one, shapes)
        return jax.tree.map(
            lambda l, s: Format(
                Layout(major_to_minor=tuple(range(l.ndim))), s),
            shapes, sh)

    def _alloc_stacked(self, template_cache, draft: bool = False):
        """Zero-init the [S, ...] pool in its pinned formats (meshed:
        heads over tp, slots over dp, from birth) and note the bytes
        the device holds for it (``kv_pool_rest_bytes``).  Returns the
        pool and its formats."""
        import jax
        import jax.numpy as jnp

        # A prefilled cache rests in the device's default layout:
        # row-major on a CPU, and on a TPU for a plane whose heads fill
        # the lanes (folded, kv_cache.lane_fold); position-minor there
        # for a head dimension under 128 lanes that does not fold.  A
        # pool leaf's own default may differ from its lane's: on a TPU
        # a [slots, 1, 3, width] convolution tail rests (1, 2, 0, 3)
        # while its lane [1, 3, width] rests row-major.
        shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                (self.n_slots,) + l.shape, l.dtype), template_cache)
        fmt = self._pool_formats(shapes)
        self._pin_is_default = self._pin_is_default and all(
            tuple(l.format.layout.major_to_minor) == tuple(range(l.ndim))
            for l in jax.tree.leaves(template_cache)
            if isinstance(l, jax.Array)) and all(
            _rests_as_pinned(l, f) for l, f in zip(
                jax.tree.leaves(shapes), jax.tree.leaves(fmt)))
        with self._exact(), self._compiling(True):
            stacked = jax.jit(
                lambda: jax.tree.map(
                    lambda l: jnp.zeros(l.shape, l.dtype), shapes),
                out_shardings=fmt)()
        self._rest_bytes[draft] = sum(
            leaf.on_device_size_in_bytes()
            for leaf in jax.tree.leaves(stacked))
        return stacked, fmt

    def _ensure_stacked(self, template_cache) -> None:
        """Allocate the stacked pool lazily from the FIRST prefilled
        cache's tree (guarantees the template matches what prefill
        actually produces — int8 scale leaves, ring position tables,
        scan-stacked layers all included)."""
        if self._stacked is None:
            self._stacked, self._cache_sh = \
                self._alloc_stacked(template_cache)
            self.plane_reads.learn(template_cache)

    def _ensure_draft_stacked(self, template_cache) -> None:
        if self._draft_stacked is None:
            self._draft_stacked, self._draft_cache_sh = \
                self._alloc_stacked(template_cache, draft=True)

    def insert(self, slot: int, cache, first_token: int,
               position: int, *, base_key=None, next_index: int = 1,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 0.0, draft_cache=None,
               spec_k: int = 0) -> None:
        """Admit a prefilled request into ``slot`` at a step boundary:
        write its B=1 cache into the pool and arm the slot's decode
        state (``SlotState.arm``; ``next_index`` is 1 for a fresh
        stream, because token 0 was sampled from the prefill logits
        at admission).

        Speculative streams pass ``draft_cache`` (the DRAFT model's
        prefill of the same prompt) and ``spec_k`` > 0; the spec step
        program drafts/verifies/commits up to ``spec_k`` tokens per
        round for this slot.

        The pool is DONATED to the insertion program, which writes
        the one lane in place and returns the same buffers: the tree
        ``_stacked`` named before the call is deleted by it."""
        self._ensure_stacked(cache)
        with self._exact():
            self._stacked = self._insert_into(
                self._stacked, cache, slot, False)
            if draft_cache is not None:
                self._ensure_draft_stacked(draft_cache)
                self._draft_stacked = self._insert_into(
                    self._draft_stacked, draft_cache, slot, True)
        self.state.arm(slot, first_token, position, base_key,
                       next_index, temperature, top_k, top_p, spec_k)

    def _insert_into(self, stacked, one, slot: int, draft: bool):
        """``stacked`` with the B=1 cache ``one`` written into
        ``slot``: the jitted slot insert for the target (or draft)
        pool.  One program per pool, the pool DONATED and pinned to
        its formats on the way in and out: the write lands in place,
        one lane, and the pool stays committed to its layout and
        (meshed) sharding — an XLA-chosen output sharding drifting to
        replicated would force a reshard on every subsequent step."""
        import jax

        taken = jax.tree.leaves(stacked)[0]
        fn = self._insert_fns.get(draft)
        with self._compiling(fn is None):
            stacked = (fn or self._build_insert(draft))(
                stacked, one, slot)
        self._count_dispatch(taken)
        return stacked

    def _build_insert(self, draft: bool):
        import jax

        if self.sentinel is not None:
            self.sentinel.miss("slot_insert",
                               "draft" if draft else "target")

        def _insert(stacked, one, idx):
            with scope("ptpu_kv_write"):
                return jax.tree.map(
                    lambda s, n: jax.lax.dynamic_update_index_in_dim(
                        s, n.astype(s.dtype), idx, 0), stacked, one)

        sh = self._draft_cache_sh if draft else self._cache_sh
        fn = jax.jit(_insert, in_shardings=(sh, None, None),
                     out_shardings=sh, donate_argnums=(0,))
        self._insert_fns[draft] = fn
        return fn

    def _build_step(self, window: int, sampled: bool):
        from ..models.generate import jit_over

        model = self.model
        # steps, then the slots' operands (``_program``'s leading one)
        packing = self._packing("sampled" if sampled else "plain",
                                (np.int32(window),))

        def program(variables, stacked, words, fed):
            # The weights are an ARGUMENT (jit_over), not a closure.
            return build_step_body(model, variables, window, sampled)(
                stacked, *packing.unpack(words, (fed,)))

        # The pool (argument 1: jit_over binds the weights as 0) is
        # DONATED and pinned to its formats in and out.  Meshed, the
        # weights keep the shardings they were placed with, the
        # packed host operands and ``fed`` commit replicated, and
        # token outputs gather back replicated.
        rep, w_sh = None, None
        if self.mesh is not None:
            rep = self.mesh.replicated
            w_sh = self.mesh.shardings_of(self.variables)
        # The host outputs rest where ``fed`` does, meshed or not
        # (``_fed_sharding``: one signature a program).
        return jit_over(
            self.variables, program, donate_argnums=(1,),
            in_shardings=(w_sh, self._cache_sh, rep, rep),
            out_shardings=(self._fed_sharding(),) * 2
            + (self._cache_sh,))

    def _program(self, window: int, sampled: bool,
                 cap: Optional[int], K: int):
        """What this manager contributes to a launch (``_launch``).
        The plain and sampled programs are built for a CAPACITY
        (``cap``, the widest window the caller will ever ask for) and
        take ``window`` as an operand, so one program a variant serves
        every window; without ``cap`` the capacity is this call's
        window.  One speculative program per (window, K)."""
        if K:
            if self._stacked is None or self._draft_stacked is None:
                raise RuntimeError("step_spec() before a speculative "
                                   "insert()")
            return ((window, "spec", K),
                    lambda: self._build_spec_step(window, K),
                    ("_stacked", "_draft_stacked"))
        if self._stacked is None:
            raise RuntimeError("step() before any insert()")
        cap = max(cap or window, window)
        return ((cap, sampled), lambda: self._build_step(cap, sampled),
                ("_stacked",), (np.int32(window),))

    # -- speculative step ------------------------------------------------

    def _build_spec_step(self, window: int, K: int):
        """One spec program per (window, K): ``window`` speculative
        rounds fused into a scan, each round drafting ``K`` proposals
        per slot from the stacked draft cache, verifying them with
        one K+1-wide target forward per slot, and committing a
        per-slot variable prefix via the shared per-row kernels
        (models/generate._spec_draft_row / _spec_verify_row — the
        exact math of ``generate_speculative``'s seed mode).  After
        the commit both caches REWIND to the accepted position
        (``_rollback_cache`` per slot); the rewound entries are
        overwritten by the next round's chunk before any query can
        admit them (absolute-position masking, models/kv_cache.py).

        Slots with ``spec_k == 0`` (greedy/sampled co-tenants, idle
        slots) commit exactly ONE token per round, drawn from the
        verify chunk's first logits row through the shared positional
        sampler — the same token the plain step programs produce —
        and rewind to position + 1."""
        from ..models.generate import jit_over

        model, draft = self.model, self.draft_model
        weights = (self.variables, self.draft_variables)
        packing = self._packing("spec")

        def program(weights, t_stacked, d_stacked, words):
            return build_spec_step_body(
                model, weights[0], draft, weights[1], window, K)(
                t_stacked, d_stacked, *packing.unpack(words, ()))

        # Both pools (arguments 1 and 2) donated and pinned, as in
        # the plain step.
        rep, w_sh = None, None
        if self.mesh is not None:
            rep = self.mesh.replicated
            w_sh = self.mesh.shardings_of(weights)
        pools = (self._cache_sh, self._draft_cache_sh)
        return jit_over(
            weights, program, donate_argnums=(1, 2),
            in_shardings=(w_sh,) + pools + (rep,),
            out_shardings=(rep, rep, rep) + pools)
