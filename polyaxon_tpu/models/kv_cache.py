"""Decode-time KV cache shared by the zoo's decoders.

One helper owns the flax cache-variable dance for GPT-2, MoE-GPT,
Llama (its RoPE rotation happens inside the append via ``rotate``),
and T5's decoder self-attention.  Two storage disciplines:

- :func:`append_kv_cache` — the standard O(max_position) cache, with
  optional int8 storage (``quantize=True``).
- :func:`append_ring_kv_cache` — O(window) position-keyed ring for
  sliding-window models; sessions stream past max_position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.quant import symmetric_int8


def _quantize_chunk(x):
    """Per-(token, head) symmetric int8 over the feature axis:
    [B, S, H, D] -> (int8 [B, S, H, D], scale [B, S, H, 1])."""
    return symmetric_int8(x, axes=(-1,))


# -- one layer's view of a cache variable -----------------------------------
#
# ``layer`` is None where the variable is this layer's own (an
# unrolled stack, or a scan that holds the cache as its xs/ys: T5,
# MoE-GPT, and every cache at its creation), and the layer's index
# (traced) where scan_stack CARRIES the stacked cache through the
# layer loop: the variable then holds every layer's plane on a leading
# axis, and a layer touches only what it changes.


def _plane(var, layer):
    """This layer's plane of ``var``, read once."""
    if layer is None:
        return var.value
    return jax.lax.dynamic_index_in_dim(var.value, layer, 0,
                                        keepdims=False)


def _store(var, layer, value) -> None:
    """Replace this layer's whole plane (the small leaves: an index,
    a ring's position table)."""
    var.value = value if layer is None \
        else var.value.at[layer].set(value)


def _put_rows(var, layer, rows, start) -> None:
    """Write ``rows`` ([B, S, H, D]) at positions ``[start, start+S)``
    of this layer's plane — on a carried stack an update of S rows in
    place, not of the plane."""
    if layer is None:
        var.value = jax.lax.dynamic_update_slice(
            var.value, rows, (0, start, 0, 0))
    else:
        var.value = jax.lax.dynamic_update_slice(
            var.value, rows[None], (layer, 0, start, 0, 0))


def _scatter_rows(var, layer, rows, slots) -> None:
    """Write ``rows`` ([B, n, H, D]) at the ring slots ``slots`` [n]
    (distinct) of this layer's plane."""
    if layer is None:
        var.value = var.value.at[:, slots].set(rows)
    else:
        # Two advanced indices around a slice: their axis leads.
        var.value = var.value.at[layer, :, slots].set(
            jnp.moveaxis(rows, 1, 0))


def append_ring_kv_cache(mod, k, v, window: int, rotate=None,
                         quantize: bool = False, slack: int = 0,
                         layer=None):
    """Sliding-window decode with an O(window) RING cache — the
    long-context serving path for Mistral-style models.

    The plain cache allocates ``max_position`` slots and refuses to
    decode past them; a sliding-window model only ever ATTENDS to the
    last ``window+1`` positions, so the ring stores exactly a window
    (capacity ``window + S``, S = the trace-time chunk length) keyed by
    ``position % capacity``, and sessions stream indefinitely — RoPE
    needs no table, so positions keep growing past ``max_position``.

    Per append: (1) read the old ring (its slot order is scrambled —
    attention is order-agnostic given the mask), (2) rotate/quantize
    the incoming chunk at its absolute positions, (3) hand attention
    ``concat(old_ring, chunk)`` with validity derived from ABSOLUTE
    positions (``q_pos - window <= k_pos <= q_pos``, unwritten slots
    hold position -1), and (4) scatter the chunk's last
    ``min(S, capacity)`` rows into the ring (earlier rows of a long
    chunk are already out of every future window).  Stale slots from a
    speculative rollback hold positions ahead of the rewound index, so
    the same position test masks them until they're overwritten —
    speculative decoding composes with no extra bookkeeping.

    ``slack``: extra capacity beyond ``window + S``.  Plain decoding
    needs none; SPECULATIVE decoding does: a k+1-wide verify chunk's
    scatter destroys the K/V living ``capacity`` positions back, and
    after a partial-acceptance rollback those positions can still be
    inside the window (destroyed max = idx+k-cap-... safe iff
    ``slack >= k-1`` — generate_speculative enforces it).

    ``layer``: as for :func:`append_kv_cache` — the ring's rows are
    scattered into the carried stack, its plane read once.

    Returns ``(k_full, v_full, mask, positions)`` shaped like
    :func:`append_kv_cache` but with key axis ``capacity + S`` — or
    ``capacity`` alone where the ring has room for the chunk (the
    write-first branch).
    """
    b, s, h, d = k.shape
    idx = mod.variable("cache", "cache_index",
                       lambda: jnp.array(0, jnp.int32))
    idx0 = _plane(idx, layer)
    pos_q = idx0 + jnp.arange(s)
    if rotate is not None:
        k = rotate(pos_q, k)
    store_dtype = jnp.int8 if quantize else k.dtype
    # Capacity is fixed by whoever CREATED the variables (generate's
    # init_cache traces a 1-token step -> window+1 slots); later
    # chunked appends must use the existing shape, not their own chunk
    # length, or the slot arithmetic would scatter out of bounds.
    ck = mod.variable("cache", "cached_key", jnp.zeros,
                      (b, window + s + slack, h, d), store_dtype)
    cap = ck.value.shape[-3]
    cv = mod.variable("cache", "cached_value", jnp.zeros,
                      (b, cap, h, d), store_dtype)
    # -1 marks never-written slots (masked off by the position test).
    cpos = mod.variable("cache", "cached_pos",
                        lambda: jnp.full((cap,), -1, jnp.int32))
    cpos0 = _plane(cpos, layer)
    if quantize:
        kq, k_scale = _quantize_chunk(k)
        vq, v_scale = _quantize_chunk(v)
        cks = mod.variable("cache", "cached_key_scale", jnp.zeros,
                           (b, cap, h, 1), jnp.bfloat16)
        cvs = mod.variable("cache", "cached_value_scale", jnp.zeros,
                           (b, cap, h, 1), jnp.bfloat16)
    else:
        kq, k_scale, vq, v_scale = k, None, v, None

    def ring():
        """The ring's keys and values as they lie, dequantized."""
        if not quantize:
            return _plane(ck, layer), _plane(cv, layer)
        return (_plane(ck, layer).astype(k.dtype)
                * _plane(cks, layer).astype(k.dtype),
                _plane(cv, layer).astype(k.dtype)
                * _plane(cvs, layer).astype(k.dtype))

    def written(pos):
        """Which ring slots hold a position that was really written
        there: position p lives in slot ``p % cap``.  A slot that was
        never written holds -1 — or 0, in a cache made all zeros
        (generate.init_cache), which only slot 0 may claim."""
        return (pos >= 0) & (pos % cap == jnp.arange(cap))

    def scatter(first: int, slots):
        _scatter_rows(ck, layer, kq[:, first:], slots)
        _scatter_rows(cv, layer, vq[:, first:], slots)
        if quantize:
            _scatter_rows(cks, layer, k_scale[:, first:], slots)
            _scatter_rows(cvs, layer, v_scale[:, first:], slots)

    if s <= cap - window:
        # The ring has ROOM for the chunk: write its rows first, then
        # hand the attention the ring itself — no concat(old_ring,
        # chunk), which copied every window layer's ring once a decode
        # step.  The slots the chunk takes held positions [idx - cap,
        # idx + S - cap); its first query needs positions >= idx -
        # window, so nothing a query of this chunk reads is lost iff
        # S <= cap - window.  A decode step (S = 1) always has room; a
        # prefill chunk has it where the ring was made with slack >=
        # its length - 1.  Validity stays by ABSOLUTE position: a
        # stale slot from a speculative rollback either is overwritten
        # by this chunk (same position, same slot) or lies ahead of
        # every query here.
        scatter(0, pos_q % cap)
        # The position table without a scatter: slot j takes the one
        # position of the chunk that is j mod cap, if there is one.
        # (``cpos0.at[slots].set(pos_q)`` on a cache made inside the
        # program folds to a scatter whose indices and updates are one
        # iota, and the v5e compiler aborts on it.)
        ahead = (jnp.arange(cap) - idx0) % cap
        pos_k = jnp.where(ahead < s, idx0 + ahead, cpos0)
        _store(cpos, layer, pos_k)
        _store(idx, layer, idx0 + s)
        k_full, v_full = ring()
        valid = (pos_k[None, :] <= pos_q[:, None]) & \
            (pos_k[None, :] >= pos_q[:, None] - window) & \
            written(pos_k)[None, :]
        return k_full, v_full, valid[None, None], pos_q

    k_old, v_old = ring()
    k_full = jnp.concatenate([k_old, k], axis=1)
    v_full = jnp.concatenate([v_old, v], axis=1)
    pos_k = jnp.concatenate([cpos0, pos_q])           # [cap + S]
    valid = (pos_k[None, :] <= pos_q[:, None]) & \
        (pos_k[None, :] >= pos_q[:, None] - window)
    # Ring entries must be strictly OLDER than this chunk's first
    # position: after a speculative rollback the ring still holds
    # REJECTED K/V at positions the chunk is now re-committing, and
    # the position test alone would admit both copies.  The chunk
    # carries its own entries for [idx, idx+S).
    ring_older = jnp.concatenate(
        [(cpos0 < idx0) & written(cpos0), jnp.ones((s,), bool)])
    valid = valid & ring_older[None, :]

    # Scatter the chunk tail into the ring.  keep = min(S, cap) rows:
    # with keep <= cap the target slots (consecutive positions mod
    # cap) are distinct, so the scatter has no duplicate-index
    # ambiguity.
    keep = min(s, cap)
    tail_pos = pos_q[s - keep:]
    slots = tail_pos % cap
    scatter(s - keep, slots)
    _store(cpos, layer, cpos0.at[slots].set(tail_pos))
    _store(idx, layer, idx0 + s)
    return k_full, v_full, valid[None, None], pos_q


def append_kv_cache(mod, k, v, max_position: int, window=None,
                    rotate=None, quantize: bool = False, layer=None):
    """Append this step's k/v ([B, S, H, D]) to ``mod``'s decode cache.

    Works for single-token steps AND chunked prefill (S > 1 — the
    whole prompt in one forward): new token i sits at absolute position
    ``idx + i``, so the returned mask ([1, 1, S, max_position]) admits
    key j iff ``j <= idx + i`` (causal over the appended chunk plus the
    previously filled prefix), clipped to ``window`` when given.

    ``rotate``: optional ``fn(positions, k) -> k`` applied BEFORE the
    append (RoPE models must store rotated keys); the returned
    ``positions`` lets the caller rotate q to match.  (One helper owns
    the variables because flax forbids re-declaring them in the same
    apply.)

    Speculative ROLLBACK contract (shared with the ring cache, and
    relied on by the serving engine's per-slot rewinds): resetting
    ``cache_index`` to a smaller value leaves stale K/V entries past
    it, but they are masked BY ABSOLUTE POSITION, never trusted —
    entry slot ``j`` is admissible only to queries at positions
    ``>= j``, appends always write ``[idx, idx + S)`` BEFORE the
    chunk's queries read, and post-rollback appends are contiguous
    from the rewound index, so every stale slot a query could admit
    has already been overwritten by the fresh chunk that contains
    that query.  Holds for any mix of chunk widths after the rewind
    (a k+1-wide verify, a 1-wide decode step, a chunked prefill
    extension) — pinned in
    tests/test_spec_engine.py::TestRollbackMasking for the plain and
    int8 disciplines.

    ``quantize``: store the cache as int8 with per-(token, head)
    bf16 scales over the feature axis.  At long context the KV read is
    the decode bandwidth bottleneck (kv_bytes/token in the decode
    bench); int8 halves it.  The dequantize on read sits in the decode
    step so XLA fuses the convert into the attention matmuls — HBM
    traffic stays int8, consumers still see k.dtype.  Rotated (RoPE)
    keys quantize AFTER rotation, so the stored rounding is the only
    error (<= scale/2 per element).

    CAPACITY contract: ``max_position`` is the CREATION width — an
    apply that receives an existing cache keeps that cache's own key
    width (``cached_key.shape[1]``) for the append and the validity
    mask.  This is what makes the PAGED serving path work: the slot
    engine materializes a per-request view of only the pages the
    request owns (a position-contiguous cache narrower than
    ``max_position`` — see :func:`gather_pages`), and the model
    attends over exactly that width.  All positions stay ABSOLUTE, so
    masking, RoPE, and the speculative rollback contract below are
    unchanged at any width.

    IN PLACE contract (``layer``): under ``scan_stack`` a decode
    apply over an existing cache hands every layer the WHOLE stacked
    cache — each variable ``[num_layers, ...]``, the layer scan's
    carry — and ``layer``, its index.  The append then
    writes only the S new rows (and scales) of each sequence at
    ``[layer, :, idx:idx+S]`` and bumps ``cache_index[layer]``: an
    update XLA performs in place on a loop carry, and in place on the
    caller's buffer when the program donates it (serving/slots.py
    does).  The layer's keys and values are read once, as the
    attention's operand.  Nothing else of the stack is read or
    written; no layer is sliced out and written back.  ``layer=None``
    (an unrolled stack, T5 and MoE-GPT's own scans, and the apply
    that CREATES the variables) is the same arithmetic on a variable
    that is the layer's own.

    Creates ``cached_key``/``cached_value``/``cache_index`` (plus
    ``cached_key_scale``/``cached_value_scale`` when quantized)
    variables in the "cache" collection on ``mod``; returns
    ``(k_full, v_full, mask, positions)``.
    """
    b, s, h, d = k.shape
    idx = mod.variable("cache", "cache_index",
                       lambda: jnp.array(0, jnp.int32))
    idx0 = _plane(idx, layer)
    pos_q = idx0 + jnp.arange(s)  # absolute positions of new rows
    if rotate is not None:
        k = rotate(pos_q, k)
    if quantize:
        store_dtype, out_dtype = jnp.int8, k.dtype
        kq, k_scale = _quantize_chunk(k)
        vq, v_scale = _quantize_chunk(v)
    else:
        store_dtype, out_dtype = k.dtype, k.dtype
        kq, k_scale, vq, v_scale = k, None, v, None
    ck = mod.variable("cache", "cached_key", jnp.zeros,
                      (b, max_position, h, d), store_dtype)
    # An existing (possibly paged-view) cache keeps ITS width; only a
    # fresh creation uses max_position.
    cap = ck.value.shape[-3]
    cv = mod.variable("cache", "cached_value", jnp.zeros,
                      (b, cap, h, d), store_dtype)
    _put_rows(ck, layer, kq, idx0)
    _put_rows(cv, layer, vq, idx0)
    if quantize:
        cks = mod.variable("cache", "cached_key_scale", jnp.zeros,
                           (b, cap, h, 1), jnp.bfloat16)
        cvs = mod.variable("cache", "cached_value_scale", jnp.zeros,
                           (b, cap, h, 1), jnp.bfloat16)
        _put_rows(cks, layer, k_scale, idx0)
        _put_rows(cvs, layer, v_scale, idx0)
        # Unwritten positions hold scale 0 -> dequantize to 0, exactly
        # like the unquantized zero-init cache (masked off anyway).
        k_full = _plane(ck, layer).astype(out_dtype) \
            * _plane(cks, layer).astype(out_dtype)
        v_full = _plane(cv, layer).astype(out_dtype) \
            * _plane(cvs, layer).astype(out_dtype)
    else:
        k_full, v_full = _plane(ck, layer), _plane(cv, layer)
    _store(idx, layer, idx0 + s)
    keys = jnp.arange(cap)
    valid = keys[None, :] <= pos_q[:, None]  # [S, cap]
    if window is not None:
        valid &= keys[None, :] >= pos_q[:, None] - window
    return k_full, v_full, valid[None, None], pos_q


# -- paged storage helpers --------------------------------------------------
#
# The serving engine's PAGED KV pool (serving/paged.py) stores every
# position-indexed cache leaf as fixed-size PAGES of ``page_tokens``
# positions each — pool leaf shape ``lead + (n_pages, page_tokens) +
# rest`` where the original leaf was ``lead + (positions,) + rest`` —
# and per-request page tables map logical position ranges to pool
# pages.  The helpers below are the two data movements that makes
# possible; both keep positions CONTIGUOUS inside the materialized
# view (page i of a table covers absolute positions [i*pt, (i+1)*pt)),
# so everything above — causal masking, RoPE, chunked prefill, the
# speculative rollback contract — sees an ordinary (narrower) cache
# and needs no paged-specific reasoning.


def paged_pool_shape(leaf_shape, pos_axis: int, n_pages: int,
                     page_tokens: int):
    """Pool-leaf shape for a cache leaf: the position axis splits into
    ``(n_pages, page_tokens)``."""
    return (tuple(leaf_shape[:pos_axis]) + (n_pages, page_tokens)
            + tuple(leaf_shape[pos_axis + 1:]))


def gather_pages(pool_leaf, table, pos_axis: int):
    """Materialize one request's position-contiguous view from the
    pool: ``table`` [P] (int32 page ids) -> view with position width
    ``P * page_tokens`` at ``pos_axis``.  A pure gather — the view is
    a copy, so the model's functional cache update never aliases the
    shared pool."""
    v = jnp.take(pool_leaf, table, axis=pos_axis)
    shape = v.shape
    return v.reshape(shape[:pos_axis]
                     + (shape[pos_axis] * shape[pos_axis + 1],)
                     + shape[pos_axis + 2:])


def scatter_pages(pool_leaf, pages, targets, pos_axis: int):
    """Write ``pages`` (``lead + (n, page_tokens) + rest``) into the
    pool at page ids ``targets`` [n].  Callers guarantee distinct
    WRITABLE targets (copy-on-write: a shared page is never a scatter
    target — redirect to a scratch/trash page instead); duplicate
    targets are only ever garbage pages whose content is masked by
    absolute position before any query can admit it."""
    idx = (slice(None),) * pos_axis + (targets,)
    return pool_leaf.at[idx].set(pages.astype(pool_leaf.dtype))
