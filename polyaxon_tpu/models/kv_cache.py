"""Decode-time KV cache shared by the zoo's decoders.

One helper owns the flax cache-variable dance for GPT-2, MoE-GPT,
Llama (its RoPE rotation happens inside the append via ``rotate``),
and T5's decoder self-attention.  Two storage disciplines:

- :func:`append_kv_cache` — the standard O(max_position) cache, with
  optional int8 storage (``quantize=True``).
- :func:`append_ring_kv_cache` — O(window) position-keyed ring for
  sliding-window models; sessions stream past max_position.

:func:`attend_kv_cache` is the append AND the attention over what it
wrote: where the caller knows how far the cache is written
(:func:`read_extent`) the attention reads the planes only that far.

:func:`attend_latent_cache` is the same for a layer that caches ONE
latent row a position (models/deepseek_v2.py) where the others cache a
key and a value a head: one plane ``[B, positions, width]``, no heads
axis, no value plane.

WHAT IS WRITTEN, WHERE AND WHEN.  An append writes its new rows into
the plane and then reads it — except a call of ONE row a sequence (a
decode step) into a stack that the layer loop carries
(:func:`defers`): it writes nothing inside the loop, hands the
attention the plane with the new row laid over its position, and
leaves the row for ``scan_stack.LayerScanBody.run`` to write once for
all layers after the loop (:func:`write_deferred`).  The cache holds
the row when the apply returns either way; what differs is how many
dependent writes a slot pool's step runs (PERF.md section 6, PR 36).

HOW A PLANE RESTS.  A K/V plane is ``[B, positions, heads, head_dim]``
— or, where the heads are narrower than the TPU's 128 lanes and fill
whole tiles together (:func:`lane_fold`), ``[B, positions, heads *
head_dim]``: the same rows with the heads folded into the lanes, handed
to the attention as ``[B, n, chunks, lanes a chunk]`` (:func:`lane_chunks`)
and read in that form where it is bound by the read
(``ops/attention.folded_attention``).  Who names an axis of a plane asks
:func:`row_axes`.

A cache tree may also hold a recurrent layer's state, which has no
position axis and goes through none of the helpers above
(models/jamba.py declares it): :func:`leaf_kinds` tells the four
kinds of leaf apart — ``window``, ``full``, ``state``, ``latent`` —
for everyone who has to.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.quant import symmetric_int8
from ..spans import scope


def _quantize_chunk(x):
    """Per-(token, head) symmetric int8 over the feature axis:
    [B, S, H, D] -> (int8 [B, S, H, D], scale [B, S, H, 1])."""
    return symmetric_int8(x, axes=(-1,))


# -- the lanes a plane rests in ---------------------------------------------
#
# The TPU tiles an array's two minor dimensions 128 lanes wide.  A
# plane ``[B, positions, heads, 64]`` rests with every row of a head in
# a tile of its own: half of every tile is padding, and every read of
# the plane moves it — gpt2-medium's pool rested in 201 MB a slot for
# 100.7 MB of rows, and a decode step's attention read the padding with
# the rows (PERF.md section 6, PR 38).  Folded, ``[B, positions, heads
# * head_dim]``, head h in lanes ``[h * head_dim, (h + 1) * head_dim)``,
# the rows fill the tiles.  Decided by the shape alone, where the plane
# is made; an existing cache keeps the form it has.

LANES = 128


def lane_fold(heads: int, head_dim: int) -> bool:
    """Whether a K/V plane of ``heads`` heads of ``head_dim`` rests
    folded: the head is narrower than a tile's lanes and the heads
    fill whole tiles together."""
    return head_dim < LANES and (heads * head_dim) % LANES == 0


def lane_chunks(heads: int) -> int:
    """How many chunks of whole heads a folded plane's rows are handed
    to the attention in (``[B, n, chunks, lanes a chunk]``,
    ``ops/attention.folded_attention``): the serving-exact mesh's
    ``tp``, which cuts the lanes so (serving/meshed.py), or 1.  Each
    device then scores its own heads against its own lanes."""
    from ..parallel.constraints import current_exact_mesh

    mesh = current_exact_mesh()
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    return tp if heads % tp == 0 else 1


def _spread(scale, head_dim: int):
    """An int8 plane's scales ``[..., heads, 1]`` laid over the folded
    lanes their rows rest in: ``[..., heads * head_dim]``."""
    lanes = jnp.broadcast_to(scale, scale.shape[:-1] + (head_dim,))
    return lanes.reshape(scale.shape[:-2] + (-1,))


# -- one layer's view of a cache variable -----------------------------------
#
# ``layer`` is None where the variable is this layer's own (an
# unrolled stack, or a scan that holds the cache as its xs/ys: T5,
# and every cache at its creation), and the layer's index
# (traced) where scan_stack CARRIES the stacked cache through the
# layer loop: the variable then holds every layer's plane on a leading
# axis, and a layer touches only what it changes.


def _plane(var, layer, rows=None, tail: int = 2, pin: bool = False):
    """This layer's plane of ``var``, read once — or its first
    ``rows`` positions (static), sliced out of the variable as it
    lies: on a carried stack no whole plane is made on the way.
    ``tail``: the axes behind the position axis (heads and features of
    a K or V plane; 1 for a latent plane's width or a folded plane's
    lanes).  ``pin``: the carried stack is read in the layout it rests
    in, row-major (a folded plane's: see :func:`_append`)."""
    stack = var.value
    if pin and layer is not None:
        stack = with_layout_constraint(
            stack, Layout(major_to_minor=tuple(range(stack.ndim))))
    axis = stack.ndim - 1 - tail
    if layer is None:
        return stack if rows is None \
            else jax.lax.slice_in_dim(stack, 0, rows, axis=axis)
    if rows is None:
        return jax.lax.dynamic_index_in_dim(stack, layer, 0,
                                            keepdims=False)
    sizes = (1,) + stack.shape[1:axis] + (rows,) + stack.shape[axis + 1:]
    return jax.lax.dynamic_slice(
        stack, (layer,) + (0,) * (stack.ndim - 1), sizes)[0]


def _store(var, layer, value) -> None:
    """Replace this layer's whole plane (the small leaves: an index,
    a ring's position table)."""
    with scope("ptpu_kv_write"):
        var.value = value if layer is None \
            else var.value.at[layer].set(value)


def _put_rows(var, layer, rows, start) -> None:
    """Write ``rows`` ([B, S, H, D], or [B, S, width] of a latent or
    a folded plane) at positions ``[start, start+S)`` of this layer's plane —
    on a carried stack an update of S rows in place, not of the
    plane.  Under a pool's vmap ``start`` differs by lane and the
    update is a scatter, a dependent write a lane: a decode step on a
    carried stack does not come here (``defers``)."""
    behind = (0,) * (rows.ndim - 2)
    with scope("ptpu_kv_write"):
        if layer is None:
            var.value = jax.lax.dynamic_update_slice(
                var.value, rows, (0, start) + behind)
        else:
            var.value = jax.lax.dynamic_update_slice(
                var.value, rows[None], (layer, 0, start) + behind)


def _scatter_rows(var, layer, rows, slots) -> None:
    """Write ``rows`` ([B, n, H, D]) at the ring slots ``slots`` [n]
    (distinct) of this layer's plane."""
    with scope("ptpu_kv_write"):
        if layer is None:
            var.value = var.value.at[:, slots].set(rows)
        else:
            # Two advanced indices around a slice: their axis leads.
            var.value = var.value.at[layer, :, slots].set(
                jnp.moveaxis(rows, 1, 0))


# -- one write a step ----------------------------------------------------------
#
# Under a slot pool's vmap every lane stands at its own index, so a
# row written at ``[layer, :, index]`` is a scatter of one index a
# lane, and the TPU runs a scatter as a loop of dependent writes: a
# trip a lane, in every layer and leaf.  gpt2-medium's 24 layers x 2
# leaves x 24 slots were 1 152 such writes a decode step, 3 of its 7
# ms (PERF.md section 6, PR 36).  So a call that appends ONE row a
# sequence to a carried stack (``defers``) does not write it inside the
# layer loop: the attention is handed the plane with the new row laid
# OVER its position, in value space (a select that fuses into the
# attention's read of its operand; what the attention sees is bit for
# bit what write-then-read gave it), the rows of all layers leave the
# loop beside it (``deferred_rows``: the scan's ys, ``[layers, B, 1,
# H, D]`` a leaf) and ``write_deferred`` puts each leaf's rows into the
# stack ONCE, after the last layer: one scatter a leaf and step.

_DEFER = threading.local()


def defers(stacked: bool, rows: int) -> bool:
    """Whether a call that appends ``rows`` rows (static) a sequence
    writes them after the layer loop instead of inside it: one row,
    into a ``stacked`` (carried) cache variable.  A call of more rows
    (a prefill piece: one sequence, one in-place update and no loop; a
    speculative verify) and a variable that is its layer's own (no
    loop to defer past) write, then read.  One rule for the program
    and for the host's count of its writes (``row_writes_a_step``)."""
    return stacked and rows == 1


@contextlib.contextmanager
def deferred_rows():
    """While TRACING one layer of a carried stack inside this scope,
    the appends that :func:`defers` names leave their new rows in the
    dict it yields — ``{module path: {"start": index, leaf name:
    rows}}`` — instead of in the cache.  ``scan_stack.LayerScanBody.
    carried`` opens it around a layer and returns the dict as the
    layer scan's ys; :func:`write_deferred` takes the stacked dict."""
    was = getattr(_DEFER, "rows", None)
    _DEFER.rows = rows = {}
    try:
        yield rows
    finally:
        _DEFER.rows = was


def write_deferred(stack, deferred) -> None:
    """After the layer loop: write what its layers left in
    :func:`deferred_rows` (stacked by the scan: rows ``[layers, B, 1,
    ...]`` a leaf) into the cache variables under ``stack``, the module
    the scan was lifted from — each leaf's rows of ALL layers in one
    in-place update at ``[:, :, start]``.  ``start`` is the first
    layer's index: the layers of a stack advance together."""
    for path, rows in deferred.items():
        below = path[len(stack.path):]
        held = stack.variables["cache"]
        for name in below:
            held = held[name]
        start = rows["start"][0]
        with scope("ptpu_kv_write"):
            written = {
                leaf: jax.lax.dynamic_update_slice(
                    held[leaf], new,
                    (0, 0, start) + (0,) * (new.ndim - 3))
                for leaf, new in rows.items() if leaf != "start"}
        for name in reversed(below):
            written = {name: written}
        for name, value in written.items():
            stack.put_variable("cache", name, value)


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
        """The ring's keys and values as they lie, dequantized: the
        first of the attention's read."""
        with scope("ptpu_attend"):
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


# -- bounded reads ----------------------------------------------------------
#
# A key past the furthest position any query of a call may see is
# masked, and a masked key weighs exp(-1e30 - max) = 0.0 exactly: the
# attention over the first n rows of a plane, for any n past that
# position, is the same arithmetic as over all of them.  So where the
# caller says how far the cache is written (``read_extent``) the
# attention is handed a PREFIX of the plane, of one of a few static
# widths (``prefix_widths``), chosen at run time by ONE conditional
# (``jax.lax.switch``): one compiled program whatever the extent, each
# branch slicing its rows out of the variable as it lies.
#
# The extent is one UNBATCHED scalar a call.  Under ``jax.vmap`` over a
# pool's slots a per-slot bound would turn the conditional into a
# select that runs every branch, and a per-slot slice into a gather:
# the pool's step computes one extent for all its slots, outside the
# vmap (serving/slots.build_step_body).  A pool with one long resident
# reads every lane to that resident's length.
#
# Under that SHARED extent only a plane of a carried stack is narrowed
# (``narrows``): its read already was a slice of the stack, cut inside
# the fusion that consumes it, and a few rows less change nothing for
# the compiler.  A variable that is the layer's own (an unrolled stack)
# was read whole, and slicing it is a new shape of program: ahead of a
# grouped matmul the v5e compiler then converts the layout of the
# WHOLE ``[slots, 1, 8192, 8, 128]`` plane in every branch (deviceless
# compile, PERF.md section 6, PR 30) — more bytes than the slice
# saves.  A prefill chunk narrows both kinds: one sequence's plane is
# small beside the scores over it.
#
# What a width costs: every branch is traced and compiled with its
# program, in every process for a pool's programs (never from the
# persistent cache: config.fresh_compile), and traced again even where
# the executable is read from it.  On the v5e's host a branch of the
# gpt2-medium programs cost 0.2-0.3 s a program (PERF.md section 6,
# PR 30).  So a prompt prefilled from position 0 names its extent as a
# Python int and gets ONE static width, no conditional.

# A plane is read to an eighth, a quarter, a half or all of its
# capacity.  Few widths, and powers of two: on the chip four of them
# served gpt2-medium's pool FASTER than eight even steps did (384 rows
# cost 1.66 x what 256 did, and every branch weighs on the conditional),
# at half the start-up cost (PERF.md section 6, PR 30).
PREFIX_SHARES = (8, 4, 2, 1)

# ``read_extent()`` without an argument: the extent is the cache's own
# index plus the rows this call appends (one sequence, or a batch that
# shares its index: the program that extends a prefilled cache).
OWN_INDEX = object()

_READ = threading.local()


@contextlib.contextmanager
def read_extent(extent=OWN_INDEX, shared: bool = False):
    """While TRACING inside this scope, :func:`attend_kv_cache` reads a
    plane only as far as ``extent``: no smaller than one past the
    furthest position a query of the traced call sees.  A Python int
    (a prompt prefilled from position 0: its length) picks the width
    while tracing, and costs the program nothing; a traced scalar, or
    the cache's own index (no argument), picks it by a conditional at
    run time.  ``shared``: the extent is one for every lane of a vmap
    around the call (a slot pool's step: compute it OUTSIDE the vmap).
    Outside any scope the plane is read whole.  Open the scope INSIDE
    the function that gets traced (a trace cached outside it knows
    nothing of it).  Not a switch anybody sets: the callers are the
    slot pool's step body and ``generate.prefill_programs``."""
    was = getattr(_READ, "scope", None)
    _READ.scope = (extent, shared)
    try:
        yield
    finally:
        _READ.scope = was


def narrows(shared: bool, stacked: bool) -> bool:
    """Whether a call narrows its read of a plane: always under its
    own index (a prefill chunk), under a pool's ``shared`` extent only
    where the plane lies in a ``stacked`` (carried) cache variable.
    One rule for the program and for the host's count of its reads."""
    return stacked or not shared


@functools.lru_cache(maxsize=None)
def prefix_widths(cap: int) -> tuple:
    """The static widths a plane of ``cap`` rows may be read to,
    ascending, the last one ``cap`` itself."""
    return tuple(sorted({-(-cap // share) for share in PREFIX_SHARES}))


def prefix_branch(extent, cap: int):
    """Which of :func:`prefix_widths` covers rows ``[0, extent)``: the
    narrowest that does, the widest for an extent past ``cap``.  One
    function for the program (``extent`` traced) and for the host's
    count of what the program read (an int or a numpy array)."""
    edges = prefix_widths(cap)[:-1]
    if isinstance(extent, jax.Array):       # three compares, no loop
        return jnp.searchsorted(jnp.asarray(edges), extent, side="left",
                                method="compare_all")
    return np.searchsorted(edges, extent, side="left")


def prefix_width(extent, cap: int):
    """Rows of a ``cap``-row plane that a call with this extent reads
    (host arithmetic: ints or numpy arrays)."""
    return np.asarray(prefix_widths(cap))[prefix_branch(extent, cap)]


# -- the kinds of leaf ------------------------------------------------------
#
# A decode cache tree holds leaves of four kinds, and ONE function
# says which (``leaf_kinds``): what the slot pool reports by kind, what
# the bounded reads count, and what a storage discipline refuses at
# start-up all read it.
#
# - ``full``: a plane of ``max_position`` rows and what lies beside it
#   (its ``cache_index``, int8 scales).  Position-keyed: rewinding the
#   index rewinds the layer, pages cut it along its position axis, a
#   mesh shards its heads.
# - ``window``: a ring's leaves (those beside a ``cached_pos`` table).
#   Position-keyed too, by the table.
# - ``state``: a recurrent layer's state (``STATE_LEAVES``): a fixed
#   size whatever the position, NO position axis, no index.  It is the
#   whole past after exactly the tokens it has seen: it can be stored,
#   copied into a slot and carried from piece to piece, never rewound
#   to an earlier position and never cut into pages.
# - ``latent``: a latent-attention layer's plane (``cached_latent``,
#   ``[B, positions, width]``) and the index beside it: ONE row a
#   position for all the heads, no heads axis and no value plane.
#   Position-keyed as ``full`` is (it rewinds, and reads to an extent),
#   but there are no heads for a mesh to shard and the paged pool's
#   gather and scatter know leaves of K and V by name.

STATE_LEAVES = ("ssm_state", "conv_tail")
LATENT_LEAF = "cached_latent"
KINDS = ("window", "full", "state", "latent")


def leaf_kinds(cache) -> list:
    """``[(path, leaf, kind)]`` of every leaf of a cache tree (one
    sequence's, or a pool's), ``kind`` one of :data:`KINDS`."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    name = lambda path: jax.tree_util.keystr(path[-1:])  # noqa: E731
    beside = lambda leaf: {path[:-1] for path, _ in flat  # noqa: E731
                           if leaf in name(path)}
    rings, latents = beside("cached_pos"), beside(LATENT_LEAF)
    return [(path, leaf,
             "state" if any(s in name(path) for s in STATE_LEAVES)
             else "window" if path[:-1] in rings
             else "latent" if path[:-1] in latents else "full")
            for path, leaf in flat]


def cache_kinds(model) -> tuple:
    """The kinds of leaf ``model``'s decode cache holds, in the order
    of :data:`KINDS` (shapes alone: nothing is allocated).  A model
    that makes no decode cache this way holds ``full`` leaves as far
    as anyone here is concerned."""
    from .generate import init_cache

    try:
        tree = jax.eval_shape(lambda: init_cache(model, 1))
    except Exception:           # not a decoder-only zoo model
        return ("full",)
    held = {kind for _, _, kind in leaf_kinds(tree)}
    return tuple(k for k in KINDS if k in held) or ("full",)


ROW_LEAVES = ("cached_key", "cached_value", "cached_key_scale",
              "cached_value_scale", LATENT_LEAF)


def row_axes(cache) -> dict:
    """``{path: axis}``: the POSITION axis of every leaf of a cache tree
    (one sequence's, a stacked layer loop's, a pool's) that holds rows
    a position — K and V planes, folded (:func:`lane_fold`) or not,
    their int8 scales, a ring's K and V, latent planes.  It follows the
    batch axis, which follows the axes of the ``cache_index`` beside
    the leaf (one number a layer, a slot, ...).  The heads of a K or V
    plane or scale lie on the axis after it, folded into the lanes or
    not; a latent plane has none.  A leaf with no index beside it is
    left out."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    name = lambda path: jax.tree_util.keystr(path[-1:])  # noqa: E731
    lead = {path[:-1]: np.ndim(leaf) for path, leaf in flat
            if "cache_index" in name(path)}
    return {path: lead[path[:-1]] + 1 for path, _ in flat
            if path[:-1] in lead
            and any(f"'{row}'" in name(path) for row in ROW_LEAVES)}


def full_planes(cache, kind: str = None) -> dict:
    """``{(capacity, stacked): planes}`` of the full-length planes in
    ONE sequence's cache tree that are read to an extent — every
    ``cached_key`` leaf of kind ``full`` and every ``cached_latent``
    leaf, a plane a layer (``kind``: those of one kind alone);
    ``stacked`` where the leaf holds its layers on a leading axis
    (``[layers, B, positions, ...]``: the stack that decoding
    carries).  A ``state`` leaf has no rows to read to an extent and
    is passed by."""
    planes = {}
    axes = row_axes(cache)
    for path, leaf, leaf_kind in leaf_kinds(cache):
        name = jax.tree_util.keystr(path[-1:])
        if not ((leaf_kind == "full" and "cached_key'" in name)
                or (leaf_kind == "latent" and LATENT_LEAF in name)):
            continue
        if kind in (None, leaf_kind):
            lead = leaf.shape[:axes[path]]
            key = (leaf.shape[axes[path]], len(lead) > 1)
            planes[key] = planes.get(key, 0) \
                + int(np.prod(lead, dtype=np.int64))
    return planes


def state_layers(cache) -> int:
    """How many layers of ONE sequence's cache tree keep a recurrent
    state (``ssm_state`` leaves)."""
    return sum("ssm_state" in jax.tree_util.keystr(path[-1:])
               for path, _, _ in leaf_kinds(cache))


def row_writes_a_step(cache) -> int:
    """Row writes that ONE decode step issues into the position-keyed
    leaves of one sequence's cache tree (planes, rings, int8 scales,
    latent planes; an index or a ring's position table is no row): one
    a leaf that is its layer's own, and for a stacked leaf (``[layers,
    B, positions, ...]``, a carried stack) one a LAYER where the layers
    write their own rows and ONE where the rows are written after the
    loop — by :func:`defers`, the rule the program traces under.
    Under a pool's vmap each is a scatter of a trip a lane."""
    writes = 0
    axes = row_axes(cache)
    for path, leaf, kind in leaf_kinds(cache):
        if kind == "state" or path not in axes:
            continue
        lead = leaf.shape[:axes[path]]
        stacked = len(lead) > 1         # [layers, B] ahead of the rows
        if stacked and not (kind == "full" and defers(stacked, 1)):
            writes += lead[0]           # every layer its own
        else:
            writes += 1
    return writes


def causal_pairs(start, length):
    """Query-key pairs of ``length`` queries at positions ``[start,
    start + length)``, each over the keys up to its own: ``length *
    start + length (length + 1) / 2`` (ints or numpy arrays)."""
    return length * start + length * (length + 1) // 2


class PlaneReads:
    """The host's count of what the bounded reads took: rows of the
    full-length planes the attention was handed (``read``: the static
    width, by the same :func:`prefix_branch` the program switches on)
    and the rows those planes hold (``held``), a plane a layer and
    sequence.  Engine stats ``kv_plane_rows_read_total`` /
    ``kv_plane_rows_held_total``.

    And of what the ``state`` leaves went through, for a model that
    keeps any (``state_layers`` > 0): ``scan_tokens``, positions x
    state layers through the prefill scan (a piece of more than one
    position: ``ops/selective_scan.selective_scan``, Mamba-2's
    ``ops/ssd.ssd_scan``), and
    ``state_steps``, sequence-steps of the one-position update (a
    decode step a slot, idle slots too; a prefill piece of one
    position).  Engine stats ``ssm_scan_tokens_total`` /
    ``ssm_state_steps_total``.

    And of the two paths of a latent-attention layer
    (``latent_planes``: its planes, of one capacity; models/
    deepseek_v2.py says which call takes which): ``pairs_expanded``
    and ``pairs_absorbed``, causal query-key pairs x latent layers of
    the calls that expanded the rows they read to a key and a value a
    head (a piece of more than one position) and of those that
    attended over the latents as they lie (one position: a decode step
    a slot, idle slots too); and ``rows_expanded``, the cached rows
    the former sent through the expansion (the static width read, a
    layer).  Engine stats ``latent_pairs_expanded_total`` /
    ``latent_pairs_absorbed_total`` / ``latent_rows_expanded_total``.

    And of the rows the decode steps wrote (``row_writes``: steps x
    :func:`row_writes_a_step`).  Engine stat ``kv_row_writes_total``."""

    def __init__(self):
        self.read = 0
        self.held = 0
        self.planes = None      # full_planes of one sequence's cache
        self.state_layers = 0
        self.scan_tokens = 0
        self.state_steps = 0
        self.latent_planes = 0
        self.latent_cap = 0
        self.pairs_expanded = 0
        self.pairs_absorbed = 0
        self.rows_expanded = 0
        self.writes_a_step = 0
        self.row_writes = 0

    def learn(self, cache) -> None:
        """The shape of one sequence's cache, from the first one seen
        (a prefilled request's: every later one has its shape)."""
        if self.planes is None:
            self.planes = full_planes(cache)
            self.state_layers = state_layers(cache)
            self.writes_a_step = row_writes_a_step(cache)
            for (cap, _), n in full_planes(cache, "latent").items():
                self.latent_planes += n
                self.latent_cap = cap

    def count_piece(self, piece: int, filled: int) -> None:
        """One prefill piece of ``piece`` positions ran, the last of
        the ``filled`` its cache now holds."""
        if self.state_layers:
            if piece > 1:
                self.scan_tokens += piece * self.state_layers
            else:
                self.state_steps += 1
        if self.latent_planes:
            pairs = self.latent_planes * int(
                causal_pairs(filled - piece, piece))
            if piece > 1:
                self.pairs_expanded += pairs
                self.rows_expanded += self.latent_planes * int(
                    prefix_width(filled, self.latent_cap))
            else:
                self.pairs_absorbed += pairs

    def count_steps(self, steps: int, positions) -> None:
        """A decode window of ``steps`` steps over the slots that stand
        at ``positions`` (every lane of the pool, idle ones too)."""
        positions = np.asarray(positions, np.int64)
        self.row_writes += steps * self.writes_a_step
        if self.state_layers:
            self.state_steps += steps * positions.size
        if self.latent_planes:
            self.pairs_absorbed += self.latent_planes * int(
                causal_pairs(positions, steps).sum())

    def count(self, extents, lanes: int = 1, cap=None,
              shared: bool = False) -> None:
        """One program ran ``len(extents)`` applies — a decode window's
        steps over ``lanes`` slots under their ``shared`` extent, or
        one prefill chunk — each over the planes learnt (``cap`` where
        the program saw a narrower view of them: the paged manager's
        gather)."""
        extents = np.asarray(extents, np.int64)
        for (own, stacked), n in self.planes.items():
            held = own if cap is None else cap
            read = prefix_width(extents, held) \
                if narrows(shared, stacked) else held
            self.read += lanes * n * int(
                np.broadcast_to(read, extents.shape).sum())
            self.held += lanes * n * held * extents.size


def _over_prefix(attend_rows, pos_q, cap: int, stacked: bool):
    """``attend_rows(n)`` — the attention over a plane's first ``n``
    rows (static) — for the narrowest of :func:`prefix_widths` that the
    extent in scope allows; for ``cap`` where none is in scope, or
    where this plane is not narrowed under it (:func:`narrows`).
    Traced under the scope ``ptpu_attend`` (spans.py): the read of
    the rows, scores, softmax and values, the width a conditional took
    readable as ``branch_<i>_fun`` under it."""
    with scope("ptpu_attend"):
        reads = getattr(_READ, "scope", None)
        widths = prefix_widths(cap)
        if reads is None or len(widths) == 1 \
                or not narrows(reads[1], stacked):
            return attend_rows(cap)
        extent = pos_q[-1] + 1 if reads[0] is OWN_INDEX else reads[0]
        if not isinstance(extent, jax.Array):   # known while tracing
            return attend_rows(int(prefix_width(extent, cap)))
        return jax.lax.switch(
            prefix_branch(jnp.asarray(extent, jnp.int32), cap),
            [lambda n=n: attend_rows(n) for n in widths])


def _append(mod, k, v, max_position, window, rotate, quantize, layer):
    """The append of :func:`append_kv_cache`.  Returns ``(read, cap,
    positions)``: ``read(n)`` is ``(keys, values, mask)`` over the
    plane's first ``n`` rows (static; ``cap`` for the whole plane) as
    they stand once this call's rows are in it, dequantised where
    stored int8 — what is read, not the plane: ``[B, n, H, D]``, or
    ``[B, n, chunks, H / chunks * D]`` where the plane rests folded
    (:func:`lane_fold`, :func:`lane_chunks`)."""
    b, s, h, d = k.shape
    idx = mod.variable("cache", "cache_index",
                       lambda: jnp.array(0, jnp.int32))
    idx0 = _plane(idx, layer)
    pos_q = idx0 + jnp.arange(s)  # absolute positions of new rows
    if rotate is not None:
        k = rotate(pos_q, k)
    out_dtype = k.dtype
    store_dtype = jnp.int8 if quantize else out_dtype
    ck = mod.variable("cache", "cached_key", jnp.zeros,
                      (b, max_position) + ((h * d,) if lane_fold(h, d)
                                           else (h, d)), store_dtype)
    # An existing (possibly paged-view) cache keeps ITS width and ITS
    # form; only a fresh creation uses max_position and the rule.
    folded = ck.value.ndim - (layer is not None) == 3
    cap = ck.value.shape[-2 if folded else -3]
    cv = mod.variable("cache", "cached_value", jnp.zeros,
                      (b, cap) + ck.value.shape[-1 if folded else -2:],
                      store_dtype)
    lay = (lambda x: x.reshape(b, s, h * d)) if folded \
        else (lambda x: x)
    if quantize:
        cks = mod.variable("cache", "cached_key_scale", jnp.zeros,
                           (b, cap, h, 1), jnp.bfloat16)
        cvs = mod.variable("cache", "cached_value_scale", jnp.zeros,
                           (b, cap, h, 1), jnp.bfloat16)
        kq, k_scale = _quantize_chunk(k)
        vq, v_scale = _quantize_chunk(v)
        new = {ck: lay(kq), cks: k_scale, cv: lay(vq), cvs: v_scale}
    else:
        new = {ck: lay(k), cv: lay(v)}
    later = defers(layer is not None, s)
    if later:
        # Written once for all layers, after the loop (write_deferred).
        _DEFER.rows[mod.path] = {
            "start": idx0, **{var.name: rows for var, rows in new.items()}}
    else:
        for var, rows in new.items():
            _put_rows(var, layer, rows, idx0)
    _store(idx, layer, idx0 + s)

    def held(var, n: int):
        """The first ``n`` rows of ``var``'s plane with this call's
        rows in them: written above, or — deferred — the one new row
        laid over its position as the in-place update will put it
        (which clamps an index past the plane to its last row)."""
        # A folded plane is read PINNED to the layout it rests in: its
        # attention is a matmul (ops/attention.folded_attention), and
        # a stack the layer loop carries into the width conditional's
        # branches (_over_prefix) went to them in the layout the
        # matmul prefers, layers major — a copy of both of
        # gpt2-medium's 1.2 GB leaves before the loop in every step
        # (deviceless compile, PERF.md section 6, PR 38).
        tail = var.value.ndim - 2 - (layer is not None)
        rows = _plane(var, layer, None if n == cap else n, tail,
                      pin=folded)
        if not later:
            return rows
        # HOW the position is compared decides what the v5e compiler
        # makes of the two reads (deviceless compile and chip, PERF.md
        # section 6, PR 36; tests/test_chip_compile.py holds both).  The
        # KEY's select compares an iota of the rows' own shape, made
        # inside the fusion that reads them: the score fusion keeps
        # the tiles it had without the select.  Over a predicate of
        # ``[n]`` — a ``[slots, n]`` operand, positions in the lanes —
        # the 512-row branch was tiled ``[2, 171]`` and ran 232 us a
        # layer where 70 were.  The VALUE's select compares that
        # ``[n]`` predicate: with an iota of the rows' shape there,
        # or with ONE predicate under both selects, the compiler lays
        # the stack out layers-major for the layer loop and copies
        # the whole leaf, 2.4 GB of gpt2-medium's pool, in every step.
        at = jnp.minimum(idx0, cap - 1)
        if var.name.startswith("cached_key"):
            here = jax.lax.broadcasted_iota(
                jnp.int32, rows.shape, rows.ndim - 1 - tail) == at
        else:
            here = (jnp.arange(n) == at)[
                (None, slice(None)) + (None,) * tail]
        return jnp.where(here, new[var], rows)

    def read(n: int):
        k_read, v_read = held(ck, n), held(cv, n)
        if quantize:
            # Unwritten positions hold scale 0 -> dequantize to 0,
            # exactly like the unquantized zero-init cache (masked off
            # anyway).  A deferred row is laid over the int8 plane and
            # its scale, so what is attended is what will be stored.
            # A folded plane's scales are spread over its head's lanes.
            spread = functools.partial(_spread, head_dim=d) if folded \
                else (lambda x: x)
            k_read = k_read.astype(out_dtype) \
                * spread(held(cks, n).astype(out_dtype))
            v_read = v_read.astype(out_dtype) \
                * spread(held(cvs, n).astype(out_dtype))
        if folded:
            c = lane_chunks(h)
            k_read = k_read.reshape(b, n, c, -1)
            v_read = v_read.reshape(b, n, c, -1)
        keys = jnp.arange(n)
        valid = keys[None, :] <= pos_q[:, None]  # [S, n]
        if window is not None:
            valid &= keys[None, :] >= pos_q[:, None] - window
        return k_read, v_read, valid[None, None]

    return read, cap, pos_q


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
    ``>= j``, appends always put ``[idx, idx + S)`` in front of the
    chunk's queries (written BEFORE they read, or — one row on a
    carried stack — laid over the plane they read and written when the
    layer loop ends), and post-rollback appends are contiguous
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
    carry — and ``layer``, its index.  An append of S > 1 rows (a
    prefill piece, a speculative verify) then writes only those rows
    (and scales) of each sequence at ``[layer, :, idx:idx+S]`` and
    bumps ``cache_index[layer]``: an update XLA performs in place on a
    loop carry, and in place on the caller's buffer when the program
    donates it (serving/slots.py does).  An append of ONE row (a
    decode step) bumps the index and writes no row here
    (:func:`defers`): the keys and values it returns are the plane's
    with the new row — rotated, quantised: as it will be stored — laid
    over position ``idx`` by a select that fuses into the attention's
    read, bit for bit what write-then-read returned, and the row is
    written after the layer loop with every other layer's, once a
    leaf (:func:`write_deferred`): under a slot pool's vmap a write at
    a lane's own index is a loop of one dependent write a lane, and
    one a layer and leaf was 3 of gpt2-medium's 7 ms a step (PERF.md
    section 6, PR 36).  Either way the layer's keys and values are
    read once, as the attention's operand, nothing else of the stack
    is read or written, no layer is sliced out and written back, and
    the cache holds the rows when the apply returns.  ``layer=None``
    (an unrolled stack, T5's own scan, and the apply that CREATES the
    variables) is the same arithmetic on a variable that is the
    layer's own: written, then read.

    WHAT IS READ: this function returns the layer's WHOLE plane
    (``max_position`` keys, most of them masked) for the caller to
    attend over.  :func:`attend_kv_cache` is the same append with the
    attention inside it, and reads the plane only as far as it is
    written where the caller knows how far that is.

    Creates ``cached_key``/``cached_value``/``cache_index`` (plus
    ``cached_key_scale``/``cached_value_scale`` when quantized)
    variables in the "cache" collection on ``mod``; returns
    ``(k_full, v_full, mask, positions)`` — the planes ``[B, cap, H,
    D]``, or ``[B, cap, chunks, H / chunks * D]`` where they rest folded
    (:func:`lane_fold`, :func:`lane_chunks`;
    ``ops/attention.dot_product_attention`` reads either).
    """
    read, cap, pos_q = _append(mod, k, v, max_position, window, rotate,
                               quantize, layer)
    return read(cap) + (pos_q,)


def attend_kv_cache(mod, attend, k, v, max_position: int, window=None,
                    rotate=None, quantize: bool = False, layer=None):
    """:func:`append_kv_cache`, and the attention over what it
    returns: ``attend(k_read, v_read, mask, positions)`` (a pure
    function of them: no module call, no variable inside it), whose
    result is handed back.

    Outside a :func:`read_extent` scope that is all: the plane is read
    whole (solo ``generate``, beam search, the speculative round).
    Inside one, ``attend`` gets the plane's first ``n`` rows and
    ``mask`` ``[1, 1, S, n]``, ``n`` the narrowest of
    :func:`prefix_widths` past the extent — chosen by a conditional at
    run time, one compiled program — so a decode step reads its lanes
    as far as the pool's furthest stream stands (the planes of a
    carried stack: :func:`narrows`) and a prefill chunk as far as it
    has written, not to the capacity.  The rows left out are
    masked for every query of the call, so nothing of the arithmetic
    changes: scores and softmax as ``attend`` computes them, the
    rollback contract by absolute position as above (a stale row
    inside the prefix is masked as it was)."""
    read, cap, pos_q = _append(mod, k, v, max_position, window, rotate,
                               quantize, layer)
    return _over_prefix(lambda n: attend(*read(n), pos_q), pos_q, cap,
                        stacked=layer is not None)


def attend_latent_cache(mod, attend, rows, max_position: int,
                        rotate=None):
    """:func:`attend_kv_cache` for a layer that caches ONE row a
    position: append ``rows`` ([B, S, width], through ``rotate(
    positions, rows)`` where given: a rope part is stored rotated) to
    the ``cached_latent`` plane ``[B, max_position, width]`` at the
    layer's ``cache_index``, and hand back ``attend(read, mask,
    positions)`` over the plane's first ``n`` rows, ``mask`` ``[1, 1,
    S, n]``: ``n`` by the extent in scope exactly as there, the whole
    plane outside one.  The contracts of :func:`append_kv_cache` hold
    as they stand — absolute positions, the capacity of an existing
    cache, the rollback by index — since nothing of them speaks of
    heads.  The plane is its layer's own variable (an unrolled stack):
    no model carries a stack of latent planes yet."""
    b, s, width = rows.shape
    idx = mod.variable("cache", "cache_index",
                       lambda: jnp.array(0, jnp.int32))
    idx0 = idx.value
    pos_q = idx0 + jnp.arange(s)
    if rotate is not None:
        rows = rotate(pos_q, rows)
    plane = mod.variable("cache", LATENT_LEAF, jnp.zeros,
                         (b, max_position, width), rows.dtype)
    cap = plane.value.shape[-2]
    _put_rows(plane, None, rows, idx0)
    idx.value = idx0 + s

    def attend_rows(n: int):
        read = _plane(plane, None, None if n == cap else n, tail=1)
        valid = jnp.arange(n)[None, :] <= pos_q[:, None]     # [S, n]
        return attend(read, valid[None, None], pos_q)

    return _over_prefix(attend_rows, pos_q, cap, stacked=False)


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
