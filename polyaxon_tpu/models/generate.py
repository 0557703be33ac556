"""Autoregressive generation with a KV cache.

The reference orchestrates serving as opaque user containers
(`V1Service`); the TPU build's zoo owns decoding natively.  The loop is
a single jitted ``lax.scan`` over positions — one compiled program for
the whole generation, no per-token dispatch — with the per-layer KV
cache living in the model's flax "cache" collection (stacked [layers,
...] by ``scan_stack``, so it shards the same way the params do).

Prefill runs ONE forward over the whole prompt (the causal-append
mask handles S > 1) — or fixed-size pieces via ``prefill_chunk`` to
bound long-prompt activation memory — then the scan generates token by
token.  Serving options compose across every entry point: int8 weights
(ops/quant), int8 KV cache, ring caches for sliding-window streaming,
speculative drafts, beam search.  Compile-once and bandwidth-bound —
the right shape for TPU decode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.quant import dequantize_params
from ..spans import scope
from .kv_cache import leaf_kinds, read_extent


def _params(variables):
    """Resolve the params tree at the point of USE.

    Weight-only int8 serving (ops/quant.py) stores QuantizedTensor
    leaves; dequantizing here — inside the apply_step closures that
    become the decode scan's body — keeps the int8 buffers in HBM and
    lets XLA fuse the convert+scale into each matmul's operand read.
    Dequantizing once up front would materialize bf16 weights and
    forfeit the bandwidth win.  Unquantized trees pass through
    untouched.
    """
    return dequantize_params(variables["params"])


def jit_over(weights, fn, **jit_kw):
    """``jax.jit(fn)`` with ``weights`` as the program's FIRST ARGUMENT,
    bound here: ``jit_over(w, lambda w, toks: ...)(toks)``.

    A jitted function that CLOSES over arrays gets them baked into the
    program as constants.  For model weights that is a whole copy of
    the model inside every compiled program — in the lowered module,
    in the compiler's host memory, in the cached executable and on the
    device.  It goes unnoticed at test sizes; at gpt2-medium width
    ``ptpu serve`` ran a 40 GiB host out of memory on its first
    request (v5e, PR 22), and gpt2-small's decode window lowered to
    995 MB of text.  Passed as an argument, the one resident copy
    serves every program, under whatever sharding it was placed with.
    """
    return functools.partial(jax.jit(fn, **jit_kw), weights)


# Collection a model may sow per-apply counts into when the caller
# makes it mutable (the expert layers' token-expert pairs,
# models/afmoe.py): the serving programs do, and hand the sums out
# beside their results.
STATS = "stats"


def stats_rows(stats):
    """What one apply sowed under :data:`STATS`, a row a layer that
    sowed (for the expert layers ``[pairs on each held expert ...,
    pairs routed]``), or None for a model that sows nothing.  Inside a
    vmapped step a row is ONE lane's: the caller sums the lanes before
    :func:`stats_total`."""
    leaves = jax.tree.leaves(stats)
    if not leaves:
        return None
    return jnp.concatenate([leaf.reshape(-1, leaf.shape[-1])
                            for leaf in leaves])


def stats_total(rows):
    """:func:`stats_rows` of one program run, every lane in, as one
    vector: the rows' sum, then how many (layer, held expert) took at
    least one pair — ``[pairs on each held expert ..., pairs routed,
    held experts touched]``.  A touched expert's weights are what the
    layer's grouped matmuls cannot avoid reading."""
    if rows is None:
        return None
    touched = jnp.count_nonzero(rows[:, :-1]).astype(rows.dtype)
    return jnp.concatenate([rows.sum(axis=0), touched[None]])


def init_cache(model, batch_size: int):
    """Allocate the stacked per-layer KV cache for a DECODER-ONLY
    ``model``, all zeros with cache_index 0.  (Abstract init only:
    running a real init decode step would advance the index and write
    a garbage token-0 entry.)

    Seq2seq (encoder-decoder) models must NOT use this: their cache
    holds the computed cross-attention K/V, which zeros would silently
    shadow — their loops start from an empty cache dict so the prefill
    step creates every entry (see :func:`generate_seq2seq`)."""
    tokens = jnp.zeros((batch_size, 1), jnp.int32)
    shapes = jax.eval_shape(
        # Shape probe under eval_shape (nothing is ever drawn from
        # this key), not a sampling draw.  # ptpu: ignore[RNG-DET]
        lambda: model.init(jax.random.PRNGKey(0), tokens, decode=True,
                           decode_position=0))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def extract_logits(out) -> jax.Array:
    """The zoo's output contract: a model's __call__ returns either
    ``logits`` or ``(logits, aux)`` (MoE load-balance loss).  This is
    the same contract the registry loss fns rely on; anything else is
    an error here rather than a silent mis-slice."""
    if isinstance(out, jax.Array):
        return out
    if isinstance(out, tuple) and len(out) == 2 and \
            isinstance(out[0], jax.Array):
        return out[0]
    raise TypeError(
        f"model output must be logits or (logits, aux); got "
        f"{type(out).__name__}")


def _modified_logits(logits, temperature: float, top_k: Optional[int],
                     top_p: Optional[float] = None):
    """The temp/top-k/top-p-shaped logits ``_sample`` draws from —
    factored out so speculative rejection sampling can evaluate the
    EXACT draft/target densities the samplers use."""
    logits = logits / temperature
    if top_k is not None:
        # lax.top_k, not a full vocab sort — this runs once per decoded
        # token.
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None:
        # Nucleus sampling: keep the smallest prefix of the sorted
        # distribution whose mass reaches top_p (a token enters the
        # nucleus iff the cumulative mass BEFORE it is < top_p, so the
        # top token always survives).  One descending sort per decoded
        # token; composes with top_k (masked lanes sort to the tail).
        sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        before = jnp.cumsum(probs, axis=-1) - probs
        cut = jnp.where(before < top_p, sorted_l, jnp.inf)
        kth = jnp.min(cut, axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -1e30, logits)
    return logits


def _sample(logits, rng, temperature: float, top_k: Optional[int],
            top_p: Optional[float] = None):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        rng, _modified_logits(logits, temperature, top_k, top_p),
        axis=-1)


def _check_top_p(top_p) -> None:
    """top_p=0 would mask EVERY lane (before<0 is never true) and
    degenerate to uniform noise over the full vocab — refuse anything
    outside (0, 1] at the entry points."""
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(
            f"top_p must be in (0, 1]; got {top_p} (use "
            f"temperature=0 for greedy decoding)")


def _check_temperature(temperature) -> None:
    """A typo'd negative temperature must not silently decode greedy
    — one message shared by the server, the positional entry points,
    and speculative decoding."""
    if temperature < 0.0:
        raise ValueError(
            f"temperature must be >= 0; got {temperature}")


def _check_top_k(top_k, vocab=None) -> None:
    """top_k outside [1, vocab] would fail at jit-trace time inside
    lax.top_k (opaque shape error, possibly under a server's device
    lock) — refuse it at the entry points, with ONE message every
    serving path shares."""
    if top_k is None:
        return
    if top_k < 1 or (vocab is not None and top_k > vocab):
        hi = vocab if vocab is not None else "vocab_size"
        raise ValueError(f"top_k must be in [1, {hi}]; got {top_k}")


def _check_spec_k(spec_k) -> None:
    """A draft length < 1 can't propose anything — refuse it at every
    entry point (server, CLI, library) with ONE shared message."""
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1; got {spec_k}")


# Shared by the server and the CLI so speculative+beam is refused with
# one message regardless of which surface fields the request.
SPEC_BEAM_MSG = ("speculative decoding cannot combine with beam "
                 "search (greedy or sampled only)")


def _check_positional_sampling(top_k, top_p, temperature,
                               vocab=None) -> None:
    """Shared validation for the positional entry points — only for
    CONCRETE params (jitted callers pass traced scalars and validate
    in the server layer instead).  ``0`` is the internal "disabled"
    encoding, so it passes here; the public HTTP surface rejects it
    per the uniform-validation contract (server-side _check_top_k)."""
    if isinstance(top_k, int) and top_k:
        _check_top_k(top_k, vocab)
    if isinstance(top_p, (int, float)) and top_p:
        _check_top_p(float(top_p))
    if isinstance(temperature, (int, float)):
        _check_temperature(temperature)


def positional_eligible(model, temperature) -> bool:
    """Whether a request decodes under the POSITION-KEYED sampling
    schedule: sampled (temperature != 0) on a decoder-only model.
    The single predicate behind the server's solo + prefix-hit paths
    and the CLI, so every surface routes — and therefore samples —
    identically (seq2seq models keep the chain-rng generate_seq2seq
    path; greedy never consults the PRNG at all)."""
    return temperature != 0.0 and not hasattr(model, "encode")


# -- position-keyed sampling ---------------------------------------------
#
# The chain schedule above (``rng, key = split(rng)`` per token) makes
# a request's i-th sample depend on how many times the chain was split
# before it — fine solo, but hostile to the continuous-batching engine,
# where a stream's tokens are produced by whatever fused step windows
# the scheduler happened to run.  The POSITION-KEYED schedule below
# derives row r's i-th token key as fold_in(fold_in(PRNGKey(seed), r),
# i): a pure function of (seed, row, token index) — never of batch
# shape, decode-slot id, engine step count, or co-tenancy — so the
# engine's per-slot streams and the solo reference draw identical
# samples for one request, under ANY admission schedule.


def sample_stream_keys(seed: int, rows: int) -> jax.Array:
    """Per-row base keys for the position-keyed schedule: row ``r``
    gets ``fold_in(PRNGKey(seed), r)``; its i-th generated token is
    then drawn with ``fold_in(base, i)`` (:func:`_sample_positional_row`)."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda r: jax.random.fold_in(base, r))(
        jnp.arange(rows))


def _sortable_bits(x):
    """f32 -> uint32 order-preserving key (IEEE total order, NaN-free
    inputs): unsigned comparison on the keys == value comparison on
    the floats.  Positive floats get the sign bit set; negative
    floats are bit-complemented (their bit patterns grow as the value
    shrinks)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                     jnp.uint32)
    return jnp.where((b >> 31) == 0, b | jnp.uint32(0x80000000), ~b)


def _bitwise_threshold(pred):
    """Largest uint32 ``t`` with ``pred(t)`` true, for a predicate
    monotone non-increasing in ``t``: greedy MSB-first bit
    construction, 32 fixed iterations.  This is branchless exact
    SELECTION — the returned threshold lands exactly on a data key —
    at O(32 V) elementwise work, replacing the O(V log V) vocab sort
    a per-slot-per-token sampler cannot afford (a 4096-wide XLA CPU
    sort costs more than the decode step it follows)."""
    def body(i, t):
        t_try = t | (jnp.uint32(1)
                     << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(pred(t_try), t_try, t)
    return jax.lax.fori_loop(0, 32, body, jnp.uint32(0))


def _shape_logits_positional(logits, temperature, top_k, top_p):
    """Temperature/top-k/top-p shaping with TRACED per-row params —
    the engine's slot step feeds per-slot arrays through ``vmap``,
    the solo positional path broadcasts request scalars; both run
    THIS function, so the two paths shape identically bit-for-bit.

    Returns ``(shaped f32 logits, greedy flag)``.  ``temperature <=
    0`` marks the row greedy (shaping still runs — in a dead lane —
    because a mixed pool shares one program); ``top_k <= 0`` /
    ``top_p <= 0`` disable those masks, and ``top_p >= 1`` is a no-op
    by definition (the nucleus is the whole distribution).

    Both cutoffs are found by 32-step bitwise binary search over the
    float bit-space (:func:`_bitwise_threshold`) instead of a vocab
    sort.  The selected VALUES are exactly the sort-based ones:

    - top-k keeps ``{x : x >= k-th largest}`` (ties at the threshold
      survive, like the static ``lax.top_k`` kth-value mask);
    - top-p keeps ``{x : mass(values > x) < top_p}`` — the value
      formulation of the sorted-prefix cumsum rule (provably the same
      kept set: mass-above is monotone in the value, so the sorted
      cut and the value test agree, ties included, and the top token
      always survives since mass above it is 0).
    """
    v = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    greedy = temperature <= 0.0
    # greedy rows divide by 1 (not 0) so the dead sampling lane stays
    # finite instead of poisoning the where with inf/nan
    l = logits.astype(jnp.float32) / jnp.where(greedy, 1.0,
                                               temperature)
    # top-k: threshold = the k-th largest value = max t with
    # |{keys >= t}| >= k
    lbits = _sortable_bits(l)
    k = jnp.clip(top_k, 1, v)
    t_k = _bitwise_threshold(lambda t: jnp.sum(lbits >= t) >= k)
    l = jnp.where((top_k > 0) & (lbits < t_k), -1e30, l)
    # nucleus over the top-k-masked logits (masked lanes underflow to
    # probability 0): boundary = max t whose strictly-above mass
    # still holds >= top_p of the total
    lbits = _sortable_bits(l)
    e = jnp.exp(l - jnp.max(l))
    pz = top_p * jnp.sum(e)
    t_p = _bitwise_threshold(
        lambda t: jnp.sum(jnp.where(lbits > t, e, 0.0)) >= pz)
    l = jnp.where((top_p > 0.0) & (top_p < 1.0) & (lbits <= t_p),
                  -1e30, l)
    return l, greedy


def _sample_positional_row(logits, base_key, index, temperature,
                           top_k, top_p):
    """Sample ONE token for ONE row under the position-keyed RNG
    contract.  Every argument may be traced (the engine feeds
    per-slot arrays, the solo path broadcasts request scalars).
    ``temperature <= 0`` rows take argmax over the raw logits — the
    greedy lane, identical to the greedy decode programs.  Shaping
    runs in f32 (:func:`_shape_logits_positional`) so bf16 models
    sample from the same grid the f32 solo reference uses.  Traced
    whole under the scope ``ptpu_sample`` (spans.py)."""
    with scope("ptpu_sample"):
        key = jax.random.fold_in(base_key, index)
        l, greedy = _shape_logits_positional(logits, temperature, top_k,
                                             top_p)
        sampled = jax.random.categorical(key, l)
        return jnp.where(greedy, jnp.argmax(logits, axis=-1),
                         sampled).astype(jnp.int32)


def _sample_positional(logits, keys, index, temperature, top_k, top_p):
    """Batch wrapper over :func:`_sample_positional_row`: [B, V]
    logits + [B] base keys -> [B] tokens, one request's scalar params
    broadcast to every row."""
    return jax.vmap(lambda l, k: _sample_positional_row(
        l, k, index, temperature, top_k, top_p))(logits, keys)


# -- position-keyed speculative kernels -----------------------------------
#
# Speculative decoding draws THREE kinds of randomness per proposed
# token: the draft's proposal, the accept/reject uniform, and the
# residual resample.  Keying each by (base key, token index, lane)
# makes every draw a pure function of the request alone — like the
# plain sampled schedule above — so an engine slot and the solo
# reference commit identical tokens under any co-tenancy, and a
# partially-rejected round's re-derivation next round (same keys, same
# prefix) reproduces the same tokens instead of forking the stream.
# Exactness of rejection sampling is preserved: whether round N's
# first rejection lands at index j is a function of draws at indices
# <= j only, so the draws at later indices are still fresh uniforms
# conditioned on the committed prefix even though their keys were
# "used" for discarded proposals in an earlier round.

_SPEC_LANE_DRAFT = 1
_SPEC_LANE_ACCEPT = 2
_SPEC_LANE_RESIDUAL = 3


def _spec_round_key(base_key, index, lane):
    """Key for one speculative draw: fold_in(fold_in(base, token
    index), lane) — disjoint from the plain sampled schedule's
    fold_in(base, index) committed-token keys."""
    return jax.random.fold_in(jax.random.fold_in(base_key, index),
                              lane)


def _spec_draft_row(logits, base_key, index, temperature, top_k,
                    top_p):
    """Draft proposal for ONE row at new-token ``index``: returns
    ``(token, q_row)`` where ``q_row`` is the draft's shaped density
    (softmax of the temp/top-k/top-p-shaped logits — what the accept
    test divides by).  ``temperature <= 0`` rows take the argmax lane
    (greedy speculative needs no density; q_row is a dead value
    then)."""
    l, greedy = _shape_logits_positional(logits, temperature, top_k,
                                         top_p)
    key = _spec_round_key(base_key, index, _SPEC_LANE_DRAFT)
    sampled = jax.random.categorical(key, l)
    tok = jnp.where(greedy, jnp.argmax(logits, axis=-1),
                    sampled).astype(jnp.int32)
    return tok, jax.nn.softmax(l.astype(jnp.float32), axis=-1)


def _spec_verify_row(t_logits, d_toks, q_rows, base_key, index0,
                     temperature, top_k, top_p, k_eff):
    """Verify ONE row's K proposals against the target: ``t_logits``
    [K, V] are the target's logits at the K draft positions,
    ``d_toks`` [K] the proposals, ``q_rows`` [K, V] their draft
    densities, ``index0`` the new-token index of the first proposal.
    Returns ``(out_toks [K], c, m)``: the committed tokens are
    ``out_toks[:c]`` with ``c`` in [1, k_eff] and ``m`` the accepted
    draft count (``c - 1`` correction/bonus excluded, clipped to
    ``k_eff``).

    Greedy lane (``temperature <= 0``): longest draft/target-argmax
    matching prefix plus the target's argmax correction — identical
    commits to ``generate_speculative``'s greedy path.  Sampled lane:
    rejection speculative sampling (accept ``x ~ q`` with prob
    ``min(1, p(x)/q(x))``, first rejection resamples from
    ``norm(max(p - q, 0))``) under the position-keyed key schedule,
    with BOTH densities shaped by :func:`_shape_logits_positional` —
    the same function the plain sampled paths run, so engine and solo
    shape bit-identically.

    ``k_eff`` may be a traced scalar <= K (the engine compiles one
    program at the pool's max draft length; a slot with a smaller
    ``spec_k`` caps its accepts/commits at its own k — proposals and
    accept draws at indices < k_eff are identical to a K = k_eff
    program's, so the committed stream is unchanged)."""
    k = d_toks.shape[0]
    idxs = index0 + jnp.arange(k)
    shaped = jax.vmap(lambda l: _shape_logits_positional(
        l, temperature, top_k, top_p)[0])(t_logits)        # [K, V]
    p_rows = jax.nn.softmax(shaped.astype(jnp.float32), axis=-1)
    px = jnp.take_along_axis(p_rows, d_toks[:, None],
                             axis=-1)[:, 0]                # [K]
    qx = jnp.take_along_axis(q_rows, d_toks[:, None], axis=-1)[:, 0]
    u = jax.vmap(lambda i: jax.random.uniform(
        _spec_round_key(base_key, i, _SPEC_LANE_ACCEPT)))(idxs)
    t_arg = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
    greedy = jnp.asarray(temperature, jnp.float32) <= 0.0
    accept = jnp.where(greedy, d_toks == t_arg,
                       u * qx < px)      # u < p/q without the divide
    k_eff = jnp.clip(jnp.asarray(k_eff, jnp.int32), 1, k)
    accept = accept & (jnp.arange(k) < k_eff)
    m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    c = jnp.minimum(m + 1, k_eff)
    resid = jnp.clip(p_rows - q_rows, 0.0, None)
    res = jax.vmap(lambda i, r: jax.random.categorical(
        _spec_round_key(base_key, i, _SPEC_LANE_RESIDUAL),
        jnp.log(r + 1e-20)))(idxs, resid).astype(jnp.int32)
    correction = jnp.where(greedy, t_arg, res)
    out = jnp.where(jnp.arange(k) < m, d_toks, correction)
    return out.astype(jnp.int32), c.astype(jnp.int32), \
        m.astype(jnp.int32)


def _decode_loop_positional(apply_step, cache, first_logits, *,
                            max_new_tokens: int, keys,
                            temperature, top_k, top_p,
                            eos_id: Optional[int]):
    """Position-keyed twin of :func:`_decode_loop`: token i draws with
    ``fold_in(base, i)`` instead of a split chain, so a prefill/
    continue split — or the engine's slot schedule — can never shift
    the stream."""
    first = _sample_positional(first_logits, keys, 0, temperature,
                               top_k, top_p)
    done = jnp.zeros((first.shape[0],), bool)
    if eos_id is not None:
        done = first == eos_id

    def step(carry, t):
        cache, tok, done = carry
        logits, cache = apply_step(cache, tok, t)
        nxt = _sample_positional(logits, keys, t + 1, temperature,
                                 top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt.astype(jnp.int32), done), nxt

    if max_new_tokens > 1:
        _, toks = jax.lax.scan(
            step, (cache, first.astype(jnp.int32), done),
            jnp.arange(max_new_tokens - 1))
        new = jnp.concatenate([first[:, None], toks.T], axis=1)
    else:
        new = first[:, None]
    return new.astype(jnp.int32)


def generate_positional(model, variables, prompt, *,
                        max_new_tokens: int, seed: int = 0,
                        keys: Optional[jax.Array] = None,
                        temperature=1.0, top_k=None, top_p=None,
                        eos_id: Optional[int] = None,
                        prefill_chunk: Optional[int] = None
                        ) -> jax.Array:
    """:func:`generate` under the position-keyed sampling schedule —
    the solo REFERENCE the continuous-batching engine's sampled slots
    are pinned against.

    Row r's i-th generated token is sampled with
    ``fold_in(fold_in(PRNGKey(seed), r), i)`` — a function of (seed,
    row, token index) only — so the same request returns identical
    tokens solo, in a full slot pool, or admitted mid-flight.
    ``temperature``/``top_k``/``top_p`` may be traced scalars (the
    server jits ONE program per shape and feeds them at run time);
    ``top_k=None``/``0`` and ``top_p=None``/``0`` disable the masks,
    ``temperature=0`` decodes greedily.  ``keys`` overrides the
    seed-derived per-row base keys ([B]-batched PRNG keys).
    """
    if max_new_tokens < 0:
        # same contract as generate(): 0 echoes the prompt
        raise ValueError(f"max_new_tokens must be >= 0; got "
                         f"{max_new_tokens}")
    cfg = getattr(model, "cfg", None)
    _check_positional_sampling(top_k, top_p, temperature,
                               getattr(cfg, "vocab_size", None))
    if top_k is None:
        top_k = 0
    if top_p is None:
        top_p = 0.0
    prompt = jnp.asarray(prompt, jnp.int32)
    if max_new_tokens == 0:
        return prompt
    b, p_len = prompt.shape
    max_pos = getattr(cfg, "max_position", None)
    if max_pos is not None and p_len + max_new_tokens > max_pos and \
            not getattr(cfg, "kv_cache_ring", False):
        raise ValueError(
            f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's max_position ({max_pos})")
    if keys is None:
        keys = sample_stream_keys(seed, b)
    first_logits, cache = _prefill(model, variables, prompt,
                                   chunk=prefill_chunk)
    new = generate_continue_positional(
        model, variables, cache, first_logits, p_len,
        max_new_tokens=max_new_tokens, keys=keys,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_id=eos_id, _validated=True)
    return jnp.concatenate([prompt, new], axis=1)


def generate_continue_positional(model, variables, cache, last_logits,
                                 position: int, *, max_new_tokens: int,
                                 seed: int = 0,
                                 keys: Optional[jax.Array] = None,
                                 temperature=1.0, top_k=None,
                                 top_p=None,
                                 eos_id: Optional[int] = None,
                                 _validated: bool = False
                                 ) -> jax.Array:
    """Decode from a prefilled cache under the position-keyed schedule
    (:func:`generate_positional`'s split form — same contract as
    :func:`generate_continue` vs :func:`generate`).  Token indices
    start at 0 for the first NEW token regardless of ``position``, so
    a prefix-cache hit draws the same stream as a cold request."""
    if not _validated:
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1; got "
                             f"{max_new_tokens}")
        cfg = getattr(model, "cfg", None)
        _check_positional_sampling(top_k, top_p, temperature,
                                   getattr(cfg, "vocab_size", None))
        max_pos = getattr(cfg, "max_position", None)
        if max_pos is not None and position + max_new_tokens > max_pos \
                and not getattr(cfg, "kv_cache_ring", False):
            raise ValueError(
                f"position ({position}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the model's max_position "
                f"({max_pos})")
    if top_k is None:
        top_k = 0
    if top_p is None:
        top_p = 0.0
    if keys is None:
        keys = sample_stream_keys(seed, last_logits.shape[0])

    def apply_step(cache, tok, t):
        out, mut = model.apply(
            {"params": _params(variables), "cache": cache},
            tok[:, None], decode=True, decode_position=position + t,
            mutable=["cache"])
        return extract_logits(out)[:, -1], mut["cache"]

    return _decode_loop_positional(
        apply_step, cache, last_logits,
        max_new_tokens=max_new_tokens, keys=keys,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_id=eos_id)


def _decode_loop(apply_step, cache, first_logits, *,
                 max_new_tokens: int, rng, temperature: float,
                 top_k: Optional[int], eos_id: Optional[int],
                 top_p: Optional[float] = None):
    """Shared sample-first + scan-over-tokens machinery for
    :func:`generate` and :func:`generate_seq2seq` (one place owns the
    eos-freeze and sampling semantics).

    ``apply_step(cache, tok, t) -> (logits, cache)`` runs one decoder
    step on ``tok`` [B] at scan tick ``t`` (the caller's closure maps
    ``t`` to its absolute decode position).  Returns the generated
    tokens [B, max_new_tokens].
    """
    rng, key = jax.random.split(rng)
    first = _sample(first_logits, key, temperature, top_k, top_p)
    done = jnp.zeros((first.shape[0],), bool)
    if eos_id is not None:
        done = first == eos_id

    def step(carry, t):
        cache, tok, rng, done = carry
        logits, cache = apply_step(cache, tok, t)
        rng, key = jax.random.split(rng)
        nxt = _sample(logits, key, temperature, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt.astype(jnp.int32), rng, done), nxt

    if max_new_tokens > 1:
        _, toks = jax.lax.scan(
            step, (cache, first.astype(jnp.int32), rng, done),
            jnp.arange(max_new_tokens - 1))
        new = jnp.concatenate([first[:, None], toks.T], axis=1)
    else:
        new = first[:, None]
    return new.astype(jnp.int32)


def generate(model, variables, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None,
             prefill_chunk: Optional[int] = None) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    ``prompt``: [B, P] int32 (a shared prompt length; left-trim or pad
    ragged prompts upstream).  Returns [B, P + max_new_tokens].
    ``temperature=0`` is greedy; ``eos_id`` freezes finished rows (they
    keep emitting eos).
    """
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0; got "
                         f"{max_new_tokens}")
    _check_top_p(top_p)
    cfg = getattr(model, "cfg", None)
    _check_top_k(top_k, getattr(cfg, "vocab_size", None))
    if rng is None:
        rng = jax.random.PRNGKey(0)
    prompt = jnp.asarray(prompt, jnp.int32)
    if max_new_tokens == 0:
        return prompt
    b, p_len = prompt.shape
    total = p_len + max_new_tokens
    max_pos = getattr(cfg, "max_position", None)
    if max_pos is not None and total > max_pos and \
            not getattr(cfg, "kv_cache_ring", False):
        # Overflow would silently clamp the cache write index (garbage
        # output, no error) — refuse up front.  Ring-cache models
        # (kv_cache_ring) stream past max_position by design: their
        # O(window) cache is position-keyed, not capacity-bounded.
        raise ValueError(
            f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's max_position ({max_pos})")

    # Prefill fills the KV cache in one forward (the causal-append
    # mask handles S > 1) — or in fixed-size pieces when
    # ``prefill_chunk`` bounds the activation memory of long prompts.
    first_logits, cache = _prefill(model, variables, prompt,
                                   chunk=prefill_chunk)
    new = generate_continue(
        model, variables, cache, first_logits, p_len,
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, rng=rng, eos_id=eos_id,
        _validated=True)
    return jnp.concatenate([prompt, new], axis=1)


def prefill(model, variables, prompt, *, chunk: Optional[int] = None,
            cache=None, position: int = 0, with_stats: bool = False):
    """Fill — or EXTEND — a decode cache with ``prompt`` tokens.

    With ``cache=None`` this is the standalone prefill: a fresh cache
    is created and filled from position 0.  Passing an existing
    ``cache`` (and the ``position`` it has consumed up to) APPENDS the
    tokens instead — the causal-append machinery is position-keyed,
    so ``prefill(suffix, cache=c, position=n)`` after
    ``prefill(prefix)`` produces bit-identical state to one
    ``prefill(prefix ++ suffix)`` (the chunked-prefill exactness
    contract).  This is the building block for serving-side PREFIX
    CACHING: reuse a stored prefill across requests sharing a prompt
    prefix and pay only for the suffix.

    Returns ``(last_position_logits [B, V], cache)`` — feed both to
    :func:`generate_continue` — and with ``with_stats`` a third:
    :func:`stats_total` of what the model sowed over these tokens
    (None for a model that sows nothing).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    return _prefill(model, variables, prompt, chunk=chunk, cache=cache,
                    position=position, with_stats=with_stats)


def prefill_programs(model, chunk: Optional[int] = None):
    """``(ptpu_prefill(w, toks), ptpu_extend(w, cache, toks, pos))``:
    :func:`prefill` into a fresh cache and onto an existing one, each
    returning ``(logits, cache, stats)``, as functions to jit over the
    weights.  NAMED, so that a device trace tells a server's prefill
    programs (``jit_ptpu_prefill``, ``jit_ptpu_extend``) from its
    decode program.  One request (or a batch that shares its index) a
    call: the attention reads the full-length planes as far as they
    are written (``kv_cache.read_extent``), not to ``max_position`` —
    a fresh cache as far as the prompt's length, known while tracing
    (one static width: no conditional to compile), an extended one as
    far as its own index and the chunk, known at run time."""
    def ptpu_prefill(w, toks):
        with read_extent(toks.shape[1]):
            return prefill(model, w, toks, chunk=chunk,
                           with_stats=True)

    def ptpu_extend(w, cache, toks, pos):
        with read_extent():
            return prefill(model, w, toks, chunk=chunk, cache=cache,
                           position=pos, with_stats=True)

    return ptpu_prefill, ptpu_extend


def generate_continue(model, variables, cache, last_logits,
                      position: int, *, max_new_tokens: int,
                      temperature: float = 0.0,
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None,
                      rng: Optional[jax.Array] = None,
                      eos_id: Optional[int] = None,
                      _validated: bool = False) -> jax.Array:
    """Decode ``max_new_tokens`` from a prefilled cache (see
    :func:`prefill`): returns the NEW tokens [B, max_new_tokens].

    Exactness contract: ``generate(model, vars, prompt, ...)`` equals
    ``prompt ++ generate_continue(model, vars, *prefill(model, vars,
    prompt), len(prompt), ...)`` with the same rng — they are the same
    program split at the prefill/decode boundary.
    """
    if not _validated:
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1; got "
                             f"{max_new_tokens}")
        _check_top_p(top_p)
        cfg = getattr(model, "cfg", None)
        _check_top_k(top_k, getattr(cfg, "vocab_size", None))
        if rng is None:
            rng = jax.random.PRNGKey(0)
        max_pos = getattr(cfg, "max_position", None)
        if max_pos is not None and position + max_new_tokens > max_pos \
                and not getattr(cfg, "kv_cache_ring", False):
            raise ValueError(
                f"position ({position}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the model's max_position "
                f"({max_pos})")

    def apply_step(cache, tok, t):
        out, mut = model.apply(
            {"params": _params(variables), "cache": cache},
            tok[:, None], decode=True, decode_position=position + t,
            mutable=["cache"])
        return extract_logits(out)[:, -1], mut["cache"]

    return _decode_loop(apply_step, cache, last_logits,
                        max_new_tokens=max_new_tokens, rng=rng,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, eos_id=eos_id)


def generate_seq2seq(model, variables, enc_tokens, *,
                     max_new_tokens: int, temperature: float = 0.0,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     rng: Optional[jax.Array] = None,
                     eos_id: Optional[int] = None,
                     enc_mask: Optional[jax.Array] = None,
                     start_id: Optional[int] = None) -> jax.Array:
    """Seq2seq generation (T5-style encoder-decoder models).

    Encodes ``enc_tokens`` [B, S] ONCE, then runs the decoder token by
    token through its KV cache in a single ``lax.scan`` (same
    compile-once shape as :func:`generate`).  The model must expose
    ``encode``/``decode`` flax methods (see models/t5.py).  Returns the
    GENERATED tokens [B, max_new_tokens] (no prompt prefix — the
    decoder's start token is bookkeeping, not output).
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1; got "
                         f"{max_new_tokens}")
    _check_top_p(top_p)
    _check_top_k(top_k, getattr(model.cfg, "vocab_size", None))
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if start_id is None:
        start_id = model.cfg.pad_id
    max_pos = getattr(model.cfg, "max_position", None)
    if max_pos is not None and max_new_tokens > max_pos:
        # Cache slots used: the start token at 0 plus the fed-back
        # generated tokens at 1..max_new_tokens-1 (the last token is
        # never fed back) — exactly max_new_tokens slots.
        raise ValueError(
            f"max_new_tokens ({max_new_tokens}) exceeds the decoder's "
            f"max_position ({max_pos})")
    enc_tokens = jnp.asarray(enc_tokens, jnp.int32)
    b = enc_tokens.shape[0]
    params = {"params": _params(variables)}
    enc_out = model.apply(params, enc_tokens, enc_mask=enc_mask,
                          method="encode")

    # EMPTY cache: the prefill step below creates the self-attn ring
    # AND the computed cross-attention K/V (init_cache's zeros would
    # shadow the cross projections).
    start = jnp.full((b, 1), start_id, jnp.int32)
    cache = {}

    def apply_step(cache, tok, pos):
        out, mut = model.apply(
            {"params": _params(variables), "cache": cache},
            tok, enc_out, enc_mask=enc_mask, decode=True,
            decode_position=pos, last_only=True, mutable=["cache"],
            method="decode")
        return extract_logits(out)[:, -1], mut["cache"]

    logits, cache = apply_step(cache, start, 0)
    return _decode_loop(
        lambda cache, tok, t: apply_step(cache, tok[:, None], 1 + t),
        cache, logits, max_new_tokens=max_new_tokens, rng=rng,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_id=eos_id)


def _prefill(model, variables, prompt, chunk: Optional[int] = None,
             cache=None, position: int = 0, with_stats: bool = False):
    """Prefill shared by generate / generate_beam /
    generate_speculative; returns (last-position logits [B, V], cache).

    Default: ONE forward over the whole prompt.  ``chunk`` bounds the
    prefill's activation memory for long prompts: the prompt is
    consumed ``chunk`` tokens at a time through a ``lax.scan`` (one
    traced chunk step, attention cost O(chunk x visible) per step)
    plus one remainder step — the causal-append cache machinery is
    position-keyed, so chunking changes memory, never logits.

    ``cache``/``position`` extend an EXISTING cache instead of
    creating one (the public :func:`prefill` surface) — the appends
    start at ``position``, so the result equals one prefill of the
    concatenated tokens.

    ``with_stats``: return a third value, :func:`stats_total` of what
    the model sowed under :data:`STATS` over the whole prompt.
    """
    if chunk is not None and chunk < 1:
        raise ValueError(f"prefill_chunk must be >= 1; got {chunk}")
    b, p_len = prompt.shape
    cfg = getattr(model, "cfg", None)
    if getattr(cfg, "kv_cache_ring", False):
        max_pos = getattr(cfg, "max_position", None)
        if max_pos is not None and p_len > max_pos:
            # Ring models stream past max_position, but the MODEL's
            # per-forward sequence check still caps one apply at
            # max_position tokens — auto-chunk (and clamp an explicit
            # oversized chunk) so the unbounded-session promise holds
            # regardless of what the caller passed.
            chunk = min(chunk, max_pos) if chunk else max_pos
    if cache is None:
        cache = init_cache(model, b)
        position = 0

    def apply_chunk(cache, toks, pos):
        # _params INSIDE the closure: for int8 weights the dequant
        # must sit in each traced step (fused into the matmul operand
        # read), not be hoisted into a resident bf16 copy — see the
        # _params docstring.
        out, mut = model.apply(
            {"params": _params(variables), "cache": cache},
            toks, decode=True, decode_position=pos, last_only=True,
            mutable=["cache", STATS] if with_stats else ["cache"])
        return (extract_logits(out)[:, -1], mut["cache"],
                stats_total(stats_rows(mut.get(STATS))))

    def done(logits, cache, *stats):
        if not with_stats:
            return logits, cache
        stats = [s for s in stats if s is not None]
        return logits, cache, sum(stats) if stats else None

    if not chunk or p_len <= chunk:
        return done(*apply_chunk(cache, prompt, position))

    n_full, rem = divmod(p_len, chunk)

    def chunk_step(carry, toks):
        cache, pos = carry
        _, cache, stats = apply_chunk(cache, toks, pos)
        return (cache, pos + chunk), stats

    pos = jnp.array(position, jnp.int32)
    scanned = None
    if n_full > 1:
        # All but the last full chunk run through the scan emitting
        # NOTHING — stacking per-chunk logits would add n_full x B x
        # vocab of dead memory to a memory-bounding feature.  The last
        # full chunk runs standalone so its logits are the only ones
        # materialized.
        head = prompt[:, :(n_full - 1) * chunk].reshape(
            b, n_full - 1, chunk).swapaxes(0, 1)  # [n-1, B, chunk]
        (cache, pos), scanned = jax.lax.scan(chunk_step, (cache, pos),
                                             head)
        if scanned is not None:
            scanned = scanned.sum(axis=0)
    logits, cache, last = apply_chunk(
        cache, prompt[:, (n_full - 1) * chunk:n_full * chunk], pos)
    pos = pos + chunk
    tail = None
    if rem:
        logits, cache, tail = apply_chunk(
            cache, prompt[:, n_full * chunk:], pos)
    return done(logits, cache, scanned, last, tail)


def _rollback_cache(cache, new_index):
    """Rewind a decode cache to ``new_index`` consumed tokens.

    Stale entries past the index are invisible (the causal-append mask
    admits only positions <= the query's) and get overwritten by the
    next append, so rollback is just resetting every ``cache_index``
    leaf — no data movement.  A ``state`` leaf (kv_cache.leaf_kinds)
    has no index and no earlier self to return to: refused."""
    if any(kind == "state" for _, _, kind in leaf_kinds(cache)):
        raise ValueError(
            "a cache that holds recurrent state without a position "
            "axis cannot be rewound: no speculative decoding over "
            "this model")

    def one(path, leaf):
        if jax.tree_util.keystr(path).endswith("cache_index']"):
            return jnp.full_like(leaf, new_index)
        return leaf
    return jax.tree_util.tree_map_with_path(one, cache)


def generate_speculative(model, variables, draft_model, draft_variables,
                         prompt, *, max_new_tokens: int, k: int = 4,
                         eos_id: Optional[int] = None,
                         prefill_chunk: Optional[int] = None,
                         temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         rng: Optional[jax.Array] = None,
                         seed: Optional[int] = None,
                         keys: Optional[jax.Array] = None) -> jax.Array:
    """Speculative decoding: a small DRAFT model proposes ``k`` tokens
    per round; the target verifies all of them in ONE chunked forward
    (k+1 positions through the causal-append mask).

    **Greedy (temperature=0, the default):** commits the longest
    draft/target-argmax matching prefix plus the target's correction —
    output EXACTLY equals ``generate(model, ...)``'s greedy output
    (pinned in tests).  **Sampled (temperature>0):** standard
    rejection speculative sampling — proposal ``x ~ q`` is accepted
    with probability ``min(1, p(x)/q(x))``; the first rejected
    position resamples from the residual ``norm(max(p - q, 0))``.
    Each committed token is therefore distributed EXACTLY as a sample
    from the target's (temp/top-k/top-p-shaped) distribution, for any
    draft — the draft only changes the schedule.

    Sampled randomness comes from ONE of two schedules: ``rng``
    (split-chain per round, shaping via the same ``_modified_logits``
    the plain sampler uses), or ``seed``/``keys`` — the POSITION-KEYED
    schedule the continuous-batching engine's speculative slots run
    (every draft/accept/residual draw keyed by (seed, row, token
    index, lane) through the shared :func:`_spec_draft_row` /
    :func:`_spec_verify_row` kernels, shaping via
    :func:`_shape_logits_positional`): tokens are a pure function of
    the request, so this form is the solo REFERENCE engine
    speculative slots are pinned against, and a served sampled
    speculative request returns the same tokens solo or in a slot.

    Each round costs one draft scan (k small steps) plus one target
    forward of k+1 positions; at acceptance rate a the target runs
    ~(a*k+1)x fewer serial steps, which is the whole win on TPU where
    decode is latency-bound on weight reads per step.

    Per round the batch advances in LOCKSTEP by the minimum acceptance
    across rows (per-row cache indices would desynchronize the shared
    cache_index); rows that verified further simply re-derive those
    tokens next round — wasted work, never wrong tokens (sampled mode
    re-derives with FRESH randomness, which is still an exact sample
    from the target conditional).  Commits are capped at k per round
    (the all-accepted bonus token is dropped) so the cache rollback
    arithmetic is uniform.

    Both models must be decoder-only with the same vocab; ``eos_id``
    freezing is applied to the finished rows after the loop (identical
    semantics to generate()'s in-loop freeze).
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1; got "
                         f"{max_new_tokens}")
    _check_spec_k(k)
    sampled = temperature != 0.0
    positional = sampled and (keys is not None or seed is not None)
    if sampled and rng is None and not positional:
        raise ValueError("temperature > 0 requires an rng key or a "
                         "seed (use temperature=0 for greedy "
                         "decoding)")
    if positional and rng is not None:
        raise ValueError(
            "pass either rng (split-chain schedule) or seed/keys "
            "(position-keyed schedule), not both")
    _check_temperature(temperature)
    _check_top_p(top_p)
    _check_top_k(top_k, getattr(getattr(model, "cfg", None),
                                "vocab_size", None))
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    if positional and keys is None:
        keys = sample_stream_keys(seed, b)
    for m, nm in ((model, "target"), (draft_model, "draft")):
        max_pos = getattr(getattr(m, "cfg", None), "max_position", None)
        # The final round (entered at count <= max_new_tokens - 1,
        # i.e. consumed <= p_len + max_new_tokens - 2) appends k+1
        # entries, touching position p_len + max_new_tokens + k - 2 at
        # most — capacity needed is one more than that.  Ring caches
        # are position-keyed, not capacity-bounded — but the k+1-wide
        # verify scatter destroys K/V ``capacity`` positions back,
        # which a partial-acceptance rollback can put BACK inside the
        # window: they need ``kv_cache_ring_slack >= k-1`` spare slots
        # (see append_ring_kv_cache).
        mcfg = getattr(m, "cfg", None)
        if getattr(mcfg, "kv_cache_ring", False):
            slack = getattr(mcfg, "kv_cache_ring_slack", 0)
            if slack < k - 1:
                raise ValueError(
                    f"speculative decoding with k={k} on a ring-cache "
                    f"{nm} model needs kv_cache_ring_slack >= {k - 1} "
                    f"(got {slack}): the verify chunk overwrites up "
                    f"to k-1 still-in-window slots on a rollback")
            continue
        if max_pos is not None and \
                p_len + max_new_tokens + k - 1 > max_pos:
            raise ValueError(
                f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
                f"+ k ({k}) - 1 exceeds the {nm} model's max_position "
                f"({max_pos}); speculative rounds need k-1 slack slots")

    t_logits, t_cache = _prefill(model, variables, prompt,
                                 chunk=prefill_chunk)
    _, d_cache = _prefill(draft_model, draft_variables, prompt,
                          chunk=prefill_chunk)
    if positional:
        # Token index 0 draws exactly like the plain positional paths
        # (and the engine's admission sampler): fold_in(base, 0).
        rng = jax.random.PRNGKey(0)  # unused; keeps one loop carry
        first = _sample_positional(
            t_logits, keys, 0, temperature, top_k or 0,
            top_p or 0.0).astype(jnp.int32)               # [B]
    elif sampled:
        rng, key = jax.random.split(rng)
        first = _sample(t_logits, key, temperature, top_k,
                        top_p).astype(jnp.int32)          # [B]
    else:
        rng = jax.random.PRNGKey(0)  # unused; keeps one loop carry
        first = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)

    buf = jnp.zeros((b, max_new_tokens + k), jnp.int32)
    buf = buf.at[:, 0].set(first)

    def draft_step(carry, _):
        cache, tok, pos, key = carry
        out, mut = draft_model.apply(
            {"params": _params(draft_variables), "cache": cache},
            tok[:, None], decode=True, decode_position=pos,
            mutable=["cache"])
        logits = extract_logits(out)[:, -1]
        if sampled:
            key, sub = jax.random.split(key)
            q_logits = _modified_logits(logits, temperature, top_k,
                                        top_p)
            nxt = jax.random.categorical(sub, q_logits,
                                         axis=-1).astype(jnp.int32)
            q_row = jax.nn.softmax(q_logits.astype(jnp.float32),
                                   axis=-1)               # [B, V]
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            q_row = jnp.zeros((0,), jnp.float32)  # greedy: no density
        return (mut["cache"], nxt, pos + 1, key), (nxt, q_row)

    def round_body(state):
        t_cache, d_cache, x, buf, count, rng = state
        consumed = p_len + count - 1      # tokens both caches hold

        # Draft proposes d_1..d_k (feeds x, d_1..d_{k-1}).
        rng, r_draft, r_accept, r_res = jax.random.split(rng, 4)
        (d_cache, _, _, _), (d_toks, q_rows) = jax.lax.scan(
            draft_step, (d_cache, x, consumed, r_draft), None,
            length=k)
        d_toks = d_toks.T                 # [B, k]

        # Target verifies the whole chunk in one forward.
        chunk = jnp.concatenate([x[:, None], d_toks], axis=1)
        out, mut = model.apply(
            {"params": _params(variables), "cache": t_cache},
            chunk, decode=True, decode_position=consumed,
            mutable=["cache"])
        t_logits_all = extract_logits(out)                # [B, k+1, V]

        if sampled:
            # Rejection speculative sampling: accept x_i ~ q_i with
            # prob min(1, p_i(x_i)/q_i(x_i)); the first rejection
            # resamples from the residual norm(max(p_i - q_i, 0)).
            p_logits = _modified_logits(
                t_logits_all[:, :k], temperature, top_k, top_p)
            p_rows = jax.nn.softmax(p_logits.astype(jnp.float32),
                                    axis=-1)              # [B, k, V]
            q_rows = jnp.moveaxis(q_rows, 0, 1)           # [B, k, V]
            px = jnp.take_along_axis(
                p_rows, d_toks[..., None], axis=-1)[..., 0]  # [B, k]
            qx = jnp.take_along_axis(
                q_rows, d_toks[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(r_accept, (b, k))
            accept = u * qx < px          # u < p/q without the divide
            m_row = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
            c = jnp.minimum(jnp.min(m_row) + 1, k)        # scalar >= 1
            # Residual resample at EVERY position (vectorized); only
            # each row's first-rejection column is ever committed.
            resid = jnp.clip(p_rows - q_rows, 0.0, None)
            res = jax.random.categorical(
                r_res, jnp.log(resid + 1e-20),
                axis=-1).astype(jnp.int32)                # [B, k]
            cols = jnp.arange(k)[None, :]
            out_toks = jnp.where(cols < m_row[:, None], d_toks, res)
        else:
            t_toks = jnp.argmax(t_logits_all,
                                axis=-1).astype(jnp.int32)  # [B, k+1]
            # Leading-match count per row, lockstep min across the
            # batch; commit c = min(m)+1 target tokens, capped at k.
            matches = d_toks == t_toks[:, :k]             # [B, k]
            m_row = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
            c = jnp.minimum(jnp.min(m_row) + 1, k)        # scalar >= 1
            out_toks = t_toks[:, :k]

        # Write a static k-wide window at count; only c of it counts —
        # the next round's window overwrites the rest.
        buf = jax.lax.dynamic_update_slice(
            buf, out_toks, (0, count))
        x = jnp.take(out_toks, c - 1, axis=1)     # column c-1, [B]
        new_consumed = consumed + c
        t_cache = _rollback_cache(mut["cache"], new_consumed)
        d_cache = _rollback_cache(d_cache, new_consumed)
        return t_cache, d_cache, x, buf, count + c, rng

    # -- position-keyed rounds (the engine-shared schedule) -------------

    tk_, tp_ = (top_k or 0), (top_p or 0.0)

    def draft_step_positional(carry, _):
        cache, tok, pos, idx = carry
        out, mut = draft_model.apply(
            {"params": _params(draft_variables), "cache": cache},
            tok[:, None], decode=True, decode_position=pos,
            mutable=["cache"])
        logits = extract_logits(out)[:, -1]
        nxt, q_row = jax.vmap(lambda l, bk: _spec_draft_row(
            l, bk, idx, temperature, tk_, tp_))(logits, keys)
        return (mut["cache"], nxt, pos + 1, idx + 1), (nxt, q_row)

    def round_body_positional(state):
        t_cache, d_cache, x, buf, count, rng = state
        consumed = p_len + count - 1

        (d_cache, _, _, _), (d_toks, q_rows) = jax.lax.scan(
            draft_step_positional, (d_cache, x, consumed, count),
            None, length=k)
        d_toks = d_toks.T                                 # [B, k]
        q_rows = jnp.moveaxis(q_rows, 0, 1)               # [B, k, V]

        chunk = jnp.concatenate([x[:, None], d_toks], axis=1)
        out, mut = model.apply(
            {"params": _params(variables), "cache": t_cache},
            chunk, decode=True, decode_position=consumed,
            mutable=["cache"])
        t_logits_all = extract_logits(out)                # [B, k+1, V]

        out_toks, c_rows, _ = jax.vmap(
            lambda tl, dt, qr, bk: _spec_verify_row(
                tl[:k], dt, qr, bk, count, temperature, tk_, tp_,
                k))(t_logits_all[:, :k + 1], d_toks, q_rows, keys)
        # Lockstep cache advance by the batch-min acceptance (shared
        # schedule mechanics, exactly like the chain path) — but the
        # TOKENS stay per-row exact: a row that verified further
        # re-derives the same tokens next round, because every draw
        # is keyed by (row, token index) and the committed prefix is
        # unchanged.  Per-slot engine execution therefore matches
        # this lockstep reference bit-for-bit.
        c = jnp.min(c_rows)                               # scalar >= 1
        buf = jax.lax.dynamic_update_slice(buf, out_toks, (0, count))
        x = jnp.take(out_toks, c - 1, axis=1)             # [B]
        new_consumed = consumed + c
        t_cache = _rollback_cache(mut["cache"], new_consumed)
        d_cache = _rollback_cache(d_cache, new_consumed)
        return t_cache, d_cache, x, buf, count + c, rng

    def cond(state):
        return state[4] < max_new_tokens

    state = (t_cache, d_cache, first, buf, jnp.array(1, jnp.int32),
             rng)
    *_, buf, _, _ = jax.lax.while_loop(
        cond, round_body_positional if positional else round_body,
        state)
    new = buf[:, :max_new_tokens]

    if eos_id is not None:
        # Freeze rows after their first eos (generate()'s semantics).
        hit = jnp.cumsum(
            jnp.cumsum(new == eos_id, axis=1), axis=1) > 1
        new = jnp.where(hit, eos_id, new)
    return jnp.concatenate([prompt, new], axis=1)


def generate_beam(model, variables, prompt, *, max_new_tokens: int,
                  num_beams: int = 4, eos_id: Optional[int] = None,
                  length_penalty: float = 1.0,
                  prefill_chunk: Optional[int] = None) -> jax.Array:
    """Beam-search decoding (one jitted scan, KV cache tiled per beam).

    Returns the highest-scoring sequence per batch row, [B, P +
    max_new_tokens].  Scores are summed token log-probs divided by
    ``len ** length_penalty``; finished beams (eos) freeze their score
    and keep emitting eos.  ``num_beams=1`` is greedy search.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1; got "
                         f"{max_new_tokens}")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1; got {num_beams}")
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    k = num_beams
    # The per-beam tile and parent reorder address the BATCH axis of
    # the cache entries: axis 1 for the scan-stacked [layers, B, S,
    # ...] layout, axis 0 for unstacked [B, S, ...] entries (round 5
    # — previously refused; gathering the wrong axis would permute
    # POSITIONS into garbage, ADVICE r2, so the axis is layout-keyed).
    batch_axis = 1 if getattr(getattr(model, "cfg", None),
                              "scan_layers", True) else 0
    ring = getattr(getattr(model, "cfg", None), "kv_cache_ring", False)
    max_pos = getattr(getattr(model, "cfg", None), "max_position", None)
    # Ring caches are position-keyed, not capacity-bounded: beam
    # decoding streams past max_position like greedy does (RoPE is
    # pure arithmetic).  The batch-invariant ring leaves (cached_pos
    # [layers, cap], no batch axis) are handled inside _beam_loop —
    # beams decode in lockstep, so every beam shares one position
    # schedule and those leaves are never tiled or reordered.
    if not ring and max_pos is not None \
            and p_len + max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's max_position ({max_pos})")

    # Prefill once on [B, P]; _beam_loop tiles the cache per beam.
    first_logits, cache = _prefill(model, variables, prompt,
                                   chunk=prefill_chunk)

    def apply_step(cache, toks_flat, t):
        out, mut = model.apply(
            {"params": _params(variables), "cache": cache},
            toks_flat, decode=True, decode_position=p_len + t,
            mutable=["cache"])
        return extract_logits(out)[:, -1], mut["cache"]

    seq = _beam_loop(apply_step, cache, first_logits, b=b,
                     max_new_tokens=max_new_tokens, num_beams=k,
                     eos_id=eos_id, length_penalty=length_penalty,
                     batch_axis=batch_axis)
    return jnp.concatenate([prompt, seq], axis=1)


def _beam_loop(apply_step, cache, first_logits, *, b: int,
               max_new_tokens: int, num_beams: int,
               eos_id: Optional[int], length_penalty: float,
               batch_axis: int = 1):
    """Shared beam-search machinery for :func:`generate_beam` and
    :func:`generate_beam_seq2seq`.

    ``apply_step(cache, toks_flat, t) -> (logits, cache)`` runs one
    decoder step on ``toks_flat`` [B*K, 1] at scan tick ``t``;
    ``first_logits`` [B, V] are the prefill's last-position logits and
    ``cache`` the post-prefill (un-tiled, batch B) cache.  Beams live
    b-major on the cache entries' BATCH axis — ``batch_axis`` keys the
    layout: 1 for scan-stacked [layers, B*K, ...] entries, 0 for
    unstacked [B*K, ...] ones.  Only rank>=2 leaves tile/reorder
    (cache_index scalars/[layers] vectors skip by rank; the ring's
    batch-less cached_pos by name).  Returns the generated tokens
    [B, max_new_tokens].
    """
    k = num_beams
    lp = jax.nn.log_softmax(first_logits.astype(jnp.float32), axis=-1)
    vocab = lp.shape[-1]
    scores, first = jax.lax.top_k(lp, k)                   # [B, K]

    def _batch_invariant(path) -> bool:
        # Leaves with no batch axis: the ring cache's position table
        # (cached_pos [layers, cap] — axis 1 is SLOTS) is shared by
        # every row and beam (lockstep decoding), so tiling or
        # parent-gathering it would corrupt the slot arithmetic.
        return "cached_pos" in jax.tree_util.keystr(path)

    def _tile(path, x):
        if x.ndim < 2 or _batch_invariant(path):
            return x
        if x.shape[batch_axis] != b:
            # Structural guard (ADVICE r2 failure class): a rank>=2
            # cache leaf whose expected batch axis is NOT batch-sized
            # would be tiled/gathered along slots or positions and
            # silently emit garbage — fail loudly naming the leaf so
            # a new batch-less cache table gets added to the skip
            # list instead of corrupting beams.
            raise ValueError(
                f"beam search cannot tile cache leaf "
                f"{jax.tree_util.keystr(path)}: axis {batch_axis} has "
                f"size {x.shape[batch_axis]}, expected batch {b} "
                f"(batch-less tables must be skipped explicitly)")
        return jnp.repeat(x, k, axis=batch_axis)

    cache = jax.tree_util.tree_map_with_path(_tile, cache)
    done = (first == eos_id) if eos_id is not None \
        else jnp.zeros((b, k), bool)
    # Per-beam GENERATED length at finish (the length-penalty
    # denominator); unfinished beams hold the full budget.
    fin_len = jnp.where(done, 1, max_new_tokens).astype(jnp.float32)

    def step(carry, t):
        cache, toks_prev, scores, done, fin_len = carry    # toks [B,K]
        logits, cache = apply_step(cache, toks_prev.reshape(b * k, 1),
                                   t)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                axis=-1).reshape(b, k, vocab)
        if eos_id is not None:
            # Finished beams contribute exactly one continuation (eos
            # at no cost) so they compete but never fork.
            frozen = jnp.full((vocab,), -jnp.inf).at[eos_id].set(0.0)
            lp = jnp.where(done[..., None], frozen[None, None], lp)
        cand = scores[..., None] + lp                      # [B,K,V]
        scores, flat = jax.lax.top_k(cand.reshape(b, k * vocab), k)
        parent = flat // vocab                             # [B,K]
        tok = (flat % vocab).astype(jnp.int32)
        flat_parent = (jnp.arange(b)[:, None] * k + parent).reshape(-1)

        def reorder(path, x):
            # Cross-attention K/V (seq2seq) are beam-INVARIANT: every
            # beam of a batch row holds the same encoder projections,
            # and parents never cross batch rows, so the gather would
            # be a no-op permutation — skip it (they still tile above
            # so attention sees the [B*K, ...] batch layout).  Ring
            # position tables have no batch axis at all — skip.
            if x.ndim < 2 or "cross_" in jax.tree_util.keystr(path) \
                    or _batch_invariant(path):
                return x
            return jnp.take(x, flat_parent, axis=batch_axis)

        cache = jax.tree_util.tree_map_with_path(reorder, cache)
        done = jnp.take_along_axis(done, parent, axis=1)
        fin_len = jnp.take_along_axis(fin_len, parent, axis=1)
        if eos_id is not None:
            newly = ~done & (tok == eos_id)
            # token emitted at scan step t is generated token #t+2
            fin_len = jnp.where(newly, jnp.float32(t + 2), fin_len)
            done = done | newly
        return (cache, tok, scores, done, fin_len), (tok, parent)

    carry = (cache, first.astype(jnp.int32), scores, done, fin_len)
    if max_new_tokens > 1:
        carry, (toks, parents) = jax.lax.scan(
            step, carry, jnp.arange(max_new_tokens - 1))
    else:
        toks = jnp.zeros((0, b, k), jnp.int32)
        parents = jnp.zeros((0, b, k), jnp.int32)
    _, _, scores, _, fin_len = carry

    # Backtrack the surviving beams from last step to first.
    def back(beam, step_t):
        tok_t, parent_t = step_t
        tok = jnp.take_along_axis(tok_t, beam[:, None], 1)[:, 0]
        beam = jnp.take_along_axis(parent_t, beam[:, None], 1)[:, 0]
        return beam, tok

    best = jnp.argmax(scores / (fin_len ** length_penalty), axis=-1)
    beam = best
    rev = []
    for t in range(toks.shape[0] - 1, -1, -1):
        beam, tok = back(beam, (toks[t], parents[t]))
        rev.append(tok)
    first_tok = jnp.take_along_axis(first, beam[:, None], 1)[:, 0]
    seq = jnp.stack([first_tok] + rev[::-1], axis=1) if rev else \
        first_tok[:, None]
    return seq.astype(jnp.int32)


def generate_beam_seq2seq(model, variables, enc_tokens, *,
                          max_new_tokens: int, num_beams: int = 4,
                          eos_id: Optional[int] = None,
                          length_penalty: float = 1.0,
                          enc_mask: Optional[jax.Array] = None,
                          start_id: Optional[int] = None) -> jax.Array:
    """Beam-search decoding for seq2seq (T5-style) models.

    Encodes once, then beams over the decoder KV cache (same scan +
    per-beam cache reorder as :func:`generate_beam`); the encoder
    output and padding mask are tiled per beam so cross-attention sees
    the beam-major [B*K, ...] batch layout.  Returns the
    highest-scoring GENERATED tokens [B, max_new_tokens].
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1; got "
                         f"{max_new_tokens}")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1; got {num_beams}")
    # Cache-entry batch axis follows the layout (see generate_beam):
    # 1 for scanned [layers, B, ...], 0 for unstacked [B, ...].
    batch_axis = 1 if getattr(model.cfg, "scan_layers", True) else 0
    if start_id is None:
        start_id = model.cfg.pad_id
    max_pos = getattr(model.cfg, "max_position", None)
    if max_pos is not None and max_new_tokens > max_pos:
        raise ValueError(
            f"max_new_tokens ({max_new_tokens}) exceeds the decoder's "
            f"max_position ({max_pos})")
    enc_tokens = jnp.asarray(enc_tokens, jnp.int32)
    b = enc_tokens.shape[0]
    params = {"params": _params(variables)}
    enc_out = model.apply(params, enc_tokens, enc_mask=enc_mask,
                          method="encode")
    enc_tiled = jnp.repeat(enc_out, num_beams, axis=0)     # b-major
    mask_tiled = None if enc_mask is None else \
        jnp.repeat(jnp.asarray(enc_mask), num_beams, axis=0)

    # Empty cache: the prefill creates self-attn + cross K/V entries
    # (generate_seq2seq rationale).
    start = jnp.full((b, 1), start_id, jnp.int32)
    out, mut = model.apply(
        {"params": _params(variables), "cache": {}},
        start, enc_out, enc_mask=enc_mask, decode=True,
        decode_position=0, last_only=True, mutable=["cache"],
        method="decode")

    def apply_step(cache, toks_flat, t):
        out, mut = model.apply(
            {"params": _params(variables), "cache": cache},
            toks_flat, enc_tiled, enc_mask=mask_tiled, decode=True,
            decode_position=1 + t, last_only=True, mutable=["cache"],
            method="decode")
        return extract_logits(out)[:, -1], mut["cache"]

    return _beam_loop(apply_step, mut["cache"],
                      extract_logits(out)[:, -1], b=b,
                      max_new_tokens=max_new_tokens, num_beams=num_beams,
                      eos_id=eos_id, length_penalty=length_penalty,
                      batch_axis=batch_axis)
