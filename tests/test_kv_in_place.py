"""The KV pool is updated IN PLACE (serving/slots.py, models/
scan_stack.py, models/kv_cache.py).

Five obligations: (a) every program that takes the pool consumes the
tree it was handed, and the manager counts it; (b) the compiled decode
window aliases every pool leaf to an output and moves nothing of the
pool's size but the rows it writes; (c) what the pool decodes equals,
token for token, a forward that has no cache at all; (d) a dispatch
that fails AFTER it consumed the pool goes to recovery, one that fails
before it is still retried in place; (e) a step reads the planes only
as far as the pool's furthest position — the same tokens and logits as
a step that reads them whole, and the rows the host counts are the rows
the program took; (f) a decode step writes its rows ONCE, after the
layer loop — the same tokens and logits, bit for bit, as the step that
writes them inside it, and the writes the host counts are the writes
the program makes.
"""

import collections
import contextlib
import dataclasses
import importlib.util
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models import kv_cache
from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving import (DecodeEngine, FaultPlan, RetryPolicy)
from polyaxon_tpu.serving.paged import PagedSlotKVManager
from polyaxon_tpu.serving.scheduler import SamplingSpec, SchedulerPolicy
from polyaxon_tpu.serving.slots import SlotKVManager

from test_chip_compile import _layer_loop

SLOTS = 4
# One prompt a slot, every slot at another position.
PROMPTS = ([3, 1, 4], [1, 5, 9, 2, 6], [5, 3, 5, 8, 9, 7, 9],
           [2, 7, 1, 8, 2, 8, 1, 8, 2])
SAMP = dict(temperature=0.9, top_k=16, top_p=0.95)


def _model(int8=False):
    cfg = dataclasses.replace(
        GPT2Config.tiny(), vocab_size=32, hidden_size=32,
        num_layers=2, num_heads=2, max_position=64,
        dtype=jnp.float32, kv_cache_int8=int8)
    model = GPT2Model(cfg=cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


@pytest.fixture(scope="module")
def small_model():
    return _model()


def _key(row):
    return np.asarray(jax.random.key_data(
        jax.random.fold_in(jax.random.PRNGKey(11), row)), np.uint32)


def _fill(mgr, model, variables, *, sampled=False, first=None, **kw):
    """Prefill every prompt (the B=1 chunked prefill, decode=True with
    S > 1) and insert it; ``first[i]`` is slot i's token 0."""
    for slot, prompt in enumerate(PROMPTS):
        assert mgr.acquire() == slot
        _, cache = G.prefill(model, variables,
                             np.asarray([prompt], np.int32))
        extra = dict(base_key=_key(slot), **SAMP) if sampled else {}
        mgr.insert(slot, cache, first[slot] if first else 1,
                   len(prompt), **extra, **kw)


# -- (a) consumed, and counted ----------------------------------------------


def _leaves(*pools):
    return [l for p in pools for l in jax.tree.leaves(p)]


def test_step_and_insert_consume_the_pool(small_model):
    model, variables = small_model
    mgr = SlotKVManager(model, variables, SLOTS)
    _, cache = G.prefill(model, variables, np.asarray([PROMPTS[0]]))
    mgr.insert(mgr.acquire(), cache, 1, 3)
    assert mgr.kv_pool_bytes == sum(
        l.nbytes for l in jax.tree.leaves(mgr.kv_pool()))
    old = _leaves(mgr.kv_pool())
    mgr.insert(mgr.acquire(), cache, 1, 3)
    assert all(l.is_deleted() for l in old)
    for window, sampled in ((1, False), (4, True)):
        old = _leaves(mgr.kv_pool())
        mgr.step(window, sampled)
        assert all(l.is_deleted() for l in old)
        assert not mgr.pool_lost()
    assert mgr.kv_pool_dispatches_total == 4
    assert mgr.kv_pool_in_place_total == 4
    mgr.reset()
    assert mgr.kv_pool_bytes == 0 and not mgr.pool_lost()
    assert mgr.kv_pool_dispatches_total == 4    # totals outlive a reset


def test_pool_programs_compile_outside_the_persistent_cache_where_pinned(
        small_model, monkeypatch):
    """Where the pinned layout is not the device's default (a TPU,
    heads under 128 lanes) every pool program's FIRST call runs with
    the persistent compilation cache off — an executable read back
    from it mislabels its results' layout — and later calls, and the
    process's setting afterwards, are left alone.  A CPU rests
    row-major, so nothing is switched here unless told."""
    from polyaxon_tpu import config

    model, variables = small_model
    seen = []
    real = config.fresh_compile

    def spy():
        seen.append(jax.config.jax_enable_compilation_cache)
        return real()

    monkeypatch.setattr(config, "fresh_compile", spy)
    was = jax.config.jax_enable_compilation_cache
    _, cache = G.prefill(model, variables, np.asarray([PROMPTS[0]]))
    for pinned_default, firsts in ((True, 0), (False, 3)):
        mgr = SlotKVManager(model, variables, SLOTS)
        mgr._pin_is_default = pinned_default
        del seen[:]
        for _ in range(2):
            mgr.insert(mgr.acquire(), cache, 1, 3)     # alloc + insert
            mgr.step(2, False)
        assert mgr._pin_is_default == pinned_default
        assert len(seen) == firsts      # alloc, insert, step: once each
        assert jax.config.jax_enable_compilation_cache == was
    with config.fresh_compile():
        assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_enable_compilation_cache == was


def test_spec_step_consumes_both_pools(small_model):
    model, variables = small_model
    dvars = model.init(jax.random.PRNGKey(99),
                       jnp.zeros((1, 4), jnp.int32))
    mgr = SlotKVManager(model, variables, SLOTS, draft_model=model,
                        draft_variables=dvars)
    prompt = np.asarray([PROMPTS[1]])
    _, cache = G.prefill(model, variables, prompt)
    _, d_cache = G.prefill(model, dvars, prompt)
    mgr.insert(mgr.acquire(), cache, 1, 5, draft_cache=d_cache, spec_k=2)
    old = _leaves(mgr._stacked, mgr._draft_stacked)
    mgr.insert(mgr.acquire(), cache, 1, 5, draft_cache=d_cache, spec_k=2)
    assert all(l.is_deleted() for l in old)
    old = _leaves(mgr._stacked, mgr._draft_stacked)
    mgr.step_spec(2, 2)
    assert all(l.is_deleted() for l in old)
    # two pools an insertion, one dispatch for the round
    assert mgr.kv_pool_dispatches_total == 5
    assert mgr.kv_pool_in_place_total == 5
    assert mgr.kv_pool_bytes == sum(
        l.nbytes for l in _leaves(mgr._stacked, mgr._draft_stacked))


# -- (b) the compiled window ------------------------------------------------


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_decode_window_aliases_the_pool_and_moves_only_rows(
        small_model, sampled):
    model, variables = small_model
    mgr = SlotKVManager(model, variables, SLOTS)
    _fill(mgr, model, variables, sampled=sampled)
    mgr.step(8, sampled)
    fn = mgr._step_fns[(8, sampled)]        # partial(jitted, weights)
    operands = [jnp.asarray(8, jnp.int32)] + [
        jnp.asarray(x) for x in mgr.state.operands(
            "sampled" if sampled else "plain")]
    pool = mgr.kv_pool()
    text = fn.func.lower(*fn.args, pool, *operands).compile().as_text()

    # every pool leaf (the parameters after the weights') is aliased
    # to an output
    n_w = len(jax.tree.leaves(fn.args))
    header = text.split("\n", 1)[0]
    aliased = {int(m) for m in re.findall(
        r"\{\d+\}: \((\d+), \{\}", header.split(
            "input_output_alias=", 1)[1].split(
            "entry_computation_layout", 1)[0])}
    want = set(range(n_w, n_w + len(jax.tree.leaves(pool))))
    assert want <= aliased, (want, aliased)

    # and nothing of a K/V leaf's whole shape is copied or rewritten:
    # the only instructions that produce it are the row writes (and
    # the loops, tuples and parameters that carry it)
    # (a fusion of that shape is a row write's wrapper)
    kv = {l.shape for l in jax.tree.leaves(pool) if l.ndim >= 5}
    assert kv
    made, movers = _made(text, kv)
    assert not movers, movers
    writes = made["scatter"] + made["dynamic-update-slice"]
    n_kv = sum(l.ndim >= 5 for l in jax.tree.leaves(pool))
    assert writes == n_kv and made["fusion"] <= writes, made
    # exactly one write a K/V leaf, and none of them inside the loop
    # that walks the layers: a decode step's rows are written after it
    # (kv_cache.defers)
    inside, _ = _made(_layer_loop(text), kv)
    assert not (inside["scatter"] + inside["dynamic-update-slice"]
                + inside["fusion"]), inside


_MADE = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                   r"([a-z\-]+)\(")


def _made(text, shapes):
    """``(Counter of the opcodes that make a result of one of
    ``shapes``, the lines of those that are no carrier and no row
    write)`` over an HLO text."""
    made, movers = collections.Counter(), []
    for line in text.splitlines():
        m = _MADE.match(line)
        if not m or not m.group(2):
            continue
        if tuple(int(d) for d in m.group(2).split(",")) not in shapes:
            continue
        made[m.group(3)] += 1
        if m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast", "while",
                "dynamic-update-slice", "scatter", "fusion"):
            movers.append(line.strip()[:160])
    return made, movers


# -- (c) equal to a forward without any cache -------------------------------


def _no_cache_tokens(model, variables, prompt, row, n, sampled):
    """``n`` tokens after ``prompt`` with NO cache: every token from
    the whole prefix through ``model.apply`` in float32."""
    seq, out = list(prompt), []
    for index in range(n):
        logits = model.apply({"params": variables["params"]},
                             jnp.asarray([seq], jnp.int32))[0, -1]
        assert logits.dtype == jnp.float32
        if sampled:
            tok = G._sample_positional_row(
                logits, jnp.asarray(_key(row)), index,
                SAMP["temperature"], SAMP["top_k"], SAMP["top_p"])
        else:
            tok = jnp.argmax(logits)
        out.append(int(tok))
        seq.append(int(tok))
    return out


@pytest.fixture(scope="module")
def no_cache():
    memo = {}

    def get(int8, sampled):
        # int8 KV storage rounds what is stored: its reference is the
        # same weights read through the same forward, so the case also
        # says the rounding moved no token of these streams.
        if (int8, sampled) not in memo:
            model, variables = _model()
            memo[(int8, sampled)] = [
                _no_cache_tokens(model, variables, p, row, 13, sampled)
                for row, p in enumerate(PROMPTS)]
        return memo[(int8, sampled)]
    return get


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("storage", ["plain", "int8", "paged"])
def test_pool_decode_equals_forward_without_cache(no_cache, storage,
                                                  sampled, window):
    """Four slots at four positions, 12 decode steps (window 1: twelve
    dispatches; window 8: one of 8 and one of 4), every dispatch
    through the program of capacity 8 (the paged pool keeps a program
    a window)."""
    model, variables = _model(int8=(storage == "int8"))
    want = no_cache(storage == "int8", sampled)
    kw = {}
    if storage == "paged":
        mgr = PagedSlotKVManager(model, variables, SLOTS, page_tokens=8,
                                 max_position=64, decode_window=8)
        kw = dict(total_tokens=32)
    else:
        mgr = SlotKVManager(model, variables, SLOTS)
    _fill(mgr, model, variables, sampled=sampled,
          first=[w[0] for w in want], **kw)
    windows = [1] * 12 if window == 1 else [8, 4]
    got = np.concatenate(
        [mgr.step(w, sampled, 8) for w in windows])     # [12, S]
    assert got.T.tolist() == [w[1:] for w in want]
    # one program a variant, whatever the windows asked for
    assert len(mgr._step_fns) == (len(set(windows))
                                  if storage == "paged" else 1)


# -- (d) the failure contract -----------------------------------------------


def _engine(model, variables, faults=None):
    return DecodeEngine(
        model, variables,
        policy=SchedulerPolicy(n_slots=4, decode_window=2,
                               queue_depth=16),
        faults=FaultPlan.load(faults) if faults is not None else None,
        retry_policy=RetryPolicy(max_attempts=3, base_delay_s=0.001,
                                 max_delay_s=0.01))


def _requests():
    return [(np.asarray([p], np.int32), 8,
             SamplingSpec(seed=5, **SAMP) if i % 2 else None)
            for i, p in enumerate(PROMPTS)]


def _solo(model, variables, prompt, new, samp):
    if samp is None:
        return G.generate(model, variables, prompt, max_new_tokens=new)
    return G.generate_positional(
        model, variables, prompt, max_new_tokens=new, seed=samp.seed,
        temperature=samp.temperature, top_k=samp.top_k,
        top_p=samp.top_p)


def test_failure_after_the_pool_was_consumed_goes_to_recovery(
        small_model):
    """The third dispatch runs its program — the pool is consumed —
    and then fails.  No retry in place: the residents are requeued,
    the pool is rebuilt, and every reply is the solo reference's."""
    model, variables = small_model
    eng = _engine(model, variables)
    real, calls = eng.slots.launch, []

    def launch(window, **kw):
        calls.append(window)
        if len(calls) == 3:
            old = jax.tree.leaves(eng.slots.kv_pool())
            real(window, **kw)
            # what a failed execution leaves behind: the old tree,
            # its arrays deleted
            eng.slots._stacked = jax.tree.unflatten(
                jax.tree.structure(eng.slots._stacked), old)
            raise RuntimeError("the device failed mid-program")
        return real(window, **kw)

    eng.slots.launch = launch
    try:
        groups = [eng.submit(p, new, None, None, sampling=s)
                  for p, new, s in _requests()]
        for g in groups:
            assert g.event.wait(timeout=120), "hung caller"
        st = eng.stats()
    finally:
        eng.close()
    for g, (p, new, s) in zip(groups, _requests()):
        assert g.error is None
        assert g.result().tolist() == \
            np.asarray(_solo(model, variables, p, new, s)).tolist()
    assert st["kv_pool_lost_total"] == 1
    assert st["step_retries_total"] == 0
    assert st["requests_requeued_total"] >= 1
    assert st["kv_pool_in_place_total"] \
        == st["kv_pool_dispatches_total"] > 0


def test_fault_before_dispatch_still_retries_in_place(small_model):
    model, variables = small_model
    eng = _engine(model, variables, faults={"seed": 2, "faults": [
        {"site": "step", "kind": "transient", "times": 2}]})
    try:
        groups = [eng.submit(p, new, None, None, sampling=s)
                  for p, new, s in _requests()]
        for g in groups:
            assert g.event.wait(timeout=120), "hung caller"
        st = eng.stats()
    finally:
        eng.close()
    for g, (p, new, s) in zip(groups, _requests()):
        assert g.error is None
        assert g.result().tolist() == \
            np.asarray(_solo(model, variables, p, new, s)).tolist()
    assert st["step_retries_total"] == 2
    assert st["kv_pool_lost_total"] == 0
    assert st["requests_requeued_total"] == 0


# -- (e) the planes are read as far as the furthest position ----------------

CAP = 64            # _model()'s max_position: widths 8, 16, 32, 64


@contextlib.contextmanager
def _whole_plane():
    """No read extent reaches a program traced in here: the step body
    reads every plane whole, as before the bounded read."""
    from polyaxon_tpu.serving import slots

    real = slots.read_extent
    slots.read_extent = lambda *a, **kw: contextlib.nullcontext()
    try:
        yield
    finally:
        slots.read_extent = real


@pytest.fixture(scope="module")
def twin_pools():
    """For a storage and a variant: two managers over the same model,
    one whose programs read to the extent and one whose programs read
    the planes whole, and the prefill of a position.  Compiled once;
    a case resets them."""
    memo = {}

    def get(storage, sampled):
        if (storage, sampled) not in memo:
            model, variables = _model(int8=(storage == "int8"))
            pair = (SlotKVManager(model, variables, SLOTS),
                    SlotKVManager(model, variables, SLOTS))
            caches = {}

            def cache_at(position):
                if position not in caches:
                    toks = (np.arange(position) * 7 + 3) % 32
                    caches[position] = G.init_cache(model, 1) \
                        if not position else G.prefill(
                            model, variables, toks[None].astype(
                                np.int32))[1]
                return caches[position]

            memo[(storage, sampled)] = pair, cache_at
        return memo[(storage, sampled)]
    return get


def _step_both(pair, cache_at, positions, window, sampled):
    """Reset both managers, seat a stream at each of ``positions``
    (the other slots stay idle, parked at 0), run one window on each.
    Returns (tokens, last logits) of the bounded and of the whole
    read."""
    out = []
    for whole, mgr in enumerate(pair):
        mgr.reset()
        for slot, position in enumerate(positions):
            assert mgr.acquire() == slot
            extra = dict(base_key=_key(slot), **SAMP) if sampled else {}
            mgr.insert(slot, cache_at(position), 1 + slot, position,
                       **extra)
        with _whole_plane() if whole else contextlib.nullcontext():
            toks = mgr.step(window, sampled, 8)
        out.append((toks, np.asarray(mgr.last_logits)))
    return out


# The furthest slot at 0; one under, on and one past the first width's
# edge (a step at position p reads rows [0, p]: extent p + 1); at the
# last position; and a window of 8 from position 5, whose extents 6..13
# cross the edge inside the loop.
EXTENT_CASES = {"at-0": (0, 1), "under-the-edge": (6, 1),
                "on-the-edge": (7, 1), "past-the-edge": (8, 1),
                "at-cap-1": (CAP - 1, 1), "window-crosses": (5, 8)}


@pytest.mark.parametrize("case", sorted(EXTENT_CASES))
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("storage", ["plain", "int8"])
def test_bounded_read_equals_the_whole_plane_read(twin_pools, storage,
                                                  sampled, case):
    """Two streams at unlike positions and two idle slots: the step
    that reads to the extent gives the tokens of the step that reads
    the planes whole, and its logits to float32 rounding."""
    furthest, window = EXTENT_CASES[case]
    pair, cache_at = twin_pools(storage, sampled)
    positions = (furthest, min(furthest, 3))
    (toks, logits), (toks_w, logits_w) = _step_both(
        pair, cache_at, positions, window, sampled)
    live = slice(0, len(positions))
    assert toks[:, live].tolist() == toks_w[:, live].tolist()
    np.testing.assert_allclose(logits[live], logits_w[live],
                               rtol=1e-6, atol=1e-6)
    # what the host counted is what the extents give: a plane a layer
    # and slot, every step of the window
    bounded = pair[0]
    before = (bounded.plane_reads.read, bounded.plane_reads.held)
    _step_both(pair[:1], cache_at, positions, window, sampled)
    widths = kv_cache.prefix_width(
        furthest + 1 + np.arange(window), CAP)
    planes = 2 * SLOTS            # layers x slots
    assert bounded.plane_reads.read - before[0] == planes * widths.sum()
    assert bounded.plane_reads.held - before[1] == planes * CAP * window


def _poisoned(pool, first_row, last_row=None):
    """The pool with NaN in rows [first_row, last_row) of every VALUE
    plane: a masked key weighs exactly 0, and 0 x NaN is NaN, so a
    poisoned row that the program reads shows in every logit."""
    def one(path, leaf):
        if jax.tree_util.keystr(path).endswith("cached_value']"):
            return leaf.at[..., first_row:last_row, :, :].set(jnp.nan)
        return leaf
    return jax.tree_util.tree_map_with_path(one, pool)


@pytest.mark.parametrize("furthest", [0, 6, 7, 8, 30, CAP - 1])
def test_the_rows_counted_are_the_rows_the_program_took(twin_pools,
                                                        furthest):
    """``prefix_width`` against the compiled program: the step leaves
    the rows past the counted width unread (poisoned, they change
    nothing) and reads the last row inside it (poisoned, though masked,
    it turns the logits to NaN)."""
    (mgr, _), cache_at = twin_pools("plain", False)
    positions = (furthest, min(furthest, 3))

    def step(poison):
        mgr.reset()
        for slot, position in enumerate(positions):
            mgr.acquire()
            mgr.insert(slot, cache_at(position), 1 + slot, position)
        mgr._stacked = poison(mgr._stacked)
        before = mgr.plane_reads.read
        mgr.step(1, False, 8)
        return (np.asarray(mgr.last_logits)[:len(positions)],
                (mgr.plane_reads.read - before) // (2 * SLOTS))

    clean, width = step(lambda pool: pool)
    assert width == kv_cache.prefix_width(furthest + 1, CAP) \
        >= furthest + 1
    if width < CAP:
        beyond, _ = step(lambda pool: _poisoned(pool, width))
        assert np.array_equal(beyond, clean)
    if width - 1 > furthest:        # a masked row inside the width
        inside, _ = step(lambda pool: _poisoned(pool, width - 1, width))
        assert np.isnan(inside).all()


@pytest.mark.parametrize("cap", [5, 64, 100, 1024, 8192])
def test_prefix_width_covers_the_extent_and_no_more_than_the_plane(cap):
    choices = kv_cache.prefix_widths(cap)
    assert choices == tuple(sorted(set(choices))) and choices[-1] == cap
    assert len(choices) <= len(kv_cache.PREFIX_SHARES)
    extents = np.arange(0, cap + 40)
    widths = kv_cache.prefix_width(extents, cap)
    assert (np.diff(widths) >= 0).all()                 # monotone
    assert (widths >= np.minimum(extents, cap)).all()   # covers it
    assert set(widths) == set(choices)
    # the narrowest that covers: the next narrower one would not
    branch = kv_cache.prefix_branch(extents, cap)
    narrower = np.asarray((0,) + choices)[branch]
    assert (narrower < np.maximum(extents, 1)).all()
    # the traced branch index is the host's
    traced = jax.jit(lambda e: kv_cache.prefix_branch(e, cap))(
        jnp.asarray(extents, jnp.int32))
    assert np.array_equal(traced, branch)


def test_without_an_extent_the_plane_is_read_whole(small_model):
    """Solo ``generate``'s step, the speculative round, beam search:
    nothing opens a read extent, no conditional is traced."""
    model, variables = small_model
    cache = G.init_cache(model, 1)
    tok = jnp.zeros((1, 1), jnp.int32)

    def apply(cache, tok):
        return model.apply({"params": variables["params"],
                            "cache": cache}, tok, decode=True,
                           decode_position=0, mutable=["cache"])

    def bounded(cache, tok):    # the scope opens INSIDE what is traced
        with kv_cache.read_extent():
            return apply(cache, tok)

    assert "cond" not in str(jax.make_jaxpr(apply)(cache, tok))
    assert "cond" in str(jax.make_jaxpr(bounded)(cache, tok))


def test_engine_counts_plane_rows_of_steps_and_chunks(small_model):
    model, variables = small_model
    eng = _engine(model, variables)
    try:
        groups = [eng.submit(p, new, None, None, sampling=s)
                  for p, new, s in _requests()]
        for g in groups:
            assert g.event.wait(timeout=120), "hung caller"
        st = eng.stats()
    finally:
        eng.close()
    # every stream stands under position 17 of 64: no step and no
    # prefill read past the third of the four widths
    assert 0 < st["kv_plane_rows_read_total"] \
        <= st["kv_plane_rows_held_total"] * 32 // CAP
    # 2 layers: a plane a layer for each prefill, a plane a layer and
    # slot for each step
    assert st["kv_plane_rows_held_total"] == 2 * CAP * (
        st["prefill_chunks_total"] + 4 * st["decode_steps_total"])


# -- (f) one write a step, after the layer loop -----------------------------


@contextlib.contextmanager
def _writes_in_the_loop():
    """A program traced in here writes every new row inside the layer
    loop and then reads the plane, as every call of several rows does:
    the one rule (``kv_cache.defers``) says no."""
    real = kv_cache.defers
    kv_cache.defers = lambda stacked, rows: False
    try:
        yield
    finally:
        kv_cache.defers = real


def _rotating_model():
    """``llama-tiny`` cut to this file's sizes: keys are rotated at
    their positions inside the append (``rotate``), two query heads
    share a KV head."""
    from polyaxon_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = dataclasses.replace(
        LlamaConfig.tiny(), vocab_size=32, hidden_size=32,
        intermediate_size=64, num_layers=2, num_heads=2, num_kv_heads=1,
        max_position=64, dtype=jnp.float32)
    model = LlamaModel(cfg=cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))


@pytest.fixture(scope="module")
def write_twins():
    """For a storage: the model, a manager whose programs write a
    step's rows after the layer loop (the overlay: the attention is
    handed the plane with the new row laid over its position) and one
    whose programs write them inside it, then read."""
    memo = {}

    def get(storage):
        if storage not in memo:
            model, variables = _rotating_model() \
                if storage == "rotate" else _model(int8=storage == "int8")
            memo[storage] = model, variables, (
                SlotKVManager(model, variables, SLOTS),
                SlotKVManager(model, variables, SLOTS))
        return memo[storage]
    return get


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("storage", ["plain", "int8", "rotate"])
def test_rows_written_after_the_loop_read_as_rows_written_in_it(
        write_twins, storage, sampled, window):
    """The overlay changes nothing the attention sees: four streams at
    four positions decode the same tokens AND the same logits, bit for
    bit, whether a step's rows are written once after the layer loop
    or inside it — and those tokens are ``generate``'s, solo, over the
    whole plane."""
    model, variables, pair = write_twins(storage)
    want = []
    for row, prompt in enumerate(PROMPTS):
        ids = np.asarray([prompt], np.int32)
        out = G.generate_positional(
            model, variables, ids, max_new_tokens=13,
            keys=jnp.asarray(_key(row))[None], **SAMP) if sampled \
            else G.generate(model, variables, ids, max_new_tokens=13)
        want.append(np.asarray(out)[0, len(prompt):].tolist())
    windows = [1] * 4 if window == 1 else [8, 4]
    got = []
    for in_loop, mgr in enumerate(pair):
        mgr.reset()
        with _writes_in_the_loop() if in_loop \
                else contextlib.nullcontext():
            _fill(mgr, model, variables, sampled=sampled,
                  first=[w[0] for w in want])
            toks, logits = [], []
            for w in windows:
                toks.append(mgr.step(w, sampled, 8))
                logits.append(np.asarray(mgr.last_logits))
        got.append((np.concatenate(toks), logits))
    (toks, logits), (toks_in, logits_in) = got
    assert toks.tolist() == toks_in.tolist()
    for after, inside in zip(logits, logits_in):
        assert np.array_equal(after, inside)
    assert toks.T.tolist() == [w[1:1 + sum(windows)] for w in want]


def _tiny(name):
    """A registry preset in float32, its variables, and one sequence's
    prefilled cache."""
    from polyaxon_tpu.models.registry import get_model

    spec = get_model(name)
    model = spec.make_model(dtype=jnp.float32)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _row_writes(jaxpr, shapes) -> int:
    """Equations of a traced program, its loops' and conditionals'
    bodies included, that write rows into an array of one of
    ``shapes``: a ``scatter`` (an update at a lane's own position under
    the pool's vmap) or a ``dynamic_update_slice``."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scatter", "dynamic_update_slice") \
                and eqn.outvars[0].aval.shape in shapes:
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _row_writes(sub, shapes)
    return n


# layers x leaves where every layer writes its own rows: afmoe-tiny's
# five layers keep a ring or a plane, K and V each; jamba-tiny's one
# attention layer (of four) keeps a plane; a carried stack writes its
# two stacked leaves once each.
ROW_WRITES = {"gpt2-tiny": 2, "llama-tiny": 2, "afmoe-tiny": 10,
              "jamba-tiny": 2}


@pytest.mark.parametrize("name", sorted(ROW_WRITES))
def test_the_row_writes_counted_are_the_writes_the_program_makes(name):
    """``kv_row_writes_total`` against the compiled window: a step's
    count (``kv_cache.row_writes_a_step``, from one sequence's cache
    and the rule the program traces under) is the number of
    equations of the traced window that write rows into a pool leaf —
    one a K/V leaf of a carried stack (the compiled program:
    ``test_decode_window_aliases_the_pool_and_moves_only_rows``), one a
    layer and leaf of an unrolled one — and the manager's count grows
    by it every step."""
    model, variables = _tiny(name)
    mgr = SlotKVManager(model, variables, 2)
    prompt = np.asarray([PROMPTS[1]], np.int32)
    _, cache = G.prefill(model, variables, prompt)
    mgr.insert(mgr.acquire(), cache, 1, prompt.shape[1])
    assert mgr.plane_reads.writes_a_step == ROW_WRITES[name] \
        == kv_cache.row_writes_a_step(cache)
    mgr.step(2, False, 8)
    mgr.step(3, False, 8)
    assert mgr.plane_reads.row_writes == 5 * ROW_WRITES[name]
    fn = mgr._step_fns[(8, False)]
    operands = [jnp.asarray(8, jnp.int32)] + [
        jnp.asarray(x) for x in mgr.state.operands("plain")]
    pool = mgr.kv_pool()
    rows = {leaf.shape for path, leaf, kind in kv_cache.leaf_kinds(pool)
            if kind != "state" and leaf.ndim >= 4}
    traced = jax.make_jaxpr(fn.func)(*fn.args, pool, *operands)
    assert _row_writes(traced.jaxpr, rows) == ROW_WRITES[name]
    with _writes_in_the_loop():     # what the rule decides, it counts
        assert kv_cache.row_writes_a_step(cache) == (
            4 if name in ("gpt2-tiny", "llama-tiny")  # 2 layers x 2
            else ROW_WRITES[name])


def test_engine_reports_row_writes(small_model):
    model, variables = small_model
    eng = _engine(model, variables)
    try:
        groups = [eng.submit(p, new, None, None, sampling=s)
                  for p, new, s in _requests()]
        for g in groups:
            assert g.event.wait(timeout=120), "hung caller"
        st = eng.stats()
    finally:
        eng.close()
    # K and V of the carried stack, once a step
    assert st["kv_row_writes_total"] == 2 * st["decode_steps_total"] > 0


def _reader(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "layer_metrics",
        name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("info_open,info_close,want", [
    ({"kv_plane_rows_read_total": 1000, "kv_plane_rows_held_total": 4000},
     {"kv_plane_rows_read_total": 4000, "kv_plane_rows_held_total": 14000},
     30.0),
    ({"kv_plane_rows_read_total": 0, "kv_plane_rows_held_total": 0},
     {"kv_plane_rows_read_total": 512, "kv_plane_rows_held_total": 512},
     100.0),
    # nothing stepped in the window; a program without the counters
    ({"kv_plane_rows_read_total": 7, "kv_plane_rows_held_total": 9},
     {"kv_plane_rows_read_total": 7, "kv_plane_rows_held_total": 9},
     None),
    ({"decode_steps_total": 1}, {"decode_steps_total": 90}, None),
], ids=["a-third", "whole", "idle", "parent"])
def test_kv_plane_read_pct_reader(info_open, info_close, want):
    ctx = types.SimpleNamespace(collected={"info_open": info_open,
                                           "info_close": info_close})
    assert _reader("kv_plane_read_pct")(ctx) == want


@pytest.mark.parametrize("info_open,info_close,want", [
    ({"kv_row_writes_total": 96, "decode_steps_total": 48},
     {"kv_row_writes_total": 1096, "decode_steps_total": 548}, 2.0),
    ({"kv_row_writes_total": 0, "decode_steps_total": 0},
     {"kv_row_writes_total": 480, "decode_steps_total": 10}, 48.0),
    # nothing stepped in the window; a program without the counter
    ({"kv_row_writes_total": 6, "decode_steps_total": 3},
     {"kv_row_writes_total": 6, "decode_steps_total": 3}, None),
    ({"decode_steps_total": 1}, {"decode_steps_total": 90}, None),
], ids=["after-the-loop", "a-layer-and-leaf", "idle", "parent"])
def test_kv_row_writes_per_step_reader(info_open, info_close, want):
    ctx = types.SimpleNamespace(collected={"info_open": info_open,
                                           "info_close": info_close})
    assert _reader("kv_row_writes_per_step")(ctx) == want
