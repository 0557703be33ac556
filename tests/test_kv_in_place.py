"""The KV pool is updated IN PLACE (serving/slots.py, models/
scan_stack.py, models/kv_cache.py).

Four obligations: (a) every program that takes the pool consumes the
tree it was handed, and the manager counts it; (b) the compiled decode
window aliases every pool leaf to an output and moves nothing of the
pool's size but the rows it writes; (c) what the pool decodes equals,
token for token, a forward that has no cache at all; (d) a dispatch
that fails AFTER it consumed the pool goes to recovery, one that fails
before it is still retried in place.
"""

import collections
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving import (DecodeEngine, FaultPlan, RetryPolicy)
from polyaxon_tpu.serving.paged import PagedSlotKVManager
from polyaxon_tpu.serving.scheduler import SamplingSpec, SchedulerPolicy
from polyaxon_tpu.serving.slots import SlotKVManager

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
    operands = [jnp.asarray(8, jnp.int32), jnp.asarray(mgr.tokens),
                jnp.asarray(mgr.positions)]
    if sampled:
        operands += [jnp.asarray(x) for x in (
            mgr.keys, mgr.next_index, mgr.temps, mgr.top_ks,
            mgr.top_ps)]
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
    made, movers = collections.Counter(), []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                     r"([a-z\-]+)\(", line)
        if not m or not m.group(2):
            continue
        shape = tuple(int(d) for d in m.group(2).split(","))
        if shape not in kv:
            continue
        made[m.group(3)] += 1
        if m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast", "while",
                "dynamic-update-slice", "scatter", "fusion"):
            movers.append(line.strip()[:160])
    assert not movers, movers
    writes = made["scatter"] + made["dynamic-update-slice"]
    n_kv = sum(l.ndim >= 5 for l in jax.tree.leaves(pool))
    assert writes == n_kv and made["fusion"] <= writes, made


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
    real, calls = eng.slots.step, []

    def step(window, *args):
        calls.append(window)
        if len(calls) == 3:
            old = jax.tree.leaves(eng.slots.kv_pool())
            real(window, *args)
            # what a failed execution leaves behind: the old tree,
            # its arrays deleted
            eng.slots._stacked = jax.tree.unflatten(
                jax.tree.structure(eng.slots._stacked), old)
            raise RuntimeError("the device failed mid-program")
        return real(window, *args)

    eng.slots.step = step
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
