"""Paged KV cache + shared-prefix radix reuse (serving/paged.py,
serving/radix.py, the server's page-backed prefix store).

The defining contracts, in test form:

- DETERMINISM: the paged engine's output is bit-identical to solo
  generation per seed — and to the fixed-lane engine — under any
  co-tenancy or admission schedule, for plain, sampled, and
  speculative streams (the storage layout must never touch tokens).
- ROLLBACK: the speculative accept/rewind contract holds on paged
  storage (rollback is a cache_index rewind on the gathered view;
  stale entries are masked by absolute position before reuse).
- PAGE HYGIENE: freed and copy-on-write pages never leak stale KV
  into a co-tenant; every terminal path returns its pages; shared
  prefix pages are mapped read-only and survive entry eviction while
  referenced.
- OVERLOAD: a request that can NEVER fit the pool sheds with 503
  ``reason: kv_pages``; one that fits-but-not-now waits admit-ready
  and resumes when evictions free pages.
- RECOMPILES: zero steady-state compile-cache misses per
  (window, pages-per-slot-pad) shape class.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models.generate import (
    generate,
    generate_positional,
    generate_speculative,
    prefill,
)
from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving import DecodeEngine, SchedulerPolicy
from polyaxon_tpu.serving.radix import RadixPrefixIndex
from polyaxon_tpu.serving.scheduler import SamplingSpec, ShedError

PROMPT = np.asarray([[3, 1, 4, 1]], np.int32)
SPEC = dict(temperature=0.9, top_k=16)


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(
        GPT2Config.tiny(), vocab_size=32, hidden_size=32,
        num_layers=2, num_heads=2, max_position=64,
        dtype=jnp.float32)
    model = GPT2Model(cfg=cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


@pytest.fixture(scope="module")
def draft_vars(small_model):
    model, _ = small_model
    return model.init(jax.random.PRNGKey(99),
                      jnp.zeros((1, 4), jnp.int32))


def _engine(model, variables, dvars=None, *, paged=True, **policy):
    kw = dict(n_slots=4, decode_window=8)
    if paged:
        kw.update(kv_paged=True, kv_page_tokens=8)
    kw.update(policy)
    extra = {}
    if dvars is not None:
        extra = dict(draft_model=model, draft_variables=dvars)
    return DecodeEngine(model, variables, autostart=False,
                        policy=SchedulerPolicy(**kw), **extra)


# -- determinism: paged == solo == fixed-lane --------------------------------


def test_greedy_paged_matches_generate(small_model):
    model, variables = small_model
    eng = _engine(model, variables)
    g = eng.submit(PROMPT, 12, None, None)
    eng.run_until_idle()
    want = np.asarray(generate(model, variables, PROMPT,
                               max_new_tokens=12))
    assert g.result().tolist() == want.tolist()
    # every page returned once idle
    assert eng.slots.free_page_count() == eng.slots.n_pages


def test_sampled_paged_matches_solo_under_three_schedules(
        small_model):
    """Token identity per seed under: alone; admitted beside running
    co-tenants; slot-starved (queued, admitted into an evicted
    slot)."""
    model, variables = small_model
    want = np.asarray(generate_positional(
        model, variables, PROMPT, max_new_tokens=12, seed=7,
        temperature=1.0, top_k=8)).tolist()
    spec = SamplingSpec(seed=7, temperature=1.0, top_k=8)

    eng = _engine(model, variables)                   # 1) alone
    g = eng.submit(PROMPT, 12, None, None, sampling=spec)
    eng.run_until_idle()
    assert g.result().tolist() == want

    eng = _engine(model, variables)                   # 2) co-tenants
    a = eng.submit(np.asarray([[2, 7, 1, 8]], np.int32), 16, None,
                   None)
    b = eng.submit(np.asarray([[5, 6, 7, 8]], np.int32), 16, None,
                   None, sampling=SamplingSpec(seed=3,
                                               temperature=1.0))
    for _ in range(3):
        eng.tick()
    g = eng.submit(PROMPT, 12, None, None, sampling=spec)
    eng.run_until_idle()
    assert g.result().tolist() == want
    assert a.result().tolist() == np.asarray(generate(
        model, variables, np.asarray([[2, 7, 1, 8]], np.int32),
        max_new_tokens=16)).tolist()
    assert b.result().tolist() == np.asarray(generate_positional(
        model, variables, np.asarray([[5, 6, 7, 8]], np.int32),
        max_new_tokens=16, seed=3, temperature=1.0)).tolist()

    eng = _engine(model, variables, n_slots=2)        # 3) starved
    others = [eng.submit(np.asarray([[i, i + 1, 2, 3]], np.int32),
                         4 + i, None, None) for i in range(2)]
    g = eng.submit(PROMPT, 12, None, None, sampling=spec)
    eng.run_until_idle()
    assert g.result().tolist() == want
    del others
    assert eng.slots.free_page_count() == eng.slots.n_pages


def test_spec_paged_matches_solo_and_pins_rollback(small_model,
                                                   draft_vars):
    """Sampled speculative on paged storage == the solo seed-mode
    reference, with greedy co-tenants unchanged — this is the
    rollback-masking pin re-based on pages: every round's rejected
    tail is rewound on the gathered view and must never leak into
    any stream's tokens."""
    model, variables = small_model
    want = np.asarray(generate_speculative(
        model, variables, model, draft_vars, PROMPT,
        max_new_tokens=12, k=3, seed=7, **SPEC)).tolist()
    eng = _engine(model, variables, draft_vars)
    a = eng.submit(np.asarray([[2, 7, 1, 8]], np.int32), 16, None,
                   None)
    g = eng.submit(PROMPT, 12, None, None,
                   sampling=SamplingSpec(seed=7, spec_k=3, **SPEC))
    eng.run_until_idle()
    assert g.result().tolist() == want
    assert a.result().tolist() == np.asarray(generate(
        model, variables, np.asarray([[2, 7, 1, 8]], np.int32),
        max_new_tokens=16)).tolist()
    assert eng.slots.free_page_count() == eng.slots.n_pages


def test_paged_equals_fixed_lane_engine(small_model):
    """The two storage disciplines produce byte-identical responses
    for one mixed co-tenancy run — layout changes memory, never
    tokens."""
    model, variables = small_model
    results = []
    for paged in (False, True):
        eng = _engine(model, variables, paged=paged)
        groups = [
            eng.submit(PROMPT, 12, None, None),
            eng.submit(np.asarray([[5, 6, 7, 8]], np.int32), 10,
                       None, None,
                       sampling=SamplingSpec(seed=3,
                                             temperature=1.0)),
            eng.submit(np.asarray([[9, 8, 7, 6]], np.int32), 6,
                       None, None),
        ]
        eng.run_until_idle()
        results.append([g.result().tolist() for g in groups])
    assert results[0] == results[1]


def test_windowed_and_single_step_agree_on_paged(small_model):
    model, variables = small_model
    outs = []
    for window in (1, 8):
        eng = _engine(model, variables, decode_window=window)
        g = eng.submit(PROMPT, 13, None, None,
                       sampling=SamplingSpec(seed=5, temperature=1.0,
                                             top_p=0.9))
        eng.run_until_idle()
        outs.append(g.result().tolist())
    assert outs[0] == outs[1]


# -- page hygiene ------------------------------------------------------------


def test_freed_page_reuse_never_leaks(small_model):
    """Page poison: a request decoding in RECYCLED pages (freed by a
    finished co-tenant) produces exactly the tokens a fresh-pool run
    does — freed-page content is dead the moment the reservation
    returns."""
    model, variables = small_model
    p2 = np.asarray([[9, 8, 7, 6]], np.int32)
    # fresh-pool reference
    eng = _engine(model, variables, kv_pages=6)
    g = eng.submit(p2, 12, None, None,
                   sampling=SamplingSpec(seed=11, temperature=1.0))
    eng.run_until_idle()
    want = g.result().tolist()
    # now force reuse: pool of 6 pages, run a first request that
    # touches most of them, then the same request as above
    eng = _engine(model, variables, kv_pages=6)
    a = eng.submit(PROMPT, 30, None, None)       # 38 tok -> 5 pages
    eng.run_until_idle()
    assert eng.slots.free_page_count() == 6
    g = eng.submit(p2, 12, None, None,
                   sampling=SamplingSpec(seed=11, temperature=1.0))
    eng.run_until_idle()
    assert g.result().tolist() == want
    del a


def test_shared_prefix_pages_map_copy_on_write(small_model):
    """Two streams seeded from one stored prefix SHARE its full pages
    read-only (refcount > 1 while resident) and still match the cold
    unshared run token-for-token; the entry's pages survive both
    releases."""
    model, variables = small_model
    sys_toks = np.asarray([list(range(1, 21))], np.int32)  # 20 tok
    q1 = np.concatenate([sys_toks, [[25, 26]]], axis=1)
    q2 = np.concatenate([sys_toks, [[28, 29]]], axis=1)
    # cold references (fresh engine, no sharing)
    eng = _engine(model, variables)
    cold = []
    for q in (q1, q2):
        g = eng.submit(q, 8, None, None)
        eng.run_until_idle()
        cold.append(g.result().tolist())

    eng = _engine(model, variables)
    mgr = eng.slots
    logits, cache = prefill(model, variables, sys_toks)
    n = mgr.pages_needed(sys_toks.shape[1])          # 3 pages of 8
    ids = mgr.try_reserve(n)
    mgr.scatter_cache(cache, ids)                    # the "entry"
    full = ids[:sys_toks.shape[1] // mgr.page_tokens]  # 2 full pages
    groups = []
    for q in (q1, q2):
        mgr.pin(full)                 # one pin per mapping stream
        ent_cache = mgr.materialize(ids, sys_toks.shape[1])
        groups.append(eng.submit(
            q, 8, None, None,
            prefix=(sys_toks.shape[1], logits, ent_cache),
            shared_pages=tuple(full)))
    # drive until both resident, then check sharing is live
    while eng.slots.active_slots < 2:
        eng.tick()
    stats = mgr.page_stats()
    assert stats["kv_pages_shared"] >= len(full)
    eng.run_until_idle()
    assert [g.result().tolist() for g in groups] == cold
    # streams released their references; the entry still owns ids
    stats = mgr.page_stats()
    assert stats["kv_pages_free"] == mgr.n_pages - n
    mgr.unpin(ids)
    assert mgr.free_page_count() == mgr.n_pages


def test_cancel_and_failure_release_pages(small_model):
    model, variables = small_model
    eng = _engine(model, variables)
    g = eng.submit(PROMPT, 30, None, None)
    for _ in range(3):
        eng.tick()                   # resident, mid-decode
    assert eng.slots.free_page_count() < eng.slots.n_pages
    eng.cancel(g)
    eng.tick()                       # boundary delivery
    assert g.error is not None
    assert eng.slots.free_page_count() == eng.slots.n_pages


# -- overload ----------------------------------------------------------------


def test_impossible_request_sheds_kv_pages(small_model):
    model, variables = small_model
    eng = _engine(model, variables, n_slots=2, kv_pages=2)
    with pytest.raises(ShedError) as e:
        eng.submit(PROMPT, 30, None, None)   # 34 tokens > 16
    assert e.value.reason == "kv_pages"
    assert eng.shed_kv_pages_total == 1
    assert eng.stats()["shed_kv_pages_total"] == 1


def test_insert_page_race_requeues_instead_of_failing(small_model):
    """A handler thread can reserve pages BETWEEN the engine's
    admission gate and the slot insert (prefix store racing
    admission): the stream must re-queue and complete when pages
    free — fits-but-not-now waits, never a 500 (regression)."""
    model, variables = small_model
    eng = _engine(model, variables)
    real_reserve = eng.slots.try_reserve
    stolen = {}

    def stealing_reserve(n, _real=real_reserve):
        if "done" not in stolen:
            stolen["done"] = True
            # Simulate the racing handler: the pages vanish between
            # gate and insert.
            stolen["pages"] = _real(n)
            return None
        return _real(n)

    eng.slots.try_reserve = stealing_reserve
    g = eng.submit(PROMPT, 12, None, None)
    eng.tick()                       # gate passes, insert loses the
    #                                  race, stream re-queues
    assert g.error is None
    eng.slots.try_reserve = real_reserve
    eng.slots.unpin(stolen["pages"])  # the "handler" releases them
    eng.run_until_idle()
    want = np.asarray(generate(model, variables, PROMPT,
                               max_new_tokens=12)).tolist()
    assert g.result().tolist() == want
    assert eng.slots.free_page_count() == eng.slots.n_pages


def test_admission_resumes_when_pages_free(small_model):
    """Fits-the-pool-but-not-now: the request waits fully prefilled
    and admits the boundary evictions free enough pages — never a
    shed, never a deadlock."""
    model, variables = small_model
    # decode_window=1: observe the blocked head boundary by boundary
    # (fused windows would run the residents to completion inside
    # one tick — page-blocked heads no longer pin the window to 1).
    eng = _engine(model, variables, kv_pages=4, decode_window=1)
    g1 = eng.submit(PROMPT, 12, None, None)              # 2 pages
    g2 = eng.submit(np.asarray([[9, 8, 7, 6]], np.int32), 12, None,
                    None)                                # 2 pages
    g3 = eng.submit(np.asarray([[1, 2, 3, 4]], np.int32), 12, None,
                    None)                                # must wait
    # while g1/g2 hold all pages, g3 stays queued
    for _ in range(3):
        eng.tick()
    assert g3.t_first_admit is None
    assert eng.slots.free_page_count() == 0
    eng.run_until_idle()
    want = np.asarray(generate(
        model, variables, np.asarray([[1, 2, 3, 4]], np.int32),
        max_new_tokens=12)).tolist()
    assert g3.result().tolist() == want


# -- recompiles --------------------------------------------------------------


def test_zero_steady_state_recompiles_on_paged(small_model):
    """Warm-twice-then-flat per (window, pages-per-slot-pad) class:
    same-shaped traffic after warmup must add ZERO compile-cache
    misses — page tables are runtime args, so occupancy mix never
    enters a program key."""
    model, variables = small_model

    def round_(eng):
        gs = [
            eng.submit(PROMPT, 12, None, None),
            eng.submit(np.asarray([[5, 6, 7, 8]], np.int32), 9, None,
                       None, sampling=SamplingSpec(
                           seed=3, temperature=0.8, top_k=8)),
            eng.submit(np.asarray([[9, 8, 7, 6]], np.int32), 5, None,
                       None),
        ]
        eng.run_until_idle()
        return gs

    eng = _engine(model, variables)
    round_(eng)
    round_(eng)
    warm = eng.sentinel.misses
    assert warm > 0
    for _ in range(3):
        round_(eng)
    assert eng.sentinel.misses == warm, eng.sentinel.snapshot()


# -- server: page-backed prefix store + overload surfaces --------------------


class TestPagedServer:
    def _server(self, small_model, **kw):
        from polyaxon_tpu.serving import ModelServer

        model, variables = small_model
        args = dict(model_name="t", max_batch=2, n_slots=4,
                    prefix_cache=4, kv_paged=True, kv_page_tokens=8)
        args.update(kw)
        return ModelServer(model, variables, **args)

    def test_warm_equals_cold_and_pages_shared(self, small_model):
        ms = self._server(small_model)
        try:
            sys_p = list(range(1, 21))               # 20 tokens
            body = {"prompt": sys_p + [25, 26], "max_new_tokens": 8}
            cold = ms.generate(dict(body))
            assert "prefix_hit_len" not in cold
            ms.prefill_prompt({"prompt": sys_p})
            warm = ms.generate(dict(body))
            assert warm["new_tokens"] == cold["new_tokens"]
            assert warm["prefix_hit_len"] == len(sys_p)
            # sampled warm rides the engine too, token-identical
            sbody = {"prompt": sys_p + [27, 28], "max_new_tokens": 8,
                     "temperature": 0.9, "top_k": 8, "seed": 5}
            ms2 = self._server(small_model, kv_paged=False)
            try:
                want = ms2.generate(dict(sbody))["new_tokens"]
            finally:
                ms2.close()
            assert ms.generate(dict(sbody))["new_tokens"] == want
            info = ms.info()
            assert info["kv_paged"] is True
            assert info["prefix_hits"] == 2
            assert info["prefix_hit_tokens"] == 2 * len(sys_p)
            # session store-backs share the system prompt's full
            # pages instead of recopying them
            assert info["kv_pages_shared"] >= 2
            text = ms.metrics_text()
            for gauge in ("ptpu_serving_kv_pages_free",
                          "ptpu_serving_kv_pages_shared",
                          "ptpu_serving_prefix_hit_tokens_total",
                          "ptpu_serving_shed_kv_pages_total"):
                assert gauge in text
        finally:
            ms.close()

    def test_logits_on_request_match_the_fixed_lanes(self):
        """``{"logits": true}`` is accepted by any server; a paged one
        answered it with an AttributeError in the engine thread while
        its step programs dropped what the shared body computed.  One
        dispatch keeps the last step's logits for both managers: the
        same rows from the same greedy request."""
        import base64

        from polyaxon_tpu.models.registry import get_model
        from polyaxon_tpu.serving import ModelServer

        model, variables = get_model("gpt2-tiny").init_params(
            batch_size=1)
        body = {"prompt": [5, 6, 7, 8, 9], "max_new_tokens": 10,
                "logits": True}
        rows = []
        for paged in (dict(kv_paged=True, kv_page_tokens=8), {}):
            ms = ModelServer(model, variables, model_name="gpt2-tiny",
                             n_slots=2, decode_window=4, **paged)
            try:
                reply = ms.generate(dict(body))
            finally:
                ms.close()
            field = reply["logits"]
            got = np.frombuffer(base64.b64decode(field["b64"]),
                                "<f4").reshape(field["shape"])
            assert got.shape == (10, model.cfg.vocab_size)
            assert reply["new_tokens"][0] == \
                [int(t) for t in got.argmax(-1)]
            rows.append(got)
        np.testing.assert_allclose(rows[0], rows[1], rtol=1e-6,
                                   atol=1e-6)

    def test_http_level_kv_pages_shed(self, small_model):
        ms = self._server(small_model, n_slots=2, kv_pages=2,
                          prefix_cache=0)
        try:
            with pytest.raises(ShedError) as e:
                ms.generate({"prompt": list(range(1, 9)),
                             "max_new_tokens": 30})
            assert e.value.reason == "kv_pages"
        finally:
            ms.close()

    def test_prefix_entries_yield_to_live_traffic(self, small_model):
        """Page-pressure reclaim: stored prefix entries holding most
        of a small pool are LRU-evicted when a live request needs
        their pages — stored-but-idle prefixes never starve
        admission."""
        ms = self._server(small_model, n_slots=2, kv_pages=6)
        try:
            # two entries x 2 pages = 4 of 6 pages held by the store
            ms.prefill_prompt({"prompt": list(range(1, 16))})
            ms.prefill_prompt({"prompt": list(range(20, 35))})
            assert ms.engine.slots.free_page_count() == 2
            # a 40-token request needs 5 pages -> reclaim must evict
            r = ms.generate({"prompt": list(range(40, 48)),
                             "max_new_tokens": 30})
            assert len(r["new_tokens"][0]) == 30
            assert len(ms._prefix) < 2
        finally:
            ms.close()

    def test_paged_rejects_non_engine_modes(self, small_model):
        from polyaxon_tpu.serving import ModelServer

        model, variables = small_model
        with pytest.raises(ValueError, match="kv_paged"):
            ModelServer(model, variables, batching="coalesce",
                        kv_paged=True)


# -- radix index -------------------------------------------------------------


class TestRadixIndex:
    @staticmethod
    def _t(*xs):
        return np.asarray([list(xs)], np.int32)

    def test_longest_match_and_miss(self):
        ix = RadixPrefixIndex(8)
        ix.store(self._t(1, 2, 3, 4), "A")
        ix.store(self._t(1, 2, 3, 4, 5, 6), "AB")
        assert ix.lookup(self._t(1, 2, 3, 4, 5, 6, 9))[1] == "AB"
        assert ix.lookup(self._t(1, 2, 3, 4, 9))[1] == "A"
        assert ix.lookup(self._t(1, 2, 3)) is None
        assert ix.lookup(self._t(2, 2, 3, 4)) is None

    def test_mid_edge_split(self):
        ix = RadixPrefixIndex(8)
        ix.store(self._t(1, 2, 3, 4, 5), "LONG")
        ix.store(self._t(1, 2, 9), "FORK")
        assert ix.lookup(self._t(1, 2, 3, 4, 5, 0))[1] == "LONG"
        assert ix.lookup(self._t(1, 2, 9, 9))[1] == "FORK"
        assert len(ix) == 2

    def test_longest_ancestor_for_store_sharing(self):
        ix = RadixPrefixIndex(8)
        ix.store(self._t(1, 2, 3, 4), "SYS")
        anc = ix.longest_ancestor(self._t(1, 2, 3, 4, 7, 8))
        assert anc is not None and anc[1] == "SYS"
        assert ix.longest_ancestor(self._t(5, 5)) is None

    def test_lru_eviction_and_overwrite_report_displaced(self):
        ix = RadixPrefixIndex(2)
        ix.store(self._t(1), "A")
        ix.store(self._t(2), "B")
        ix.lookup(self._t(1, 9))             # refresh A
        ev = ix.store(self._t(3), "C")       # evicts B (LRU)
        assert [p for _, p in ev] == ["B"]
        ev = ix.store(self._t(3), "C2")      # overwrite displaces C
        assert [p for _, p in ev] == ["C"]
        assert ix.lookup(self._t(3, 0))[1] == "C2"

    def test_eviction_prunes_but_keeps_descendants(self):
        ix = RadixPrefixIndex(8)
        ix.store(self._t(1, 2), "P")
        ix.store(self._t(1, 2, 3, 4), "CHILD")
        ev = ix.pop_lru()
        assert ev[1] == "P"
        assert ix.lookup(self._t(1, 2, 3, 4, 5))[1] == "CHILD"
        assert ix.lookup(self._t(1, 2, 9)) is None

    def test_cold_insertion_is_scan_resistant(self):
        """A stream of one-shot cold stores (session store-backs)
        cycles itself out of the LRU; a HOT registered entry — kept
        warm by lookups — survives far more than ``cap`` of them."""
        ix = RadixPrefixIndex(3)
        ix.store(self._t(1, 2, 3, 4), "SYS")            # hot
        for i in range(10, 30):
            ix.store(self._t(1, 2, 3, 4, i), f"s{i}", hot=False)
            assert ix.lookup(self._t(1, 2, 3, 4, 99))[1] == "SYS"
        assert len(ix) == 3

    def test_multi_row_prompts_radix_by_columns(self):
        ix = RadixPrefixIndex(8)
        m = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
        ix.store(m, "MR")
        hit = ix.lookup(np.asarray([[1, 2, 3, 9], [4, 5, 6, 9]],
                                   np.int32))
        assert hit is not None and hit[1] == "MR"
        # one diverging row breaks the column match
        assert ix.lookup(np.asarray([[1, 2, 3, 9], [4, 5, 0, 9]],
                                    np.int32)) is None
        # batch widths never cross
        assert ix.lookup(self._t(1, 2, 3, 9)) is None
