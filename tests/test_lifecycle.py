"""Request lifecycle — cancellation, deadlines, priority classes,
SLO preemption with token-identical resume, and graceful overload
(serving/scheduler.py + serving/engine.py + the server front-end).

The defining contracts, pinned here:

- a cancelled or deadline-expired request frees its slot within ONE
  step boundary — queued, mid-prefill, and decoding requests all take
  the same eviction path — and co-tenants' tokens never change;
- a PREEMPTED-then-resumed request is token-identical to an
  uninterrupted run, per seed, across plain/sampled/speculative
  decode (the position-keyed RNG contract makes resumption a pure
  re-derivation: re-prefill ``prompt ++ out[:-1]``, re-enter feeding
  ``out[-1]`` with ``next_index == len(out)``);
- graceful overload: per-class queue deadlines shed unstartable
  requests with the structured 503 reason, per-class depth bounds
  reject independently, and /drain stops admission while in-flight
  work finishes;
- the front-end wait is BOUNDED: a wedged engine sheds its waiters
  instead of collecting HTTP workers.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models.generate import (generate,
                                          generate_positional,
                                          generate_speculative)
from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving import (DeadlineExceeded, DecodeEngine,
                                  ModelServer, QueueFullError,
                                  RequestCancelled, SchedulerPolicy,
                                  ShedError, Telemetry)
from polyaxon_tpu.serving.scheduler import SamplingSpec


def _small_model(vocab=32, **over):
    """f32 vocab-32 model (the spec/sampled-engine test shape):
    margins dominate cross-program rounding, so token equality is
    exact."""
    cfg = dataclasses.replace(
        GPT2Config.tiny(), vocab_size=vocab, hidden_size=32,
        num_layers=2, num_heads=2, max_position=64,
        dtype=jnp.float32, **over)
    model = GPT2Model(cfg=cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _engine(model, variables, *, draft_vars=None, telemetry=None,
            **policy):
    kw = dict(n_slots=2, decode_window=1)
    kw.update(policy)
    return DecodeEngine(
        model, variables, autostart=False,
        policy=SchedulerPolicy(**kw),
        telemetry=telemetry,
        **({"draft_model": model, "draft_variables": draft_vars}
           if draft_vars is not None else {}))


PROMPT = np.asarray([[3, 1, 4, 1]], np.int32)
OTHER = np.asarray([[2, 7, 1, 8]], np.int32)


class TestCancellation:
    """Cancel delivery at step boundaries: one boundary frees the
    slot, co-tenants are untouched, spans + counters record it."""

    def test_cancel_decoding_frees_slot_within_one_boundary(self):
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)
        g = eng.submit(PROMPT, 30, None, None)
        for _ in range(3):
            eng.tick()
        assert eng.slots.active_slots == 1
        partial = len(g.streams[0].out)
        eng.cancel(g)
        eng.tick()                       # exactly ONE boundary
        assert eng.slots.free_slots == 1
        assert g.event.is_set()
        assert isinstance(g.error, RequestCancelled)
        assert g.status == "cancelled"
        assert eng.cancelled_total == 1
        assert len(g.streams[0].out) == partial  # no further decode

    def test_cancel_queued_and_mid_prefill(self):
        """All three pre-terminal phases cancel cleanly: a QUEUED
        request (zero engine attention) and a MID-PREFILL request
        (partial chunked cache) both vanish at the next boundary,
        without disturbing the resident co-tenant."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)
        resident = eng.submit(PROMPT, 12, None, None)
        eng.tick()                       # admit the co-tenant
        long_prompt = np.asarray([list(range(1, 13))], np.int32)
        mid = eng.submit(long_prompt, 4, None, 3)   # 4 chunks of 3
        eng.tick()                       # first prefill chunk
        assert mid.streams[0].filled == 3
        queued = eng.submit(OTHER, 4, None, None)
        assert len(eng.queue) == 2
        eng.cancel(mid)
        eng.cancel(queued)
        eng.tick()
        assert len(eng.queue) == 0
        assert mid.status == "cancelled"
        assert queued.status == "cancelled"
        assert eng.cancelled_total == 2
        eng.run_until_idle()
        # the resident co-tenant's tokens are exactly its solo run
        want = np.asarray(generate(model, variables, PROMPT,
                                   max_new_tokens=12)).tolist()
        assert resident.result().tolist() == want

    def test_cancelled_span_and_terminal_status_emitted(self):
        model, variables = _small_model()
        tel = Telemetry(buffer=256)
        eng = _engine(model, variables, n_slots=1, telemetry=tel)
        g = eng.submit(PROMPT, 30, None, None)
        for _ in range(3):
            eng.tick()
        eng.cancel(g)
        eng.tick()
        names = [e["name"] for e in tel.events()]
        assert "cancelled" in names
        # the decode span closed at the eviction boundary with the
        # terminal status in its args
        decode = [e for e in tel.events() if e["name"] == "decode"]
        assert decode and decode[-1]["args"]["terminal"] == \
            "cancelled"


class TestDeadline:
    def test_deadline_expires_mid_decode(self):
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)
        g = eng.submit(PROMPT, 500, None, None, deadline_s=0.01)
        t0 = time.perf_counter()
        while not g.event.is_set():
            eng.tick()
            assert time.perf_counter() - t0 < 60
        assert isinstance(g.error, DeadlineExceeded)
        assert g.status == "expired"
        assert eng.expired_total == 1
        assert eng.slots.free_slots == 1
        assert 0 < len(g.streams[0].out) < 500  # partial, discarded

    def test_deadline_expires_while_queued(self):
        """A queued-but-unadmitted request expires through the same
        sweep — no slot was ever consumed."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)
        resident = eng.submit(PROMPT, 20, None, None)
        eng.tick()
        g = eng.submit(OTHER, 4, None, None, deadline_s=0.005)
        time.sleep(0.02)
        eng.tick()
        assert g.event.is_set()
        assert isinstance(g.error, DeadlineExceeded)
        assert "queued" in str(g.error)
        eng.run_until_idle()
        assert resident.event.is_set() and resident.error is None

    def test_windowed_engine_still_frees_within_a_boundary(self):
        """A resident with an armed deadline pins the decode window
        to single steps, so expiry is delivered at the very next
        boundary instead of after a fused window tail."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1, decode_window=8)
        g = eng.submit(PROMPT, 40, None, None, deadline_s=3600)
        eng.tick()
        assert eng._pick_window() == 1
        eng.cancel(g, RequestCancelled("test"))
        eng.tick()
        assert eng.slots.free_slots == 1
        assert g.status == "cancelled"


class TestPriorityAndPreemption:
    def test_interactive_pops_ahead_of_batch(self):
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)
        batch = eng.submit(PROMPT, 4, None, None, priority="batch")
        inter = eng.submit(OTHER, 4, None, None,
                           priority="interactive")
        eng.tick()      # one slot: the interactive request gets it
        assert eng.slots.active_slots == 1
        resident = next(iter(eng._resident.values()))
        assert resident.group is inter
        eng.run_until_idle()
        assert batch.event.is_set() and inter.event.is_set()
        assert eng.admitted_by_class["interactive"] == 1
        assert eng.admitted_by_class["batch"] == 1

    @pytest.mark.parametrize("mode", ["plain", "sampled", "spec"])
    def test_preempt_and_resume_is_token_identical(self, mode):
        """THE determinism matrix: a batch request preempted
        mid-decode and later resumed commits exactly the tokens its
        uninterrupted solo run would — for greedy, sampled, and
        speculative decode — and the interactive request that forced
        the preemption matches ITS solo run too."""
        model, variables = _small_model()
        draft_vars = model.init(jax.random.PRNGKey(99),
                                jnp.zeros((1, 4), jnp.int32)) \
            if mode == "spec" else None
        if mode == "plain":
            sampling = None
            want = np.asarray(generate(
                model, variables, PROMPT,
                max_new_tokens=14)).tolist()
        elif mode == "sampled":
            sampling = SamplingSpec(seed=7, temperature=0.9,
                                    top_k=16)
            want = np.asarray(generate_positional(
                model, variables, PROMPT, max_new_tokens=14, seed=7,
                temperature=0.9, top_k=16)).tolist()
        else:
            sampling = SamplingSpec(seed=7, temperature=0.9,
                                    top_k=16, spec_k=3)
            want = np.asarray(generate_speculative(
                model, variables, model, draft_vars, PROMPT,
                max_new_tokens=14, k=3, seed=7, temperature=0.9,
                top_k=16)).tolist()
        eng = _engine(model, variables, draft_vars=draft_vars,
                      n_slots=1, slo_ttft_s=0.0001)
        victim = eng.submit(PROMPT, 14, None, None,
                            sampling=sampling, priority="batch")
        for _ in range(4):
            eng.tick()
        committed_before = len(victim.streams[0].out)
        assert 2 <= committed_before < 14, \
            "preemption must land mid-decode"
        inter = eng.submit(OTHER, 3, None, None,
                           priority="interactive")
        eng.run_until_idle()
        assert eng.preempted_total == 1
        assert eng.resumed_total == 1
        assert victim.result().tolist() == want, \
            f"{mode}: resumed tokens differ from uninterrupted run"
        assert inter.result().tolist() == np.asarray(generate(
            model, variables, OTHER, max_new_tokens=3)).tolist()

    def test_resume_prefill_compiles_go_steady_state_quiet(self):
        """Preemption-resume must honor the zero-steady-state-
        recompile contract: resume re-prefill lengths are
        data-dependent, so they split into power-of-two pieces
        (SchedulerPolicy.pow2_pieces) — once a few preemptions have
        warmed those shapes, further preemptions at NEW commit
        points add no compile-cache misses."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1,
                      slo_ttft_s=0.0001)

        def preempt_once(k):
            """Preempt the victim once it has committed k(+2)
            tokens — the +2 is deterministic: the engine runs one
            dispatch ahead, so one token is in flight when the
            interactive request arrives; the tick that prefills the
            interactive head collects it and (serial now: an
            interactive head under an armed SLO) decodes once more,
            and preemption fires at the NEXT boundary."""
            victim = eng.submit(PROMPT, 34, None, None,
                                priority="batch")
            while len(victim.streams[0].out) < k:
                eng.tick()
            inter = eng.submit(OTHER, 2, None, None,
                               priority="interactive")
            eng.run_until_idle()
            assert victim.event.is_set() and inter.event.is_set()

        # Warm with the LARGEST resume length in the pow2 band
        # (k=26 -> resume length 31 = [16, 8, 4, 2, 1]): that one
        # run compiles every piece program smaller lengths in the
        # band can use.
        preempt_once(26)
        warm = eng.sentinel.snapshot()["compile_cache_misses"]
        for k in (12, 18, 24):           # new, smaller commit points
            preempt_once(k)
        assert eng.preempted_total == 4
        assert eng.sentinel.snapshot()["compile_cache_misses"] \
            == warm, "resume prefill recompiled in steady state"

    def test_pow2_pieces_decomposition(self):
        assert SchedulerPolicy.pow2_pieces(39) == [32, 4, 2, 1]
        assert SchedulerPolicy.pow2_pieces(1) == [1]
        assert SchedulerPolicy.pow2_pieces(64) == [64]
        assert SchedulerPolicy.pow2_pieces(0) == []
        for n in range(1, 200):
            pieces = SchedulerPolicy.pow2_pieces(n)
            assert sum(pieces) == n
            assert all(p & (p - 1) == 0 for p in pieces)
            assert pieces == sorted(pieces, reverse=True)

    def test_no_preemption_without_slo(self):
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)   # slo unset
        victim = eng.submit(PROMPT, 10, None, None,
                            priority="batch")
        for _ in range(3):
            eng.tick()
        inter = eng.submit(OTHER, 3, None, None,
                           priority="interactive")
        eng.run_until_idle()
        assert eng.preempted_total == 0
        assert victim.event.is_set() and inter.event.is_set()

    def test_interactive_residents_are_never_preempted(self):
        """With only interactive residents the scheduler DEFERS —
        priority protects the class, it never cannibalizes it."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1, slo_ttft_s=0.0001)
        first = eng.submit(PROMPT, 10, None, None,
                           priority="interactive")
        for _ in range(3):
            eng.tick()
        second = eng.submit(OTHER, 3, None, None,
                            priority="interactive")
        eng.run_until_idle()
        assert eng.preempted_total == 0
        assert first.event.is_set() and second.event.is_set()

    def test_degraded_ttft_p99_arms_preemption_and_washes_out(self):
        """The admission-anchored interactive-TTFT p99 is the control
        signal — read over a SLIDING window of recent observations:
        a degraded p99 triggers preemption even for a just-arrived
        interactive request (its own wait still under target), and
        healthy TTFTs wash the degradation out instead of latching
        aggressive preemption until restart."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1, slo_ttft_s=5.0)
        # Degrade the recent-window p99 past the 5s target.
        for _ in range(50):
            eng._ttft_recent.append(30.0)
        victim = eng.submit(PROMPT, 14, None, None,
                            priority="batch")
        for _ in range(4):
            eng.tick()
        inter = eng.submit(OTHER, 3, None, None,
                           priority="interactive")
        eng.run_until_idle()
        assert eng.preempted_total == 1
        assert victim.result().tolist() == np.asarray(generate(
            model, variables, PROMPT, max_new_tokens=14)).tolist()
        assert inter.event.is_set()
        # Wash-out: a run of healthy TTFTs displaces the bad period
        # (bounded window), so the signal disarms...
        for _ in range(64):
            eng._ttft_recent.append(0.001)
        assert eng._recent_ttft_p99() < 5.0
        victim2 = eng.submit(PROMPT, 14, None, None,
                             priority="batch")
        for _ in range(4):
            eng.tick()
        inter2 = eng.submit(OTHER, 3, None, None,
                            priority="interactive")
        eng.run_until_idle()
        # ...and with the head's own wait far under slo/2, no second
        # preemption fires.
        assert eng.preempted_total == 1
        assert victim2.event.is_set() and inter2.event.is_set()


class TestAdmissionPopRace:
    def test_concurrent_submit_between_head_and_pop_loses_nothing(
            self):
        """Regression: with per-class queues, an interactive submit
        landing between the tick's ``head()`` (which returned a
        batch stream) and the admission pop CHANGES the head.  The
        old pop-the-head would drop the interactive newcomer on the
        floor and leave the batch stream queued for a second,
        state-corrupting admission (it re-admits with its prefill
        logits already consumed).  Admission must pop exactly the
        stream it prefilled."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=2)
        batch = eng.submit(PROMPT, 4, None, None, priority="batch")
        head = eng.queue.head()
        assert head.group is batch
        # The racing handler thread's submit, interleaved exactly
        # where the loop is about to admit the batch head:
        inter = eng.submit(OTHER, 4, None, None,
                           priority="interactive")
        eng._advance_prefill(head)
        # the batch stream was admitted ONCE and left the queue; the
        # interactive stream is still queued, not dropped
        assert head.slot is not None
        assert len(eng.queue) == 1
        assert eng.queue.head().group is inter
        eng.run_until_idle()
        assert batch.result().tolist() == np.asarray(generate(
            model, variables, PROMPT, max_new_tokens=4)).tolist()
        assert inter.result().tolist() == np.asarray(generate(
            model, variables, OTHER, max_new_tokens=4)).tolist()


class TestOverload:
    def test_queue_deadline_sheds_unstarted_batch_only(self):
        """Per-class queue deadlines under saturation: batch requests
        that got zero engine attention past their class deadline shed
        with the structured reason — OLDEST first, and the
        interactive class (its own deadline unset) keeps waiting."""
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1,
                      batch_queue_deadline_s=0.01)
        resident = eng.submit(PROMPT, 30, None, None,
                              priority="interactive")
        eng.tick()                       # pool saturated
        b1 = eng.submit(OTHER, 4, None, None, priority="batch")
        time.sleep(0.02)                 # b1 is now past deadline
        b2 = eng.submit(np.asarray([[9, 9, 2, 6]], np.int32), 4,
                        None, None, priority="batch")
        inter_q = eng.submit(np.asarray([[5, 5, 5, 5]], np.int32),
                             4, None, None, priority="interactive")
        eng.tick()
        assert b1.event.is_set()
        assert isinstance(b1.error, ShedError)
        assert b1.error.reason == "queue_deadline"
        assert b1.status == "shed"
        # b2 arrived inside its deadline window; inter has none
        assert not b2.event.is_set()
        assert not inter_q.event.is_set()
        assert eng.shed_by_class["batch"] == 1
        assert eng.shed_by_class["interactive"] == 0
        eng.cancel(resident)
        eng.run_until_idle()
        assert b2.event.is_set() and inter_q.event.is_set()

    def test_per_class_depth_limits_are_independent(self):
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1, queue_depth=8,
                      batch_queue_depth=1)
        resident = eng.submit(PROMPT, 30, None, None)
        eng.tick()
        eng.submit(OTHER, 2, None, None, priority="batch")
        with pytest.raises(QueueFullError, match="batch"):
            eng.submit(OTHER, 2, None, None, priority="batch")
        # the interactive class still has room
        eng.submit(OTHER, 2, None, None, priority="interactive")
        assert eng.queue.class_len("interactive") == 1
        assert eng.queue.class_len("batch") == 1
        eng.cancel(resident)
        eng.run_until_idle()

    def test_drain_stops_admission_finishes_in_flight(self):
        model, variables = _small_model()
        eng = _engine(model, variables, n_slots=1)
        resident = eng.submit(PROMPT, 8, None, None)
        queued = eng.submit(OTHER, 4, None, None)
        eng.tick()
        eng.drain()
        with pytest.raises(ShedError) as ei:
            eng.submit(PROMPT, 2, None, None)
        assert ei.value.reason == "draining"
        eng.run_until_idle()             # accepted work still lands
        assert resident.event.is_set() and resident.error is None
        assert queued.event.is_set() and queued.error is None
        assert eng.slots.active_slots == 0
        assert eng.stats()["draining"] is True


class TestBoundedFrontEndWait:
    def test_wedged_engine_sheds_the_waiter(self):
        """The satellite fix: a caller whose request sits behind a
        wedged engine used to hold its HTTP worker until engine
        drain.  Now the bounded wait sheds it with the structured
        503 reason, within the configured timeout."""
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2, n_slots=1,
                         request_timeout_s=0.5)
        try:
            with ms._lock:      # wedge the device: nothing drains
                t0 = time.perf_counter()
                with pytest.raises(ShedError) as ei:
                    ms.generate({"prompt": [1, 2, 3],
                                 "max_new_tokens": 4})
                assert ei.value.reason == "request_timeout"
                assert time.perf_counter() - t0 < 30
        finally:
            ms.close()

    def test_request_timeout_validated(self):
        model, variables = _small_model()
        with pytest.raises(ValueError, match="request_timeout_s"):
            ModelServer(model, variables, request_timeout_s=0)
        with pytest.raises(ValueError, match="default_priority"):
            ModelServer(model, variables, default_priority="urgent")


class TestServerLifecycleParams:
    def test_priority_and_deadline_validation(self):
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2, n_slots=1)
        try:
            with pytest.raises(ValueError, match="priority"):
                ms.generate({"prompt": [1, 2], "max_new_tokens": 2,
                             "priority": "urgent"})
            with pytest.raises(ValueError, match="deadline_ms"):
                ms.generate({"prompt": [1, 2], "max_new_tokens": 2,
                             "deadline_ms": 0})
            with pytest.raises(ValueError, match="deadline_ms"):
                ms.generate({"prompt": [1, 2], "max_new_tokens": 2,
                             "deadline_ms": True})
        finally:
            ms.close()

    def test_default_priority_applies(self):
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2, n_slots=1,
                         default_priority="batch")
        try:
            ms.generate({"prompt": [1, 2], "max_new_tokens": 2})
            assert ms.engine.admitted_by_class["batch"] == 1
            assert ms.engine.admitted_by_class["interactive"] == 0
        finally:
            ms.close()

    def test_coalesce_path_honors_deadline_before_dispatch(self):
        """The coalescer can't stop a merged batch mid-flight, so an
        expired request must shed BEFORE joining one — same contract
        as the solo device-lock check."""
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2,
                         batching="coalesce")
        done = threading.Event()

        def hold():
            with ms._lock:
                done.wait(1.0)

        t = threading.Thread(target=hold)
        t.start()
        try:
            time.sleep(0.05)
            with pytest.raises(DeadlineExceeded):
                ms.generate({"prompt": [1, 2], "max_new_tokens": 2,
                             "deadline_ms": 1})
        finally:
            done.set()
            t.join()
            ms.close()

    def test_drain_gate_sheds_are_counted(self):
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2, n_slots=1)
        try:
            ms.drain()
            for _ in range(3):
                with pytest.raises(ShedError):
                    ms.generate({"prompt": [1, 2],
                                 "max_new_tokens": 2})
            assert ms.drain_rejected == 3
            assert "ptpu_serving_drain_rejected_total 3" \
                in ms.metrics_text()
            assert ms.info()["drain_rejected_total"] == 3
        finally:
            ms.close()

    def test_prefix_cached_path_honors_deadline(self):
        """The prefix-cache solo branch (engine-less modes, or
        multi-row hits) checks the deadline under the device lock
        like every other solo path."""
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2,
                         batching="off", prefix_cache=2)
        done = threading.Event()
        try:
            ms.prefill_prompt({"prompt": [1, 2, 3, 4]})

            def hold():
                with ms._lock:
                    done.wait(1.0)

            t = threading.Thread(target=hold)
            t.start()
            try:
                time.sleep(0.05)
                with pytest.raises(DeadlineExceeded):
                    ms.generate({"prompt": [1, 2, 3, 4, 5, 6],
                                 "max_new_tokens": 2,
                                 "deadline_ms": 1})
            finally:
                done.set()
                t.join()
        finally:
            ms.close()

    def test_solo_path_deadline_sheds_before_device_work(self):
        """Engine-less modes honor deadlines up to the device-lock
        acquisition: a request that expired waiting for the device
        504s without burning a decode."""
        model, variables = _small_model()
        ms = ModelServer(model, variables, max_batch=2,
                         batching="off")
        done = threading.Event()

        def hold():
            with ms._lock:
                done.wait(1.0)

        t = threading.Thread(target=hold)
        t.start()
        try:
            time.sleep(0.05)
            with pytest.raises(DeadlineExceeded):
                ms.generate({"prompt": [1, 2], "max_new_tokens": 2,
                             "deadline_ms": 1})
        finally:
            done.set()
            t.join()
            ms.close()
