"""Native model server (polyaxon_tpu/serving/): HTTP surface over the
decode stack.  The server runs in-process on an ephemeral port;
requests go through real HTTP.  Greedy traffic exercises the
continuous-batching engine (the default batching mode)."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from polyaxon_tpu.models.generate import generate, generate_positional
from polyaxon_tpu.models.registry import get_model
from polyaxon_tpu.serving import (DecodeEngine, ModelServer,
                                  SamplingSpec, SchedulerPolicy,
                                  make_server)


@pytest.fixture(scope="module")
def server():
    spec = get_model("gpt2-tiny")
    model, variables = spec.init_params(batch_size=2)
    # self-draft: full acceptance, output must equal plain greedy
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     max_batch=4, draft_model=model,
                     draft_variables=variables)
    srv = make_server("127.0.0.1", 0, ms)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    yield base, model, variables
    srv.shutdown()
    ms.close()


def _post(base, payload, expect=200):
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == expect
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        assert e.code == expect, e.read()
        return json.loads(e.read())


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.loads(r.read())


class TestServer:
    def test_healthz_and_info(self, server):
        base, _, _ = server
        assert _get(base, "/healthz")["status"] == "ok"
        info = _get(base, "/info")
        assert info["model"] == "gpt2-tiny"
        assert info["config"]["vocab_size"] == 1024

    def test_generate_matches_library(self, server):
        base, model, variables = server
        out = _post(base, {"prompt": [5, 6, 7, 8],
                           "max_new_tokens": 6})
        want = np.asarray(generate(
            model, variables, np.asarray([[5, 6, 7, 8]], np.int32),
            max_new_tokens=6))
        assert out["tokens"] == want.tolist()
        assert len(out["new_tokens"][0]) == 6

    def test_batch_and_beam(self, server):
        base, _, _ = server
        out = _post(base, {"prompt": [[1, 2, 3], [4, 5, 6]],
                           "max_new_tokens": 4, "num_beams": 2})
        assert np.asarray(out["tokens"]).shape == (2, 7)

    def test_sampling_deterministic_by_seed(self, server):
        base, _, _ = server
        a = _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 5,
                         "temperature": 0.9, "top_p": 0.95, "seed": 7})
        b = _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 5,
                         "temperature": 0.9, "top_p": 0.95, "seed": 7})
        assert a["new_tokens"] == b["new_tokens"]

    def test_compile_cache_reuse(self, server):
        base, _, _ = server
        _post(base, {"prompt": [9, 9, 9, 9], "max_new_tokens": 6})
        n = _get(base, "/info")["compiled_shapes"]
        _post(base, {"prompt": [1, 1, 1, 1], "max_new_tokens": 6})
        assert _get(base, "/info")["compiled_shapes"] == n

    def test_errors(self, server):
        base, _, _ = server
        assert "error" in _post(base, {}, expect=400)
        assert "error" in _post(
            base, {"prompt": [[1, 2], [3]]}, expect=400)  # ragged
        assert "error" in _post(
            base, {"prompt": [1], "max_new_tokens": 0}, expect=400)
        big = [[1, 2]] * 10
        assert "max_batch" in _post(
            base, {"prompt": big}, expect=400)["error"]
        over = {"prompt": [1] * 120, "max_new_tokens": 50}
        assert "max_position" in _post(base, over,
                                       expect=400)["error"]

    def test_malformed_bodies_are_400s(self, server):
        base, _, _ = server
        assert "error" in _post(base, {"prompt": 5}, expect=400)
        assert "error" in _post(base, [1, 2], expect=400)
        assert "error" in _post(base, {"prompt": [1, 2],
                                       "top_k": [5]}, expect=400)

    def test_speculative_matches_greedy(self, server):
        base, _, _ = server
        want = _post(base, {"prompt": [5, 6, 7, 8],
                            "max_new_tokens": 6})
        got = _post(base, {"prompt": [5, 6, 7, 8],
                           "max_new_tokens": 6, "speculative": True,
                           "spec_k": 3})
        assert got["new_tokens"] == want["new_tokens"]

    def test_prefill_chunk_matches_unchunked(self, server):
        base, _, _ = server
        want = _post(base, {"prompt": [5, 6, 7, 8, 9, 1, 2, 3],
                            "max_new_tokens": 4})
        got = _post(base, {"prompt": [5, 6, 7, 8, 9, 1, 2, 3],
                           "max_new_tokens": 4, "prefill_chunk": 3})
        assert got["new_tokens"] == want["new_tokens"]
        bad = _post(base, {"prompt": [1, 2], "prefill_chunk": 0},
                    expect=400)
        assert "prefill_chunk" in bad["error"]

    def test_speculative_without_draft_400(self):
        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        ms = ModelServer(model, variables)
        with pytest.raises(ValueError, match="draft model"):
            ms.generate({"prompt": [1, 2], "speculative": True})

    def test_beam_rejects_sampling_params(self, server):
        base, _, _ = server
        out = _post(base, {"prompt": [1, 2], "num_beams": 2,
                           "temperature": 0.9}, expect=400)
        assert "deterministic" in out["error"]

    def test_404(self, server):
        base, _, _ = server
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)

    def test_boolean_tokens_rejected(self, server):
        base, _, _ = server
        out = _post(base, {"prompt": [True, False]}, expect=400)
        assert "integer token ids" in out["error"]

    def test_boolean_scalar_params_rejected(self, server):
        base, _, _ = server
        for field in ("max_new_tokens", "num_beams", "top_k", "seed",
                      "temperature", "top_p"):
            out = _post(base, {"prompt": [1, 2], field: True},
                        expect=400)
            assert "error" in out, field
        # null where an int is required is a 400, not a 500
        out = _post(base, {"prompt": [1, 2], "max_new_tokens": None},
                    expect=400)
        assert "error" in out


def _tiny_engine(n_slots=2, queue_depth=16, prefill_chunk=None,
                 decode_window=1):
    """A manually-driven engine (no loop thread): tick() is called by
    the test, so scheduling decisions are deterministic.
    decode_window=1 pins one decode step per tick so the tests'
    step-count arithmetic is exact; windowed fusion has its own
    tests."""
    spec = get_model("gpt2-tiny")
    model, variables = spec.init_params(batch_size=1)
    eng = DecodeEngine(
        model, variables, autostart=False,
        policy=SchedulerPolicy(n_slots=n_slots,
                               queue_depth=queue_depth,
                               prefill_chunk=prefill_chunk,
                               decode_window=decode_window))
    return eng, model, variables


class TestContinuousBatching:
    """The continuous-batching engine (serving/engine.py): step-level
    scheduling over a fixed slot pool.  Greedy engine responses must
    be bit-identical to solo ``generate`` — slots never interact, and
    eos-evicted rows pad to budget exactly like the solo eos-freeze."""

    def _server(self, **kw):
        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        return ModelServer(model, variables, max_batch=8,
                           **kw), model, variables

    def test_concurrent_mixed_shapes_match_solo(self):
        """The case the old coalescer could not serve: concurrent
        greedy requests with DIFFERENT prompt lengths and budgets
        share the slot pool, and every response equals its solo
        output."""
        ms, model, variables = self._server(n_slots=4)
        reqs = [
            {"prompt": [3, 1, 4, 1], "max_new_tokens": 5},
            {"prompt": [2, 7, 1, 8, 2, 8], "max_new_tokens": 8},
            {"prompt": [9, 9], "max_new_tokens": 3},
            {"prompt": [[1, 2, 3], [4, 5, 6]], "max_new_tokens": 4},
            {"prompt": [5, 6, 7, 8, 9, 1, 2, 3], "max_new_tokens": 4,
             "prefill_chunk": 3},
        ]
        refs = []
        for r in reqs:
            rows = r["prompt"] if isinstance(r["prompt"][0], list) \
                else [r["prompt"]]
            refs.append(np.asarray(generate(
                model, variables, np.asarray(rows, np.int32),
                max_new_tokens=r["max_new_tokens"])).tolist())
        results = [None] * len(reqs)

        def go(i):
            results[i] = ms.generate(dict(reqs[i]))

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(len(reqs))]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            for got, ref in zip(results, refs):
                assert got["tokens"] == ref
            stats = ms.engine.stats()
            # 6 streams through 4 slots: admission happened at step
            # boundaries, not one giant merged batch
            assert stats["admitted_total"] == 6
            assert stats["evicted_total"] == 6
            assert stats["decode_steps_total"] >= 7  # longest budget
        finally:
            ms.close()

    def test_step_boundary_admission_preserves_output(self):
        """A request submitted while the batch is mid-decode joins at
        a step boundary and still reproduces its solo output — and the
        resident request is unaffected."""
        eng, model, variables = _tiny_engine(n_slots=2)
        a = eng.submit(np.asarray([[3, 1, 4, 1]], np.int32), 8,
                       None, None)
        for _ in range(3):          # prefill+admit A, decode 2 steps
            eng.tick()
        assert eng.slots.active_slots == 1
        mid = eng.decode_steps_total
        b = eng.submit(np.asarray([[2, 7, 1, 8]], np.int32), 4,
                       None, None)
        eng.run_until_idle()
        assert a.event.is_set() and b.event.is_set()
        assert eng.decode_steps_total > mid
        want_a = np.asarray(generate(
            model, variables, np.asarray([[3, 1, 4, 1]], np.int32),
            max_new_tokens=8)).tolist()
        want_b = np.asarray(generate(
            model, variables, np.asarray([[2, 7, 1, 8]], np.int32),
            max_new_tokens=4)).tolist()
        assert a.result().tolist() == want_a
        assert b.result().tolist() == want_b

    def test_eos_eviction_frees_capacity_same_step(self):
        """A slot hitting EOS is released within that decode step, and
        the freed capacity admits a queued request at the very next
        boundary — short requests stop paying long requests' tails."""
        eng, model, variables = _tiny_engine(n_slots=1)
        # Learn a greedy continuation, then replay with eos_id set to
        # its THIRD generated token: solo semantics say tokens after
        # it freeze to eos.  The eos must fire at decode step 2
        # exactly, so it may not already be one of the first two
        # generated tokens — which a seeded random model's argmax
        # does not promise on every host (rounding moves it).  So the
        # precondition is constructed: take the first prompt whose
        # continuation has it.
        rs = np.random.RandomState(0)
        candidates = [[3, 1, 4, 1]] + rs.randint(
            1, 60, size=(15, 4)).tolist()
        for tokens in candidates:
            prompt = np.asarray([tokens], np.int32)
            solo = np.asarray(generate(
                model, variables, prompt,
                max_new_tokens=6)).tolist()[0]
            eos = solo[6]
            if eos not in solo[4:6]:
                break
        else:
            pytest.fail("no candidate prompt's greedy continuation "
                        "has a third token unseen in its first two")
        a = eng.submit(prompt, 6, eos, None)
        b = eng.submit(np.asarray([[9, 9, 2, 6]], np.int32), 3,
                       None, None)
        eng.tick()                  # prefill+admit A, decode step 1
        assert eng.slots.free_slots == 0
        assert len(eng.queue) == 1  # B waits: no capacity
        eng.tick()                  # decode step 2: A emits eos
        # eviction happened inside the step — capacity is back NOW,
        # with 4 of A's 6 budgeted tokens never decoded
        assert eng.slots.free_slots == 1
        assert a.event.is_set()
        assert eng.evicted_total == 1
        eng.tick()                  # next boundary admits B
        assert eng.slots.free_slots == 0
        eng.run_until_idle()
        # A's padded output equals solo eos-freeze; B matches solo
        want_a = np.asarray(generate(
            model, variables, prompt,
            max_new_tokens=6, eos_id=eos)).tolist()
        want_b = np.asarray(generate(
            model, variables, np.asarray([[9, 9, 2, 6]], np.int32),
            max_new_tokens=3)).tolist()
        assert a.result().tolist() == want_a
        assert b.result().tolist() == want_b

    def test_chunked_prefill_never_starves_decodes(self):
        """While a long prompt prefills chunk-by-chunk, the resident
        batch advances one token at EVERY boundary — prefill work is
        interleaved, never a stall."""
        eng, model, variables = _tiny_engine(n_slots=2)
        a = eng.submit(np.asarray([[3, 1, 4, 1]], np.int32), 10,
                       None, None)
        eng.tick()                  # admit A
        stream_a = eng._resident[next(iter(eng._resident))]
        # long prompt, tiny chunks: 5 boundaries of prefill work
        long_prompt = np.asarray([list(range(1, 11))], np.int32)
        b = eng.submit(long_prompt, 2, None, 2)
        progress = []
        while b.t_first_prefill is None or len(eng.queue) > 0:
            before = len(stream_a.out)
            eng.tick()
            progress.append(len(stream_a.out) - before)
            assert len(progress) < 50
        # every tick that carried a prefill chunk ALSO advanced A
        assert progress and all(d == 1 for d in progress)
        eng.run_until_idle()
        want_b = np.asarray(generate(
            model, variables, long_prompt, max_new_tokens=2)).tolist()
        assert b.result().tolist() == want_b
        assert a.result().tolist() == np.asarray(generate(
            model, variables, np.asarray([[3, 1, 4, 1]], np.int32),
            max_new_tokens=10)).tolist()

    def test_prefill_works_ahead_while_slots_full(self):
        """With every slot busy, a queued prompt still prefills (one
        chunk per boundary) so a freed slot admits an already-ready
        request at the next boundary instead of paying its whole
        prefill serially after the eviction."""
        eng, model, variables = _tiny_engine(n_slots=1)
        a = eng.submit(np.asarray([[3, 1, 4, 1]], np.int32), 8,
                       None, None)
        eng.tick()                  # admit A: pool is now full
        assert eng.slots.free_slots == 0
        long_prompt = np.asarray([list(range(1, 9))], np.int32)
        b = eng.submit(long_prompt, 2, None, 2)     # 4 chunks of 2
        for _ in range(4):
            eng.tick()
        # B's prompt fully consumed while A still owns the only slot
        assert eng.slots.free_slots == 0
        assert b.streams[0].pf_done
        assert len(eng.queue) == 1  # still queued, waiting on a slot
        eng.run_until_idle()
        want_b = np.asarray(generate(
            model, variables, long_prompt, max_new_tokens=2)).tolist()
        assert b.result().tolist() == want_b
        assert a.result().tolist() == np.asarray(generate(
            model, variables, np.asarray([[3, 1, 4, 1]], np.int32),
            max_new_tokens=8)).tolist()

    def test_queue_full_is_429_with_retry_after(self):
        """Backpressure surface: once the bounded admission queue is
        full, /generate sheds load with 429 + Retry-After instead of
        queueing unboundedly; queued requests still complete."""
        ms, model, variables = self._server(n_slots=1, queue_depth=2)
        srv = make_server("127.0.0.1", 0, ms)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        results = {}

        def go(name):
            results[name] = _post(base, {"prompt": [1, 2, 3],
                                         "max_new_tokens": 4})

        try:
            # Stall the engine by holding the device lock: submits
            # enqueue but nothing drains.
            threads = []
            with ms._lock:
                for name in ("a", "b"):
                    th = threading.Thread(target=go, args=(name,))
                    th.start()
                    threads.append(th)
                deadline = 100
                while deadline and len(ms.engine.queue) < 2:
                    threading.Event().wait(0.05)
                    deadline -= 1
                assert len(ms.engine.queue) == 2
                # queue full -> immediate 429 with the retry header
                req = urllib.request.Request(
                    base + "/generate",
                    data=json.dumps({"prompt": [1, 2, 3],
                                     "max_new_tokens": 4}).encode(),
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=30)
                assert ei.value.code == 429
                assert int(ei.value.headers["Retry-After"]) >= 1
                body = json.loads(ei.value.read())
                assert "retry_after" in body
            for th in threads:
                th.join(timeout=120)
            want = np.asarray(generate(
                model, variables, np.asarray([[1, 2, 3]], np.int32),
                max_new_tokens=4)).tolist()
            assert results["a"]["tokens"] == want
            assert results["b"]["tokens"] == want
            assert ms.engine.stats()["rejected_total"] == 1
            assert "ptpu_serving_rejected_total 1" in ms.metrics_text()
        finally:
            srv.shutdown()
            srv.server_close()
            ms.close()

    def test_windowed_decode_is_exact_and_fuses_dispatches(self):
        """With no admission pressure the engine fuses decode steps
        into windows (one dispatch for up to decode_window steps);
        outputs stay bit-identical to solo, including an eos that
        fires INSIDE a window (later window tokens for that stream are
        discarded garbage)."""
        eng, model, variables = _tiny_engine(n_slots=4,
                                             decode_window=8)
        solo = np.asarray(generate(
            model, variables, np.asarray([[3, 1, 4, 1]], np.int32),
            max_new_tokens=12)).tolist()[0]
        eos = solo[6]  # third generated token: eos mid-first-window
        a = eng.submit(np.asarray([[3, 1, 4, 1]], np.int32), 12,
                       eos, None)
        b = eng.submit(np.asarray([[2, 7, 1, 8]], np.int32), 12,
                       None, None)
        ticks = 0
        while not (a.event.is_set() and b.event.is_set()):
            eng.tick()
            ticks += 1
            assert ticks < 50
        # fused: B's 11 post-admission tokens took ~3 decode
        # dispatches (8+2+1), not 11 single-step boundaries
        assert ticks <= 6
        want_a = np.asarray(generate(
            model, variables, np.asarray([[3, 1, 4, 1]], np.int32),
            max_new_tokens=12, eos_id=eos)).tolist()
        want_b = np.asarray(generate(
            model, variables, np.asarray([[2, 7, 1, 8]], np.int32),
            max_new_tokens=12)).tolist()
        assert a.result().tolist() == want_a
        assert b.result().tolist() == want_b

    def test_window_drops_to_single_steps_under_pressure(self):
        """A queued request with a free slot forces single-step
        granularity (admission next boundary), and the window never
        fuses past the earliest budget eviction.  The engine runs one
        dispatch ahead, so a window is read where it is LAUNCHED:
        tokens committed plus tokens in flight."""
        eng, _, _ = _tiny_engine(n_slots=2, decode_window=8)

        def launched(group):
            stream = group.streams[0]
            return len(stream.out) + stream.in_flight

        a = eng.submit(np.asarray([[3, 1, 4, 1]], np.int32), 20,
                       None, None)
        eng.tick()          # admit A (token 1) + one full window of 8
        assert launched(a) == 9
        # alone, rem=11 -> full window
        assert eng._pick_window() == 8
        b = eng.submit(np.asarray([[2, 7]], np.int32), 4, None, None)
        # queued + a free slot -> single step (admission next tick)
        assert eng._pick_window() == 1
        eng.tick()          # prefills B behind the window in flight
        assert launched(a) == 10 and b.streams[0].pf_done
        eng.tick()          # admits B; window = min(rem) = 3 -> 2
        assert len(eng.queue) == 0
        assert launched(b) == 3
        # B one token from budget: the window clamps to it
        assert eng._pick_window() == 1
        eng.tick()          # B's budget ends with this launch ...
        assert launched(b) == 4 and b.streams[0].slot not in eng._resident
        assert eng._pick_window() == 4      # A alone again, rem 7
        eng.tick()          # ... and B completes when it is collected
        assert b.event.is_set()
        eng.run_until_idle()
        assert a.event.is_set()

    def test_window_stays_single_step_while_queued_prefill_pending(self):
        """A queued prompt mid-chunked-prefill pins the window to 1
        even with a full pool and no eos-capable resident: fusing
        would starve prefill-ahead (one chunk per BOUNDARY) and leave
        the next evicted slot waiting on an unfinished prompt."""
        eng, _, _ = _tiny_engine(n_slots=1, decode_window=8)
        a = eng.submit(np.asarray([[3, 1, 4, 1]], np.int32), 20,
                       None, None)
        eng.tick()                  # admit A + one fused window
        assert eng._pick_window() == 8      # alone, empty queue
        b = eng.submit(
            np.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32), 4,
            None, 2)
        assert eng._pick_window() == 1      # head still mid-prefill
        for _ in range(3):          # one 2-token chunk per boundary
            eng.tick()
            assert eng._pick_window() == 1
        assert not b.streams[0].pf_done
        stream = a.streams[0]
        before = len(stream.out) + stream.in_flight
        # The tick that finishes B's last chunk resumes fusion in its
        # own decode phase (prefilled, pool full, no eos: the only
        # capacity event is A's budget eviction).
        eng.tick()
        assert b.streams[0].pf_done
        assert len(stream.out) + stream.in_flight - before > 1
        eng.run_until_idle()
        assert a.event.is_set() and b.event.is_set()

    def test_response_carries_phase_breakdown(self):
        ms, _, _ = self._server(n_slots=2)
        try:
            out = ms.generate({"prompt": [1, 2, 3],
                               "max_new_tokens": 4})
            for f in ("queue_ms", "prefill_ms", "decode_ms"):
                assert f in out and out[f] >= 0.0
        finally:
            ms.close()

    def test_http_concurrent_greedy(self, server):
        """End-to-end over HTTP: concurrent same-shape greedy clients
        all get the same answer as a solo request."""
        base, _, _ = server
        solo = _post(base, {"prompt": [4, 4, 4, 4],
                            "max_new_tokens": 5})
        results = [None] * 4

        def go(i):
            results[i] = _post(base, {"prompt": [4, 4, 4, 4],
                                      "max_new_tokens": 5})

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for r in results:
            assert r["new_tokens"] == solo["new_tokens"]


class TestLegacyCoalescing:
    """The seed coalescing path survives as ``batching="coalesce"`` —
    the measured A/B baseline for bench_serving_load.py.  Concurrent
    same-shape greedy requests merge into one device batch,
    bit-identical to solo execution."""

    def test_forced_coalesce_matches_solo(self):
        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        ms = ModelServer(model, variables, max_batch=8,
                         batching="coalesce")
        assert ms.engine is None
        prompts = [[3, 1, 4, 1], [2, 7, 1, 8], [9, 9, 2, 6]]
        # Solo references (also pre-warms the b=1 compile; the merged
        # n=3 batch pads to bucket 4 — a different program).
        refs = [ms.generate({"prompt": p, "max_new_tokens": 5})
                for p in prompts]
        results = [None] * len(prompts)

        def go(i):
            results[i] = ms.generate({"prompt": prompts[i],
                                      "max_new_tokens": 5})

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(len(prompts))]
        # Hold the device lock so every worker ENQUEUES before any can
        # lead — guarantees one merged batch instead of racing on
        # thread-start timing.
        pending = ms._coalescer._pending
        with ms._lock:
            for t in threads:
                t.start()
            deadline = 50
            while deadline > 0 and sum(
                    len(q) for q in pending.values()) < len(prompts):
                threading.Event().wait(0.1)
                deadline -= 1
            assert sum(len(q) for q in pending.values()) \
                == len(prompts)
        for t in threads:
            t.join(timeout=120)
        assert ms.coalesced_batches == 1
        assert ms.coalesced_requests == len(prompts)
        for got, ref in zip(results, refs):
            assert got["new_tokens"] == ref["new_tokens"]

    @staticmethod
    def _coalesce_server(max_batch=8):
        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        return ModelServer(model, variables, max_batch=max_batch,
                           batching="coalesce")

    def test_beam_and_speculative_stay_solo_under_coalesce(self):
        """Beam and speculative greedy requests must never be
        hijacked by the greedy coalescer: a coalesced argmax batch
        would silently answer a beam request with greedy tokens."""
        from polyaxon_tpu.models.generate import generate_beam

        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        ms = ModelServer(model, variables, batching="coalesce",
                         draft_model=model, draft_variables=variables)
        try:
            out = ms.generate({"prompt": [1, 2, 3], "num_beams": 2,
                               "max_new_tokens": 4})
            want = generate_beam(model, variables,
                                 np.asarray([[1, 2, 3]], np.int32),
                                 max_new_tokens=4, num_beams=2)
            assert out["tokens"] == np.asarray(want).tolist()
            ms.generate({"prompt": [1, 2, 3], "max_new_tokens": 4,
                         "speculative": True, "spec_k": 2})
            # the speculative request compiled/ran the spec program
            # (token equality with greedy is BY DESIGN, so assert the
            # routing itself)
            assert any(k[0] == "spec" for k in ms._fns)
        finally:
            ms.close()

    def test_seq2seq_default_falls_back_to_coalesce(self):
        """The slot engine is decoder-only; a seq2seq model under the
        default batching='continuous' must keep request batching via
        the coalescer (the seed behavior) — and /info must report the
        mode that actually runs, not a silently-serialized
        'continuous'."""
        spec = get_model("t5-tiny")
        model, variables = spec.init_params(batch_size=1)
        ms = ModelServer(model, variables)
        assert ms.engine is None
        assert ms._coalescer is not None
        assert ms.batching == "coalesce"
        assert ms.info()["batching"] == "coalesce"

    def test_heterogeneous_lengths_merge(self):
        """Requests differing only in max_new_tokens merge into one
        batch decoding to the longest; every response equals its solo
        output (eos-freeze rows truncate exactly)."""
        ms = self._coalesce_server()
        reqs = [
            {"prompt": [3, 1, 4, 1], "max_new_tokens": 3},
            {"prompt": [2, 7, 1, 8], "max_new_tokens": 7},
            {"prompt": [9, 9, 2, 6], "max_new_tokens": 5},
        ]
        refs = [ms.generate(dict(r)) for r in reqs]
        results = [None] * len(reqs)

        def go(i):
            results[i] = ms.generate(dict(reqs[i]))

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(len(reqs))]
        pending = ms._coalescer._pending
        with ms._lock:
            for t in threads:
                t.start()
            deadline = 50
            while deadline > 0 and sum(
                    len(q) for q in pending.values()) < len(reqs):
                threading.Event().wait(0.1)
                deadline -= 1
            # ONE key despite three different budgets
            assert len(pending) == 1
        for t in threads:
            t.join(timeout=120)
        assert ms.coalesced_batches == 1
        assert ms.coalesced_requests == len(reqs)
        for got, ref, req in zip(results, refs, reqs):
            assert got["new_tokens"] == ref["new_tokens"]
            assert len(got["new_tokens"][0]) == req["max_new_tokens"]

    def test_mixed_shapes_coalesce_per_key(self):
        """Different prompt lengths queue under different keys (new is
        NOT part of the key — budgets merge); a leader only merges its
        own key's queue."""
        ms = self._coalesce_server()
        a_ref = ms.generate({"prompt": [1, 2, 3], "max_new_tokens": 4})
        b_ref = ms.generate({"prompt": [5, 6], "max_new_tokens": 3})
        results = {}

        def go(name, payload):
            results[name] = ms.generate(payload)

        threads = [
            threading.Thread(target=go, args=(
                "a", {"prompt": [1, 2, 3], "max_new_tokens": 4})),
            threading.Thread(target=go, args=(
                "b", {"prompt": [5, 6], "max_new_tokens": 3})),
        ]
        pending = ms._coalescer._pending
        with ms._lock:
            for t in threads:
                t.start()
            deadline = 50
            while deadline > 0 and sum(
                    len(q) for q in pending.values()) < 2:
                threading.Event().wait(0.1)
                deadline -= 1
        for t in threads:
            t.join(timeout=120)
        assert results["a"]["new_tokens"] == a_ref["new_tokens"]
        assert results["b"]["new_tokens"] == b_ref["new_tokens"]
        # two keys -> two solo-sized batches, nothing merged
        assert ms.coalesced_batches == 0

    def test_multirow_requests_merge_within_cap(self):
        """A 2-row and a 1-row request merge (3 rows, bucket 4); a
        request that would overflow max_batch waits for the next
        leader round instead of being dropped."""
        ms = self._coalesce_server(max_batch=4)
        p2 = [[1, 2, 3], [4, 5, 6]]
        p1 = [7, 8, 9]
        ref2 = ms.generate({"prompt": p2, "max_new_tokens": 4})
        ref1 = ms.generate({"prompt": p1, "max_new_tokens": 4})
        big = [[i, i + 1, i + 2] for i in range(4)]  # fills the cap
        ref_big = ms.generate({"prompt": big, "max_new_tokens": 4})
        results = {}

        def go(name, payload):
            results[name] = ms.generate(payload)

        threads = [
            threading.Thread(target=go, args=(
                "two", {"prompt": p2, "max_new_tokens": 4})),
            threading.Thread(target=go, args=(
                "one", {"prompt": p1, "max_new_tokens": 4})),
            threading.Thread(target=go, args=(
                "big", {"prompt": big, "max_new_tokens": 4})),
        ]
        pending = ms._coalescer._pending
        with ms._lock:
            for t in threads:
                t.start()
            deadline = 50
            while deadline > 0 and sum(
                    len(q) for q in pending.values()) < 3:
                threading.Event().wait(0.1)
                deadline -= 1
        for t in threads:
            t.join(timeout=180)
        assert results["two"]["new_tokens"] == ref2["new_tokens"]
        assert results["one"]["new_tokens"] == ref1["new_tokens"]
        assert results["big"]["new_tokens"] == ref_big["new_tokens"]


def _fp32_tiny():
    """gpt2-tiny in f32: the sampled exactness tests compare tokens
    ACROSS compiled programs (engine slot step vs the solo positional
    reference, split vs one-shot prefill), where bf16's one-ulp
    cross-program rounding can flip a borderline top-k/nucleus
    threshold (docs/SERVING.md caveat); f32 margins dominate that
    noise."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
    model = GPT2Model(cfg=cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


class TestSampledEngine:
    """Sampled requests as engine citizens (PR 2): per-slot
    position-keyed PRNG streams + per-slot sampling params in the
    slot step program.  The load-bearing contract is CO-TENANCY-
    INVARIANT DETERMINISM: a request's i-th generated token is drawn
    with ``fold_in(fold_in(PRNGKey(seed), row), i)`` — a function of
    the request alone — so the engine must reproduce the solo
    ``generate_positional`` reference under ANY admission schedule."""

    PROMPT = [3, 1, 4, 1]
    SPEC = dict(seed=7, temperature=0.9, top_k=16, top_p=0.95)

    def _reference(self, model, variables, new=8, **over):
        kw = {**self.SPEC, **over}
        return np.asarray(generate_positional(
            model, variables, np.asarray([self.PROMPT], np.int32),
            max_new_tokens=new, **kw)).tolist()

    def test_determinism_across_cotenancy_schedules(self):
        """The property test the contract is named for: the same
        sampled request + seed, run under three different co-tenancy/
        admission schedules (alone; into a full mixed pool; admitted
        mid-flight next to a running stream), returns byte-identical
        tokens — all equal to the position-keyed solo reference."""
        model, variables = _fp32_tiny()
        want = self._reference(model, variables)
        prompt = np.asarray([self.PROMPT], np.int32)

        def run(schedule):
            eng = DecodeEngine(
                model, variables, autostart=False,
                policy=SchedulerPolicy(n_slots=4, decode_window=4))
            if schedule == "alone":
                g = eng.submit(prompt, 8, None, None,
                               sampling=SamplingSpec(**self.SPEC))
            elif schedule == "full-pool":
                # three co-tenants with their own streams (greedy and
                # sampled) occupy the pool before the target arrives
                for i in range(3):
                    eng.submit(
                        np.asarray([[9, 9, 2, 6]], np.int32), 6,
                        None, None,
                        sampling=SamplingSpec(seed=i, temperature=1.1,
                                              top_k=8) if i else None)
                g = eng.submit(prompt, 8, None, None,
                               sampling=SamplingSpec(**self.SPEC))
            else:  # mid-flight admission into a decoding batch
                eng.submit(np.asarray([[2, 7, 1, 8]], np.int32), 10,
                           None, None)
                for _ in range(3):
                    eng.tick()
                g = eng.submit(prompt, 8, None, None,
                               sampling=SamplingSpec(**self.SPEC))
            eng.run_until_idle()
            return g.result().tolist()

        for schedule in ("alone", "full-pool", "mid-flight"):
            assert run(schedule) == want, schedule

    def test_greedy_cotenant_unaffected_by_sampled_neighbors(self):
        """A greedy stream sharing the pool with sampled streams still
        reproduces solo greedy ``generate`` exactly — the sampled step
        program's argmax lane is the same argmax."""
        model, variables = _fp32_tiny()
        prompt = np.asarray([self.PROMPT], np.int32)
        want = np.asarray(generate(
            model, variables, prompt, max_new_tokens=8)).tolist()
        eng = DecodeEngine(
            model, variables, autostart=False,
            policy=SchedulerPolicy(n_slots=3, decode_window=4))
        g = eng.submit(prompt, 8, None, None)
        eng.submit(np.asarray([[9, 9, 2, 6]], np.int32), 8, None,
                   None, sampling=SamplingSpec(seed=1, temperature=1.0,
                                               top_k=8))
        eng.submit(np.asarray([[2, 7, 1, 8]], np.int32), 8, None,
                   None, sampling=SamplingSpec(seed=2, temperature=0.8,
                                               top_p=0.9))
        eng.run_until_idle()
        assert g.result().tolist() == want
        assert eng.admitted_sampled_total == 2
        assert eng.admitted_greedy_total == 1

    def test_sampled_eos_freeze_matches_reference(self):
        """A sampled stream hitting EOS mid-budget evicts its slot and
        pads to budget exactly like the solo reference's eos-freeze."""
        model, variables = _fp32_tiny()
        prompt = np.asarray([self.PROMPT], np.int32)
        free = self._reference(model, variables, new=8)
        eos = free[0][4 + 2]            # third generated token
        assert eos not in free[0][4:6]  # freeze fires at step 2
        want = self._reference(model, variables, new=8, eos_id=eos)
        eng = DecodeEngine(model, variables, autostart=False,
                           policy=SchedulerPolicy(n_slots=2))
        g = eng.submit(prompt, 8, eos, None,
                       sampling=SamplingSpec(**self.SPEC))
        eng.run_until_idle()
        assert g.result().tolist() == want
        assert eng.evicted_total == 1

    def test_sampled_chunked_prefill_matches_reference(self):
        """Chunked prefill is position-keyed cache mechanics — it must
        not shift a sampled stream either."""
        model, variables = _fp32_tiny()
        long_prompt = np.asarray([list(range(1, 11))], np.int32)
        want = np.asarray(generate_positional(
            model, variables, long_prompt, max_new_tokens=5,
            **self.SPEC)).tolist()
        eng = DecodeEngine(model, variables, autostart=False,
                           policy=SchedulerPolicy(n_slots=2))
        g = eng.submit(long_prompt, 5, None, 3,
                       sampling=SamplingSpec(**self.SPEC))
        eng.run_until_idle()
        assert g.result().tolist() == want

    def test_multirow_sampled_request_matches_reference(self):
        """Each row of a B>1 sampled request is its own stream with
        base key fold_in(PRNGKey(seed), row) — together they equal the
        batched positional reference."""
        model, variables = _fp32_tiny()
        rows = np.asarray([[3, 1, 4, 1], [2, 7, 1, 8]], np.int32)
        want = np.asarray(generate_positional(
            model, variables, rows, max_new_tokens=6,
            **self.SPEC)).tolist()
        eng = DecodeEngine(model, variables, autostart=False,
                           policy=SchedulerPolicy(n_slots=4))
        g = eng.submit(rows, 6, None, None,
                       sampling=SamplingSpec(**self.SPEC))
        eng.run_until_idle()
        assert g.result().tolist() == want

    def test_sampled_prefix_hit_rides_engine_and_matches_cold(self):
        """A sampled single-row prefix-cache hit seeds an engine
        stream (no solo device-lock hold) and must return the cold
        response bit-for-bit: position-keyed token indices restart at
        0 for new tokens, so the prefill split cannot shift the
        draw."""
        model, variables = _fp32_tiny()
        ms = ModelServer(model, variables, max_batch=4)
        try:
            system = [7, 3, 9, 2, 5, 1]
            req = {"prompt": system + [4, 8], "max_new_tokens": 5,
                   "temperature": 0.8, "top_k": 32, "seed": 9}
            cold = ms.generate(dict(req))
            assert "prefix_hit_len" not in cold
            ms.prefill_prompt({"prompt": system})
            before = ms.engine.stats()
            warm = ms.generate(dict(req))
            after = ms.engine.stats()
            assert warm["prefix_hit_len"] == len(system)
            assert warm["new_tokens"] == cold["new_tokens"]
            assert after["admitted_sampled_total"] == \
                before["admitted_sampled_total"] + 1
        finally:
            ms.close()

    def test_uniform_validation_messages_across_paths(self):
        """Satellite contract: top_k out of [1, vocab] and top_p out
        of (0, 1] are 400-mapped ValueErrors with ONE message on
        every path — engine, coalesce, serialized, speculative."""
        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        bad = {
            "top_k_zero": {"temperature": 0.9, "top_k": 0},
            "top_k_over": {"temperature": 0.9, "top_k": 4096},
            "top_p_zero": {"temperature": 0.9, "top_p": 0.0},
            "top_p_over": {"temperature": 0.9, "top_p": 1.5},
            "spec_top_k": {"speculative": True, "temperature": 0.9,
                           "top_k": 0},
        }
        msgs = {}
        for mode in ("continuous", "coalesce", "off"):
            ms = ModelServer(model, variables, batching=mode,
                             draft_model=model,
                             draft_variables=variables)
            try:
                for name, extra in bad.items():
                    with pytest.raises(ValueError) as ei:
                        ms.generate({"prompt": [1, 2],
                                     "max_new_tokens": 2, **extra})
                    msgs.setdefault(name, set()).add(str(ei.value))
            finally:
                ms.close()
        for name, seen in msgs.items():
            assert len(seen) == 1, (name, seen)
        assert "top_k must be in [1, 1024]" in msgs["top_k_zero"].pop()
        assert "top_p must be in (0, 1]" in msgs["top_p_over"].pop()


class TestRingBeam:
    def test_beam_on_ring_cache_serves(self):
        """Beam search works on ring-cache models (round 5): the
        server must not reject it, and the response matches the
        library's beam output on the same ring model."""
        import numpy as np

        from polyaxon_tpu.models.generate import generate_beam

        spec = get_model("mistral-tiny")
        model, variables = spec.init_params(batch_size=1)
        ring = spec.make_model(kv_cache_ring=True)
        ms = ModelServer(ring, variables)
        out = ms.generate({"prompt": [1, 2, 3], "num_beams": 2,
                           "max_new_tokens": 4})
        want = generate_beam(ring, variables,
                             np.asarray([[1, 2, 3]], np.int32),
                             max_new_tokens=4, num_beams=2)
        assert out["tokens"] == np.asarray(want).tolist()

    def test_beam_on_unstacked_layers_serves(self):
        """Beam on scan_layers=False models works (round 5: the beam
        tile/reorder targets the layout's batch axis) — the server
        must serve it, matching the library's output."""
        import numpy as np

        from polyaxon_tpu.models.generate import generate_beam

        spec = get_model("llama-tiny")
        flat = spec.make_model(scan_layers=False)
        import jax
        import jax.numpy as jnp
        variables = flat.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 3), jnp.int32))
        ms = ModelServer(flat, variables)
        out = ms.generate({"prompt": [1, 2, 3], "num_beams": 2,
                           "max_new_tokens": 4})
        want = generate_beam(flat, variables,
                             np.asarray([[1, 2, 3]], np.int32),
                             max_new_tokens=4, num_beams=2)
        assert out["tokens"] == np.asarray(want).tolist()


class TestSampledSpeculative:
    def test_sampled_speculative_serves_and_is_seeded(self, server):
        """Rejection speculative sampling through the server: sampled
        speculative requests are accepted (round 5 — no longer
        greedy-only), deterministic by seed, and vary across seeds."""
        base, _, _ = server
        req = {"prompt": [5, 6, 7, 8], "max_new_tokens": 6,
               "speculative": True, "spec_k": 3,
               "temperature": 0.9, "top_k": 16, "seed": 7}
        a = _post(base, dict(req))
        b = _post(base, dict(req))
        assert a["new_tokens"] == b["new_tokens"]
        c = _post(base, {**req, "seed": 8})
        assert len(c["new_tokens"][0]) == 6
        # a different seed must change the sample — this is the guard
        # against the server silently falling back to greedy
        assert c["new_tokens"] != a["new_tokens"]
        # sampling flags without temperature are rejected, not dropped
        out = _post(base, {"prompt": [1, 2], "speculative": True,
                           "top_k": 5}, expect=400)
        assert "temperature" in out["error"]
        # beam + speculative is still rejected
        out = _post(base, {"prompt": [1, 2], "speculative": True,
                           "num_beams": 2}, expect=400)
        assert "beam" in out["error"]


class TestMetrics:
    def test_metrics_endpoint(self, server):
        """GET /metrics: Prometheus text with the serving counters,
        advancing with traffic (incl. the error counter)."""
        base, _, _ = server
        _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 4})
        _post(base, {"prompt": [1], "max_new_tokens": 0}, expect=400)
        with urllib.request.urlopen(base + "/metrics",
                                    timeout=30) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        metrics = {}
        for line in body.splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                metrics[name] = float(value)
        assert metrics["ptpu_serving_requests_total"] >= 1
        assert metrics["ptpu_serving_errors_total"] >= 1
        assert metrics["ptpu_serving_tokens_generated_total"] >= 4
        assert metrics["ptpu_serving_request_seconds_count"] >= 1
        assert metrics["ptpu_serving_request_seconds_sum"] > 0
        # per-request phase breakdown (queue -> prefill -> decode)
        assert metrics["ptpu_serving_queue_seconds_count"] >= 1
        assert metrics["ptpu_serving_prefill_seconds_sum"] >= 0
        assert metrics["ptpu_serving_decode_seconds_sum"] > 0
        # continuous-batching engine surface
        assert metrics["ptpu_serving_slots"] >= 1
        assert metrics["ptpu_serving_admitted_total"] >= 1
        assert metrics["ptpu_serving_evicted_total"] >= 1
        assert metrics["ptpu_serving_decode_steps_total"] >= 1
        assert metrics["ptpu_serving_rejected_total"] >= 0


class TestPrefixCache:
    """Prefix caching (round 5): /prefill registers a prompt's KV
    prefill; /generate requests extending it skip that prefill and
    must be BIT-IDENTICAL to cold responses."""

    def _server(self, **kw):
        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=1)
        ms = ModelServer(model, variables, max_batch=4, **kw)
        srv = make_server("127.0.0.1", 0, ms)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        return ms, srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def _post_to(self, base, path, payload, expect=200):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                assert r.status == expect
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            assert e.code == expect, e.read()
            return json.loads(e.read())

    def test_hit_is_bit_identical_to_cold(self):
        ms, srv, base = self._server()
        try:
            system = [7, 3, 9, 2, 5, 1]
            user = system + [4, 8]
            # cold responses first (greedy + sampled)
            cold_g = self._post_to(base, "/generate",
                                   {"prompt": user,
                                    "max_new_tokens": 5})
            cold_s = self._post_to(base, "/generate",
                                   {"prompt": user, "max_new_tokens": 5,
                                    "temperature": 0.8, "seed": 9})
            assert "prefix_hit_len" not in cold_g
            # register the system prefix
            r = self._post_to(base, "/prefill", {"prompt": system})
            assert r["cached_len"] == len(system)
            warm_g = self._post_to(base, "/generate",
                                   {"prompt": user,
                                    "max_new_tokens": 5})
            assert warm_g["prefix_hit_len"] == len(system)
            assert warm_g["new_tokens"] == cold_g["new_tokens"]
            warm_s = self._post_to(base, "/generate",
                                   {"prompt": user, "max_new_tokens": 5,
                                    "temperature": 0.8, "seed": 9})
            assert warm_s["new_tokens"] == cold_s["new_tokens"]
            info = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            assert info["prefix_hits"] == 2
            # the extension stored the longer prompt: exact repeat now
            # hits at FULL length (session growth)
            again = self._post_to(base, "/generate",
                                  {"prompt": user,
                                   "max_new_tokens": 5})
            assert again["prefix_hit_len"] == len(user)
            assert again["new_tokens"] == cold_g["new_tokens"]
        finally:
            srv.shutdown()
            srv.server_close()

    def test_greedy_hit_routes_through_engine(self):
        """A greedy single-row hit rides the continuous-batching
        engine seeded with the stored prefill — no whole-decode
        device-lock hold — paying prefill only for the suffix, and
        NOTHING on a full-length hit; the extension is stored back
        from the engine thread (session growth)."""
        ms, srv, base = self._server()
        try:
            system = [7, 3, 9, 2, 5, 1]
            user = system + [4, 8]
            cold = self._post_to(base, "/generate",
                                 {"prompt": user,
                                  "max_new_tokens": 5})
            self._post_to(base, "/prefill", {"prompt": system})
            before = ms.engine.stats()
            warm = self._post_to(base, "/generate",
                                 {"prompt": user, "max_new_tokens": 5})
            mid = ms.engine.stats()
            # through the engine (admitted), prefilling ONLY the
            # 2-token suffix (one chunk), not the 8-token prompt
            assert mid["admitted_total"] == before["admitted_total"] + 1
            assert mid["prefill_chunks_total"] == \
                before["prefill_chunks_total"] + 1
            assert warm["new_tokens"] == cold["new_tokens"]
            assert warm["prefix_hit_len"] == len(system)
            # the engine stored the extension back: a repeat hits at
            # FULL length and skips prefill entirely
            again = self._post_to(base, "/generate",
                                  {"prompt": user, "max_new_tokens": 5})
            after = ms.engine.stats()
            assert again["prefix_hit_len"] == len(user)
            assert again["new_tokens"] == cold["new_tokens"]
            assert after["admitted_total"] == mid["admitted_total"] + 1
            assert after["prefill_chunks_total"] == \
                mid["prefill_chunks_total"]   # zero prefill work
        finally:
            srv.shutdown()
            srv.server_close()
            ms.close()

    def test_engine_prefix_seeded_submit_matches_unseeded(self):
        """Engine-level contract for the prefix-hit path: a stream
        seeded with (p_cached, logits, cache) from a stored prefill
        produces the same tokens as an unseeded submit, for partial
        and full-length seeds, and fires on_prefilled exactly once."""
        from polyaxon_tpu.models.generate import prefill

        eng, model, variables = _tiny_engine(n_slots=2)
        prompt = np.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
        want = eng.submit(prompt, 6, None, None)
        eng.run_until_idle()
        want = want.result().tolist()
        stored = []
        for pc in (5, 8):           # partial and full-length seeds
            lg, cache = prefill(model, variables, prompt[:, :pc])
            g = eng.submit(prompt, 6, None, None,
                           prefix=(pc, lg, cache),
                           on_prefilled=stored.append)
            eng.run_until_idle()
            assert g.result().tolist() == want
        assert len(stored) == 2
        assert stored[0].filled == 8    # suffix consumed before admit

    def test_prefill_validation(self):
        ms, srv, base = self._server()
        try:
            # over max_position: 400 in the validation layer
            out = self._post_to(base, "/prefill",
                                {"prompt": [1] * 500}, expect=400)
            assert "max_position" in out["error"]
            # boolean / non-scalar prefill_chunk: normalized 400s,
            # same message contract as /generate
            for bad in (True, [1], "x"):
                out = self._post_to(base, "/prefill",
                                    {"prompt": [1, 2],
                                     "prefill_chunk": bad},
                                    expect=400)
                assert "prefill_chunk must be an int" in out["error"]
        finally:
            srv.shutdown()
            srv.server_close()

    def test_lru_bound_and_disable(self):
        ms, srv, base = self._server(prefix_cache=2)
        try:
            for i in range(3):
                self._post_to(base, "/prefill",
                              {"prompt": [i + 1, i + 2, i + 3]})
            info = json.loads(urllib.request.urlopen(
                base + "/info", timeout=30).read())
            assert info["prefix_entries"] == 2  # LRU evicted the first
        finally:
            srv.shutdown()
            srv.server_close()
        ms2, srv2, base2 = self._server(prefix_cache=0)
        try:
            out = self._post_to(base2, "/prefill", {"prompt": [1, 2]},
                                expect=400)
            assert "disabled" in out["error"]
        finally:
            srv2.shutdown()
            srv2.server_close()


@pytest.mark.slow
class TestRequestSpace:
    """Seeded property test over the request-combination space the
    round-5 features opened up (lengths x greedy/sampled/beam/
    speculative/sampled-speculative x eos x chunk x prefix hits):
    every response is well-formed and greedy repeats replay
    bit-identically across the cold, warm-prefix, and solo paths.
    (Concurrent coalescing and the HTTP error surface have their own
    dedicated tests above.)"""

    def test_randomized_requests_deterministic(self):
        import random

        spec = get_model("gpt2-tiny")
        model, variables = spec.init_params(batch_size=2)
        ms = ModelServer(model, variables, max_batch=4,
                         draft_model=model, draft_variables=variables)
        rng = random.Random(12345)
        vocab = model.cfg.vocab_size
        # one registered prefix so hits interleave with cold paths
        ms.prefill_prompt({"prompt": [3, 1, 4]})

        greedy_outputs = {}
        for i in range(60):
            p_len = rng.choice([2, 3, 4, 6])
            b = rng.choice([1, 1, 1, 2])
            rows = [[rng.randrange(0, vocab) for _ in range(p_len)]
                    for _ in range(b)]
            if rng.random() < 0.3:  # force prefix-hit candidates
                rows = [[3, 1, 4] + r[:p_len - 3] for r in rows] \
                    if p_len > 3 and b == 1 else rows
            new = rng.choice([1, 3, 5])
            req = {"prompt": rows if b > 1 else rows[0],
                   "max_new_tokens": new}
            mode = rng.choice(["greedy", "sampled", "beam", "spec",
                               "spec-sampled"])
            if mode == "sampled":
                req.update(temperature=0.8, seed=rng.randrange(99))
            elif mode == "beam":
                req.update(num_beams=2)
            elif mode == "spec":
                req.update(speculative=True, spec_k=2)
            elif mode == "spec-sampled":
                req.update(speculative=True, spec_k=2,
                           temperature=0.7, seed=rng.randrange(99))
            if rng.random() < 0.2 and p_len > 2:
                req["prefill_chunk"] = 2
            if rng.random() < 0.2:
                req["eos_id"] = rng.randrange(0, vocab)
            out = ms.generate(dict(req))
            # well-formed: every row has exactly `new` new tokens in
            # vocab range
            assert len(out["new_tokens"]) == b
            for row in out["new_tokens"]:
                assert len(row) == new
                assert all(0 <= t < vocab for t in row)
            if mode == "greedy":
                key = json.dumps(req, sort_keys=True)
                prev = greedy_outputs.get(key)
                if prev is not None:
                    # replay determinism across cold/warm/coalesced
                    assert prev == out["new_tokens"], key
                greedy_outputs[key] = out["new_tokens"]
        # the run exercised prefix hits
        assert ms.prefix_hits > 0


class TestSpeculativeEngineServing:
    """Speculative requests as engine citizens (PR 3): routing,
    cross-mode token agreement per seed, and the shared spec
    observability surface.  Engine-vs-solo exactness under schedules
    lives in tests/test_spec_engine.py; this class pins the SERVER
    layer."""

    def _servers(self, **kw):
        model, variables = _fp32_tiny()
        return model, variables, {
            mode: ModelServer(model, variables, max_batch=4,
                              batching=mode, draft_model=model,
                              draft_variables=variables, **kw)
            for mode in ("continuous", "coalesce", "off")}

    def test_every_batching_mode_agrees_per_seed(self):
        """Greedy AND sampled speculative requests return identical
        tokens through the engine (continuous), the coalesce-mode
        solo fallback, and the serialized floor — the solo sampled
        path runs generate_speculative's seed mode, the same
        schedule the engine's spec slots run."""
        model, variables, servers = self._servers()
        reqs = {
            "greedy": {"prompt": [5, 6, 7, 8], "max_new_tokens": 6,
                       "speculative": True, "spec_k": 3},
            "sampled": {"prompt": [5, 6, 7, 8], "max_new_tokens": 6,
                        "speculative": True, "spec_k": 3,
                        "temperature": 0.9, "top_k": 16, "seed": 7},
        }
        try:
            for name, req in reqs.items():
                outs = {mode: ms.generate(dict(req))["new_tokens"]
                        for mode, ms in servers.items()}
                assert outs["continuous"] == outs["coalesce"], name
                assert outs["continuous"] == outs["off"], name
            # the engine actually served them (not a silent solo)
            es = servers["continuous"].engine.stats()
            assert es["admitted_spec_total"] == len(reqs)
            assert es["completed_spec_total"] == len(reqs)
        finally:
            for ms in servers.values():
                ms.close()

    def test_coalesce_fallback_logged_and_reported(self):
        """The satellite fix: engine-less modes route speculative
        requests solo — no longer silently.  The fallback lands in
        /info's routing report with a reason and a count."""
        model, variables, servers = self._servers()
        try:
            ms = servers["coalesce"]
            assert ms.info()["routing"]["speculative"] == "solo"
            ms.generate({"prompt": [1, 2, 3], "max_new_tokens": 2,
                         "speculative": True, "spec_k": 2})
            ms.generate({"prompt": [1, 2, 3], "max_new_tokens": 2,
                         "speculative": True, "spec_k": 2})
            fb = ms.info()["solo_fallbacks"]["speculative"]
            assert fb["count"] == 2
            assert "solo" in fb["reason"]
            # the engine-backed server reports engine routing and no
            # speculative fallback
            info = servers["continuous"].info()
            assert info["routing"]["speculative"] == "engine"
            assert "speculative" not in info["solo_fallbacks"]
        finally:
            for ms in servers.values():
                ms.close()

    def test_spec_k_over_cap_falls_back_solo_with_same_tokens(self):
        """A request asking for a draft length above the server's
        --spec-k cap decodes solo (the pool program is compiled at
        the cap) — logged, counted, and token-identical to an
        engine-less server."""
        model, variables = _fp32_tiny()
        eng = ModelServer(model, variables, max_batch=2,
                          draft_model=model,
                          draft_variables=variables, spec_k=2)
        solo = ModelServer(model, variables, max_batch=2,
                           batching="off", draft_model=model,
                           draft_variables=variables, spec_k=2)
        try:
            req = {"prompt": [5, 6, 7, 8], "max_new_tokens": 6,
                   "speculative": True, "spec_k": 4,
                   "temperature": 0.9, "seed": 3}
            a = eng.generate(dict(req))
            b = solo.generate(dict(req))
            assert a["new_tokens"] == b["new_tokens"]
            assert eng.engine.stats()["admitted_spec_total"] == 0
            fb = eng.info()["solo_fallbacks"]
            assert any("spec_k" in k for k in fb)
            # default spec_k comes from the server flag
            assert eng.info()["spec_k_default"] == 2
        finally:
            eng.close()
            solo.close()

    def test_near_capacity_cotenant_falls_back_solo(self):
        """On a spec-capable engine every resident's verify chunk is
        cap+1 wide, so a greedy request within cap-1 tokens of
        max_position decodes solo (correctly, with a logged reason)
        instead of scribbling past the cache end."""
        model, variables = _fp32_tiny()
        max_pos = model.cfg.max_position
        ms = ModelServer(model, variables, max_batch=1,
                         draft_model=model,
                         draft_variables=variables, spec_k=4)
        try:
            p_len = 8
            new = max_pos - p_len          # exactly at capacity
            req = {"prompt": list(range(1, p_len + 1)),
                   "max_new_tokens": new}
            out = ms.generate(dict(req))
            want = generate(model, variables,
                            np.asarray([req["prompt"]], np.int32),
                            max_new_tokens=new)
            assert out["tokens"] == np.asarray(want).tolist()
            assert ms.engine.stats()["admitted_total"] == 0
            assert "near-capacity" in ms.info()["solo_fallbacks"]
        finally:
            ms.close()

    def test_spec_metrics_and_info_share_counters(self):
        """/metrics' speculative counters and histogram render the
        SAME engine.stats() dict /info reports — no drift."""
        model, variables, servers = self._servers()
        try:
            ms = servers["continuous"]
            ms.generate({"prompt": [5, 6, 7, 8], "max_new_tokens": 6,
                         "speculative": True, "spec_k": 3,
                         "temperature": 0.9, "seed": 1})
            info = ms.info()
            text = ms.metrics_text()
            metrics = {}
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    name, _, value = line.rpartition(" ")
                    metrics[name] = float(value)
            assert metrics["ptpu_serving_admitted_spec_total"] == \
                info["admitted_spec_total"] == 1
            assert metrics["ptpu_serving_completed_spec_total"] == \
                info["completed_spec_total"] == 1
            assert metrics["ptpu_serving_spec_drafted_total"] == \
                info["spec_drafted_total"] > 0
            assert metrics["ptpu_serving_spec_accepted_total"] == \
                info["spec_accepted_total"]
            assert metrics["ptpu_serving_spec_accept_rate_count"] \
                == info["spec_accept_count"] == 1
            # histogram: cumulative buckets end at the observation
            # count, and the per-bucket counts in /info sum to it
            assert metrics[
                'ptpu_serving_spec_accept_rate_bucket{le="+Inf"}'] \
                == 1
            assert sum(info["spec_accept_hist"]) == 1
        finally:
            for ms in servers.values():
                ms.close()
