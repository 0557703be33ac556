"""The engine one decode dispatch ahead (serving/engine.py
``_decode_step``, serving/slots.py ``SlotManager._dispatch``): dispatch
N+1 is launched before dispatch N's tokens are fetched wherever the
boundary between them can be decided without those tokens.

What is held here: the tokens of a pool served ahead are bitwise the
serial order's, which the tests force through the conditions the engine
itself observes (an armed deadline, an ``eos_id``, ...), never through a
switch; every such condition is taken and counted under its name; a
failure or a cancel with a dispatch in flight loses and delivers
nothing it should not; the counters, and their reader.
"""

import dataclasses
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models.afmoe import AfmoeConfig, AfmoeModel
from polyaxon_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                             DeepseekV2Model)
from polyaxon_tpu.models.jamba import JambaConfig, JambaModel
from polyaxon_tpu.models.registry import get_model
from polyaxon_tpu.serving import DecodeEngine, SchedulerPolicy
from polyaxon_tpu.serving.faults import FaultPlan
from polyaxon_tpu.serving.scheduler import RequestCancelled, SamplingSpec

VOCAB = 48      # every model below draws and is prompted under it


def _built(kind):
    if kind == "gpt2-tiny":
        return get_model("gpt2-tiny").init_params(batch_size=1)
    cfg, cls = {"jamba-tiny": (JambaConfig, JambaModel),
                "afmoe-tiny": (AfmoeConfig, AfmoeModel),
                "deepseek-v2-tiny": (DeepseekV2Config,
                                     DeepseekV2Model)}[kind]
    model = cls(dataclasses.replace(cfg.tiny(), dtype=jnp.float32))
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))


@pytest.fixture(scope="module", params=["gpt2-tiny", "jamba-tiny",
                                        "afmoe-tiny", "deepseek-v2-tiny"])
def any_model(request):
    return _built(request.param)


@pytest.fixture(scope="module")
def tiny():
    return _built("gpt2-tiny")


# (prompt length, budget): budgets of 1 (complete at admission), ending
# inside a window of 4 and at its end, and long ones; prompts of one to
# three pieces of 8.  Eight callers on three slots: a slot frees, and an
# admission follows, at most boundaries.
MIX = [(3, 9), (19, 4), (8, 12), (17, 3), (13, 5), (5, 1), (9, 8), (4, 2)]


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, VOCAB, (1, n)).astype(
        np.int32)


def _sampling(i, **kw):
    """Odd callers sample, even ones are greedy."""
    if i % 2 or kw:
        return SamplingSpec(seed=100 + i, **({"temperature": 0.9,
                                              "top_k": 16} if i % 2
                                             else {}), **kw)
    return None


def _engine(model, variables, *, engine_kw=None, **policy):
    kw = dict(n_slots=3, queue_depth=32, decode_window=4, prefill_chunk=8)
    kw.update(policy)
    return DecodeEngine(model, variables, autostart=False,
                        policy=SchedulerPolicy(**kw), **(engine_kw or {}))


def _serve(model, variables, mix=MIX, *, eos=None, submit_kw=None,
           sampling_kw=None, engine_kw=None, before_run=None, **policy):
    """The mix through one engine, drained by manual ticks: ``(tokens a
    request, stats)``."""
    eng = _engine(model, variables, engine_kw=engine_kw, **policy)
    try:
        groups = [
            eng.submit(_prompt(n, i), new, eos, None,
                       sampling=_sampling(i, **(sampling_kw or {})),
                       **(submit_kw or {}))
            for i, (n, new) in enumerate(mix)]
        if before_run is not None:
            before_run(eng)
        eng.run_until_idle()
        assert all(g.event.is_set() and g.error is None for g in groups)
        return [g.result().tolist() for g in groups], eng.stats()
    finally:
        eng.close()


def _ordered(stats):
    return (stats["decode_dispatches_total"],
            stats["decode_dispatches_ahead_total"],
            dict(stats["decode_serial_reasons"]))


def test_a_mixed_pool_served_ahead_gives_the_serial_orders_tokens(
        any_model):
    """Greedy and sampled streams, budgets ending inside and at window
    ends, more callers than slots: one dispatch ahead, and in the
    serial order an armed deadline (an hour away) forces."""
    model, variables = any_model
    ahead, a = _serve(model, variables)
    serial, s = _serve(model, variables, submit_kw={"deadline_s": 3600.0})
    assert ahead == serial
    for (n, new), row in zip(MIX, ahead):
        assert len(row[0]) == n + new
    total, n_ahead, reasons = _ordered(a)
    # a run of dispatches starts with one that had nothing to run
    # ahead of; every other one was launched before its predecessor
    # was collected
    assert set(reasons) == {"first"}
    assert n_ahead == total - reasons["first"] and n_ahead > reasons["first"]
    total, n_ahead, reasons = _ordered(s)
    assert n_ahead == 0 and reasons == {"deadline": total}
    # a dispatch's window is committed whole, in either order
    assert a["decode_steps_total"] >= max(new for _, new in MIX) - 1
    assert a["completed_total"] == s["completed_total"] == len(MIX)


class _Waited:
    """A device lock some handler thread is always waiting on."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()

    def acquire(self, *a, **kw):
        return self._lock.acquire(*a, **kw)

    def release(self):
        self._lock.release()

    def __enter__(self):
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)

    def waiters(self):
        return 1


def _serial_cases(model, variables):
    return {
        # an eos that no stream ever draws: its stop is still data
        "eos": dict(eos=VOCAB + 1000),
        "logits": dict(submit_kw={"record_logits": True}),
        "deadline": dict(submit_kw={"deadline_s": 3600.0}),
        "spec": dict(sampling_kw={"spec_k": 2},
                     engine_kw={"draft_model": model,
                                "draft_variables": variables}),
        "fault": dict(engine_kw={"faults": FaultPlan.load(
            {"faults": [{"site": "step", "kind": "transient",
                         "times": 1}]})}),
        "paged": dict(kv_paged=True, kv_page_tokens=8, kv_lazy=True),
        "lock_waiter": dict(engine_kw={"device_lock": _Waited()}),
        "drain": dict(before_run=lambda eng: eng.drain()),
    }


@pytest.mark.parametrize("reason", ["eos", "logits", "deadline", "spec",
                                    "fault", "paged", "lock_waiter",
                                    "drain"])
def test_each_serial_reason_is_taken_and_counted(tiny, reason):
    """What the engine observes at a boundary decides the order, and
    every dispatch it keeps serial is counted under that name; the
    tokens are the pool's served ahead."""
    model, variables = tiny
    want, _ = _serve(model, variables)
    got, stats = _serve(model, variables,
                        **_serial_cases(model, variables)[reason])
    total, n_ahead, reasons = _ordered(stats)
    assert total > 0 and n_ahead == 0
    assert reasons == {reason: total}
    if reason == "spec":
        # a speculative stream's accept lanes are exact for greedy
        # streams; sampled ones draw through the speculative keys
        got, want = got[0::2], want[0::2]
    assert got == want


@pytest.mark.parametrize("policy", [{}, {"kv_paged": True,
                                         "kv_page_tokens": 8}],
                         ids=["lanes", "paged"])
def test_a_pool_program_keeps_one_signature(tiny, policy):
    """The feedback token a launch takes is the host's stand-in once
    and a program's output ever after: were the two not the same kind
    of array, every step program would be compiled a second time at
    its second call — outside ``_compiling``, so into (and, in the
    next process, out of) the persistent cache, whose executables
    mislabel the pool's pinned layout (PERF.md section 6, PR 34: a
    warm server's pool was copied until the chip was full)."""
    model, variables = tiny
    eng = _engine(model, variables, **policy)
    try:
        groups = [eng.submit(_prompt(n, i), new, None, None,
                             sampling=_sampling(i))
                  for i, (n, new) in enumerate(MIX)]
        eng.run_until_idle()
        assert all(g.error is None for g in groups)
        programs = {**eng.slots._step_fns,
                    **getattr(eng.slots, "_insert_fns", {})}
        assert len(programs) >= 3       # plain, sampled, an insertion
        assert {key: getattr(fn, "func", fn)._cache_size()
                for key, fn in programs.items()} \
            == dict.fromkeys(programs, 1)
    finally:
        eng.close()


def test_a_fully_reserved_paged_pool_runs_ahead(tiny):
    """Tables and dirty pages are read off positions, which move at
    the launch: only ``--kv-lazy`` growth waits for a commit."""
    model, variables = tiny
    want, _ = _serve(model, variables)
    got, stats = _serve(model, variables, kv_paged=True, kv_page_tokens=8)
    assert got == want
    total, n_ahead, reasons = _ordered(stats)
    assert set(reasons) == {"first"} and n_ahead > 0


def test_an_error_at_a_collect_with_a_dispatch_in_flight_resumes_token_identically(
        tiny):
    """The third collect fails, with its successor already launched:
    everything in flight is dropped, every stream — resident, or gone
    from its slot and waiting for its last tokens — resumes from its
    committed prefix, and nothing is delivered twice."""
    model, variables = tiny
    want, _ = _serve(model, variables)
    eng = _engine(model, variables)
    collects, real = [], eng.slots._collect

    def failing(flight):
        collects.append(eng._flight is not None)
        if len(collects) == 3:
            raise RuntimeError("device lost")
        return real(flight)

    eng.slots._collect = failing
    try:
        groups = [eng.submit(_prompt(n, i), new, None, None,
                             sampling=_sampling(i))
                  for i, (n, new) in enumerate(MIX)]
        eng.run_until_idle()
        assert collects[2], "the failing collect had a dispatch in flight"
        assert all(g.event.is_set() and g.error is None for g in groups)
        assert [g.result().tolist() for g in groups] == want
        stats = eng.stats()
        assert stats["kv_pool_lost_total"] == 1
        assert stats["requests_requeued_total"] >= 1
        assert stats["completed_total"] == len(MIX)
        assert eng._flight is None and not eng._resident
    finally:
        eng.close()


def test_a_cancel_with_a_dispatch_in_flight_delivers_nothing_and_frees_the_slot(
        tiny):
    model, variables = tiny
    mix = [(6, 24), (7, 24), (5, 6)]
    want, _ = _serve(model, variables, mix=mix, n_slots=2)
    eng = _engine(model, variables, n_slots=2)
    try:
        a, b, c = [eng.submit(_prompt(n, i), new, None, None,
                              sampling=_sampling(i))
                   for i, (n, new) in enumerate(mix)]
        stream = a.streams[0]
        for _ in range(50):
            eng.tick()
            if eng._flight is not None and stream.in_flight \
                    and len(stream.out) > 4:
                break
        else:
            raise AssertionError("no dispatch was left in flight")
        slot, had = stream.slot, list(stream.out)
        assert c.streams[0].slot is None    # waits for a slot
        eng.cancel(a)
        eng.tick()      # the boundary that delivers the cancel
        assert isinstance(a.error, RequestCancelled)
        # the slot was free at that boundary: the waiting caller has it
        assert eng._resident.get(slot) is c.streams[0]
        eng.run_until_idle()
        assert stream.out == had and stream.in_flight == 0
        assert c.streams[0].last_slot == slot
        assert [g.result().tolist() for g in (b, c)] == want[1:]
        assert eng.stats()["cancelled_total"] == 1
    finally:
        eng.close()


def test_counters_reach_info_and_metrics(tiny):
    from polyaxon_tpu.serving import ModelServer

    model, variables = tiny
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     n_slots=2, decode_window=4)
    try:
        ms.generate({"prompt": [5, 6, 7], "max_new_tokens": 12})
        info = ms.info()
        assert info["decode_dispatches_total"] \
            == info["decode_dispatches_ahead_total"] \
            + sum(info["decode_serial_reasons"].values()) > 0
        text = ms.metrics_text()
        assert "ptpu_serving_decode_dispatches_total " in text
        assert "ptpu_serving_decode_dispatches_ahead_total " in text
        assert 'ptpu_serving_decode_serial_reasons{reason="first"}' in text
    finally:
        ms.close()


def _reader(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "layer_metrics",
        name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("info_open,info_close,want", [
    ({"decode_dispatches_total": 10, "decode_dispatches_ahead_total": 5},
     {"decode_dispatches_total": 210, "decode_dispatches_ahead_total": 195},
     95.0),
    ({"decode_dispatches_total": 0, "decode_dispatches_ahead_total": 0},
     {"decode_dispatches_total": 8, "decode_dispatches_ahead_total": 0},
     0.0),
    # nothing dispatched in the window; a program without the counters
    ({"decode_dispatches_total": 7, "decode_dispatches_ahead_total": 6},
     {"decode_dispatches_total": 7, "decode_dispatches_ahead_total": 6},
     None),
    ({"decode_steps_total": 1}, {"decode_steps_total": 90}, None),
], ids=["ahead", "serial", "idle", "parent"])
def test_dispatch_ahead_pct_reader(info_open, info_close, want):
    ctx = types.SimpleNamespace(collected={"info_open": info_open,
                                           "info_close": info_close})
    assert _reader("dispatch_ahead_pct")(ctx) == want
