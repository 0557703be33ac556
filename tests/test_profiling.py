"""Tier-1 coverage for device-truth profiling: the xprof trace parser
(analysis/xprof.py) pinned on hand-built synthetic Chrome-trace
fixtures, the per-model decode-flop estimate, the FlightRecorder's
cadence/single-flight/publish machinery against a fake profiler
session, and the live smoke-server integration — windows fire under
real traffic, the /metrics gauges move, GET /profile/report
round-trips the same numbers, manual /profile/start 409s against an
open recorder window, and the disabled mode stays a no-op with zero
steady-state recompiles."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from polyaxon_tpu.analysis.xprof import (attribute_events,
                                         classify_name,
                                         merge_intervals,
                                         subtract_intervals)
from polyaxon_tpu.serving.profiling import (FlightRecorder,
                                            decode_flops_per_token)

# ---------------------------------------------------------------------------
# classification + interval math
# ---------------------------------------------------------------------------


def test_classify_name_categories():
    assert classify_name("all-reduce.17") == "collective"
    assert classify_name("AllGather_fusion") == "collective"
    assert classify_name("reduce-scatter.2") == "collective"
    assert classify_name("collective-permute-send.1") == "collective"
    assert classify_name("psum_combiner") == "collective"
    assert classify_name("copy.3") == "transfer"
    assert classify_name("MemcpyD2H") == "transfer"
    assert classify_name("infeed-dequeue") == "transfer"
    assert classify_name("fusion.12") == "compute"
    assert classify_name("dot.5") == "compute"
    assert classify_name("reduce-window.clone") == "compute"
    assert classify_name("scan_loop") == "compute"


def test_interval_union_and_subtract():
    assert merge_intervals([(0, 10), (5, 20), (30, 40),
                            (40, 50)]) == [(0, 20), (30, 50)]
    assert subtract_intervals([(0, 100)], [(20, 30), (50, 60)]) == \
        [(0, 20), (30, 50), (60, 100)]
    assert subtract_intervals([(0, 10)], [(0, 10)]) == []
    assert subtract_intervals([(0, 10)], []) == [(0, 10)]


# ---------------------------------------------------------------------------
# synthetic-fixture attribution pins
# ---------------------------------------------------------------------------


def _meta(pid, name):
    return {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": name}}


def _thread(pid, tid, name):
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": name}}


def _ev(name, pid, tid, ts, dur):
    return {"ph": "X", "name": name, "pid": pid, "tid": tid,
            "ts": ts, "dur": dur}


def test_attribution_device_track_overlap_pinned():
    """Compute/collective/transfer overlap on a real device track:
    categories partition the busy union by priority (collective >
    transfer > compute), the host process is ignored, and the shares
    are pinned exactly."""
    events = [
        _meta(1, "/device:TPU:0"),
        _meta(99, "/host:CPU"),
        _ev("fusion.1", 1, 0, 0, 100),        # compute [0, 100)
        _ev("all-reduce.2", 1, 0, 50, 100),   # collective [50, 150)
        _ev("copy.3", 1, 0, 200, 50),         # transfer [200, 250)
        _ev("host_noise", 99, 0, 0, 1000),    # not a device track
    ]
    att = attribute_events(events, window=(0, 500))
    assert not att["host_fallback"]
    assert att["device_pids"] == ["1"]
    assert att["wall_s"] == 0.0005
    # collective owns its whole span; compute loses the overlap
    assert att["category_s"] == {"collective": 0.0001,
                                 "transfer": 0.00005,
                                 "compute": 0.00005}
    assert att["host_gap_s"] == 0.0003
    assert att["shares"] == {"collective": 0.2, "transfer": 0.1,
                             "compute": 0.1}
    assert att["host_gap_share"] == 0.6
    assert att["device_busy_share"] == 0.4
    assert sum(att["shares"].values()) <= 1.0
    assert sum(att["shares"].values()) + att["host_gap_share"] \
        == pytest.approx(1.0)


def test_attribution_multi_track_no_double_count():
    """The same wall-clock span busy on TWO device tracks counts
    once: busy time is an interval union, not a sum over tracks."""
    events = [
        _meta(1, "/device:TPU:0"),
        _ev("dot.1", 1, 1, 0, 100),
        _ev("dot.2", 1, 2, 0, 100),           # parallel track
    ]
    att = attribute_events(events, window=(0, 200))
    assert att["category_s"]["compute"] == 0.0001
    assert att["device_busy_share"] == 0.5


def test_attribution_step_marker_window_and_clipping():
    """Without an explicit window the span of the ptpu_step markers
    anchors the attribution, and device events are CLIPPED to it —
    profiler startup noise outside the steps never attributes."""
    events = [
        _meta(1, "/device:TPU:0"),
        _meta(7, "/host:CPU"),
        _ev("ptpu_step", 7, 3, 100, 100),
        _ev("ptpu_step", 7, 3, 300, 100),
        _ev("fusion.a", 1, 0, 0, 150),       # clips to [100, 150)
        _ev("fusion.b", 1, 0, 350, 100),     # clips to [350, 400)
    ]
    att = attribute_events(events)
    assert att["step_markers"] == 2
    assert att["wall_s"] == 0.0003           # [100, 400) us
    assert att["category_s"]["compute"] == 0.0001
    assert att["device_busy_share"] == pytest.approx(1 / 3, abs=1e-6)


def test_attribution_max_steps_caps_marker_anchor():
    """max_steps anchors the window to the FIRST N markers: a
    straggler dispatch that lands an extra ptpu_step between the
    recorder's logical close and the async profiler stop must not
    stretch the wall (and so understate MFU / busy share)."""
    events = [
        _meta(1, "/device:TPU:0"),
        _meta(7, "/host:CPU"),
        _ev("ptpu_step", 7, 3, 100, 100),
        _ev("ptpu_step", 7, 3, 300, 100),
        _ev("ptpu_step", 7, 3, 900, 100),    # post-close straggler
        _ev("fusion.a", 1, 0, 100, 100),
        _ev("fusion.b", 1, 0, 950, 50),      # straggler's compute
    ]
    att = attribute_events(events, max_steps=2)
    assert att["step_markers"] == 2          # straggler excluded
    assert att["wall_s"] == 0.0003           # [100, 400) us
    assert att["category_s"]["compute"] == 0.0001
    # uncapped, the straggler stretches the window
    assert attribute_events(events)["wall_s"] == 0.0009


def test_attribution_host_fallback_thread_selection():
    """No /device: process (the CPU smoke): XLA runtime worker
    threads (tf_*) stand in for the device track, python threads and
    bookkeeping noise are excluded, and the record says so
    (host_fallback)."""
    events = [
        _meta(7, "/host:CPU"),
        _thread(7, 1, "tf_XLAEigen/1"),
        _thread(7, 2, "python"),
        _thread(7, 3, "tf_XLATfrtCpuClient/3"),
        _ev("dot.5", 7, 1, 0, 100),                      # counts
        _ev("ThreadpoolListener::Record", 7, 1, 0, 50),  # noise
        _ev("ThunkExecutor::Execute (wait for completion)",
            7, 3, 0, 80),                                # a wait
        _ev("$builtins isinstance", 7, 2, 0, 30),        # py tracer
        _ev("PjitFunction(f)", 7, 2, 0, 40),             # py thread
    ]
    att = attribute_events(events, window=(0, 200))
    assert att["host_fallback"]
    assert att["events"] == 1
    assert att["category_s"]["compute"] == 0.0001
    assert att["device_busy_share"] == 0.5


def test_attribution_empty_window():
    att = attribute_events([])
    assert att["wall_s"] == 0.0
    assert att["device_busy_share"] == 0.0
    assert att["category_s"] == {"collective": 0.0, "transfer": 0.0,
                                 "compute": 0.0}


# ---------------------------------------------------------------------------
# decode-flop estimate
# ---------------------------------------------------------------------------


class _Cfg:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_decode_flops_generic_transformer_pinned():
    # per_layer = 4h^2 + 2h*4h = 12h^2 = 192; n_matmul = 2*192 + 40
    cfg = _Cfg(hidden_size=4, num_layers=2, vocab_size=10)
    assert decode_flops_per_token(cfg, 0) == 2.0 * 424
    # + attention 4 * L * pos * h = 4*2*8*4 = 256
    assert decode_flops_per_token(cfg, 8) == 2.0 * 424 + 256


def test_decode_flops_llama_gqa_swiglu_pinned():
    # per_layer = 2h^2 + 2h*kv*hd + 3h*inter = 32 + 16 + 96 = 144
    cfg = _Cfg(hidden_size=4, num_layers=1, vocab_size=10,
               head_dim=2, num_kv_heads=1, intermediate_size=8)
    assert decode_flops_per_token(cfg, 0) == 2.0 * 184


def test_decode_flops_moe_router_term():
    base = _Cfg(hidden_size=4, num_layers=2, vocab_size=10)
    moe = _Cfg(hidden_size=4, num_layers=2, vocab_size=10,
               num_experts=4)
    assert decode_flops_per_token(moe, 0) == \
        decode_flops_per_token(base, 0) + 2.0 * (2 * 4 * 4)


def test_decode_flops_refuses_non_decoder_configs():
    assert decode_flops_per_token(None, 0) is None
    assert decode_flops_per_token(_Cfg(d_model=8, num_layers=2,
                                       hidden_size=8,
                                       vocab_size=10), 0) is None
    assert decode_flops_per_token(_Cfg(hidden_size=8, num_layers=2,
                                       vocab_size=10,
                                       num_classes=10), 0) is None


# ---------------------------------------------------------------------------
# FlightRecorder against a fake profiler session
# ---------------------------------------------------------------------------


# One device-track fixture whose attribution window is exactly
# [0, 1000) us -> wall 0.001s, busy 400us, collective 100us.
_FAKE_TRACE = [
    _meta(1, "/device:TPU:0"),
    _ev("fusion.1", 1, 0, 0, 300),
    _ev("all-reduce.1", 1, 0, 300, 100),
    _ev("fusion.2", 1, 0, 950, 50),
]


class _FakeSession:
    """ProfileSession stand-in: same owner contract, writes the
    synthetic trace on stop."""

    def __init__(self, root):
        self.root = root
        self.owner = None
        self.n = 0
        self._d = None

    def start(self, owner="manual", python_tracer=True):
        if self.owner is not None:
            raise RuntimeError("busy")
        self.owner = owner
        self.n += 1
        self._d = os.path.join(self.root, f"w{self.n}")
        os.makedirs(self._d)
        return self._d

    def stop(self, owner="manual"):
        if self.owner is None:
            raise RuntimeError("not running")
        if owner != self.owner:
            raise RuntimeError("owner mismatch")
        self.owner = None
        with open(os.path.join(self._d, "x.trace.json"), "w") as f:
            json.dump({"traceEvents": _FAKE_TRACE}, f)
        return self._d


def _wait_latest(rec, deadline_s=10.0):
    end = time.time() + deadline_s
    while time.time() < end:
        r = rec.latest()
        if r is not None:
            return r
        time.sleep(0.01)
    raise AssertionError("recorder never published a record")


def test_recorder_cadence_and_published_record(tmp_path):
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=3, steps=2, prime=False,
                         flops_fn=lambda pos: 100.0,
                         peak_flops=1e6, n_devices=1,
                         position_probe=lambda: 7.0)
    # two boundaries below the cadence: no window
    rec.on_step_start(); rec.on_step_end(5)
    rec.on_step_start(); rec.on_step_end(5)
    assert sess.owner is None and rec.windows_total == 0
    # third boundary opens; the window spans exactly `steps`
    rec.on_step_start()
    assert sess.owner == "recorder"
    rec.on_step_end(4)
    assert sess.owner == "recorder"      # still open after 1 of 2
    rec.on_step_start(); rec.on_step_end(6)
    r = _wait_latest(rec)
    assert sess.owner is None    # the async close released the
    #                              session before publishing
    assert r["window"] == 1 and r["steps"] == 2 and r["tokens"] == 10
    assert r["mean_position"] == 7.0
    # pinned against the fixture: wall 0.001s, busy 450us
    assert r["wall_s"] == 0.001
    assert r["collective_share"] == 0.1
    assert r["device_busy_share"] == 0.45
    assert r["host_gap_share"] == 0.55
    # mfu = tokens * flops / (wall * peak) = 10*100 / (0.001 * 1e6)
    assert r["mfu"] == 1.0
    # /metrics gauges render from the SAME record (no drift)
    lines = rec.metrics_lines()
    assert f"ptpu_serving_collective_share " \
           f"{r['collective_share']}" in lines
    assert f"ptpu_serving_device_busy_share " \
           f"{r['device_busy_share']}" in lines
    assert f"ptpu_serving_mfu {r['mfu']}" in lines
    rep = rec.report()
    assert rep["latest"] == r and rep["windows"][-1] == r
    rec.close()


def test_recorder_defers_to_manual_profile(tmp_path):
    """A manual profile holding the session makes the recorder SKIP
    its window (counted) and re-arm a full cadence — never an error,
    never a stolen stop."""
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=2, steps=1, prime=False)
    sess.start(owner="manual")
    for _ in range(4):
        rec.on_step_start(); rec.on_step_end(1)
    assert rec.windows_total == 0 and rec.windows_skipped == 2
    assert sess.owner == "manual"        # untouched
    sess.stop(owner="manual")
    rec.on_step_start(); rec.on_step_end(1)   # cadence restarts
    assert rec.windows_total == 0
    rec.on_step_start(); rec.on_step_end(1)
    assert rec.windows_total == 1
    _wait_latest(rec)
    rec.close()


def test_recorder_validates_knobs(tmp_path):
    sess = _FakeSession(str(tmp_path))
    with pytest.raises(ValueError):
        FlightRecorder(sess, every=0, prime=False)
    with pytest.raises(ValueError):
        FlightRecorder(sess, every=1, steps=0, prime=False)


def test_recorder_defers_own_inflight_stop(tmp_path):
    """A cadence boundary arriving before the previous window's
    async stop finished is OUR OWN in-flight stop, not a manual
    profile: counted as deferred (not skipped) and retried at the
    very next boundary instead of paying a full cadence."""
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=3, steps=1, prime=False)
    sess.owner = "recorder"      # previous stop still in flight
    for _ in range(3):
        rec.on_step_start(); rec.on_step_end(1)
    assert rec.windows_deferred == 1 and rec.windows_skipped == 0
    sess.owner = None            # the stop lands
    rec.on_step_start()          # retried immediately
    assert rec.windows_total == 1
    rec.close()


def test_recorder_prime_discards_its_dump(tmp_path):
    """The construction-time profiler prime must not leave an orphan
    xprof session per server start."""
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=1, prime=True)
    assert sess.n == 1
    assert not os.path.exists(os.path.join(str(tmp_path), "w1"))
    rec.close()


def test_recorder_deletes_analyzed_dumps(tmp_path):
    """Recorder dumps are parsed once and deleted — a production
    recorder fires a window every few seconds and each xprof session
    is MBs, so retention would grow --profile-dir without bound."""
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=1, steps=1, prime=False)
    rec.on_step_start(); rec.on_step_end(2)
    r = _wait_latest(rec)
    assert not os.path.exists(r["trace_dir"])
    rec.close()


def test_recorder_watchdog_closes_idle_window(tmp_path):
    """Traffic draining mid-window must not leave the profiler
    session open forever (manual /profile/start would 409 against a
    window that never ends): the watchdog force-closes an overdue
    window, releases the session, and publishes an honestly-marked
    partial record covering only the steps that ran."""
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=1, steps=100, prime=False,
                         max_window_s=0.15)
    rec.on_step_start()
    rec.on_step_end(4)          # 1 of 100 steps; then traffic stops
    assert sess.owner == "recorder"
    r = _wait_latest(rec)
    assert sess.owner is None                # session released
    assert r["deadline_closed"] is True
    assert r["steps"] == 1 and r["tokens"] == 4
    # a fresh window can open afterwards
    rec.on_step_start()
    assert rec.windows_total == 2
    rec.close()
    with pytest.raises(ValueError):
        FlightRecorder(sess, every=1, max_window_s=0,
                       prime=False)


def test_recorder_mfu_none_without_flops_model(tmp_path):
    """Encoder/seq2seq configs have no decode-flop estimate: the MFU
    field is omitted (None), never invented."""
    sess = _FakeSession(str(tmp_path))
    rec = FlightRecorder(sess, every=1, steps=1, prime=False,
                         flops_fn=lambda pos: None, peak_flops=1e6)
    rec.on_step_start(); rec.on_step_end(3)
    r = _wait_latest(rec)
    assert r["mfu"] is None and r["flops_per_token"] is None
    assert "ptpu_serving_mfu" not in "\n".join(rec.metrics_lines())
    rec.close()


# ---------------------------------------------------------------------------
# live smoke server
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    from polyaxon_tpu.models.registry import get_model

    spec = get_model("gpt2-tiny")
    return spec.init_params(batch_size=1)


def _serve(tiny, tmp, **kw):
    from polyaxon_tpu.serving import ModelServer, make_server

    model, variables = tiny
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     max_batch=4, n_slots=2, decode_window=1,
                     **({"profile_dir": os.path.join(tmp, "prof")}
                        if kw.pop("with_profile_dir", True) else {}),
                     **kw)
    srv = make_server("127.0.0.1", 0, ms)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return f"http://127.0.0.1:{srv.server_address[1]}", ms, srv


def _post(base, payload, path="/generate", timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get_json(base, path, timeout=60):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def _get_text(base, path, timeout=60):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode()


def test_flight_recorder_live_window_gauges_and_report(tiny,
                                                       tmp_path):
    """The acceptance loop: recorder windows fire under real engine
    traffic, the attribution gauges move (non-zero device-busy,
    finite MFU on the host platform), /profile/report returns the
    SAME numbers /metrics exports, the trace ring carries the window
    instants, and steady-state traffic stays recompile-quiet with
    the recorder on."""
    from polyaxon_tpu.serving.telemetry import parse_prometheus_text

    base, ms, srv = _serve(tiny, str(tmp_path), profile_every=2,
                           profile_steps=3)
    try:
        for _ in range(3):
            _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 12})
        deadline = time.time() + 60
        rep = None
        while time.time() < deadline:
            try:
                rep = _get_json(base, "/profile/report")
                break
            except urllib.error.HTTPError as e:
                assert e.code == 404
                e.read()
                _post(base, {"prompt": [1, 2, 3],
                             "max_new_tokens": 12})
        assert rep is not None, "no recorder window analyzed in 60s"
        # Traffic is quiet now.  Wait until no window is open and no
        # analysis is in flight (every window opened has been
        # analyzed; one the traffic left open is closed by the
        # recorder's deadline watchdog), and until /profile/report,
        # read before and after /metrics, agrees with it on that: then
        # all three read one record.  (A fixed sleep did not wait for
        # an analysis that a loaded machine stretches past it.)
        deadline = time.time() + 60
        while True:
            rep = _get_json(base, "/profile/report")
            metrics = parse_prometheus_text(_get_text(base, "/metrics"))
            again = _get_json(base, "/profile/report")
            counts = [(r["windows_total"], r["windows_analyzed"])
                      for r in (rep, again)] + [(
                metrics["ptpu_serving_profile_windows_total"],
                metrics["ptpu_serving_profile_windows_analyzed_total"])]
            if len(set(counts)) == 1 and counts[0][0] == counts[0][1]:
                break
            assert time.time() < deadline, (counts, rep["last_error"])
            time.sleep(0.1)
        latest = rep["latest"]
        # what a window under traffic holds: the newest that closed at
        # its own boundary (the watchdog's may hold fewer steps)
        full = [w for w in rep["windows"]
                if not w.get("deadline_closed")][-1]
        assert full["steps"] == 3
        assert full["host_fallback"] is True     # cpu smoke
        assert full["device_busy_share"] > 0
        assert full["mfu"] is not None
        assert 0 <= full["mfu"] < 1e6            # finite
        assert full["peak_flops_source"] == "nominal"
        for w in rep["windows"]:
            assert sum(w["shares"].values()) <= 1.0 + 1e-9
        # one reduction, no drift: gauges == report numbers
        assert metrics["ptpu_serving_collective_share"] == \
            latest["collective_share"]
        assert metrics["ptpu_serving_host_gap_share"] == \
            latest["host_gap_share"]
        assert metrics["ptpu_serving_device_busy_share"] == \
            latest["device_busy_share"]
        assert metrics["ptpu_serving_mfu"] == latest["mfu"]
        # /info summarizes the same record
        info = _get_json(base, "/info")
        prof = info["profiling"]
        assert prof["enabled"] and prof["windows_analyzed"] >= 1
        assert prof["device_busy_share"] == \
            latest["device_busy_share"]
        assert prof["mfu"] == latest["mfu"]
        # window instants land on the trace ring's engine track
        names = {e["name"] for e in ms.telemetry.events()}
        assert "profile_window_start" in names
        assert "profile_window_stop" in names
        # steady state stays recompile-quiet with the recorder on
        pre = _get_json(base, "/info")["compile_cache_misses"]
        for _ in range(3):
            _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 12})
        assert _get_json(base, "/info")["compile_cache_misses"] == pre
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


def test_manual_profile_409_against_open_recorder_window(tiny,
                                                         tmp_path):
    """Single-flight: while a recorder window holds the profiler
    session, POST /profile/start AND /profile/stop both 409 — the
    manual surface can neither race start_trace nor steal the
    recorder's stop."""
    base, ms, srv = _serve(tiny, str(tmp_path), profile_every=1,
                           profile_steps=10**6)
    try:
        # hold the window open past the HTTP round-trips below — the
        # watchdog closing it mid-test would flip the 409s to 200s
        ms.recorder.max_window_s = 3600.0
        _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 4})
        assert ms.profiler.owner == "recorder"   # window held open
        for path in ("/profile/start", "/profile/stop"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base, {}, path=path)
            assert ei.value.code == 409
            body = json.loads(ei.value.read())
            assert "flight recorder" in body["error"]
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()
    # close() released the process-global profiler state
    assert ms.profiler.owner is None


def test_recorder_disabled_is_noop(tiny, tmp_path):
    """Off by default: no recorder object on the engine, the report
    endpoint 400s, no attribution gauges in /metrics, and warm
    traffic adds zero compile-cache misses."""
    base, ms, srv = _serve(tiny, str(tmp_path))
    try:
        assert ms.recorder is None
        assert ms.engine.recorder is None
        _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 8})
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(base, "/profile/report")
        assert ei.value.code == 400
        body = _get_text(base, "/metrics")
        assert "ptpu_serving_collective_share" not in body
        assert "ptpu_serving_mfu" not in body
        pre = _get_json(base, "/info")["compile_cache_misses"]
        for _ in range(2):
            _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 8})
        assert _get_json(base, "/info")["compile_cache_misses"] == pre
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


def test_recorder_requires_profile_dir_and_engine(tiny, tmp_path):
    from polyaxon_tpu.serving import ModelServer

    model, variables = tiny
    with pytest.raises(ValueError, match="profile_dir"):
        ModelServer(model, variables, profile_every=5)
    with pytest.raises(ValueError, match="continuous"):
        ModelServer(model, variables, batching="off",
                    profile_every=5,
                    profile_dir=str(tmp_path / "p"))
