"""Tier-1 coverage for the serving telemetry layer (serving/
telemetry.py): histogram bucket math pinned against reference
cumulative counts, the shared Prometheus exposition helper (including
the spec-acceptance regression pin), request lifecycle spans for the
engine/coalesce/solo paths, /trace Chrome trace-event round-trips,
/metrics parsed by a tiny Prometheus text-format checker, the
``timings`` response block, the structured access log, and the
guarded /profile endpoints."""

import io
import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from polyaxon_tpu.models.registry import get_model
from polyaxon_tpu.serving import ModelServer, make_server
from polyaxon_tpu.serving.engine import (SPEC_ACCEPT_BUCKETS,
                                         DecodeEngine)
from polyaxon_tpu.serving.scheduler import (SamplingSpec,
                                            SchedulerPolicy)
from polyaxon_tpu.serving.telemetry import (ENGINE_PID, REQUESTS_PID,
                                            Histogram, Telemetry,
                                            dump_spans_jsonl,
                                            load_trace_events,
                                            parse_prometheus_text,
                                            render_histogram,
                                            strip_exemplar)

# ---------------------------------------------------------------------------
# histogram core
# ---------------------------------------------------------------------------


def test_histogram_bucket_math_pinned():
    """Per-bucket counts against a hand-computed reference, and the
    rendered CUMULATIVE exposition against hand-computed partial
    sums."""
    h = Histogram((0.1, 0.5, 1.0))
    for v in (0.05, 0.1, 0.3, 0.7, 2.0, 0.5):
        h.observe(v)
    counts, total, n = h.snapshot()
    # 0.05, 0.1 <= 0.1; 0.3, 0.5 <= 0.5; 0.7 <= 1.0; 2.0 -> +Inf
    assert counts == [2, 2, 1, 1]
    assert n == 6
    assert abs(total - 3.65) < 1e-9
    lines = render_histogram("t", h.buckets, counts, round(total, 6),
                             n)
    assert lines == [
        "# TYPE t histogram",
        't_bucket{le="0.1"} 2',
        't_bucket{le="0.5"} 4',
        't_bucket{le="1.0"} 5',
        't_bucket{le="+Inf"} 6',
        "t_sum 3.65",
        "t_count 6",
    ]


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram(())
    with pytest.raises(ValueError):
        Histogram((0.5, 0.5))
    with pytest.raises(ValueError):
        Histogram((1.0, 0.5))


def test_spec_accept_exposition_unchanged():
    """Regression pin: the shared render helper reproduces the seed's
    bespoke SPEC_ACCEPT_BUCKETS rendering byte for byte (same le
    labels, same cumulative counts, same sum/count lines)."""
    h = Histogram(SPEC_ACCEPT_BUCKETS)
    for rate in (0.05, 0.25, 0.6, 0.8, 1.0, 1.0):
        h.observe(rate)
    counts, total, n = h.snapshot()
    assert counts == [1, 1, 0, 1, 1, 2, 0]
    lines = render_histogram("ptpu_serving_spec_accept_rate",
                             SPEC_ACCEPT_BUCKETS, counts,
                             round(total, 6), n)
    # Literal lines the pre-refactor loop emitted for these values.
    assert lines == [
        "# TYPE ptpu_serving_spec_accept_rate histogram",
        'ptpu_serving_spec_accept_rate_bucket{le="0.1"} 1',
        'ptpu_serving_spec_accept_rate_bucket{le="0.25"} 2',
        'ptpu_serving_spec_accept_rate_bucket{le="0.5"} 2',
        'ptpu_serving_spec_accept_rate_bucket{le="0.75"} 3',
        'ptpu_serving_spec_accept_rate_bucket{le="0.9"} 4',
        'ptpu_serving_spec_accept_rate_bucket{le="1.0"} 6',
        'ptpu_serving_spec_accept_rate_bucket{le="+Inf"} 6',
        "ptpu_serving_spec_accept_rate_sum 3.7",
        "ptpu_serving_spec_accept_rate_count 6",
    ]


def test_trace_ring_bounded_and_disabled():
    tel = Telemetry(buffer=4)
    for i in range(10):
        tel.span(1, f"s{i}", 0.0, 1.0)
    evs = tel.events()
    assert len(evs) == 4
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]
    assert tel.dropped == 6
    assert tel.chrome_trace()["droppedEvents"] == 6

    off = Telemetry(buffer=0)
    assert not off.enabled
    off.span(1, "x", 0.0, 1.0)
    off.instant(1, "y", 0.0)
    off.step("z", 0.0, 1.0)
    assert off.events() == []
    # histograms stay live with the ring off (they are /metrics)
    off.observe("total", 0.5)
    assert off.hist["total"].snapshot()[2] == 1


def test_prometheus_checker():
    good = ("# TYPE a counter\na 1\n"
            'b_bucket{le="0.1"} 2\nb_sum 0.5\nb_count 2\n')
    m = parse_prometheus_text(good)
    assert m["a"] == 1.0 and m['b_bucket{le="0.1"}'] == 2.0
    with pytest.raises(ValueError):
        parse_prometheus_text("name value_not_a_number\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("no space here_1.0\n")


# ---------------------------------------------------------------------------
# live server (engine path, greedy + sampled + speculative)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    spec = get_model("gpt2-tiny")
    return spec.init_params(batch_size=1)


@pytest.fixture(scope="module")
def tel_server(tiny):
    model, variables = tiny
    # The model doubles as its own draft (greedy spec accepts every
    # draft — the accept lane + the acceptance histogram's 1.0 bucket
    # get exercised without a second model build).
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     max_batch=8, n_slots=4, queue_depth=32,
                     prefill_chunk=8, decode_window=4,
                     draft_model=model, draft_variables=variables,
                     spec_k=2)
    srv = make_server("127.0.0.1", 0, ms)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", ms
    srv.shutdown()
    srv.server_close()
    ms.close()


def _post(base, payload, path="/generate", timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(base, path, timeout=60):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode()


def _mixed_burst(base):
    """Concurrent greedy + sampled + speculative requests — the
    acceptance-criteria burst for /trace and /metrics."""
    reqs = [
        {"prompt": [1, 2, 3], "max_new_tokens": 4},
        {"prompt": list(range(1, 11)), "max_new_tokens": 5,
         "temperature": 0.9, "top_k": 16, "seed": 3},
        {"prompt": [4, 5, 6, 7], "max_new_tokens": 4,
         "speculative": True, "spec_k": 2},
    ]
    errors = []

    def go(i):
        try:
            _post(base, dict(reqs[i]))
        except Exception as e:  # noqa: BLE001 - the assert reports it
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors


def test_trace_endpoint_chrome_schema(tel_server):
    base, ms = tel_server
    _mixed_burst(base)
    doc = json.loads(_get(base, "/trace"))    # round-trips json.loads
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert isinstance(evs, list) and evs
    for ev in evs:
        # Chrome trace-event schema: name/ph/pid/tid always; ts on
        # everything but metadata; complete events carry dur >= 0.
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    req_names = {e["name"] for e in evs
                 if e["pid"] == REQUESTS_PID and e["ph"] != "M"}
    assert {"queue", "prefill", "admit", "decode",
            "complete"} <= req_names
    steps = [e for e in evs
             if e["pid"] == ENGINE_PID and e["ph"] == "X"]
    assert steps, "engine step records missing from /trace"
    kinds = set()
    for s in steps:
        args = s["args"]
        kinds.add(args["kind"])
        assert args["batch"] == 4
        assert 0 <= args["occupancy"] <= 4
        assert args["window"] >= 1
        assert args["tokens"] >= 0
        assert args["device_s"] >= 0
    assert "spec" in kinds       # the speculative burst leg ran
    # a speculative stream's decode span carries its accept counts
    spec_decodes = [
        e for e in evs if e["pid"] == REQUESTS_PID
        and e["name"] == "decode"
        and "spec_accepted" in e.get("args", {})]
    assert spec_decodes


def test_metrics_histograms_and_checker(tel_server):
    base, ms = tel_server
    _mixed_burst(base)
    body = _get(base, "/metrics")
    metrics = parse_prometheus_text(body)   # grammar check
    families = {}
    for line in body.splitlines():
        # exemplar suffixes (forensics.py) ride bucket lines; the
        # shared stripper recovers the bare sample for the checker
        m = re.match(r'^(\w+)_bucket\{le="([^"]+)"\} (\d+)$',
                     strip_exemplar(line))
        if m:
            families.setdefault(m.group(1), []).append(
                (m.group(2), int(m.group(3))))
    for name in ("ptpu_serving_queue_wait_seconds",
                 "ptpu_serving_prefill_phase_seconds",
                 "ptpu_serving_decode_per_token_seconds",
                 "ptpu_serving_ttft_seconds",
                 "ptpu_serving_request_latency_seconds",
                 "ptpu_serving_spec_accept_rate"):
        assert name in families, name
        buckets = families[name]
        les, counts = zip(*buckets)
        assert les[-1] == "+Inf"
        le_vals = [float(x) for x in les[:-1]]
        assert le_vals == sorted(le_vals)          # ascending le
        assert list(counts) == sorted(counts)      # cumulative
        assert counts[-1] == metrics[f"{name}_count"]
        assert f"{name}_sum" in metrics
    assert metrics["ptpu_serving_request_latency_seconds_count"] >= 3
    assert metrics["ptpu_serving_ttft_seconds_count"] >= 3
    # /info reports the SAME spec-acceptance structure /metrics
    # renders (one engine.stats() dict behind both endpoints)
    info = json.loads(_get(base, "/info"))
    assert info["spec_accept_buckets"] == list(SPEC_ACCEPT_BUCKETS)
    assert len(info["spec_accept_hist"]) == \
        len(SPEC_ACCEPT_BUCKETS) + 1
    cum = 0
    for le, n in zip(info["spec_accept_buckets"],
                     info["spec_accept_hist"]):
        cum += n
        assert metrics[
            f'ptpu_serving_spec_accept_rate_bucket{{le="{le}"}}'] \
            == cum


def test_timings_block(tel_server):
    base, ms = tel_server
    r = _post(base, {"prompt": list(range(1, 11)),
                     "max_new_tokens": 4, "timings": True})
    t = r["timings"]
    assert t["ttft_ms"] >= 0
    spans = t["streams"][0]["spans"]
    names = [s["name"] for s in spans]
    # queue-entry instant first, then the queue-wait span
    assert names[:2] == ["queued", "queue"]
    assert names[-1] == "complete"
    assert "admit" in names and "decode" in names
    assert names.index("admit") < names.index("decode")
    starts = [s["start_ms"] for s in spans]
    assert starts == sorted(starts)
    assert all(s["dur_ms"] >= 0 for s in spans)
    # prefill chunking is visible: a 10-token prompt at chunk 8 is
    # two pieces
    assert [s for s in spans if s["name"] == "prefill"
            and s["args"]["piece"] == 8]
    # the flag is validated like every other request field
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(base, {"prompt": [1, 2], "max_new_tokens": 2,
                     "timings": "yes"})
    assert ei.value.code == 400
    # without the flag, no timings block rides the response
    assert "timings" not in _post(base, {"prompt": [1, 2],
                                         "max_new_tokens": 2})


def test_profile_endpoints_guarded(tel_server, tmp_path):
    base, ms = tel_server
    # this server was started without a profile dir -> explicit 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(base, {}, path="/profile/start")
    assert ei.value.code == 400
    # arm it (the CLI would pass --profile-dir) and run one cycle
    from polyaxon_tpu.serving.telemetry import ProfileSession

    ms.profiler = ProfileSession(str(tmp_path / "prof"))
    try:
        r = _post(base, {}, path="/profile/start")
        assert r["profiling"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, {}, path="/profile/start")   # single-flight
        assert ei.value.code == 409
        _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 2})
        r = _post(base, {}, path="/profile/stop")
        assert r["profiling"] is False
        import os

        assert os.path.isdir(r["dir"])
        assert any(os.scandir(r["dir"])), "profiler wrote nothing"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, {}, path="/profile/stop")    # nothing running
        assert ei.value.code == 409
    finally:
        ms.profiler.close()
        ms.profiler = None


# ---------------------------------------------------------------------------
# span lifecycle: engine (3-way co-tenant), coalesce, solo
# ---------------------------------------------------------------------------


def test_engine_spans_complete_and_ordered(tiny):
    """Three co-tenant streams through a 2-slot pool (the third
    queues behind the first eviction): every stream's lifecycle spans
    are present, in order, with monotone timestamps."""
    model, variables = tiny
    tel = Telemetry(buffer=256)
    eng = DecodeEngine(model, variables,
                       policy=SchedulerPolicy(n_slots=2,
                                              queue_depth=16,
                                              prefill_chunk=4,
                                              decode_window=2),
                       autostart=False, telemetry=tel)
    groups = [
        eng.submit(np.asarray([[1, 2, 3]], np.int32), 3, None, None),
        eng.submit(np.asarray([[4, 5, 6, 7, 8]], np.int32), 4, None,
                   None, sampling=SamplingSpec(seed=5,
                                               temperature=0.9,
                                               top_k=8)),
        eng.submit(np.asarray([[9, 10]], np.int32), 2, None, None),
    ]
    eng.run_until_idle()
    for g in groups:
        assert g.event.is_set() and g.error is None
    by_tid = {}
    for ev in tel.events():
        if ev["pid"] == REQUESTS_PID:
            by_tid.setdefault(ev["tid"], []).append(ev)
    assert len(by_tid) == 3
    for tid, evs in by_tid.items():
        names = [e["name"] for e in evs]
        # queue-entry instant first, then the queue-wait span
        assert names[:2] == ["queued", "queue"]
        assert names[-2:] == ["decode", "complete"]
        assert "admit" in names
        prefills = [i for i, n in enumerate(names) if n == "prefill"]
        assert prefills, names
        assert max(prefills) < names.index("admit")
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
    # the engine track recorded the decode dispatches
    assert any(e["pid"] == ENGINE_PID for e in tel.events())


def _tiny_server(tiny, **kw):
    model, variables = tiny
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     max_batch=4, **kw)
    srv = make_server("127.0.0.1", 0, ms)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return f"http://127.0.0.1:{srv.server_address[1]}", ms, srv


def test_coalesce_and_solo_paths_emit_spans(tiny):
    for mode, span_name in (("coalesce", "coalesce_decode"),
                            ("off", "solo_decode")):
        base, ms, srv = _tiny_server(tiny, batching=mode)
        try:
            r = _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 2,
                             "timings": True})
            names = [e["name"] for e in ms.telemetry.events()]
            assert span_name in names, (mode, names)
            assert "complete" in names
            spans = r["timings"]["spans"]
            assert [s["name"] for s in spans][-1] == "complete"
            assert spans[0]["start_ms"] >= 0
        finally:
            srv.shutdown()
            srv.server_close()
            ms.close()


def test_access_log_lines(tiny):
    base, ms, srv = _tiny_server(tiny, batching="off",
                                 access_log=True)
    ms._access_log_file = io.StringIO()
    try:
        _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 2,
                     "temperature": 0.7, "seed": 1})
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 0})
        assert ei.value.code == 400
        # the line lands just AFTER the response is sent (logging
        # must never delay a reply) — give the handler thread a beat
        import time

        for _ in range(100):
            if ms._access_log_file.getvalue().count("\n") >= 2:
                break
            time.sleep(0.02)
        lines = [json.loads(ln) for ln in
                 ms._access_log_file.getvalue().splitlines()]
        assert len(lines) == 2
        # each line lands after its own reply, on its own handler
        # thread: the two may land in either order
        ok, bad = sorted(lines, key=lambda ln: ln["status"])
        assert ok["status"] == 200 and ok["kind"] == "sampled"
        assert ok["rows"] == 1 and ok["new_tokens"] == 2
        assert ok["ms"] > 0
        # the satellite fix: FAILED requests get a line too
        assert bad["status"] == 400 and "max_new_tokens" in \
            bad["error"]
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


def test_access_log_off_by_default(tiny):
    base, ms, srv = _tiny_server(tiny, batching="off")
    ms._access_log_file = io.StringIO()
    try:
        _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 1})
        assert ms._access_log_file.getvalue() == ""
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


def test_trace_file_dump_roundtrip(tmp_path):
    tel = Telemetry(buffer=64)
    tel.span(1, "queue", 0.0, 0.5, row=0)
    tel.span(1, "decode", 0.5, 1.0, row=0)
    tel.step("step", 0.0, 0.1, window=2, occupancy=1, batch=4,
             tokens=2)
    path = str(tmp_path / "spans.jsonl")
    n = dump_spans_jsonl(tel, path)
    assert n == 3
    evs = load_trace_events(path)
    assert [e["name"] for e in evs] == ["queue", "decode", "step"]
    # the same loader reads a saved GET /trace document
    doc_path = str(tmp_path / "trace.json")
    with open(doc_path, "w") as f:
        json.dump(tel.chrome_trace(), f)
    evs2 = load_trace_events(doc_path)
    assert [e["name"] for e in evs2 if e["ph"] != "M"] == \
        ["queue", "decode", "step"]


def test_trace_report_summary(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(
            os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            "benchmarks", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    tel = Telemetry(buffer=64)
    for tid, (q, d) in enumerate([(0.001, 0.01), (0.002, 0.02),
                                  (0.004, 0.04)], start=1):
        tel.span(tid, "queue", 0.0, q, row=0)
        tel.span(tid, "decode", q, q + d, row=0)
    for i in range(4):
        t = 0.01 * i
        tel.step("step", t, t + 0.005, kind="plain", window=2,
                 occupancy=2 + (i % 2), batch=4, tokens=4)
    path = str(tmp_path / "trace.json")
    with open(path, "w") as f:
        json.dump(tel.chrome_trace(), f)
    s = tr.summarize(path)
    assert s["phases"]["queue"]["count"] == 3
    assert s["phases"]["decode"]["p50_ms"] == 20.0
    eng = s["engine"]
    assert eng["steps"] == 4
    assert eng["pool_width"] == 4
    assert eng["tokens_total"] == 16
    assert eng["mean_occupancy"] == 2.5
    assert len(eng["occupancy_strip"]) == 20
