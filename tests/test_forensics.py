"""Tail-latency forensics proof obligations (serving/forensics.py +
the exemplar layer in serving/telemetry.py).

THE pins:

- PARTITION: the phase ledger is an EXACT partition of wall time —
  phases + explicit ``unattributed`` sum to ``wall_s`` with zero
  epsilon, on synthetic fixtures covering overlapping phases,
  preempt-resume gaps, hedged two-attempt router traces, disagg
  handoff, and zero-length requests (the sweep works in integer
  microseconds; docs/DESIGN.md partition contract).
- SAME BYTES: the history record's ``phases`` block, the live
  ``timings`` block, and the stitched ``GET /fleet/requests/<id>``
  segment carry byte-identical ledgers — ONE function computes all
  three surfaces.
- EXEMPLARS: histogram buckets retain the last K request IDs
  (bounded, oldest evicted first), the /metrics exposition carries
  OpenMetrics exemplar suffixes that the repo's own parsers strip,
  and ``GET /debug/exemplars`` serves the full K.
- SENTRY: a seeded slowdown (FaultPlan ``slow_step``) is flagged
  within the first anomalous window with the RIGHT phase, a steady
  fixture produces ZERO findings, and an armed forensics directory
  receives a diagnostic bundle per episode.
- OVERHEAD SHAPE: forensics armed adds zero steady-state recompiles.
"""

import dataclasses
import json
import math
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving import (LocalReplica, ModelServer,
                                  ReplicaRouter, make_router_server,
                                  make_server)
from polyaxon_tpu.serving.faults import FaultPlan
from polyaxon_tpu.serving.forensics import (
    PHASE_ADMIT_WAIT, PHASE_DECODE, PHASE_DEVICE_LOCK_WAIT,
    PHASE_FINALIZE, PHASE_KV_HANDOFF, PHASE_KV_WIRE_FETCH,
    PHASE_PREEMPT_GAP, PHASE_PREFILL, PHASE_PREFILL_REMOTE,
    PHASE_QUEUE_WAIT, PHASE_REPLICA_ATTEMPT, PHASE_RETRY_BACKOFF,
    PHASE_ROUTE_PICK, PHASE_UNATTRIBUTED, PHASES, ROUTER_PHASES,
    AnomalySentry, ForensicsCore, compute_ledger,
    compute_router_ledger, is_solo_events, ledger_shares)
from polyaxon_tpu.serving.telemetry import (Histogram, Telemetry,
                                            parse_prometheus_text,
                                            render_histogram,
                                            strip_exemplar)


def _exact(ledger):
    """The partition contract: phases + unattributed == wall, EXACT
    at the ledger's microsecond resolution (every value is n/1e6)."""
    total = sum(ledger["phases"].values()) + ledger["unattributed"]
    assert round(total * 1e6) == round(ledger["wall_s"] * 1e6), ledger


# ---------------------------------------------------------------------------
# ledger: synthetic fixtures
# ---------------------------------------------------------------------------


class TestLedgerPartition:
    def test_plain_engine_request(self):
        # queue 0-1, prefill 1-2, (admit gap 2-2.5), decode 2.5-4,
        # trailing finalize 4-4.2
        ev = [("queue", 0.0, 1.0, {}),
              ("prefill", 1.0, 2.0, {}),
              ("decode", 2.5, 4.0, {}),
              ("complete", 4.0, 4.0, {})]
        led = compute_ledger(ev, 0.0, 4.2)
        _exact(led)
        assert led["wall_s"] == pytest.approx(4.2)
        assert led["phases"][PHASE_QUEUE_WAIT] == pytest.approx(1.0)
        assert led["phases"][PHASE_PREFILL] == pytest.approx(1.0)
        assert led["phases"][PHASE_ADMIT_WAIT] == pytest.approx(0.5)
        assert led["phases"][PHASE_DECODE] == pytest.approx(1.5)
        assert led["phases"][PHASE_FINALIZE] == pytest.approx(0.2)
        assert led["unattributed"] == 0.0
        assert led["dominant"] == PHASE_DECODE

    def test_overlap_priority_wire_fetch_inside_decode(self):
        # A wire fetch bracketed by the fused solo span: the wire
        # phase wins its overlap, decode keeps the rest.
        ev = [("queue", 0.0, 0.2, {}),
              ("solo_decode", 0.2, 2.0, {}),
              ("prefix_wire_fetch", 0.5, 1.0, {"bytes": 10})]
        led = compute_ledger(ev, 0.0, 2.0, solo=True)
        _exact(led)
        assert led["phases"][PHASE_KV_WIRE_FETCH] \
            == pytest.approx(0.5)
        assert led["phases"][PHASE_DECODE] == pytest.approx(1.3)
        # solo=True maps the queue span to device-lock wait
        assert led["phases"][PHASE_DEVICE_LOCK_WAIT] \
            == pytest.approx(0.2)
        assert PHASE_QUEUE_WAIT not in led["phases"]

    def test_preempt_resume_gap(self):
        # decode, eviction gap, decode again: the uncovered middle is
        # preempt_gap (left neighbor is decode), not unattributed.
        ev = [("queue", 0.0, 0.5, {}),
              ("prefill", 0.5, 1.0, {}),
              ("decode", 1.0, 2.0, {"terminal": "preempted"}),
              ("decode", 3.0, 4.0, {})]
        led = compute_ledger(ev, 0.0, 4.0)
        _exact(led)
        assert led["phases"][PHASE_PREEMPT_GAP] == pytest.approx(1.0)
        assert led["phases"][PHASE_DECODE] == pytest.approx(2.0)
        assert led["unattributed"] == 0.0

    def test_disagg_handoff(self):
        # Stage-2 admission: KV handoff span between prefill and
        # decode — its own phase, beating the spans it overlaps.
        ev = [("queue", 0.0, 0.1, {}),
              ("prefill", 0.1, 0.6, {}),
              ("kv_handoff", 0.6, 0.9, {"entries": 2}),
              ("decode", 0.8, 1.8, {})]
        led = compute_ledger(ev, 0.0, 1.8)
        _exact(led)
        assert led["phases"][PHASE_KV_HANDOFF] == pytest.approx(0.3)
        assert led["phases"][PHASE_DECODE] == pytest.approx(0.9)

    def test_zero_length_and_empty(self):
        led = compute_ledger([], 5.0, 5.0)
        _exact(led)
        assert led["wall_s"] == 0.0 and led["phases"] == {}
        assert "dominant" not in led
        # instants (a == b) contribute no time
        led = compute_ledger([("complete", 1.0, 1.0, {})], 0.0, 1.0)
        _exact(led)
        assert led["phases"] == {}
        assert led["unattributed"] == pytest.approx(1.0)
        assert led["dominant"] == PHASE_UNATTRIBUTED

    def test_caller_paid_span_extends_window(self):
        # A wire fetch the CALLER paid for legally precedes t0: the
        # ledger window widens to cover it instead of clamping.
        ev = [("prefix_wire_fetch", -0.5, 0.0, {}),
              ("queue", 0.0, 0.2, {}),
              ("decode", 0.2, 1.0, {})]
        led = compute_ledger(ev, 0.0, 1.0)
        _exact(led)
        assert led["wall_s"] == pytest.approx(1.5)
        assert led["phases"][PHASE_KV_WIRE_FETCH] \
            == pytest.approx(0.5)

    def test_unknown_span_names_are_ignored(self):
        led = compute_ledger(
            [("mystery", 0.0, 1.0, {}), ("decode", 1.0, 2.0, {})],
            0.0, 2.0)
        _exact(led)
        # the mystery span's bracket stays honest: unattributed
        assert led["unattributed"] == pytest.approx(1.0)
        assert led["phases"][PHASE_DECODE] == pytest.approx(1.0)

    def test_irrational_durations_stay_exact(self):
        # Floats that don't round-trip through decimal: the integer-
        # microsecond sweep still partitions exactly.
        a, b = math.pi / 10, math.e / 3
        ev = [("queue", 0.0, a, {}), ("prefill", a, a + b, {}),
              ("decode", a + b, a + b + 0.1234567, {})]
        led = compute_ledger(ev, 0.0, a + b + 0.2, solo=False)
        _exact(led)

    def test_shares_sum_to_one(self):
        ev = [("queue", 0.0, 1.0, {}), ("decode", 1.5, 3.0, {})]
        led = compute_ledger(ev, 0.0, 3.0)
        sh = ledger_shares(led)
        assert sum(sh.values()) == pytest.approx(1.0)
        assert sh[PHASE_UNATTRIBUTED] == pytest.approx(0.5 / 3.0)

    def test_is_solo_events(self):
        assert is_solo_events(["queue", "solo_decode"])
        assert is_solo_events(iter(["coalesce_decode"]))
        assert not is_solo_events(["queue", "prefill", "decode"])


class TestRouterLedger:
    def test_hedged_two_attempt(self):
        # Primary attempt 0.1-2.0; hedge fires at 1.0 and wins at
        # 1.5: overlapping attempt brackets coalesce into one
        # replica_attempt total (the sweep counts covered TIME, not
        # per-span sums), leading gap is route_pick.
        ev = [("route", 0.05, 0.05, {}),
              ("attempt", 0.1, 2.0, {"n": 1}),
              ("attempt", 1.0, 1.5, {"n": 2, "hedge": True}),
              ("hedge_won", 1.5, 1.5, {})]
        led = compute_router_ledger(ev, 0.0, 2.1)
        _exact(led)
        assert led["phases"][PHASE_REPLICA_ATTEMPT] \
            == pytest.approx(1.9)
        assert led["phases"][PHASE_ROUTE_PICK] == pytest.approx(0.1)
        assert led["phases"][PHASE_FINALIZE] == pytest.approx(0.1)
        assert led["dominant"] == PHASE_REPLICA_ATTEMPT
        assert set(led["phases"]) <= set(ROUTER_PHASES)

    def test_retry_backoff_between_attempts(self):
        ev = [("attempt", 0.0, 1.0, {"outcome": "error"}),
              ("attempt", 1.5, 2.5, {"outcome": "ok"})]
        led = compute_router_ledger(ev, 0.0, 2.5)
        _exact(led)
        assert led["phases"][PHASE_RETRY_BACKOFF] \
            == pytest.approx(0.5)

    def test_disagg_remote_prefill_beats_attempt(self):
        ev = [("attempt", 0.0, 2.0, {}),
              ("prefill_remote", 0.2, 0.8, {})]
        led = compute_router_ledger(ev, 0.0, 2.0)
        _exact(led)
        assert led["phases"][PHASE_PREFILL_REMOTE] \
            == pytest.approx(0.6)
        assert led["phases"][PHASE_REPLICA_ATTEMPT] \
            == pytest.approx(1.4)


# ---------------------------------------------------------------------------
# exemplars: retention, exposition, parsers
# ---------------------------------------------------------------------------


class TestExemplars:
    def test_retention_bound_and_eviction(self):
        h = Histogram([1.0, 10.0], exemplar_k=2)
        for i in range(5):
            h.observe(0.5, exemplar=f"req-{i}")
        h.observe(5.0, exemplar="mid")
        h.observe(100.0)                    # no exemplar: kept out
        ex = h.exemplars()
        # bucket 0 keeps the LAST 2, oldest evicted first
        assert [rid for rid, _ in ex[0]] == ["req-3", "req-4"]
        assert [rid for rid, _ in ex[1]] == ["mid"]
        assert ex[2] == []                  # +Inf saw no exemplar
        # disarmed histogram: always-empty shape, no retention
        h0 = Histogram([1.0])
        h0.observe(0.5, exemplar="x")
        assert h0.exemplars() == [[], []]

    def test_render_byte_identical_without_exemplars(self):
        base = render_histogram("m", [1.0, 2.0], [3, 1, 2], 4.5, 6)
        again = render_histogram("m", [1.0, 2.0], [3, 1, 2], 4.5, 6,
                                 exemplars=None)
        assert base == again
        withex = render_histogram(
            "m", [1.0, 2.0], [3, 1, 2], 4.5, 6,
            exemplars=[[("rid-a", 0.7)], [], [("rid-b", 9.0)]])
        assert withex[1].endswith(' # {trace_id="rid-a"} 0.7')
        assert withex[3].endswith(' # {trace_id="rid-b"} 9.0')
        # stripping the suffix recovers the exact base exposition
        assert [strip_exemplar(ln) for ln in withex] == base

    def test_parsers_survive_exemplar_suffixes(self):
        tel = Telemetry(buffer=0, exemplar_k=2)
        tel.observe("ttft", 0.05, exemplar="req-p99")
        text = "\n".join(tel.metrics_lines()) + "\n"
        assert '# {trace_id="req-p99"}' in text
        parsed = parse_prometheus_text(text)
        # the suffix didn't corrupt any parsed sample value
        assert parsed["ptpu_serving_ttft_seconds_count"] == 1.0
        rep = tel.exemplars_report()
        assert rep["exemplar_k"] == 2
        buckets = rep["histograms"]["ptpu_serving_ttft_seconds"][
            "buckets"]
        assert any(b["exemplars"][0]["request_id"] == "req-p99"
                   for b in buckets)


# ---------------------------------------------------------------------------
# sentry: detection, false positives, bundles
# ---------------------------------------------------------------------------


def _mk_ledger(phase_s, wall_s):
    phases = dict(phase_s)
    un = wall_s - sum(phases.values())
    return {"wall_s": wall_s, "phases": phases,
            "unattributed": max(0.0, un)}


class TestAnomalySentry:
    def test_steady_stream_zero_findings(self):
        s = AnomalySentry(window=8, baseline_windows=2)
        out = []
        for i in range(8 * 10):
            out += s.note(_mk_ledger(
                {PHASE_DECODE: 0.8, PHASE_QUEUE_WAIT: 0.1}, 1.0),
                f"r{i}")
        assert out == [] and s.findings() == []
        assert s.baseline()["armed"]

    def test_disarmed_until_baseline(self):
        s = AnomalySentry(window=4, baseline_windows=2)
        # a spike in the very first window must NOT fire
        for i in range(4):
            assert s.note(_mk_ledger(
                {PHASE_QUEUE_WAIT: 0.9}, 1.0), f"r{i}") == []
        assert s.findings() == []

    def test_detects_spike_in_first_anomalous_window(self, tmp_path):
        recs = {"slow-3": {"request_id": "slow-3", "status": "ok"}}
        s = AnomalySentry(
            window=4, baseline_windows=2, out_dir=str(tmp_path),
            snapshot_fn=lambda: {"state": "snap"},
            record_fn=lambda rid: recs.get(rid),
            trace_tail_fn=lambda: [{"name": "step"}])
        # 3 baseline windows: decode-dominant, tiny queue share
        for i in range(12):
            s.note(_mk_ledger(
                {PHASE_DECODE: 0.85, PHASE_QUEUE_WAIT: 0.05}, 1.0),
                f"ok-{i}")
        assert s.findings() == []
        # the seeded slowdown: queue_wait explodes; request 3 worst
        found = []
        for i in range(4):
            sh = 0.6 if i != 3 else 0.9
            found += s.note(_mk_ledger(
                {PHASE_QUEUE_WAIT: 2.0 * sh,
                 PHASE_DECODE: 2.0 * (1.0 - sh)},
                2.0), f"slow-{i}")
        assert [f["phase"] for f in found] == [PHASE_QUEUE_WAIT]
        f = found[0]
        assert f["share"] > f["baseline_ewma"]
        assert f["exemplars"] == ["slow-3"]     # window's worst rid
        assert s.anomalies_total[PHASE_QUEUE_WAIT] == 1
        # the bundle: anomaly + state snapshot + exemplar record +
        # trace tail, on disk
        bundle = json.loads(
            open(f["bundle"]).read())
        assert bundle["anomaly"]["phase"] == PHASE_QUEUE_WAIT
        assert bundle["state"] == {"state": "snap"}
        assert bundle["exemplar_records"]["slow-3"]["status"] == "ok"
        assert bundle["trace_tail"] == [{"name": "step"}]
        # ONE-SHOT: a second anomalous window extends the episode,
        # no new finding...
        again = []
        for i in range(4):
            again += s.note(_mk_ledger(
                {PHASE_QUEUE_WAIT: 1.4, PHASE_DECODE: 0.6}, 2.0),
                f"slow2-{i}")
        assert again == []
        assert s.anomalies_total[PHASE_QUEUE_WAIT] == 1
        # ...and recovery re-arms: windows back in band, then a new
        # spike fires a SECOND episode.
        for i in range(4 * 6):
            s.note(_mk_ledger(
                {PHASE_DECODE: 0.85, PHASE_QUEUE_WAIT: 0.05}, 1.0),
                f"calm-{i}")
        redo = []
        for i in range(4):
            redo += s.note(_mk_ledger(
                {PHASE_QUEUE_WAIT: 1.6, PHASE_DECODE: 0.4}, 2.0),
                f"slow3-{i}")
        assert [f["phase"] for f in redo] == [PHASE_QUEUE_WAIT]
        assert s.anomalies_total[PHASE_QUEUE_WAIT] == 2

    def test_min_share_floor(self):
        # A phase that grew 10x but stays tiny in absolute share is
        # noise, not an incident.
        s = AnomalySentry(window=4, baseline_windows=2,
                          min_share=0.05)
        for i in range(8):
            s.note(_mk_ledger(
                {PHASE_DECODE: 0.9, PHASE_FINALIZE: 0.001}, 1.0),
                f"a{i}")
        out = []
        for i in range(4):
            out += s.note(_mk_ledger(
                {PHASE_DECODE: 0.89, PHASE_FINALIZE: 0.02}, 1.0),
                f"b{i}")
        assert out == []

    def test_core_metrics_lines_families(self):
        core = ForensicsCore(window=4, baseline_windows=2)
        lines = core.metrics_lines("ptpu_serving")
        # TYPE lines render before first traffic (labeled-family
        # idiom: the scraper learns the family exists)
        assert "# TYPE ptpu_serving_phase_seconds_total counter" \
            in lines
        assert "# TYPE ptpu_serving_phase_share gauge" in lines
        assert "# TYPE ptpu_serving_anomalies_total counter" in lines
        core.note(_mk_ledger({PHASE_DECODE: 0.5}, 1.0), "r1")
        text = "\n".join(core.metrics_lines("ptpu_serving"))
        assert 'ptpu_serving_phase_seconds_total{phase="decode"} ' \
            "0.5" in text
        assert 'ptpu_serving_phase_share{phase="decode"} 0.5' in text
        rep = core.report()
        assert rep["requests_total"] == 1
        assert rep["phase_share"]["decode"] == 0.5


# ---------------------------------------------------------------------------
# integration: live server surfaces
# ---------------------------------------------------------------------------


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


def _post(base, payload, timeout=120, path="/generate",
          headers=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def _get_text(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode()


@pytest.fixture(scope="module")
def served(small_model):
    model, variables = small_model
    ms = ModelServer(model, variables, model_name="tiny",
                     max_batch=4, n_slots=2, queue_depth=16,
                     decode_window=2, request_history=64,
                     exemplar_k=3)
    srv = make_server("127.0.0.1", 0, ms)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", ms
    srv.shutdown()
    srv.server_close()
    ms.close()


class TestServerSurfaces:
    def test_timings_history_and_metrics_agree(self, served):
        base, ms = served
        body = _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 4,
                            "timings": True},
                     headers={"X-Request-Id": "forensic-1"})
        led = body["timings"]["phases"]
        _exact(led)
        assert led["phases"], led
        # SAME BYTES: the history record carries the identical ledger
        rec = _get(base, "/requests/forensic-1")
        assert json.dumps(rec["phases"], sort_keys=True) \
            == json.dumps(led, sort_keys=True)
        # /metrics: phase families + exemplar suffixes, parseable
        text = _get_text(base, "/metrics")
        assert 'ptpu_serving_phase_seconds_total{phase=' in text
        assert 'ptpu_serving_phase_share{phase=' in text
        assert "# TYPE ptpu_serving_anomalies_total counter" in text
        parse_prometheus_text(text)          # exemplars don't break it
        assert '# {trace_id="' in text
        # /debug/exemplars resolves a retained request id
        rep = _get(base, "/debug/exemplars")
        rids = {e["request_id"]
                for h in rep["histograms"].values()
                for b in h["buckets"] for e in b["exemplars"]}
        assert "forensic-1" in rids
        # /anomalies: live report shape
        rep = _get(base, "/anomalies")
        assert rep["requests_total"] >= 1
        assert set(rep["phase_share"]) <= set(PHASES)
        assert rep["findings"] == []

    def test_forensics_off_is_a_400_and_no_exemplars(self,
                                                     small_model):
        model, variables = small_model
        ms = ModelServer(model, variables, model_name="tiny",
                         max_batch=2, n_slots=2, queue_depth=8,
                         forensics=False)
        srv = make_server("127.0.0.1", 0, ms)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            _post(base, {"prompt": [1, 2], "max_new_tokens": 2})
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base, "/anomalies")
            assert ei.value.code == 400
            text = _get_text(base, "/metrics")
            assert "phase_seconds_total" not in text
            assert '# {trace_id="' not in text
        finally:
            srv.shutdown()
            srv.server_close()
            ms.close()

    def test_solo_path_ledger_device_lock_wait(self, small_model):
        model, variables = small_model
        ms = ModelServer(model, variables, model_name="tiny",
                         batching="off")
        try:
            out = ms.generate(
                {"prompt": [1, 2, 3], "max_new_tokens": 2,
                 "timings": True})
            led = out["timings"]["phases"]
            _exact(led)
            assert PHASE_DECODE in led["phases"]
            assert PHASE_QUEUE_WAIT not in led["phases"]
            assert ms.forensics.accumulator.requests_total == 1
        finally:
            ms.close()

    def test_zero_steady_state_recompiles_with_forensics(
            self, served):
        base, ms = served
        for _ in range(2):
            _post(base, {"prompt": [4, 5, 6], "max_new_tokens": 4})
        before = ms.engine.stats()["compile_cache_misses"]
        for _ in range(3):
            _post(base, {"prompt": [7, 8, 9], "max_new_tokens": 4,
                         "timings": True})
        assert ms.engine.stats()["compile_cache_misses"] == before


class TestSentryIntegration:
    def test_seeded_slowdown_flagged(self, small_model, tmp_path):
        """A FaultPlan ``slow_step`` stall inflates queue_wait for
        the requests stuck behind it; the sentry must flag that
        phase within the first anomalous window, with a bundle on
        disk — and the steady baseline traffic must have produced
        ZERO findings first."""
        model, variables = small_model
        ms = ModelServer(model, variables, model_name="tiny",
                         max_batch=4, n_slots=2, queue_depth=32,
                         decode_window=2, request_history=64,
                         sentry_window=6, sentry_baseline_windows=2,
                         forensics_dir=str(tmp_path))
        srv = make_server("127.0.0.1", 0, ms)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            # Steady baseline: 3 windows of sequential requests
            # (queue share ~0 — each request has the engine alone).
            for i in range(18):
                _post(base, {"prompt": [1, 2, 3],
                             "max_new_tokens": 4})
            assert _get(base, "/anomalies")["findings"] == []
            # Seeded slowdown: every engine step now sleeps
            # (deterministic FaultPlan, the --fault-plan mechanism),
            # and a concurrent burst piles up behind the stalled
            # steps — queue_wait share explodes.
            ms.engine.faults = FaultPlan({"faults": [
                {"site": "slow_step", "delay_s": 0.15}]})
            threads = []
            for i in range(12):
                t = threading.Thread(
                    target=lambda: _post(
                        base, {"prompt": [1, 2, 3],
                               "max_new_tokens": 4}, timeout=300),
                    daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=300)
            rep = _get(base, "/anomalies")
            phases = [f["phase"] for f in rep["findings"]]
            assert PHASE_QUEUE_WAIT in phases, rep
            f = next(x for x in rep["findings"]
                     if x["phase"] == PHASE_QUEUE_WAIT)
            # the bundle landed on disk with the exemplar's record
            bundle = json.loads(open(f["bundle"]).read())
            assert bundle["anomaly"]["phase"] == PHASE_QUEUE_WAIT
            assert "state" in bundle
        finally:
            ms.engine.faults = None
            srv.shutdown()
            srv.server_close()
            ms.close()


# ---------------------------------------------------------------------------
# fleet: stitched ledgers, federation, clock skew
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet(small_model):
    model, variables = small_model

    def factory():
        return ModelServer(
            model, variables, model_name="tiny", max_batch=4,
            n_slots=2, queue_depth=16, decode_window=2,
            request_history=64, exemplar_k=2)

    reps = [LocalReplica(factory, f"r{i}") for i in range(2)]
    router = ReplicaRouter(reps, probe_interval_s=0.1,
                           probe_timeout_s=0.5, cooldown_s=0.2,
                           request_timeout_s=60.0)
    srv = make_router_server("127.0.0.1", 0, router)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    yield base, router
    router.close()
    srv.shutdown()
    srv.server_close()
    for r in reps:
        r.close()


class TestFleetForensics:
    def test_stitched_timeline_carries_replica_ledger(self, fleet):
        base, router = fleet
        body = _post(base, {"prompt": [1, 2, 3], "max_new_tokens": 4},
                     headers={"X-Request-Id": "fleet-led-1"})
        rid = body["request_id"]
        doc = _get(base, f"/fleet/requests/{rid}")
        segs = [s for s in doc["segments"] if s.get("phases")]
        assert segs, doc["segments"]
        led = segs[0]["phases"]
        _exact(led)
        # verbatim lift: the segment ledger IS the replica record's
        assert json.dumps(segs[0]["record"]["phases"],
                          sort_keys=True) \
            == json.dumps(led, sort_keys=True)
        # the router's own record carries the router-side ledger
        rled = doc["router"]["phases"]
        _exact(rled)
        assert PHASE_REPLICA_ATTEMPT in rled["phases"]
        assert set(rled["phases"]) <= set(ROUTER_PHASES)

    def test_p99_exemplar_resolves_to_dominant_phase(self, fleet):
        from polyaxon_tpu.serving.debug import parse_replica_rid
        base, router = fleet
        for i in range(3):
            _post(base, {"prompt": [2, 3, 4], "max_new_tokens": 4})
        # federation strips exemplar suffixes (parse_prometheus_*
        # recovers bare samples), so the debugging workflow reads the
        # REPLICA's own /metrics for the exemplar rid, then resolves
        # it through the router's stitched timeline
        text = _get_text(base, "/fleet/metrics")
        assert '# {trace_id="' not in text  # federated = stripped
        m = []
        for rep in router.replicas:
            rep_text = urllib.request.urlopen(
                rep.url + "/metrics", timeout=5).read().decode()
            m += [ln for ln in rep_text.splitlines()
                  if '# {trace_id="' in ln
                  and ("ptpu_serving_request_latency_seconds_bucket"
                       in ln)]
        assert m, "no exemplar-bearing total-latency bucket lines"
        # The LOWEST occupied bucket's exemplar: a steady request.
        # The highest holds a replica's first request, whose time is
        # compilation in prefill and decode alike, and which of the
        # two is longer there turns on the machine's load (the decode
        # side is one program since the step count is an operand).
        rid = m[0].split('trace_id="')[1].split('"')[0]
        # replica-side rid is router-prefixed ("r0-<rid>"); the bare
        # id is the router-visible handle for the stitched view
        _, bare = parse_replica_rid(rid)
        doc = _get(base, f"/fleet/requests/{bare}")
        segs = [s for s in doc["segments"] if s.get("phases")]
        assert segs
        led = segs[0]["phases"]
        dom = led["dominant"]
        assert dom in PHASES
        # WHICH phase that is (decode, on an idle machine) turns on
        # the machine's load; that it is the segment's longest never
        # does
        assert led["phases"][dom] == max(led["phases"].values())

    def test_fleet_anomalies_merges_and_ranks(self, fleet):
        base, router = fleet
        rep = _get(base, "/fleet/anomalies")
        assert rep["replicas_polled"] == 2
        assert rep["fetch_errors"] == []
        assert {"router", "r0", "r1"} <= set(rep["phase_share"])
        scores = [f["score"] for f in rep["findings"]]
        assert scores == sorted(scores, reverse=True)
        # router's own /anomalies answers too
        own = _get(base, "/anomalies")
        assert set(own["phase_share"]) <= set(ROUTER_PHASES)

    def test_clock_skew_gauge_and_annotation(self, fleet):
        base, router = fleet
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if all(r.clock_skew_s is not None
                   for r in router.replicas):
                break
            time.sleep(0.05)
        assert all(r.clock_skew_s is not None
                   for r in router.replicas)
        # in-process replicas share the host clock: skew ~ 0
        assert all(abs(r.clock_skew_s) < 0.25
                   for r in router.replicas)
        text = _get_text(base, "/metrics")
        assert "# TYPE ptpu_fleet_clock_skew_seconds gauge" in text
        assert 'ptpu_fleet_clock_skew_seconds{replica="r0"}' in text
        # stitched segments annotate the estimate, below threshold
        body = _post(base, {"prompt": [5, 6], "max_new_tokens": 2})
        doc = _get(base, f"/fleet/requests/{body['request_id']}")
        seg = doc["segments"][0]
        assert "clock_skew_est_s" in seg
        assert seg["clock_skew_suspect"] is False
        # past the threshold the segment is flagged suspect — the
        # victim is whichever replica actually served the request
        victim = next(r for r in router.replicas
                      if r.id == seg["replica"])
        old = victim.clock_skew_s
        try:
            victim.clock_skew_s = 1.5
            doc = _get(base,
                       f"/fleet/requests/{body['request_id']}")
            seg = next(s for s in doc["segments"]
                       if s["replica"] == victim.id)
            assert seg["clock_skew_suspect"] is True
        finally:
            victim.clock_skew_s = old
