"""Fleet-wide observability proof obligations (serving/router.py +
serving/debug.py + serving/telemetry.py).

THE pins:

- CROSS-TIER STITCHING: ``GET /fleet/requests/<id>`` returns ONE
  merged causal timeline — the router's route/attempt/hedge events
  plus every involved replica's own history record — for a request
  that survives a seeded replica kill (failover) and for one that
  wins a hedge race, with event ordering CAUSALLY CONSISTENT: no
  replica-sourced event outside its attempt's router send/receive
  bracket (the clock-reconciliation contract, docs/DESIGN.md).
- METRICS FEDERATION: ``GET /fleet/metrics`` is valid Prometheus
  exposition (the existing test_telemetry checker) whose per-replica
  labeled series SUM to the fleet rollups.
- STRUCTURAL NO-DRIFT: every key of ``router.stats()`` and
  ``engine.stats()`` renders on its /metrics surface (or carries an
  explicit exemption) — the contract earlier PRs re-pinned counter
  by counter, held structurally so a new counter can't silently skip
  a surface.
- SLO BURN RATES: ``ptpu_router_slo_burn_rate{objective=}`` is 0
  with no violations in the window and > 0 exactly when the window
  holds violations.

Satellites: the ``r<N>-<rid>`` parse/format helpers, the per-probe
duration histogram, and ``GET /requests?status=`` filtering on a
replica serving both direct and router-prefixed traffic.
"""

import dataclasses
import json
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving import (LocalReplica, ModelServer,
                                  ReplicaRouter, SLOTracker,
                                  make_router_server)
from polyaxon_tpu.serving.debug import (format_replica_rid,
                                        parse_replica_rid)
from polyaxon_tpu.serving.router import (STATS_METRIC_EXEMPT,
                                         STATS_METRIC_RENAMES,
                                         Replica)
from polyaxon_tpu.serving.server import (ENGINE_STATS_METRIC_EXEMPT,
                                         ENGINE_STATS_METRIC_RENAMES)
from polyaxon_tpu.serving.telemetry import (parse_prometheus_families,
                                            parse_prometheus_text)

# ---------------------------------------------------------------------------
# fixtures (the test_router.py fleet idiom, draft-free for speed)
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


def _factory(small_model, **kw):
    model, variables = small_model

    def make():
        return ModelServer(
            model, variables, model_name="tiny", max_batch=4,
            n_slots=2, queue_depth=16, decode_window=2,
            request_history=64, **kw)
    return make


def _spawn_fleet(small_model, n=3, *, router_kw=None, ms_kw=None):
    reps = [LocalReplica(_factory(small_model, **(ms_kw or {})),
                         f"r{i}")
            for i in range(n)]
    kw = dict(probe_interval_s=0.1, probe_timeout_s=0.5,
              cooldown_s=0.2, request_timeout_s=60.0)
    kw.update(router_kw or {})
    router = ReplicaRouter(reps, **kw)
    srv = make_router_server("127.0.0.1", 0, router)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    return base, router, srv, reps


def _teardown(router, srv, reps):
    router.close()
    srv.shutdown()
    srv.server_close()
    for r in reps:
        r.close()


@pytest.fixture(scope="module")
def fleet(small_model):
    """Shared non-destructive fleet (stitching, federation, filters,
    probe histogram).  Chaos tests spawn their own."""
    base, router, srv, reps = _spawn_fleet(small_model)
    yield base, router, srv, reps
    _teardown(router, srv, reps)


def _post(base, payload, timeout=120, path="/generate",
          headers=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(base, path, timeout=30, expect=200):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) \
                as r:
            assert r.status == expect
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        assert e.code == expect, body
        return json.loads(body)


def _get_text(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        assert r.status == 200
        return r.read().decode()


# ---------------------------------------------------------------------------
# satellite: the replica-prefix convention as a real helper pair
# ---------------------------------------------------------------------------


def test_replica_rid_helpers_roundtrip():
    assert format_replica_rid("r0", "abc") == "r0-abc"
    assert parse_replica_rid("r0-abc") == ("r0", "abc")
    # rids may themselves contain dashes — only the FIRST r<N>- is
    # the router's prefix
    assert parse_replica_rid("r12-a-b-c") == ("r12", "a-b-c")
    # direct (unprefixed) traffic parses as itself
    assert parse_replica_rid("abc-123") == (None, "abc-123")
    assert parse_replica_rid("request-7") == (None, "request-7")
    assert parse_replica_rid(None) == (None, None)
    # the formatted ID stays inside the sanitizer's 128-char bound
    long = format_replica_rid("r0", "x" * 200)
    assert len(long) == 128


# ---------------------------------------------------------------------------
# unit: SLO tracker
# ---------------------------------------------------------------------------


def test_slo_parse_and_validation():
    obj = SLOTracker.parse("availability=99.9, ttft_p99_ms=1000")
    assert obj == {"availability": 99.9, "ttft_p99_ms": 1000.0}
    for bad in ("availability", "availability=high", "",
                "=99", ","):
        with pytest.raises(ValueError):
            SLOTracker.parse(bad)
    with pytest.raises(ValueError):
        SLOTracker({"availability": 100.0})      # zero error budget
    with pytest.raises(ValueError):
        SLOTracker({"nonsense_p99_ms": 10.0})
    with pytest.raises(ValueError):
        SLOTracker({"ttft_p99_ms": -1.0})
    with pytest.raises(ValueError):
        SLOTracker({"availability": 99.0}, window=2)


def test_slo_burn_math():
    tr = SLOTracker({"availability": 99.0, "ttft_p90_ms": 100.0},
                    window=16)
    # 10 clean requests: zero burn everywhere
    for _ in range(10):
        tr.observe(200, ttft_s=0.01, latency_s=0.02)
    assert tr.burn_rates() == {"availability": 0.0,
                               "ttft_p90_ms": 0.0}
    # one 5xx in a window of 11: bad rate 1/11 over a 1% budget
    tr.observe(503, ttft_s=None, latency_s=0.1)
    burns = tr.burn_rates()
    assert burns["availability"] == pytest.approx(
        (1 / 11) / 0.01, rel=1e-3)
    # ttft objective ignores failed requests entirely
    assert burns["ttft_p90_ms"] == 0.0
    # one slow completed request: 1/11 completed over a 10% budget
    tr.observe(200, ttft_s=0.5, latency_s=0.5)
    assert tr.burn_rates()["ttft_p90_ms"] == pytest.approx(
        (1 / 11) / 0.10, rel=1e-3)
    # 4xx client errors spend no budget and count in no window
    before = tr.stats()["window_observations"]
    tr.observe(400, ttft_s=None, latency_s=0.01)
    assert tr.stats()["window_observations"] == before
    st = tr.stats()
    assert st["objectives"]["availability"]["violations_total"] == 1
    assert st["objectives"]["ttft_p90_ms"]["violations_total"] == 1


# ---------------------------------------------------------------------------
# unit: the federation parser
# ---------------------------------------------------------------------------


def test_parse_prometheus_families():
    body = ("# TYPE a counter\na 3\n"
            "# TYPE h histogram\n"
            'h_bucket{le="0.1"} 2\nh_sum 0.5\nh_count 2\n'
            '# TYPE g gauge\ng{x="y"} 7\n')
    types, samples = parse_prometheus_families(body)
    assert types == {"a": "counter", "h": "histogram", "g": "gauge"}
    assert ("a", "", "3") in samples
    assert ("h_bucket", 'le="0.1"', "2") in samples
    assert ("g", 'x="y"', "7") in samples
    # label VALUES may legally contain spaces (and even "} ") — a
    # federated replica exporting reason="engine down" must not cost
    # its whole scrape
    _, sp = parse_prometheus_families(
        'e{reason="engine down"} 3\nf{x="a} b"} 1\n')
    assert ("e", 'reason="engine down"', "3") in sp
    assert ("f", 'x="a} b"', "1") in sp
    with pytest.raises(ValueError):
        parse_prometheus_families("name not_a_number\n")


# ---------------------------------------------------------------------------
# structural no-drift: EVERY stats key renders on its /metrics surface
# ---------------------------------------------------------------------------


def _metric_present(text: str, name: str) -> bool:
    """A family is 'on the surface' when a sample line, a histogram
    component line, or its # TYPE declaration carries the name."""
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            return True
        if line.startswith("# TYPE " + name + " "):
            return True
        for sfx in ("_bucket{", "_sum ", "_count "):
            if line.startswith(name + sfx):
                return True
    return False


def test_router_stats_structural_no_drift():
    """Walk EVERY router.stats() key: each must render on /metrics
    under ptpu_router_<key>, a declared rename, or a declared
    exemption — a new router counter can't silently skip the
    surface."""
    router = ReplicaRouter(
        [Replica("127.0.0.1:9", "r0")], autostart=False,
        slo="availability=99.9,ttft_p99_ms=1000")
    st = router.stats()
    text = router.metrics_text()
    parse_prometheus_text(text)                  # grammar holds
    missing = []
    for key in st:
        if key in STATS_METRIC_EXEMPT:
            continue
        name = STATS_METRIC_RENAMES.get(key, f"ptpu_router_{key}")
        if not _metric_present(text, name):
            missing.append((key, name))
    assert not missing, (
        f"router.stats() keys with no /metrics rendering (add the "
        f"metric, a STATS_METRIC_RENAMES entry, or an exemption "
        f"with a reason): {missing}")
    # exemptions must name REAL stats keys (or conditional ones the
    # armed config below doesn't produce) — a stale entry is drift
    # in the other direction
    router2 = ReplicaRouter(
        [Replica("127.0.0.1:9", "r0")], autostart=False,
        fleet_faults={"seed": 0, "faults": [
            {"site": "replica_slow", "replica": 0,
             "delay_s": 0.1}]})
    all_keys = set(st) | set(router2.stats())
    stale = set(STATS_METRIC_EXEMPT) - all_keys
    assert not stale, f"stale STATS_METRIC_EXEMPT entries: {stale}"


def test_engine_stats_structural_no_drift(small_model):
    """Same contract one tier down: every engine.stats() key renders
    on the server's /metrics (paged config, so the page-pool keys
    are covered too)."""
    model, variables = small_model
    ms = ModelServer(model, variables, model_name="tiny",
                     max_batch=4, n_slots=2, queue_depth=8,
                     kv_paged=True, kv_lazy=True)
    try:
        es = ms.engine.stats()
        text = ms.metrics_text()
        parse_prometheus_text(text)              # grammar holds
        missing = []
        for key in es:
            if key in ENGINE_STATS_METRIC_EXEMPT:
                continue
            name = ENGINE_STATS_METRIC_RENAMES.get(
                key, f"ptpu_serving_{key}")
            if not _metric_present(text, name):
                missing.append((key, name))
        assert not missing, (
            f"engine.stats() keys with no /metrics rendering (add "
            f"the metric, an ENGINE_STATS_METRIC_RENAMES entry, or "
            f"an exemption with a reason): {missing}")
        stale = {k for k in ENGINE_STATS_METRIC_EXEMPT
                 if k not in es and k not in (
                     "mesh", "kv_pool_shardings")}   # meshed only
        assert not stale, \
            f"stale ENGINE_STATS_METRIC_EXEMPT entries: {stale}"
    finally:
        ms.close()


# ---------------------------------------------------------------------------
# the degenerate stitch + list/filter surfaces (shared fleet)
# ---------------------------------------------------------------------------


def test_fleet_request_single_segment_stitch(fleet):
    """A request that never leaves its first replica: ONE attempt,
    ONE segment whose replica record is present, every replica event
    inside the router's send/receive bracket, and the merged
    timeline sorted causally."""
    base, router, _, reps = fleet
    rid = "degenerate-1"
    res = _post(base, {"prompt": [5, 6, 7], "max_new_tokens": 4},
                headers={"X-Request-Id": rid})
    assert res["request_id"] == rid
    served_by = res["router"]["replica"]
    doc = _get(base, f"/fleet/requests/{rid}")
    assert doc["request_id"] == rid
    assert doc["status"] == "complete"
    assert doc["replicas"] == [served_by]
    assert len(doc["router"]["attempts"]) == 1
    att = doc["router"]["attempts"][0]
    assert att["replica"] == served_by
    assert att["outcome"] == "ok" and att["code"] == 200
    assert att["send_ms"] is not None \
        and att["recv_ms"] > att["send_ms"]
    assert len(doc["segments"]) == 1
    seg = doc["segments"][0]
    assert seg["request_id"] == format_replica_rid(served_by, rid)
    assert seg["record"]["status"] == "complete"
    # the router's own route decision rides the timeline
    router_events = [e for e in doc["timeline"]
                     if e["source"] == "router"]
    assert any(e["event"] == "route" for e in router_events)
    assert any(e["event"] == "attempt" for e in router_events)
    # CAUSAL CONSISTENCY: every replica-sourced event inside the
    # attempt's bracket
    for e in doc["timeline"]:
        if e["source"] == served_by:
            assert e["at_ms"] >= seg["send_ms"] - 1e-6, e
            assert (e["at_ms"] + e.get("dur_ms", 0.0)) \
                <= seg["recv_ms"] + 1e-6, e
    # sorted
    ats = [e["at_ms"] for e in doc["timeline"]]
    assert ats == sorted(ats)
    # the replica's causal record really is in there (queue/admit/
    # decode events from the engine timeline)
    replica_events = {e["event"] for e in doc["timeline"]
                      if e["source"] == served_by}
    assert "queued" in replica_events or "decode" in replica_events
    # list surface + 404 contract
    lst = _get(base, "/fleet/requests?status=complete")
    assert any(r["request_id"] == rid for r in lst["requests"])
    _get(base, "/fleet/requests/never-routed", expect=404)


def test_requests_status_filter_mixed_traffic(fleet):
    """Satellite: ``GET /requests?status=`` on a REPLICA that served
    both direct and router-forwarded (prefixed-id) traffic — both
    record flavors filter correctly and the prefix parses back."""
    base, router, _, reps = fleet
    rid = "mixed-1"
    res = _post(base, {"prompt": [9, 8, 7], "max_new_tokens": 3},
                headers={"X-Request-Id": rid})
    served_by = res["router"]["replica"]
    rep = next(r for r in reps if r.id == served_by)
    # direct traffic on the SAME replica: one complete, one failed
    _post(rep.url + "/generate",
          {"prompt": [1, 2, 3], "max_new_tokens": 2}, path="",
          headers={"X-Request-Id": "direct-ok"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(rep.url + "/generate", {"prompt": "bogus"}, path="",
              headers={"X-Request-Id": "direct-bad"})
    assert ei.value.code == 400
    ei.value.read()
    done = _get(rep.url, "/requests?status=complete&limit=100")
    ids = {r["request_id"] for r in done["requests"]}
    prefixed = format_replica_rid(served_by, rid)
    assert prefixed in ids and "direct-ok" in ids
    assert "direct-bad" not in ids
    assert all(r["status"] == "complete" for r in done["requests"])
    failed = _get(rep.url, "/requests?status=failed&limit=100")
    fids = {r["request_id"] for r in failed["requests"]}
    assert "direct-bad" in fids and prefixed not in fids
    # the prefix convention parses back to (replica, client rid)
    assert parse_replica_rid(prefixed) == (served_by, rid)
    assert parse_replica_rid("direct-ok") == (None, "direct-ok")


def test_probe_duration_histogram(fleet):
    """Satellite: per-probe wall time lands in the shared-helper
    histogram and the per-replica last-probe gauge — the
    slow-but-alive surface."""
    base, router, _, reps = fleet
    deadline = time.monotonic() + 10
    while router.stats()["probe_duration_count"] < len(reps) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    st = router.stats()
    assert st["probe_duration_count"] >= len(reps)
    assert st["probe_duration_sum"] > 0
    text = _get_text(base, "/metrics")
    parse_prometheus_text(text)
    assert "ptpu_router_probe_duration_seconds_bucket" in text
    assert "ptpu_router_probe_duration_seconds_count" in text
    for r in st["replicas"]:
        assert r.get("last_probe_s") is not None
        assert (f'ptpu_router_replica_last_probe_seconds'
                f'{{replica="{r["id"]}"}}') in text
    # histogram math: +Inf cumulative equals the count
    m = parse_prometheus_text(text)
    assert m['ptpu_router_probe_duration_seconds_bucket'
             '{le="+Inf"}'] == m[
        "ptpu_router_probe_duration_seconds_count"]


# ---------------------------------------------------------------------------
# metrics federation (shared fleet)
# ---------------------------------------------------------------------------


def test_fleet_metrics_federation(fleet):
    """GET /fleet/metrics: valid exposition (the test_telemetry
    checker), per-replica labeled series for every replica, and the
    per-replica series SUM to every fleet rollup — checked
    generically over all ``*_fleet{agg="sum"}`` series."""
    base, router, _, reps = fleet
    _post(base, {"prompt": [3, 2, 1], "max_new_tokens": 3})
    text = _get_text(base, "/fleet/metrics")
    metrics = parse_prometheus_text(text)        # grammar check
    # router's own families AND per-replica serving families present
    assert "ptpu_router_requests_total" in metrics
    for rep in reps:
        assert metrics[f'ptpu_fleet_replica_scrape_ok'
                       f'{{replica="{rep.id}"}}'] == 1.0
        assert (f'ptpu_serving_requests_total'
                f'{{replica="{rep.id}"}}') in metrics
    # EVERY sum rollup equals the sum of its per-replica series
    _, samples = parse_prometheus_families(text)
    per_replica = {}
    for name, labels, value in samples:
        if not labels.startswith('replica="'):
            continue
        rest = labels.split(",", 1)[1] if "," in labels else ""
        per_replica.setdefault((name, rest), 0.0)
        per_replica[(name, rest)] += float(value)
    checked = 0
    for name, labels, value in samples:
        if not name.endswith("_fleet") \
                or not labels.startswith('agg="sum"'):
            continue
        base_name = name[:-len("_fleet")]
        rest = labels.split(",", 1)[1] if "," in labels else ""
        want = per_replica.get((base_name, rest))
        assert want is not None, (name, labels)
        assert float(value) == pytest.approx(want, rel=1e-6,
                                             abs=1e-6), \
            (name, labels, value, want)
        checked += 1
    assert checked > 50, \
        f"suspiciously few sum rollups checked: {checked}"
    # gauges get min/max spread too
    assert re.search(
        r'^ptpu_serving_slots_fleet\{agg="min"\} ', text,
        re.M), "gauge min rollup missing"
    assert re.search(
        r'^ptpu_serving_slots_fleet\{agg="max"\} ', text,
        re.M), "gauge max rollup missing"
    # scrape accounting rides stats() -> both surfaces (no drift)
    st = router.stats()
    assert st["fleet_scrapes_total"] >= 1
    info = _get(base, "/info")
    assert info["fleet_scrapes_total"] >= st["fleet_scrapes_total"] \
        or info["fleet_scrapes_total"] == st["fleet_scrapes_total"]


# ---------------------------------------------------------------------------
# THE stitching pins: failover and hedge race
# ---------------------------------------------------------------------------


def _assert_causal(doc):
    """No replica-sourced event outside its attempt's send/receive
    bracket (the acceptance pin).  Brackets are per-SEGMENT; a
    replica's events must fit the bracket of the attempt whose
    record they rode in on."""
    brackets = {}
    for seg in doc["segments"]:
        if "record" in seg:
            brackets[seg["replica"]] = (seg["send_ms"],
                                        seg["recv_ms"])
    for e in doc["timeline"]:
        src = e["source"]
        if src == "router":
            continue
        assert src in brackets, \
            f"event from {src} but no fetched segment: {e}"
        lo, hi = brackets[src]
        assert e["at_ms"] >= lo - 1e-6, (e, lo)
        if hi is not None:
            assert e["at_ms"] + e.get("dur_ms", 0.0) <= hi + 1e-6, \
                (e, hi)


def test_fleet_stitch_survives_seeded_replica_kill(small_model):
    """A seeded ``replica_kill`` fells the routed-to replica; the
    request fails over and completes — and /fleet/requests/<id>
    shows the WHOLE story: the dead attempt (record honestly
    unreachable), the failover event, the surviving replica's
    record, and causal ordering inside the brackets."""
    base, router, srv, reps = _spawn_fleet(
        small_model, n=3,
        router_kw=dict(
            probe_interval_s=30.0,      # probes stay optimistic:
            #                             the FAILOVER path, not the
            #                             rotation path, must carry
            #                             this request
            retry_ratio=0.5, retry_burst=8.0, max_attempts=3,
            fleet_faults={"seed": 3, "faults": [
                {"site": "replica_kill", "replica": 0, "after": 0,
                 "times": 1}]}))
    try:
        # warm the SURVIVORS' programs directly (r0 dies on the
        # first routed request)
        for rep in reps[1:]:
            _post(rep.url + "/generate",
                  {"prompt": [5, 6, 7], "max_new_tokens": 4},
                  path="")
        # bias least-outstanding toward r0 so the doomed replica is
        # deterministically the first pick
        for rep in reps[1:]:
            rep.outstanding = 4
        rid = "survives-kill-1"
        res = _post(base, {"prompt": [5, 6, 7],
                           "max_new_tokens": 4},
                    headers={"X-Request-Id": rid})
        for rep in reps[1:]:
            rep.outstanding = 0
        assert res["router"]["attempts"] >= 2
        winner = res["router"]["replica"]
        assert winner != "r0"
        doc = _get(base, f"/fleet/requests/{rid}")
        assert doc["status"] == "complete"
        # both replicas involved, in causal order
        assert doc["replicas"][0] == "r0"
        assert doc["replicas"][-1] == winner
        atts = doc["router"]["attempts"]
        assert atts[0]["replica"] == "r0"
        assert atts[0]["outcome"] == "retryable"
        assert atts[-1]["replica"] == winner
        assert atts[-1]["outcome"] == "ok"
        # the dead replica's segment is honestly unreachable; the
        # winner's record is present and complete
        seg_by_rep = {s["replica"]: s for s in doc["segments"]}
        assert seg_by_rep["r0"].get("fetch_error") == "unreachable"
        assert seg_by_rep[winner]["record"]["status"] == "complete"
        # route + failover + attempt events on the router timeline
        names = [e["event"] for e in doc["timeline"]
                 if e["source"] == "router"]
        assert names.count("route") >= 2
        assert "failover" in names
        # the acceptance pin: causal consistency
        _assert_causal(doc)
        ats = [e["at_ms"] for e in doc["timeline"]]
        assert ats == sorted(ats)
    finally:
        _teardown(router, srv, reps)


def test_fleet_stitch_hedge_race(small_model):
    """A slow-walked primary loses a hedge race: the stitched
    timeline carries hedge_fired/hedge_won, BOTH attempts with their
    brackets, the winner's replica record — and stays causally
    consistent."""
    base, router, srv, reps = _spawn_fleet(
        small_model, n=3,
        router_kw=dict(hedge="0.2", hedge_min_s=0.15,
                       retry_ratio=0.5, retry_burst=8.0))
    try:
        for rep in reps:
            _post(rep.url + "/generate",
                  {"prompt": [5, 6, 7], "max_new_tokens": 4},
                  path="")
        reps[0].chaos_slow(2.0)      # above the hedge watermark,
        #                              below every timeout
        for rep in reps[1:]:
            rep.outstanding = 4      # primary pick -> r0
        rid = "hedge-race-1"
        res = _post(base, {"prompt": [5, 6, 7],
                           "max_new_tokens": 4},
                    headers={"X-Request-Id": rid})
        reps[0].chaos_slow(0.0)
        for rep in reps[1:]:
            rep.outstanding = 0
        assert res["router"].get("hedged") is True
        winner = res["router"]["replica"]
        assert winner != "r0"
        doc = _get(base, f"/fleet/requests/{rid}")
        assert doc["status"] == "complete"
        assert doc["router"].get("hedged") is True
        atts = doc["router"]["attempts"]
        assert atts[0]["replica"] == "r0" \
            and not atts[0].get("hedge")
        hedge_atts = [a for a in atts if a.get("hedge")]
        assert len(hedge_atts) == 1
        assert hedge_atts[0]["replica"] == winner
        names = [e["event"] for e in doc["timeline"]
                 if e["source"] == "router"]
        assert "hedge_fired" in names and "hedge_won" in names
        # the winner's record stitched in, causally bracketed
        seg_by_rep = {s["replica"]: s for s in doc["segments"]}
        assert seg_by_rep[winner]["record"]["status"] == "complete"
        _assert_causal(doc)
        st = router.stats()
        assert st["hedges_fired_total"] >= 1
        assert st["hedges_won_total"] >= 1
    finally:
        reps[0].chaos_slow(0.0)
        _teardown(router, srv, reps)


# ---------------------------------------------------------------------------
# SLO burn rates end to end
# ---------------------------------------------------------------------------


def test_slo_burn_rates_move_correctly(small_model):
    """burn == 0 while the window holds no violations; burn > 0
    exactly when it does (availability via a forced no-replica shed,
    TTFT via an impossible 1ms target) — and the gauges render per
    objective on /metrics.  Also: the router injects timings for its
    own TTFT accounting but STRIPS the block when the client never
    asked."""
    base, router, srv, reps = _spawn_fleet(
        small_model, n=1,
        router_kw=dict(slo="availability=99.0,ttft_p99_ms=60000",
                       slo_window=64))
    try:
        for _ in range(3):
            res = _post(base, {"prompt": [5, 6, 7],
                               "max_new_tokens": 3})
            assert "timings" not in res     # injected, then stripped
        res = _post(base, {"prompt": [5, 6, 7], "max_new_tokens": 3,
                           "timings": True})
        assert "timings" in res             # client asked: kept
        st = router.stats()["slo"]
        assert st["window_observations"] == 4
        assert st["objectives"]["availability"]["burn_rate"] == 0.0
        assert st["objectives"]["ttft_p99_ms"]["burn_rate"] == 0.0
        # force 5xx: take the only replica out of rotation
        reps[0].draining = True
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base, {"prompt": [1, 2], "max_new_tokens": 2})
            assert ei.value.code == 503
            ei.value.read()
        finally:
            reps[0].draining = False
        st = router.stats()["slo"]
        assert st["objectives"]["availability"]["burn_rate"] > 0
        assert st["objectives"]["availability"][
            "violations_total"] == 1
        # 4xx spends no budget
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, {"prompt": "bogus"})
        ei.value.read()
        assert router.stats()["slo"]["window_observations"] == 5
        # the burn gauges render per objective
        text = _get_text(base, "/metrics")
        parse_prometheus_text(text)
        m = parse_prometheus_text(text)
        assert m['ptpu_router_slo_burn_rate'
                 '{objective="availability"}'] > 0
        assert m['ptpu_router_slo_burn_rate'
                 '{objective="ttft_p99_ms"}'] == 0.0
        assert m['ptpu_router_slo_target'
                 '{objective="ttft_p99_ms"}'] == 60000.0
        assert m['ptpu_router_slo_violations_total'
                 '{objective="availability"}'] == 1.0
    finally:
        _teardown(router, srv, reps)


def test_slo_latency_objective_burns_on_violation(small_model):
    """A percentile objective from the router's OWN accounting: with
    an impossible 1ms latency target every completed request
    violates (latency includes the full HTTP round trip, so it can
    never be sub-millisecond) -> burn is pinned at the window
    maximum (1/budget)."""
    base, router, srv, reps = _spawn_fleet(
        small_model, n=1,
        router_kw=dict(slo="latency_p99_ms=1", slo_window=64))
    try:
        for _ in range(4):
            _post(base, {"prompt": [5, 6, 7], "max_new_tokens": 3})
        st = router.stats()["slo"]
        obj = st["objectives"]["latency_p99_ms"]
        assert obj["violations_total"] == 4
        # every observation violates: burn == 1/0.01 == 100
        assert obj["burn_rate"] == pytest.approx(100.0)
    finally:
        _teardown(router, srv, reps)
