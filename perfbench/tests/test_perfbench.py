"""The harness's own tests: ``pytest perfbench/tests`` (CPU only, run by
hand; not part of the repo's tier-1 suite)."""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

import loadgen  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
from run import load_json, load_module  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the traffic generator ---------------------------------------------------


def take(mix, seed, n, vocab=1000):
    stream = loadgen.requests(mix, seed, vocab)
    return [next(stream) for _ in range(n)]


def test_same_seed_same_requests_other_seed_others():
    mix = load_json("traffic", "closed-batch.json")
    assert take(mix, 7, 450) == take(mix, 7, 450)
    assert take(mix, 7, 450) != take(mix, 8, 450)


def test_every_seed_deals_the_same_sizes_in_another_order():
    mix = load_json("traffic", "closed-batch.json")
    n = mix["deck"]

    def column(reqs, key):
        return {"prompt_tokens": [len(r["prompt"]) for r in reqs],
                "max_new_tokens": [r["max_new_tokens"] for r in reqs],
                "sampled": ["seed" in r for r in reqs]}[key]

    a, b = take(mix, 1, n), take(mix, 2 ** 31 + 12345, n)
    for key in ("prompt_tokens", "max_new_tokens", "sampled"):
        assert sorted(column(a, key)) == sorted(column(b, key))
        assert column(a, key) != column(b, key)
    # the second deal holds the same sizes again
    assert sorted(column(take(mix, 1, 2 * n)[n:], "max_new_tokens")) \
        == sorted(column(a, "max_new_tokens"))
    # the deck follows the mix's weights to within one request
    for key in ("prompt_tokens", "max_new_tokens"):
        for value, weight in mix[key].items():
            assert abs(column(a, key).count(int(value)) - weight * n) < 1
    assert abs(sum(column(a, "sampled")) - mix["sampled_share"] * n) < 1
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 768 for r in a)


def test_the_mix_names_its_source_and_keeps_its_means():
    """The deck's mean lengths stay within 10 % of the published means
    the mix file cites (vLLM paper, Alpaca workload)."""
    mix = load_json("traffic", "closed-batch.json")
    assert "arXiv:2309.06180" in mix["source"]
    prompts, budgets, _ = loadgen.deck(mix)
    assert sum(prompts) / len(prompts) == pytest.approx(19.31, rel=0.1)
    assert sum(budgets) / len(budgets) == pytest.approx(58.45, rel=0.1)


@pytest.mark.parametrize("sent, done, want", [
    (10.0, 20.0, 64.0),         # wholly inside
    (-10.0, 10.0, 32.0),        # straddles the open: half its wait inside
    (90.0, 130.0, 16.0),        # straddles the close: a quarter inside
    (-50.0, 150.0, 32.0),       # spans the whole window
    (-20.0, -1.0, 0.0),         # before it
    (100.0, 120.0, 0.0),        # sent at the close
])
def test_a_reply_counts_for_the_part_of_its_wait_inside_the_window(
        sent, done, want):
    rec = loadgen.Record(request={"max_new_tokens": 64}, sent=sent,
                         done=done)
    assert loadgen.tokens_in_window(rec, 0.0, 100.0) == pytest.approx(want)


def test_warm_up_set_covers_every_shape():
    mix = load_json("traffic", "closed-batch.json")
    shapes = loadgen.shapes(mix)
    for sampled in (False, True):
        mine = [s for s in shapes if s["sampled"] == sampled]
        assert {s["prompt_tokens"] for s in mine} \
            == {int(k) for k in mix["prompt_tokens"]}
        assert {s["max_new_tokens"] for s in mine} \
            == {mix["warmup_max_new_tokens"]}


# -- percentiles -------------------------------------------------------------


def test_percentiles():
    values = list(range(1, 1001))
    assert stats.median(values) == 500.5
    assert stats.tail(values, 95.0) == pytest.approx(950.05)
    assert stats.median([]) is None
    assert stats.percentile([3.0], 95.0) == 3.0


@pytest.mark.parametrize("n, held", [(199, True), (200, False)])
def test_the_95th_is_withheld_under_ten_samples_beyond_it(n, held):
    assert (stats.tail(list(range(n)), 95.0) is None) == held


# -- the job driver's window -------------------------------------------------


def synthetic_events(step_s=0.1, blocks=60, stall=3.0, log_every=10):
    """Block events as train.py logs them: the first block holds a
    stall, the rest are steady."""
    t, out = 1000.0, []
    for b in range(1, blocks + 1):
        t += log_every * step_s + (stall if b == 1 else 0.0)
        out.append((b * log_every, t, 11.0 - 0.001 * b))
    return out


def test_window_arithmetic():
    job = load_module("drivers", "job")
    events = synthetic_events()
    # not decided until an event stamped after the window's end is read
    assert job.window_rate(events[:1], 2, 10.0, 4096, 1) is None
    assert job.window_rate(events[:12], 2, 10.0, 4096, 1) is None
    w = job.window_rate(events, 2, 10.0, 4096, 1)
    assert w["t0"] == events[1][1]
    assert w["blocks"][0][0] == 20 and w["blocks"][-1][0] == 120
    assert w["rate"] == pytest.approx(4096 / 0.1)
    assert w["span_s"] == pytest.approx(10.0)
    # per chip; and the first block's stall is outside the window
    assert job.window_rate(events, 2, 10.0, 4096, 4)["rate"] \
        == pytest.approx(4096 / 0.1 / 4)
    # a window that is no whole number of blocks stops at the last
    # boundary inside it
    w = job.window_rate(events, 2, 10.45, 4096, 1)
    assert w["blocks"][-1][0] == 120 and w["span_s"] == pytest.approx(10.0)


# -- the trace reduction -----------------------------------------------------


def ev(name, a, b):
    return trace_reduce.Event(name, float(a), float(b), None)


def test_interval_arithmetic():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [(0, 3), (5, 8)]
    assert trace_reduce.span([(0, 3), (5, 8)]) == 6


def test_self_times_nest_like_a_call_stack():
    ops = [ev("while", 0, 100), ev("fusion", 10, 30), ev("flash", 40, 90),
           ev("copy", 120, 130)]
    own = {e.name: t for e, t in trace_reduce.self_times(ops)}
    assert own == {"while": 30, "fusion": 20, "flash": 50, "copy": 10}


def test_reduce_on_synthetic_device_planes():
    def plane(shift):
        return {"XLA Ops": [
            ev("while.1", 0 + shift, 1000 + shift),
            ev("fusion.1", 0 + shift, 300 + shift),
            ev("all-reduce.1", 300 + shift, 700 + shift),
            ev("flash_fwd", 700 + shift, 1000 + shift),
            ev("fusion.2", 1500 + shift, 2000 + shift)],
            "XLA Modules": [ev("jit_step", shift, 1000 + shift),
                            ev("jit_step", 1500 + shift, 2000 + shift)]}

    planes = {"/device:TPU:0": plane(0), "/device:TPU:1": plane(50),
              "/host:CPU": {"python": [ev("x", 0, 5000)]}}
    r = trace_reduce.reduce(planes, kernel=re.compile("flash"))
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx(1500e-9)
    assert r["kernel_s"] == pytest.approx(300e-9)
    assert r["kernel_calls"] == 1
    assert r["collective_exposed_s"] == pytest.approx(400e-9)
    assert r["modules"] == {"jit_step": 2}
    assert r["top_ops"][0] == ("fusion.2", pytest.approx(500e-9))
    assert trace_reduce.reduce({"/host:CPU": planes["/host:CPU"]}) is None


def test_short_names():
    text = ('%block.26 = (bf16[4,16]{1,0:T(8,128)(2,1)}, bf16[4]{0}) '
            'custom-call(bf16[4,16]{1,0:T(8,128)(2,1)S(1)} %x), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert trace_reduce.short_name(text) == "%block.26 tpu_custom_call"
    assert trace_reduce.PALLAS_KERNEL.search(trace_reduce.short_name(text))
    assert trace_reduce.short_name(
        "%fusion.248 = (bf16[4096]{0:T(1024)(128)(2,1)}, bf16[4]{0}) "
        "fusion(bf16[24]{0} %a, s32[]{:T(128)S(6)} %b), kind=kLoop") \
        == "%fusion.248 fusion"
    assert trace_reduce.short_name(
        "%all-reduce-done.3 = f32[8]{0} all-reduce-done(f32[8]{0} %s)") \
        == "%all-reduce-done.3 all-reduce-done"
    assert trace_reduce.short_name("jit_step(123)") == "jit_step(123)"


def test_reduce_on_the_recorded_trace():
    """A small trace recorded by jax.profiler (CPU: two jitted matmul
    steps), read through ProfileData as a chip's trace is."""
    path = os.path.join(HERE, "data", "small.xplane.pb")
    planes = trace_reduce.load(path)
    assert trace_reduce.reduce(planes) is None      # no TPU plane: no result
    r = trace_reduce.reduce(planes, rehearse=True)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any(name.startswith("dot") for name, _ in r["top_ops"])
    assert "PLANE '/host:CPU'" in trace_reduce.summary(path)


# -- BENCHMARK.json ----------------------------------------------------------


def test_every_cell_resolves_to_files(bench):
    for cell in bench["workloads"]:
        manifest = load_json("workloads", cell["name"] + ".json")
        assert manifest == cell
        config = load_json("configs", cell["config"] + ".json")
        mix = load_json("traffic", cell["traffic"] + ".json")
        assert os.path.isfile(os.path.join(BENCH_DIR, "drivers",
                                           mix["driver"] + ".py"))
        if mix["driver"] == "job":
            assert os.path.isfile(os.path.join(
                BENCH_DIR, "configs", config["job"]["polyaxonfile"]))
        else:
            assert config["serve"]["args"]
    for config in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
        body = load_json("configs", config["name"] + ".json")
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
        assert any(c["config"] == config["name"] for c in bench["workloads"])
    for metric in bench["per_layer"]:
        assert hasattr(load_module("layer_metrics", metric["name"]), "read")


def test_names_units_and_lengths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for entry in bench["configs"] + bench["workloads"] \
            + bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200
                assert "\n" not in entry[key] and "\t" not in entry[key]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for metric in bench["end_to_end"]:
        assert set(metric) - {"workloads"} \
            == {"name", "unit", "better", "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) - {"workloads"} \
            == {"name", "unit", "better", "source", "layer", "moves"}
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_reports_what_the_contract_asks(bench):
    cells = [c["name"] for c in bench["workloads"]]

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    end = {m["name"]: m for m in bench["end_to_end"]}
    assert cells_of(end["setup_s"]) == set(cells)
    for cell in cells:
        assert any(cell in cells_of(m) for m in bench["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(cell in cells_of(m) for m in bench["per_layer"])
    for metric in bench["per_layer"]:
        moved = end[metric["moves"]]
        assert cells_of(metric) <= cells_of(moved), metric["name"]
        assert cells_of(metric) <= set(cells)
