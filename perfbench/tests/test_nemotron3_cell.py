"""The cell ``nemotron3-serve-reason`` and what it brought: its files
resolve, the benchmark's copy of the reference is the program's, the
driver raises the replay's cap to the mix's shortest reply, the new
readers read what the program reports (and nothing, without raising,
from a program that lacks it), and no roofline share can pass 100 on
the counts a run of the cell gives.  ``pytest perfbench/tests`` (by
hand; not tier-1)."""

import ast
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

import device_scopes  # noqa: E402
import loadgen  # noqa: E402
from run import load_json, load_module  # noqa: E402

CELL = "nemotron3-serve-reason"
CONFIG = "nemotron-3-nano-30b-a3b"
NEW = ("ssd_scan_roofline_pct", "state_step_roofline_pct",
       "ungated_experts_roofline_pct", "ssd_serve_mfu_pct")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_resolves_and_lists_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == load_json("workloads", CELL + ".json")
    assert cell["chips"] == 1
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(
        BENCH_DIR, "drivers", mix["driver"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH_DIR, config["reference"]["file"]))
    names = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert set(NEW) <= names
    # counts of other architectures
    assert not {"grouped_matmul_roofline_pct", "serve_mfu_pct",
                "decode_mfu_pct", "ssm_serve_mfu_pct",
                "selective_scan_roofline_pct",
                "selective_scan_share_pct"} & names
    for name in names:
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_out_tok_s")["workloads"]


def test_the_mix_holds_its_buckets():
    mix = load_json("traffic", "reason-closed.json")
    assert mix["clients"] == 288 and mix["deck"] == 50
    assert mix["prompt_tokens"] == {"128": 0.4, "512": 0.4, "1536": 0.2}
    assert mix["max_new_tokens"] == {"512": 0.3, "1024": 0.4,
                                     "2048": 0.3}
    prompts, budgets, modes = loadgen.deck(mix)
    assert sum(prompts) / 50 == pytest.approx(563.2)
    assert sum(budgets) / 50 == pytest.approx(1177.6)
    assert sum(modes) == 25
    config = load_json("configs", CONFIG + ".json")
    assert config["serve"]["args"] == [
        "--slots", "192", "--prefill-chunk", "512", "--queue-depth", "288"]


def test_the_driver_replays_the_shortest_budget():
    driver = load_module("drivers", "traffic_ref_state_long")
    driver.state.run = lambda ctx: {}
    for rehearse, want in ((False, 512), (True, 128)):
        ctx = types.SimpleNamespace(
            mix=load_json("traffic", "reason-closed.json"),
            rehearse=rehearse)
        driver.state.traffic_ref.traffic.REPLAY_MAX_NEW_TOKENS = 128
        driver.run(ctx)
        assert driver.state.traffic_ref.traffic.REPLAY_MAX_NEW_TOKENS \
            == want


@pytest.mark.parametrize("medians, rows, ok", [
    ([0.004, 0.0591, 0.0040, 0.0080], 512, True),   # the program's flips
    ([0.0197, 0.0193, 0.0190, 0.0191], 512, False),  # W8A8
    ([0.004, 0.0040, 0.0041, 0.0039], 9, False),     # a short long reply
])
def test_the_driver_holds_the_error_floor(medians, rows, ok, monkeypatch):
    """The smallest request median against ``logits_rel_err_floor_max``:
    a flip in some requests passes, a lifted floor does not; the first
    compared request is asked for the mix's ``long_new_tokens`` and
    must bring them all back."""
    import asyncio

    import run as harness

    driver = load_module("drivers", "traffic_ref_state_long")
    asked = []

    async def served(port, picked, new):
        asked.extend((p, new) for p in picked)
        return [({}, 200, {"new_tokens": [[0] * (rows if new > 8
                                                  else new)]})
                for _ in picked]

    def fake_run(ctx):
        asyncio.run(driver.state.traffic_ref.served_logits(
            0, ["long", "a", "b", "c"], 8))
        return {"ok": True, "collected": {
            "reference": {"request_medians": medians, "max": 0.1}}}

    monkeypatch.setattr(driver, "_served_logits", served)
    monkeypatch.setattr(driver.state, "run", fake_run)
    ctx = types.SimpleNamespace(
        mix=load_json("traffic", "reason-closed.json"), rehearse=False,
        config=load_json("configs", CONFIG + ".json"),
        checks=harness.Checks())
    driver.run(ctx)
    assert asked == [("long", 512), ("a", 8), ("b", 8), ("c", 8)]
    assert (not ctx.checks.failures) is ok


def _functions(path):
    with open(path) as f:
        text = f.read()
    return {node.name: ast.get_source_segment(text, node)
            for node in ast.parse(text).body
            if isinstance(node, ast.FunctionDef)}


def test_the_benchmarks_reference_is_the_programs():
    """Function for function the same text; the copy adds the child."""
    ours = _functions(os.path.join(BENCH_DIR, "reference",
                                   "nemotron_3_nano.py"))
    theirs = _functions(os.path.join(ROOT, "polyaxon_tpu", "reference",
                                     "nemotron_h.py"))
    assert set(theirs) <= set(ours) and "main" in ours
    for name, text in theirs.items():
        assert ours[name] == text, name


def ctx_with(trace, reduced, parts, monkeypatch):
    ctx = types.SimpleNamespace()
    ctx.collected = {"info_open": {}, "info_close": {},
                     "trace_open": trace[0], "trace_close": trace[1]}
    ctx.config = load_json("configs", CONFIG + ".json")
    ctx.reduced = reduced
    ctx.device = {"kind": "TPU v5 lite"}
    ctx.rehearse = False
    monkeypatch.setattr(device_scopes, "of", lambda ctx: parts)
    return ctx


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_the_new_readers_on_made_up_counters_and_a_made_up_trace(
        monkeypatch):
    """Three seconds of a run at the predicted pace: 250 decode steps
    of 192 lanes, 30 pieces of 512, the state part at 7.5 ms a step, the
    scan at 1 ms a piece and layer: every share lies in (0, 100)."""
    import ssd_flops

    a = {"prefill_tokens_total": 0, "decode_steps_total": 0,
         "prefill_chunks_total": 0, "slots_active": 192,
         "ssm_scan_tokens_total": 0, "ssm_state_steps_total": 0,
         "moe_pairs_held_total": 0, "moe_experts_touched_total": 0,
         "grouped_matmul_routes": {"pallas": 10}}
    b = dict(a, prefill_tokens_total=512 * 30, decode_steps_total=250,
             prefill_chunks_total=30, ssm_scan_tokens_total=6 * 512 * 30,
             ssm_state_steps_total=192 * 250,
             moe_pairs_held_total=5 * 0.75 * (192 * 250 + 512 * 30),
             moe_experts_touched_total=5 * 16 * 280)
    parts = {"programs": {
        "jit_program": {"executions": 30, "parts": {"state": 1.875}},
        "jit_ptpu_extend": {"executions": 30, "parts": {"scan": 0.18}}},
        "scoped_s": 2.0}
    ctx = ctx_with((a, b), {"busy_s": 2.9, "window_s": 3.0,
                            "kernel_s": 0.6, "kernel_calls": 2800},
                   parts, monkeypatch)
    for name in NEW:
        value = read(name, ctx)
        assert 0 < value < 100, (name, value)
    least = ssd_flops.state_step_bytes(ctx.config,
                                       lane_steps=6 * 192 * 250) / 819e9
    assert read("state_step_roofline_pct", ctx) == pytest.approx(
        100 * least / 1.875)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(name,
                                                         monkeypatch):
    """The parent commit's /info and trace: no value, no exception."""
    old = {"decode_steps_total": 5, "slots_active": 24,
           "prefill_tokens_total": 7, "prefill_chunks_total": 1}
    ctx = ctx_with((old, dict(old, decode_steps_total=9)),
                   {"busy_s": 1.0, "window_s": 1.0, "kernel_s": 0.2,
                    "kernel_calls": 12}, None, monkeypatch)
    assert read(name, ctx) is None


@pytest.fixture(scope="module")
def child():
    """The reference child's module, and the rehearsal model's tree as
    ``ptpu serve`` makes it."""
    from polyaxon_tpu.models.registry import get_model

    ref = load_module("reference", "nemotron_3_nano")
    config = load_json("configs", CONFIG + ".json")
    _, variables = get_model(config["rehearse_model"]).init_params(
        batch_size=1)
    return ref, config["reference"], variables["params"]


def _with(params, path, fn):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda p, x: fn(x) if "/".join(str(k.key) for k in p) == path
        else x, params)


@pytest.mark.parametrize("path, fn, ok", [
    (None, None, True),
    ("h_0/mixer/A_log", lambda x: x + 3e-6, True),   # a TPU's own log
    ("h_0/mixer/A_log", lambda x: x + 0.1, False),
    ("h_0/mixer/D", lambda x: 2 * x, False),
    ("h_2/mixer/norm_scale", lambda x: 0 * x, False),
    ("h_0/mixer/dt_bias", lambda x: x + 5.0, False),
    ("h_4/mixer/experts_up", lambda x: x[:, :, :-1], False),
    ("h_4/mixer/router_bias", lambda x: x.astype("bfloat16"), False),
])
def test_the_child_holds_the_tree_to_the_published_widths(child, path,
                                                           fn, ok):
    """``check_tree`` reads the tree the server makes as the published
    widths and the Mamba-2 convention say, and names each break."""
    ref, spec, params = child
    if path is not None:
        params = _with(params, path, fn)
    bad = ref.check_tree(params, spec["rehearse_cfg"],
                         spec["rehearse_experts_held"], 256)
    if ok:
        assert bad == []
    else:
        assert len(bad) == 1 and path in bad[0], bad


def test_w8a8_reads_further_than_int8_weights(child):
    """On a bfloat16 tree the W8A8 control moves the logits further
    from the float32 forward than int8 weights alone; on a float32
    tree (the router) neither moves anything."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref, spec, params = child
    cfg, held = spec["rehearse_cfg"], spec["rehearse_experts_held"]
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                        if x.ndim >= 2 else x, params)
    ids = np.arange(3, 40, dtype=np.int32) % 256

    def logits(tree, dense):
        monkey = pytest.MonkeyPatch()
        monkey.setattr(ref, "dense", dense)
        jax.clear_caches()      # relu2_mlp's trace holds the last dense
        try:
            return np.asarray(ref.forward(
                tree, ids, cfg, experts_held=held,
                expert_offset=spec["rehearse_expert_offset"]))
        finally:
            monkey.undo()

    base = logits(bf16, ref.dense)
    err = {}
    for how, dense in (("int8_weights", ref.dense),
                       ("int8_weights_activations", ref._dense_int8)):
        got = logits(ref._degrade(jax.tree.map(jnp.copy, bf16), how),
                     dense)
        err[how] = float(np.median(np.linalg.norm(got - base, axis=-1)
                                   / np.linalg.norm(base, axis=-1)))
    assert 0 < err["int8_weights"] < err["int8_weights_activations"], err
    np.testing.assert_array_equal(
        logits(params, ref._dense_int8), logits(params, ref.dense))
