"""The cell ``jamba2-serve-chat`` and what it brought: its files
resolve, its configuration keeps the catalog's numbers letter for
letter, the hands hold the mix's weights, the benchmark's copy of the
reference is the program's, the new readers read what the program
reports (and nothing, without raising, from a program that lacks it),
and ``--rehearse`` walks the driver on the CPU.  ``pytest
perfbench/tests`` (by hand; not tier-1)."""

import ast
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

import loadgen  # noqa: E402
from run import load_json, load_module  # noqa: E402

CELL = "jamba2-serve-chat"
CONFIG = "ai21-jamba2-3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssm_serve_mfu_pct", "selective_scan_roofline_pct",
       "selective_scan_share_pct", "kv_state_share_pct")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_resolves_and_lists_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == load_json("workloads", CELL + ".json")
    assert cell["chips"] == 1 and cell == bench["workloads"][-1]
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(
        BENCH_DIR, "drivers", mix["driver"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH_DIR, config["reference"]["file"]))
    names = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert set(NEW) | {
        "request_p50_ms", "request_p90_ms", "server_queue_wait_p90_ms",
        "engine_dispatch_ms", "engine_host_share_pct",
        "slot_occupancy_pct", "window_compiles.serve",
        "decode_device_ms", "decode_program_ms",
        "prefill_busy_share_pct", "device_idle_pct.serve",
        "idle_attributed_pct.serve", "kv_pool_in_place_pct",
        "kv_plane_read_pct", "weights_compute_dtype_pct"} <= names
    # a dense transformer's count; the expert counters
    assert not {"decode_mfu_pct", "serve_mfu_pct"} & names
    for name in names:
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    # the four new metrics are the list's last and this cell's alone
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_out_tok_s"
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_out_tok_s")["workloads"]


def test_the_configuration_keeps_the_catalogs_numbers(bench):
    """Every key of the catalog row's ``config`` under the same key at
    the file's top level, ``null`` included; nothing reduced."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = load_json("configs", CONFIG + ".json")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == entry["source"])
    assert entry["reduced"] == config["reduced"] == []
    for key, value in row["config"].items():
        assert key in config and config[key] == value, key
    assert config["sliding_window"] is None
    for key, value in config["published"].items():
        assert row["config"][key] == value, key
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_dt_rank"], config["mamba_expand"],
            config["intermediate_size"], config["vocab_size"]) == (
        28, 2560, 20, 1, 16, 4, 160, 2, 8192, 65536)
    layers = range(config["num_hidden_layers"])
    assert [i for i in layers if i % config["attn_layer_period"]
            == config["attn_layer_offset"]] == [7, 21] \
        == config["held"]["attention_layers"]
    assert config["held"]["vocab_size"] == config["vocab_size"]
    # every key traffic_ref reads of the reference block
    for pre in ("", "rehearse_"):
        for key in ("cfg", "experts_held", "expert_offset"):
            assert pre + key in config["reference"]


def test_the_zoo_model_is_the_configuration():
    from polyaxon_tpu.models.jamba import JambaConfig

    config = load_json("configs", CONFIG + ".json")
    cfg = JambaConfig.jamba2_3b()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank, cfg.mamba_expand, cfg.intermediate_size,
            cfg.vocab_size, cfg.attn_layer_period,
            cfg.attn_layer_offset, cfg.rms_norm_eps) == tuple(
        config[k] for k in (
            "num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "mamba_d_state", "mamba_d_conv",
            "mamba_dt_rank", "mamba_expand", "intermediate_size",
            "vocab_size", "attn_layer_period", "attn_layer_offset",
            "rms_norm_eps"))
    assert cfg.head_dim * cfg.num_heads == cfg.hidden_size
    assert config["serve"]["args"] == [
        "--slots", "128", "--prefill-chunk", "128", "--queue-depth", "192"]
    assert cfg.max_position >= 448 + 512


def test_the_mix_is_the_issues_traffic():
    mix = load_json("traffic", "chat-closed.json")
    assert mix["clients"] == 192 and mix["deck"] == 50
    assert mix["prompt_tokens"] == {"32": 0.3, "96": 0.3, "192": 0.2,
                                    "448": 0.2}
    assert mix["max_new_tokens"] == {"64": 0.3, "256": 0.4, "512": 0.3}
    assert (mix["ramp_s"], mix["trace_s"],
            mix["warmup_max_new_tokens"]) == (15, 3, 16)
    prompts, budgets, modes = loadgen.deck(mix)
    assert sum(prompts) / 50 == pytest.approx(166.4)
    assert sum(budgets) / 50 == pytest.approx(275.2)
    assert sum(modes) == 25
    # the state is carried across pieces in 40 % of the requests
    assert sum(p > 128 for p in prompts) == 20
    assert mix["reference"] == dict(mix["reference"], requests=4,
                                    new_tokens=8)


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_deck_is_dealt_in_hands(rehearse):
    """Every hand of ten in a row holds the mix's weights exactly
    (3/3/2/2 prompts, 5/5 modes), five hands are the deck."""
    mix = load_json("traffic", "chat-closed.json")
    if rehearse:
        mix.update(mix["rehearse"])
    driver = load_module("drivers", "traffic_ref")
    one = driver.dealt(mix)
    assert one["deck"] == mix["hand"] == 10
    whole = [sorted(c) for c in loadgen.deck(mix)]
    stream = loadgen.requests(one, 2 ** 31 + 5, 100)
    reqs = [next(stream) for _ in range(mix["deck"])]
    for i in range(0, len(reqs), 10):
        hand = reqs[i:i + 10]
        assert sorted(len(r["prompt"]) for r in hand) == whole[0][
            ::mix["deck"] // 10]
        assert sum("seed" in r for r in hand) == 5
    assert sorted(r["max_new_tokens"] for r in reqs) == whole[1]


def test_the_driver_hands_the_chunk_in_the_windows_place():
    driver = load_module("drivers", "traffic_ref_state")
    config = load_json("configs", CONFIG + ".json")
    assert driver.prefill_chunk(config["serve"]["args"]) == 128
    assert driver.prefill_chunk(config["serve"]["rehearse_args"]) == 16
    seen = {}
    driver.traffic_ref = types.SimpleNamespace(
        run=lambda ctx: seen.update(ctx.config) or {"ok": True})
    ctx = types.SimpleNamespace(config=config, rehearse=False)
    assert driver.run(ctx) == {"ok": True}
    assert seen["sliding_window"] == 128
    assert config["sliding_window"] is None       # the file's: untouched


def _functions(path):
    with open(path) as f:
        text = f.read()
    return {node.name: ast.get_source_segment(text, node)
            for node in ast.parse(text).body
            if isinstance(node, ast.FunctionDef)}


def test_the_benchmarks_reference_is_the_programs():
    """Function for function the same text; the copy adds the child."""
    ours = _functions(os.path.join(BENCH_DIR, "reference",
                                   "ai21_jamba2_3b.py"))
    theirs = _functions(os.path.join(ROOT, "polyaxon_tpu", "reference",
                                     "jamba.py"))
    assert set(theirs) <= set(ours) and "main" in ours
    for name, text in theirs.items():
        assert ours[name] == text, name


def ctx_with(info_close, trace=None, reduced=None):
    ctx = types.SimpleNamespace()
    ctx.collected = {"info_open": {}, "info_close": info_close,
                     "trace_open": (trace or (None, None))[0],
                     "trace_close": (trace or (None, None))[1]}
    ctx.config = load_json("configs", CONFIG + ".json")
    ctx.reduced = reduced or {"busy_s": 1.0, "window_s": 1.0,
                              "kernel_s": 0.0, "kernel_calls": 0}
    ctx.device = {"kind": "TPU v5 lite"}
    ctx.rehearse = False
    return ctx


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_flops_count_what_the_docstring_says():
    import ssm_flops

    config = load_json("configs", CONFIG + ".json")
    # in 26.21 M + x 0.98 + dt 0.82 + out 13.11, 4 taps, 3 x 16 a channel
    assert ssm_flops.mamba_token_flops(config) == pytest.approx(
        2 * (26214400 + 983040 + 819200 + 13107200 + 20480 + 245760))
    assert ssm_flops.attention_token_flops(config) == pytest.approx(
        2 * (2 * 2560 * 2560 + 2 * 2560 * 128))
    assert ssm_flops.token_flops(config) == pytest.approx(
        26 * ssm_flops.mamba_token_flops(config)
        + 2 * ssm_flops.attention_token_flops(config)
        + 28 * 2 * 3 * 2560 * 8192)
    # about twice the parameters outside the table: 2.86 B
    assert 5.6e9 < ssm_flops.token_flops(config) < 5.9e9
    assert ssm_flops.head_flops(config) == 2 * 2560 * 65536
    # one call over 128 positions of one layer: 10.6 MB, 12.9 us
    one = ssm_flops.scan_kernel_bytes(config, layer_tokens=128, calls=1)
    assert one == 4 * (128 * (4 * 5120 + 32) + 3 * 16 * 5120 + 5120)
    assert ssm_flops.scan_kernel_flops(config, layer_tokens=128) \
        / 197e12 < one / 819e9


def test_the_new_readers_on_made_up_counters_and_a_made_up_trace():
    import ssm_flops

    config = load_json("configs", CONFIG + ".json")
    a = {"prefill_tokens_total": 1000, "decode_steps_total": 100,
         "prefill_chunks_total": 10, "slots_active": 128,
         "ssm_scan_tokens_total": 26000}
    b = {"prefill_tokens_total": 1000 + 128 * 30,
         "decode_steps_total": 100 + 200,
         "prefill_chunks_total": 10 + 30, "slots_active": 126,
         "ssm_scan_tokens_total": 26000 + 26 * 128 * 30}
    reduced = {"busy_s": 2.5, "window_s": 3.0, "kernel_s": 0.05,
               "kernel_calls": 26 * 30}
    ctx = ctx_with({"kv_pool_bytes_by_kind": {
        "window": 0, "full": 134, "state": 1193}}, (a, b), reduced)
    decoded = 200 * 127.0
    want = ssm_flops.serve_flops(config, tokens=128 * 30 + decoded,
                                 head_rows=decoded + 30)
    mfu = read("ssm_serve_mfu_pct", ctx)
    assert mfu == pytest.approx(100 * want / 3.0 / 197e12)
    assert 0 < mfu < 100
    least = ssm_flops.scan_kernel_bytes(
        config, layer_tokens=26 * 128 * 30, calls=26 * 30) / 819e9
    roof = read("selective_scan_roofline_pct", ctx)
    assert roof == pytest.approx(100 * least / 0.05)
    assert 0 < roof < 100
    assert read("selective_scan_share_pct", ctx) == pytest.approx(2.0)
    assert read("kv_state_share_pct", ctx) == pytest.approx(
        100 * 1193 / 1327)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(name):
    """The parent commit's /info and trace (another cell's: the parent
    cannot run this one): no value, no exception — also where its trace
    holds custom calls that are not this kernel's."""
    old = {"decode_steps_total": 5, "slots_active": 24,
           "prefill_tokens_total": 7, "prefill_chunks_total": 1,
           "kv_pool_bytes_by_kind": {"window": 755, "full": 335}}
    ctx = ctx_with(old, (old, dict(old, decode_steps_total=9)),
                   {"busy_s": 1.0, "window_s": 1.0, "kernel_s": 0.2,
                    "kernel_calls": 12})
    assert read(name, ctx) is None
    assert read(name, ctx_with({}, None)) is None


def test_rehearsal_walks_the_driver():
    """``--rehearse``: jamba-tiny served on the CPU, the window, the
    logits asked for again, the reference child, one last line."""
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 77),
         "--seconds", "3", "--rehearse"],
        capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert {"serve_out_tok_s", "setup_s"} <= set(line["metrics"])
    assert "served again with logits" in run.stdout
    assert "reference: child exit 0" in run.stdout
    assert run.stdout.count("rel_err median") >= 4
