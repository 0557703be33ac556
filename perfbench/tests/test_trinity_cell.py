"""The cell ``trinity-serve-mixed`` and what it brought: its files
resolve, its configuration keeps the catalog's numbers, the benchmark's
copy of the reference is the program's, the readers read what the
program reports (and nothing, without raising, from a program that
lacks it), and ``--rehearse`` walks the ``traffic_ref`` driver on the
CPU.  ``pytest perfbench/tests`` (by hand; not tier-1)."""

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

CELL = "trinity-serve-mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    assert {"serve_mfu_pct", "decode_program_ms",
            "prefill_busy_share_pct", "moe_held_share_pct",
            "moe_busiest_expert_ratio", "kv_window_share_pct",
            "kv_pool_in_place_pct", "window_compiles.serve",
            "decode_device_ms"} <= names
    assert "decode_mfu_pct" not in names
    for name in names:
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name


def test_the_configuration_keeps_the_catalogs_numbers(bench):
    """Every number of the catalog row's ``config`` under the same key
    at the file's top level, but for the keys under ``reduced``; no
    width among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-large-preview")
    config = load_json("configs", "trinity-large-preview.json")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == entry["source"])
    assert config["reduced"] == entry["reduced"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] == config["held"][key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size"
    assert config["held"]["num_experts"] >= 8
    assert config["held"]["vocab_size"] * 8 >= \
        config["published"]["vocab_size"]


def test_the_mix_is_the_issues_traffic():
    mix = load_json("traffic", "mixed-len-closed.json")
    assert mix["clients"] == 48 and mix["deck"] == 50
    assert mix["prompt_tokens"] == {"512": 0.5, "2048": 0.3,
                                    "6144": 0.2}
    assert mix["max_new_tokens"] == {"32": 0.3, "128": 0.4, "256": 0.3}
    prompts, budgets, modes = loadgen.deck(mix)
    assert sum(prompts) / 50 == pytest.approx(2099.2)
    assert sum(modes) == 25
    # half of the cached positions lie past the window
    assert sum(p for p in prompts if p > 4096) / sum(prompts) > 0.5
    assert mix["reference"]["requests"] >= 4


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_deck_is_dealt_in_hands(rehearse):
    """Every hand of ten in a row holds the mix's weights exactly, five
    hands are the deck, and two seeds deal them differently."""
    mix = load_json("traffic", "mixed-len-closed.json")
    if rehearse:
        mix.update(mix["rehearse"])
    driver = load_module("drivers", "traffic_ref")
    one = driver.dealt(mix)
    assert one["deck"] == mix["hand"] == 10
    whole = [sorted(c) for c in loadgen.deck(mix)]
    deals = []
    for seed in (2 ** 31 + 5, 7):
        stream = loadgen.requests(one, seed, 100)
        reqs = [next(stream) for _ in range(mix["deck"])]
        for i in range(0, len(reqs), 10):
            hand = reqs[i:i + 10]
            assert sorted(len(r["prompt"]) for r in hand) == whole[0][
                ::mix["deck"] // 10]
            assert sum("seed" in r for r in hand) == 5
        assert sorted(r["max_new_tokens"] for r in reqs) == whole[1]
        deals.append([(len(r["prompt"]), r["max_new_tokens"])
                      for r in reqs])
    assert deals[0] != deals[1]
    with pytest.raises(ValueError):
        driver.dealt(dict(mix, hand=7))


def _functions(path):
    with open(path) as f:
        text = f.read()
    return {node.name: ast.get_source_segment(text, node)
            for node in ast.parse(text).body
            if isinstance(node, ast.FunctionDef)}


def test_the_benchmarks_reference_is_the_programs():
    """Function for function the same text; the copy adds the child."""
    ours = _functions(os.path.join(BENCH_DIR, "reference",
                                   "trinity_large_preview.py"))
    theirs = _functions(os.path.join(ROOT, "polyaxon_tpu", "reference",
                                     "afmoe.py"))
    assert set(theirs) <= set(ours)
    for name, text in theirs.items():
        assert ours[name] == text, name


def ctx_with(info_open, info_close, trace=None, reduced=None):
    ctx = types.SimpleNamespace()
    ctx.collected = {"info_open": info_open, "info_close": info_close,
                     "trace_open": (trace or (None, None))[0],
                     "trace_close": (trace or (None, None))[1]}
    ctx.config = load_json("configs", "trinity-large-preview.json")
    ctx.reduced = reduced or {}
    ctx.device = {"kind": "TPU v5 lite"}
    ctx.rehearse = False
    ctx.out = os.path.join(HERE, "no-such-run")
    return ctx


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_counter_readers():
    a = {"moe_pairs_held_total": 100, "moe_pairs_routed_total": 1000,
         "moe_expert_pairs": [10] * 10}
    b = {"moe_pairs_held_total": 225, "moe_pairs_routed_total": 2000,
         "moe_expert_pairs": [10 + 5] * 9 + [10 + 80],
         "kv_pool_bytes_by_kind": {"window": 755, "full": 335}}
    ctx = ctx_with(a, b)
    assert read("moe_held_share_pct", ctx) == pytest.approx(12.5)
    assert read("moe_busiest_expert_ratio", ctx) == pytest.approx(6.4)
    assert read("kv_window_share_pct", ctx) == pytest.approx(
        100 * 755 / 1090)


@pytest.mark.parametrize("name", [
    "moe_held_share_pct", "moe_busiest_expert_ratio",
    "kv_window_share_pct", "serve_mfu_pct", "decode_program_ms",
    "prefill_busy_share_pct"])
def test_a_program_without_the_counters_reads_as_nothing(name):
    """The parent commit's /info and trace: no value, no exception."""
    old = {"decode_steps_total": 5, "slots_active": 24}
    ctx = ctx_with(old, dict(old, decode_steps_total=9), (old, old),
                   {"busy_s": 1.0, "window_s": 1.0})
    assert read(name, ctx) is None


def test_serve_mfu_counts_what_the_docstring_says():
    import moe_flops

    config = load_json("configs", "trinity-large-preview.json")
    # attention 62.9 M, dense FFN 113.2 M, shared 28.3 M a layer
    assert moe_flops.token_flops(config) == pytest.approx(
        2 * (5 * 62.914560e6 + 113.246208e6 + 4 * 28.311552e6))
    assert moe_flops.pair_flops(config) == pytest.approx(2 * 28.311552e6)
    a = {"prefill_tokens_total": 0, "decode_steps_total": 0,
         "moe_pairs_held_total": 0, "prefill_chunks_total": 0,
         "slots_active": 32}
    b = {"prefill_tokens_total": 512 * 60, "decode_steps_total": 60,
         "moe_pairs_held_total": 32640, "prefill_chunks_total": 60,
         "slots_active": 32}
    ctx = ctx_with({}, {}, (a, b), {"busy_s": 2.5, "window_s": 3.0})
    tokens = 512 * 60 + 60 * 32
    want = (tokens * moe_flops.token_flops(config)
            + 32640 * moe_flops.pair_flops(config)
            + (60 * 32 + 60) * moe_flops.head_flops(config))
    got = read("serve_mfu_pct", ctx)
    assert got == pytest.approx(100 * want / 3.0 / 197e12)
    assert 0 < got < 100


def test_rehearsal_walks_the_reference_driver():
    """``--rehearse``: afmoe-tiny served on the CPU, the window, the
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
