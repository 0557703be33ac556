"""The cell ``dsv2lite-serve-longdoc`` and what it brought: its files
resolve, its configuration keeps the catalog's numbers letter for
letter (``num_hidden_layers`` alone reduced), the hands add up to the
deck, the benchmark's copy of the reference is the program's, the new
readers read what the program reports (and nothing, without raising,
from a program that lacks it), and ``--rehearse`` walks the driver on
the CPU.  Nothing here speaks of the ORDER of the per-layer list: a
later PR appends to it.  ``pytest perfbench/tests`` (by hand; not
tier-1)."""

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

CELL = "dsv2lite-serve-longdoc"
CONFIG = "deepseek-v2-lite"
MIX = "longdoc-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("mla_serve_mfu_pct", "latent_expand_ratio",
       "latent_absorbed_share_pct", "grouped_matmul_share_pct",
       "grouped_matmul_roofline_pct")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_resolves_and_lists_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == load_json("workloads", CELL + ".json")
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, CONFIG, MIX)
    config = load_json("configs", CONFIG + ".json")
    mix = load_json("traffic", MIX + ".json")
    assert mix["driver"] == "traffic_ref_state"
    assert os.path.exists(os.path.join(
        BENCH_DIR, "drivers", mix["driver"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH_DIR, config["reference"]["file"]))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = {n for n, m in by_name.items()
             if CELL in m.get("workloads", [])}
    assert set(NEW) | {
        "request_p50_ms", "engine_dispatch_ms", "engine_host_share_pct",
        "slot_occupancy_pct", "window_compiles.serve",
        "decode_device_ms", "decode_program_ms",
        "prefill_busy_share_pct", "device_idle_pct.serve",
        "idle_attributed_pct.serve", "kv_pool_in_place_pct",
        "kv_plane_read_pct", "moe_busiest_expert_ratio",
        "weights_compute_dtype_pct", "dispatch_ahead_pct"} <= names
    # another model's FLOP count, another kind of leaf, a kernel
    # this model does not run, a share of experts it holds whole; and
    # the tails, withheld under a hundred replies a window (~80 here)
    assert not {"request_p90_ms", "server_queue_wait_p90_ms",
                "engine_ttft_p90_ms",
                "decode_mfu_pct", "serve_mfu_pct", "ssm_serve_mfu_pct",
                "kv_state_share_pct", "kv_window_share_pct",
                "selective_scan_share_pct", "moe_held_share_pct"} & names
    for name in names:
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py")), name
    for name in NEW:
        # the kernel's two are every configuration's that runs it
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_out_tok_s"
        assert by_name[name]["layer"] == (
            "kernels" if name.startswith("grouped_matmul")
            else "model step")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_out_tok_s")["workloads"]


def test_the_configuration_keeps_the_catalogs_numbers(bench):
    """Every key of the catalog row's ``config`` under the same key at
    the file's top level, ``null`` and the nested ``rope_scaling``
    included; ``num_hidden_layers`` alone differs, and is listed."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = load_json("configs", CONFIG + ".json")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == entry["source"])
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced_why"]) == {"num_hidden_layers"}
    for key, value in row["config"].items():
        assert key in config, key
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 7 \
        == config["held"]["num_hidden_layers"]
    assert config["q_lora_rank"] is None
    for key, value in config["published"].items():
        assert row["config"][key] == value, key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["n_routed_experts"], config["num_experts_per_tok"],
            config["n_shared_experts"], config["moe_intermediate_size"],
            config["intermediate_size"], config["vocab_size"]) == (
        2048, 16, 512, 128, 64, 128, 64, 6, 2, 1408, 10944, 102400)
    held = config["held"]
    assert (held["num_experts"], held["expert_offset"],
            held["vocab_size"]) == (64, 0, 102400)
    for text in ("deployment", "arithmetic", "drawn"):
        assert config[text] and "TO FILL" not in config[text]
    for key, why in config["assumed"].items():
        assert why and "TO FILL" not in why, key
    for key in ("logits_rel_err_request_median", "logits_rel_err"):
        assert config["correct"][key + "_max"] > 0
        assert "TO FILL" not in config["correct"][
            key + ("_why" if key.endswith("median") else "_max_why")]
    # every key traffic_ref reads of the reference block
    ref = config["reference"]
    for pre in ("", "rehearse_"):
        for key in ("cfg", "experts_held", "expert_offset"):
            assert pre + key in ref
    assert (ref["experts_held"], ref["expert_offset"]) == (64, 0)
    assert ref["cfg"]["rope_scaling"] == config["rope_scaling"]
    for key, value in ref["cfg"].items():
        if key != "rope_scaling":
            assert config[key] == value, key


def test_the_zoo_model_is_the_configuration():
    from polyaxon_tpu.models.deepseek_v2 import DeepseekV2Config

    config = load_json("configs", CONFIG + ".json")
    cfg = DeepseekV2Config.v2_lite_stage0()
    assert (cfg.num_layers, cfg.first_k_dense, cfg.hidden_size,
            cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.n_shared_experts,
            cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.vocab_size, False,    # the route never renormalises
            cfg.routed_scaling_factor, cfg.rope_theta,
            cfg.rms_norm_eps) == tuple(
        config[k] for k in (
            "num_hidden_layers", "first_k_dense_replace", "hidden_size",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "moe_intermediate_size", "intermediate_size", "vocab_size",
            "norm_topk_prob", "routed_scaling_factor", "rope_theta",
            "rms_norm_eps"))
    rope = config["rope_scaling"]
    assert (cfg.rope_factor, cfg.rope_original_max_position,
            cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.rope_mscale,
            cfg.rope_mscale_all_dim) == tuple(rope[k] for k in (
                "factor", "original_max_position_embeddings",
                "beta_fast", "beta_slow", "mscale", "mscale_all_dim"))
    assert (cfg.experts_held, cfg.expert_offset) == (64, 0)
    args = config["serve"]["args"]
    assert args[2:] == ["--prefill-chunk", "512"]
    assert args[0] == "--slots" and 16 <= int(args[1]) <= 24
    assert cfg.max_position >= 16384 + 256
    assert cfg.max_position % 512 == 0


def test_the_mix_is_the_issues_traffic():
    mix = load_json("traffic", MIX + ".json")
    assert mix["clients"] == 24 and mix["deck"] == 50
    assert mix["prompt_tokens"] == {"2048": 0.4, "8192": 0.4,
                                    "16384": 0.2}
    assert mix["max_new_tokens"] == {"32": 0.4, "128": 0.4, "256": 0.2}
    assert (mix["ramp_s"], mix["trace_s"], mix["sampled_share"],
            mix["temperature"]) == (20, 3, 0.5, 0.8)
    prompts, budgets, modes = loadgen.deck(mix)
    assert sum(prompts) / 50 == pytest.approx(7372.8)
    assert sum(budgets) / 50 == pytest.approx(115.2)
    assert sum(modes) == 25
    assert all(p % 512 == 0 for p in prompts)   # whole pieces
    assert mix["reference"] == dict(mix["reference"], requests=4,
                                    new_tokens=8)


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_hands_add_up_to_the_deck(rehearse):
    """Every hand of ten in a row holds the mix's weights exactly
    (4/4/2 prompts, 5/5 modes); the hands are the deck."""
    mix = load_json("traffic", MIX + ".json")
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
    if not rehearse:
        lengths = sorted(len(r["prompt"]) for r in reqs[:10])
        assert lengths == [2048] * 4 + [8192] * 4 + [16384] * 2
        assert sorted(r["max_new_tokens"] for r in reqs[:10]) \
            == [32] * 4 + [128] * 4 + [256] * 2


def test_a_compared_request_passes_the_prefill_chunk():
    driver = load_module("drivers", "traffic_ref_state")
    config = load_json("configs", CONFIG + ".json")
    assert "sliding_window" not in config
    assert driver.prefill_chunk(config["serve"]["args"]) == 512
    assert driver.prefill_chunk(config["serve"]["rehearse_args"]) == 8


def _functions(path):
    with open(path) as f:
        text = f.read()
    return {node.name: ast.get_source_segment(text, node)
            for node in ast.parse(text).body
            if isinstance(node, ast.FunctionDef)}


def test_the_benchmarks_reference_is_the_programs():
    """Function for function the same text; the copy adds the child."""
    ours = _functions(os.path.join(BENCH_DIR, "reference",
                                   "deepseek_v2_lite.py"))
    theirs = _functions(os.path.join(ROOT, "polyaxon_tpu", "reference",
                                     "deepseek_v2.py"))
    assert set(theirs) <= set(ours) and "main" in ours
    for name, text in theirs.items():
        assert ours[name] == text, name


def ctx_with(info_open, info_close, trace=None, reduced=None):
    ctx = types.SimpleNamespace()
    ctx.collected = {"info_open": info_open, "info_close": info_close,
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
    import mla_flops

    config = load_json("configs", CONFIG + ".json")
    # q 6.29 M + kv_a 1.18 + kv_b 2.10 (ONE expansion) + o 4.19
    assert mla_flops.attention_token_flops(config) == pytest.approx(
        2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048))
    assert mla_flops.token_flops(config) == pytest.approx(
        7 * mla_flops.attention_token_flops(config)
        + 2 * 3 * 2048 * (10944 + 6 * 2816))
    assert mla_flops.pair_flops(config) == 2 * 3 * 2048 * 1408
    # a causal pair a layer: 16 heads x (192 + 128) multiply-adds
    assert mla_flops.attention_pair_flops(config) == 2 * 16 * 320
    assert mla_flops.head_flops(config) == 2 * 2048 * 102400
    # one 16 384-token prompt: 7 layers of 134 M pairs
    pairs = 7 * 16384 * 16385 // 2
    total = mla_flops.serve_flops(
        config, tokens=16384, held_pairs=16384 * 6 * 6,
        attention_pairs=pairs, head_rows=32)
    # 19 TFLOP of matmuls + 9.6 of attention (the 8 TFLOP the pieces
    # spend re-expanding are not in it)
    attention = pairs * mla_flops.attention_pair_flops(config)
    assert 9e12 < attention < 10e12
    assert 18.5e12 < total - attention < 19.5e12


def test_the_new_readers_on_made_up_counters():
    import grouped_matmul_flops
    import mla_flops

    config = load_json("configs", CONFIG + ".json")
    # 60 pieces touch all 64 experts of 6 layers, 40 steps 50 of them
    touched = 6 * (60 * 64 + 40 * 50)
    a = {"prefill_tokens_total": 10240, "decode_steps_total": 100,
         "prefill_chunks_total": 20, "slots_active": 16,
         "moe_pairs_held_total": 500000,
         "moe_experts_touched_total": 7000,
         "latent_pairs_expanded_total": 10 ** 9,
         "latent_pairs_absorbed_total": 10 ** 7,
         "latent_rows_expanded_total": 7 * 90000}
    b = {"prefill_tokens_total": 10240 + 512 * 60,
         "decode_steps_total": 100 + 40,
         "prefill_chunks_total": 20 + 60, "slots_active": 14,
         "moe_pairs_held_total": 500000 + 36 * (512 * 60 + 40 * 16),
         "moe_experts_touched_total": 7000 + touched,
         "latent_pairs_expanded_total": 10 ** 9 + 7 * 3 * 10 ** 8,
         "latent_pairs_absorbed_total": 10 ** 7 + 7 * 4 * 10 ** 6,
         "latent_rows_expanded_total": 7 * (90000 + 60 * 4224)}
    routes = {"grouped_matmul_routes": {"pallas": 36, "xla": 0},
              "slots": 16}
    a, b = dict(a, **routes), dict(b, **routes)
    ctx = ctx_with(a, b, (a, b), {"busy_s": 2.9, "window_s": 3.0,
                                  "kernel_s": 1.2,
                                  "kernel_calls": 18 * 100})
    decoded = 40 * 15.0
    want = mla_flops.serve_flops(
        config, tokens=512 * 60 + decoded,
        held_pairs=36 * (512 * 60 + 40 * 16),
        attention_pairs=7 * (3 * 10 ** 8 + 4 * 10 ** 6),
        head_rows=decoded + 60)
    mfu = read("mla_serve_mfu_pct", ctx)
    assert mfu == pytest.approx(100 * want / 3.0 / 197e12)
    assert 0 < mfu < 100
    assert read("latent_expand_ratio", ctx) == pytest.approx(
        4224 / 512)
    assert read("latent_absorbed_share_pct", ctx) == pytest.approx(
        100 * 4 / 304)
    assert read("grouped_matmul_share_pct", ctx) == pytest.approx(
        100 * 1.2 / 2.9)
    # The kernel's own sizes and the programs' counts, no key of this
    # configuration: a touched expert's three matrices read once, a
    # held pair's rows in and out; the weights' bytes bind (0.6 s where
    # the FLOPs take 0.1)
    held = 36 * (512 * 60 + 40 * 16)
    least = 2 * (touched * 3 * 2048 * 1408
                 + held * 3 * (2048 + 1408)) / 819e9
    assert grouped_matmul_flops.least_bytes(
        h=2048, f=1408, held_pairs=held, touched=touched) / 819e9 \
        == pytest.approx(least)
    assert grouped_matmul_flops.flops(
        h=2048, f=1408, held_pairs=held) / 197e12 \
        == pytest.approx(held * 2 * 3 * 2048 * 1408 / 197e12)
    assert held * 2 * 3 * 2048 * 1408 / 197e12 < least
    roof = read("grouped_matmul_roofline_pct", ctx)
    assert roof == pytest.approx(100 * least / 1.2)
    assert 0 < roof < 100
    # the same reader under another configuration that runs the kernel
    trinity = ctx_with(a, b, (a, b), ctx.reduced)
    trinity.config = load_json("configs", "trinity-large-preview.json")
    assert read("grouped_matmul_roofline_pct", trinity) == pytest.approx(
        100 * 2 * (touched * 3 * 3072 * 3072 + held * 3 * 6144)
        / 819e9 / 1.2)
    # a program that does not count the experts touched: nothing
    old = {k: v for k, v in b.items()
           if k != "moe_experts_touched_total"}
    assert read("grouped_matmul_roofline_pct",
                ctx_with(a, old, (a, old), ctx.reduced)) is None
    # XLA's own ragged-dot is a tpu_custom_call too: not this kernel
    xla = dict(b, grouped_matmul_routes={"pallas": 0, "xla": 36})
    other = ctx_with(a, xla, (a, xla), ctx.reduced)
    assert read("grouped_matmul_share_pct", other) is None
    assert read("grouped_matmul_roofline_pct", other) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(name):
    """The parent commit's /info (another cell's: the parent cannot run
    this one): no value, no exception."""
    old = {"decode_steps_total": 5, "slots_active": 24,
           "prefill_tokens_total": 7, "prefill_chunks_total": 1,
           "moe_pairs_held_total": 9,
           "kv_pool_bytes_by_kind": {"window": 755, "full": 335}}
    later = dict(old, decode_steps_total=9, prefill_tokens_total=99)
    ctx = ctx_with(old, later, (old, later),
                   {"busy_s": 1.0, "window_s": 1.0, "kernel_s": 0.2,
                    "kernel_calls": 12})
    assert read(name, ctx) is None
    assert read(name, ctx_with({}, {}, None)) is None


@pytest.mark.parametrize("degrade", [None, "bf16_compute"])
def test_rehearsal_walks_the_driver(degrade):
    """``--rehearse --trace 1``: deepseek-v2-tiny served on the CPU,
    the window, the logits asked for again, the reference child, one
    last line with the counters' readers in it; under
    ``bf16_compute`` the child also says what rounding the reference
    as a bfloat16 program rounds costs against float32."""
    env = {k: v for k, v in os.environ.items()
           if k != "PERFBENCH_REFERENCE_DEGRADE"}
    if degrade:
        env["PERFBENCH_REFERENCE_DEGRADE"] = degrade
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 77),
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env)
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert {"latent_expand_ratio", "latent_absorbed_share_pct",
            "kv_plane_read_pct", "moe_busiest_expert_ratio",
            "dispatch_ahead_pct"} <= set(line["metrics"])
    # (read against the configuration's 7 layers; the tiny preset has 3)
    assert line["metrics"]["latent_expand_ratio"]["value"] > 0
    assert 0 < line["metrics"]["latent_absorbed_share_pct"]["value"] < 100
    assert "served again with logits" in run.stdout
    assert "reference: child exit 0" in run.stdout
    assert run.stdout.count("rel_err median") >= 4
    assert (run.stdout.count("bf16_compute against float32") > 0) \
        == bool(degrade)
