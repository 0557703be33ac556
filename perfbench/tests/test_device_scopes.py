"""``device_scopes`` on a recorded v5e trace and on synthetic planes (CPU
only, run by hand with the harness's other tests: ``pytest
perfbench/tests``).

``data/scopes_serve.xplane.pb`` was cut from the traced run of
``gpt2m-serve-closed`` of PR 37 (seed 3000003701, TPU v5 lite): the
first device plane's ``XLA Modules`` and ``XLA Ops`` lines for ONE
insertion, ONE prefill program and ONE decode dispatch (its shortest:
one step), each event with its own stats, every metadata record they
point at with its ``tf_op``, ``hlo_category``, ``program_id``, ``flops``
and ``bytes_accessed`` (the record's name, the HLO text, cut to 120
characters) and the stat metadata those name; an empty host plane
before it and an empty second device plane behind it.  The prefill
program's executable came from a compilation cache that the parent
commit had filled: its stacks hold no scope (PERF.md section 6, PR 37).
"""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import device_scopes as ds  # noqa: E402
import trace_reduce  # noqa: E402
from run import load_module  # noqa: E402

FIXTURE = os.path.join(HERE, "data", "scopes_serve.xplane.pb")
OPS, MODULES = "XLA Ops", "XLA Modules"


@pytest.fixture(scope="module")
def plane():
    return ds.read_plane(FIXTURE)


@pytest.fixture(scope="module")
def reduced(plane):
    return ds.reduce_plane(plane)


# ---------------------------------------------------------------------------
# the wire reader
# ---------------------------------------------------------------------------


def test_reader_finds_the_first_device_plane_its_lines_and_tf_op(plane):
    name, lines, metadata = plane
    assert name == "/device:TPU:0"
    assert set(lines) == {OPS, MODULES}
    assert len(lines[MODULES]) == 3 and len(lines[OPS]) == 2190
    programs = sorted(metadata[m][0].split("(")[0]
                      for _, _, m in lines[MODULES])
    assert programs == ["jit__insert", "jit_program", "jit_ptpu_prefill"]
    stacks = {metadata[m][1] for _, _, m in lines[OPS]}
    assert "" in stacks                     # compiler-made: no tf_op
    assert any(s.startswith("jit(program)/while/body/vmap(GPT2Model)/")
               and "/ptpu_attend/cond/branch_2_fun/" in s
               and s.endswith("dot_general:") for s in stacks)
    assert any("vmap(ptpu_sample)" in s for s in stacks)
    assert all(end > start > 0 for start, end, _ in lines[OPS])


def test_reader_agrees_with_profile_data_on_the_busy_time(reduced):
    """The events' offsets and durations as this file decodes them are
    the ones ``jax.profiler.ProfileData`` hands ``trace_reduce``."""
    theirs = trace_reduce.reduce(trace_reduce.load(FIXTURE))
    # (ProfileData rounds to the nanosecond)
    assert theirs["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-5)
    assert sorted(name.split("(")[0] for name in theirs["modules"]) \
        == ["jit__insert", "jit_program", "jit_ptpu_prefill"]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes or str as a
    length-delimited field, a float as a fixed 64-bit one."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        import struct

        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, message: bytes) -> bytes:
    return _field(1, key) + _field(2, message)


def test_reader_takes_a_referenced_tf_op_and_passes_other_fields(tmp_path):
    """A stat's string may be a reference to a stat metadata's name
    (``ref_value``); fixed-width fields and fields the reader does not
    know are passed over; a negative metadata id is an int64."""
    stat_meta = [(1, "tf_op"), (2, "flops"),
                 (3, "jit(step)/ptpu_optimizer/add:")]
    by_ref = _field(1, -5 % (1 << 64)) + _field(2, "%add.1 = f32[] add()") \
        + _field(5, _field(1, 2) + _field(2, 1.5)) \
        + _field(5, _field(1, 1) + _field(7, 3))
    by_str = _field(1, 8) + _field(2, "%custom-call.2") \
        + _field(5, _field(1, 1) + _field(5, "ragged-dot-none:"))
    module = _field(1, 9) + _field(2, "jit_step(77)")
    event = lambda meta, at, took: _field(4, (  # noqa: E731
        _field(1, meta) + _field(2, at) + _field(3, took)
        + _field(4, _field(1, 2) + _field(3, 7))))
    device = _field(1, 2) + _field(2, "/device:TPU:0") \
        + _field(3, _field(1, 2) + _field(2, MODULES) + _field(3, 5)
                 + event(9, 0, 1000)) \
        + _field(3, _field(1, 3) + _field(2, OPS) + _field(3, 5)
                 + _field(9, 123) + event(-5 % (1 << 64), 100, 300)
                 + event(8, 500, 200)) \
        + _field(3, _field(2, "Steps") + event(9, 0, 1000)) \
        + b"".join(_field(4, _entry(k % (1 << 64), v)) for k, v in
                   ((-5, by_ref), (8, by_str), (9, module))) \
        + b"".join(_field(5, _entry(k, _field(1, k) + _field(2, n)))
                   for k, n in stat_meta)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, _field(2, "/host:CPU")) + _field(1, device)
                     + _field(4, "a-hostname"))
    name, lines, metadata = ds.read_plane(str(path))
    assert name == "/device:TPU:0" and set(lines) == {OPS, MODULES}
    assert lines[OPS] == [(5100, 5400, -5), (5500, 5700, 8)]
    assert metadata[-5] == ("%add.1 = f32[] add()",
                            "jit(step)/ptpu_optimizer/add:")
    assert metadata[8][1] == "ragged-dot-none:"
    out = ds.reduce_plane((name, lines, metadata))
    step = out["programs"]["jit_step"]
    assert step["parts"] == pytest.approx({"optimizer": 300e-12,
                                           "unnamed": 200e-12})
    assert step["module_s"] == pytest.approx(1000e-12)
    empty = tmp_path / "none.xplane.pb"
    empty.write_bytes(_field(1, _field(2, "/host:CPU")))
    assert ds.read_plane(str(empty)) is None
    assert ds.reduce_file(str(empty)) is None


# ---------------------------------------------------------------------------
# the part rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stack, part, way", [
    # the LAST scope of the table names the part
    ("jit(ptpu_extend)/DeepseekV2Model/h_6/attn/ptpu_attend/cond/"
     "branch_3_fun/ptpu_latent_expand/btr,rhd->bthd/dot_general:",
     "expand", ""),
    ("jit(ptpu_extend)/DeepseekV2Model/h_6/attn/ptpu_attend/cond/"
     "branch_3_fun/ptpu_attend/bshd,btd->bhst/dot_general:", "attend", ""),
    ("jit(program)/while/body/vmap(ptpu_sample)/while/body/closed_call/"
     "reduce_sum:", "sample", ""),
    ("jit(program)/while/body/vmap(AfmoeModel)/h_2/moe/ptpu_route/"
     "jit(top_k)/top_k:", "experts", ""),
    ("jit(program)/while/body/vmap(AfmoeModel)/h_2/moe/ptpu_experts/"
     "jit(gmm)/pallas_call:", "experts", ""),
    ("jit(program)/while/body/vmap(JambaModel)/h_3/mamba/"
     "ptpu_state_step/mul:", "state", ""),
    ("jit(ptpu_extend)/JambaModel/h_3/mamba/ptpu_scan/pallas_call:",
     "scan", ""),
    ("jit(_insert)/ptpu_kv_write/dynamic_update_slice:", "kv_write", ""),
    # a program's name is no scope
    ("jit(ptpu_prefill)/GPT2Model/GPT2Model.head/wte.attend/"
     "dot_general:", "dense", ""),
    # a training step: the part, and the pass whatever the part
    ("jit(step)/transpose(jvp(GPT2Model))/GPT2Model.run_blocks/h/block/"
     "ptpu_attend/pallas_call:", "attend", "backward"),
    ("jit(step)/jvp(GPT2Model)/GPT2Model.run_blocks/h/block/"
     "ptpu_attend/pallas_call:", "attend", "forward"),
    ("jit(step)/transpose(jvp(GPT2Model))/h/block/fc2/dot_general:",
     "backward", "backward"),
    ("jit(step)/jvp(GPT2Model)/h/block/fc2/dot_general:",
     "forward", "forward"),
    ("jit(step)/ptpu_optimizer/add:", "optimizer", ""),
    # a module's path and nothing else: dense
    ("jit(program)/while/body/vmap(GPT2Model)/GPT2Model.run_blocks/"
     "h.run/while/body/closed_call/h.carried/h/block/fc2/dot_general:",
     "dense", ""),
    # what JAX writes by itself names nothing
    ("jit(program)/while/body/vmap()/while/body/closed_call/reduce_sum:",
     "unnamed", ""),
    ("jit(program)/while/body/vmap()/vmap(jit(_gumbel))/add:",
     "unnamed", ""),
    ("jit(step)/jit(_where)/select_n:", "unnamed", ""),
    ("one['h']['block']['cached_key']:", "unnamed", ""),
    ("ragged-dot-none:", "unnamed", ""),    # a custom call, stack lost
    ("", "unnamed", ""),                    # compiler-made: no tf_op
])
def test_part_rule(stack, part, way):
    assert ds.part_of(stack) == (part, way)


def test_every_scope_of_the_program_has_a_part():
    from polyaxon_tpu import spans

    assert set(ds.SCOPE_PARTS) == set(spans.SCOPE_NAMES)
    assert not set(ds.SCOPE_PARTS.values()) & {
        "dense", "unnamed", "forward", "backward"}


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def test_own_times_partition_the_line(plane, reduced):
    ops = plane[1][OPS]
    union = trace_reduce.span(trace_reduce.merge(
        (a, b) for a, b, _ in ops)) / 1e12
    assert reduced["busy_s"] == pytest.approx(union, rel=1e-9)
    assert sum(rec["ops_s"] for rec in reduced["programs"].values()) \
        == pytest.approx(union, rel=1e-9)
    for rec in reduced["programs"].values():
        assert sum(rec["parts"].values()) \
            == pytest.approx(rec["ops_s"], rel=1e-9)
        assert rec["ops_s"] <= rec["module_s"] * (1 + 1e-9)
        # a program's operations account for its time on the XLA
        # Modules line to a part in a thousand
        assert rec["ops_s"] >= 0.998 * rec["module_s"]
        for part, leaves in rec["leaves"].items():
            assert sum(leaves.values()) \
                == pytest.approx(rec["parts"][part], rel=1e-9)


def test_the_decode_step_by_part(reduced):
    rec = reduced["programs"]["jit_program"]
    assert rec["executions"] == 1
    ms = {part: 1e3 * s for part, s in rec["parts"].items()}
    assert set(ms) == {"attend", "dense", "sample", "kv_write", "unnamed"}
    assert ms == pytest.approx({"attend": 3.920, "dense": 1.242,
                                "unnamed": 0.153, "sample": 0.139,
                                "kv_write": 0.080}, abs=6e-4)
    assert rec["passes"] == {}
    # the width the conditional took stays readable under the scope
    heaviest = max(rec["leaves"]["attend"], key=rec["leaves"]["attend"].get)
    assert heaviest == ("ptpu_attend/cond/branch_2_fun/ptpu_attend/"
                        "bhqk,bkhd->bqhd/dot_general:")
    # the head's projection is a module's: dense, not the sampler's
    assert any("wte.attend" in leaf for leaf in rec["leaves"]["dense"])
    assert "(no tf_op)" in rec["leaves"]["unnamed"]


def test_the_insertion_and_a_program_from_a_stale_cache(reduced):
    insert = reduced["programs"]["jit__insert"]
    assert set(insert["parts"]) == {"kv_write", "unnamed"}
    # the compiler's re-laying of the argument is named after the
    # argument: no scope reaches it
    assert set(insert["leaves"]["unnamed"]) == {
        "one['h']['block']['cached_key']:",
        "one['h']['block']['cached_value']:"}
    # this prefill executable was compiled by a tree without scopes
    assert set(reduced["programs"]["jit_ptpu_prefill"]["parts"]) == {
        "dense", "unnamed"}
    assert reduced["scoped_s"] == pytest.approx(
        sum(rec["parts"].get(p, 0.0)
            for rec in reduced["programs"].values()
            for p in ("attend", "sample", "kv_write")), rel=1e-9)
    assert reduced["unnamed_s"] == pytest.approx(
        sum(rec["parts"].get("unnamed", 0.0)
            for rec in reduced["programs"].values()), rel=1e-9)


def test_an_events_program_is_the_interval_that_holds_it(plane, reduced):
    """Every stack of the fixture names the program whose interval on
    ``XLA Modules`` holds it; a stack that names another (an inlined
    ``jit``'s may lose its prefix) stays with the interval and is
    counted, and an operation outside every interval has no
    program."""
    assert reduced["mismatch_s"] == 0.0
    assert "" not in reduced["programs"]
    metadata = {1: ("jit_program(1)", ""),
                2: ("%a", "jit(program)/while/body/ptpu_kv_write/scatter:"),
                3: ("%b", "jit(searchsorted)/ptpu_attend/reduce_sum:"),
                4: ("%c", "jit(<lambda>)/reduce:"),
                5: ("jit__lambda(2)", "")}
    lines = {MODULES: [(0, 100, 1), (200, 260, 5)],
             OPS: [(10, 30, 2), (40, 90, 3), (210, 250, 4),
                   (120, 130, 2), (50, 60, 2)]}
    out = ds.reduce_plane(("/device:TPU:0", lines, metadata))
    decode = out["programs"]["jit_program"]
    assert decode["parts"] == pytest.approx(
        {"kv_write": 30e-12, "attend": 40e-12})     # own: 50 - 10 nested
    assert out["mismatch_s"] == pytest.approx(40e-12)
    assert out["programs"]["jit__lambda"]["parts"] == pytest.approx(
        {"unnamed": 40e-12})                # <lambda> reads as _lambda_
    assert out["programs"][""]["parts"] == pytest.approx(
        {"kv_write": 10e-12})
    assert decode["module_s"] == pytest.approx(100e-12)


# ---------------------------------------------------------------------------
# the per-layer readers
# ---------------------------------------------------------------------------


def _ctx(reduced, steps=1, **collected):
    return types.SimpleNamespace(
        rehearse=False, out="/nonexistent",
        reduced={"modules": {"jit_step": 10}},
        collected={"_device_scopes": reduced,
                   "trace_open": {"decode_steps_total": 100},
                   "trace_close": {"decode_steps_total": 100 + steps},
                   **collected})


def _read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_serve_readers_over_the_fixture(reduced):
    ctx = _ctx(reduced)
    assert _read("decode_attend_ms", ctx) == pytest.approx(3.920, abs=6e-4)
    assert _read("decode_dense_ms", ctx) == pytest.approx(1.242, abs=6e-4)
    assert _read("decode_sample_ms", ctx) == pytest.approx(0.139, abs=6e-4)
    assert _read("decode_kv_write_ms", ctx) == pytest.approx(0.080,
                                                             abs=6e-4)
    # a part the programs do not have reads 0, per step or per piece
    assert _read("decode_experts_ms", ctx) == 0.0
    assert _read("decode_state_ms", ctx) == 0.0
    assert _read("prefill_expand_ms", ctx) == 0.0
    assert _read("decode_attend_ms", _ctx(reduced, steps=2)) \
        == pytest.approx(3.920 / 2, abs=6e-4)
    assert _read("device_named_pct.serve", ctx) == pytest.approx(
        100 - 10.95, abs=0.01)
    # no steps between the two /info reads, or no reads: nothing
    assert _read("decode_attend_ms", _ctx(reduced, steps=0)) is None
    assert _read("decode_attend_ms", _ctx(reduced, trace_open=None)) is None
    # no training step in a serving trace
    assert _read("step_optimizer_ms", ctx) is None


def test_readers_return_nothing_for_a_tree_without_scopes(plane):
    """The parent of the PR that brought the scopes: the same trace
    with every scope taken out of its stacks.  Each new reader returns
    None and none raises."""
    name, lines, metadata = plane
    bare = {k: (n, "/".join(s for s in stack.split("/")
                            if "ptpu_" not in s or s.startswith("jit(")))
            for k, (n, stack) in metadata.items()}
    reduced = ds.reduce_plane((name, lines, bare))
    assert reduced["scoped_s"] == 0.0
    ctx = _ctx(reduced)
    for metric in ("decode_attend_ms", "decode_dense_ms",
                   "decode_sample_ms", "decode_kv_write_ms",
                   "decode_experts_ms", "decode_state_ms",
                   "prefill_attend_ms", "prefill_expand_ms",
                   "step_forward_ms", "step_backward_ms",
                   "step_optimizer_ms", "device_named_pct.serve",
                   "device_named_pct.train"):
        assert _read(metric, ctx) is None, metric
    # and with no trace at all
    assert ds.split(_ctx(None), ds.DECODE) is None
    assert _read("device_named_pct.serve", _ctx(None)) is None


def test_train_readers_split_a_step_by_pass_and_optimizer():
    metadata = {
        1: ("jit_step(5)", ""),
        2: ("%f", "jit(step)/jvp(GPT2Model)/h/block/fc2/dot_general:"),
        3: ("%k", "jit(step)/jvp(GPT2Model)/h/block/ptpu_attend/"
                  "pallas_call:"),
        4: ("%b", "jit(step)/transpose(jvp(GPT2Model))/h/block/fc2/"
                  "dot_general:"),
        5: ("%o", "jit(step)/ptpu_optimizer/add:"),
        6: ("%c", ""),
    }
    ms = 10 ** 9                                    # ps
    lines = {MODULES: [(0, 100 * ms, 1)],
             OPS: [(0, 20 * ms, 2), (20 * ms, 30 * ms, 3),
                   (30 * ms, 80 * ms, 4), (80 * ms, 95 * ms, 5),
                   (95 * ms, 100 * ms, 6)]}
    reduced = ds.reduce_plane(("/device:TPU:0", lines, metadata))
    ctx = _ctx(reduced)                             # 10 traced steps
    assert _read("step_forward_ms", ctx) == pytest.approx(3.0)
    assert _read("step_backward_ms", ctx) == pytest.approx(5.0)
    assert _read("step_optimizer_ms", ctx) == pytest.approx(1.5)
    assert _read("device_named_pct.train", ctx) == pytest.approx(95.0)
    step = reduced["programs"]["jit_step"]
    assert step["parts"] == pytest.approx(
        {"forward": 0.020, "attend": 0.010, "backward": 0.050,
         "optimizer": 0.015, "unnamed": 0.005})
    # forward + backward + optimizer + the rest is the step
    assert sum(step["passes"].values()) + step["parts"]["optimizer"] \
        + step["parts"]["unnamed"] == pytest.approx(step["module_s"])


def test_one_parse_a_run_and_the_table(reduced, monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(ds, "reduce_file",
                        lambda f: calls.append(f) or dict(
                            reduced, file_bytes=1, seconds=0.0))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: FIXTURE)
    ctx = types.SimpleNamespace(rehearse=False, out=HERE, collected={
        "trace_open": {"decode_steps_total": 0},
        "trace_close": {"decode_steps_total": 1}})
    for metric in ("decode_attend_ms", "decode_dense_ms",
                   "device_named_pct.serve"):
        assert _read(metric, ctx) is not None
    assert calls == [FIXTURE]
    said = capsys.readouterr()
    text = said.out + said.err
    assert "device scopes:" in text and "jit_program: x1" in text
    rehearsal = types.SimpleNamespace(rehearse=True, out=HERE,
                                      collected={})
    assert ds.of(rehearsal) is None and calls == [FIXTURE]
