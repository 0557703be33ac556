"""The readers PR 28 added, each on a recorded context (CPU only, run by
hand with the harness's other tests: ``pytest perfbench/tests``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

from run import load_json, load_module  # noqa: E402


class RecordedServe:
    """What a traced `gpt2m-serve-closed` run hands its readers: the
    configuration's file, the device, the reduced trace and the `/info`
    reads (numbers of the ledger's PR 27 line, and of a run with the pool
    updated in place)."""

    def __init__(self, busy_s, steps, counters=None, active=(24, 24)):
        self.config = load_json("configs", "gpt2-medium.json")
        self.device = {"kind": "TPU v5 lite"}
        self.reduced = {"busy_s": busy_s}
        info = [{"decode_steps_total": 1000, "slots_active": active[0]},
                {"decode_steps_total": 1000 + steps,
                 "slots_active": active[1]}]
        window = [dict(counters[0]), dict(counters[1])] if counters \
            else [{}, {}]
        self.collected = {"trace_open": info[0], "trace_close": info[1],
                          "info_open": window[0], "info_close": window[1]}


@pytest.mark.parametrize("busy_s, steps, active, want", [
    # 24 slots x 706.9 MFLOP a token over 68.6 ms and 197 TFLOP/s
    (2.8126, 41, (24, 24), 0.12549),
    # the same work in 13.4 ms a step
    (0.9179, 68.5, (24, 24), 0.6427),
    # half the slots occupied at one end of the trace: 18 on average
    (0.9179, 68.5, (12, 24), 0.4820),
])
def test_decode_mfu_pct_on_a_recorded_context(busy_s, steps, active,
                                              want):
    reader = load_module("layer_metrics", "decode_mfu_pct")
    assert reader.token_flops(load_json(
        "configs", "gpt2-medium.json")["published"]) \
        == 2.0 * (24 * (4 * 1024 ** 2 + 2 * 1024 * 4096)
                  + 1024 * 50257)
    got = reader.read(RecordedServe(busy_s, steps, active=active))
    assert got == pytest.approx(want, rel=2e-3)
    assert 0 < got < 100


def test_decode_mfu_pct_reads_nothing_without_steps_or_slots():
    reader = load_module("layer_metrics", "decode_mfu_pct")
    assert reader.read(RecordedServe(1.0, 0)) is None
    ctx = RecordedServe(1.0, 10)
    del ctx.collected["trace_close"]["slots_active"]
    assert reader.read(ctx) is None


@pytest.mark.parametrize("counters, want", [
    # every dispatch and insertion of the window consumed its pool
    (({"kv_pool_dispatches_total": 310, "kv_pool_in_place_total": 310},
      {"kv_pool_dispatches_total": 3310, "kv_pool_in_place_total": 3310}),
     100.0),
    # a paged pool: dispatches counted, none in place
    (({"kv_pool_dispatches_total": 10, "kv_pool_in_place_total": 0},
      {"kv_pool_dispatches_total": 50, "kv_pool_in_place_total": 0}),
     0.0),
    # the parent has no such counter; an idle window has no dispatch
    (None, None),
    (({"kv_pool_dispatches_total": 7, "kv_pool_in_place_total": 7},
      {"kv_pool_dispatches_total": 7, "kv_pool_in_place_total": 7}), None),
])
def test_kv_pool_in_place_pct_on_a_recorded_context(counters, want):
    reader = load_module("layer_metrics", "kv_pool_in_place_pct")
    assert reader.read(RecordedServe(1.0, 10, counters)) == want
