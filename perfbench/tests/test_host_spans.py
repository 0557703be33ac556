"""``host_spans`` on synthetic planes and records (CPU only, run by hand
with the harness's other tests: ``pytest perfbench/tests``)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import host_spans  # noqa: E402
from run import load_module  # noqa: E402
from trace_reduce import Event  # noqa: E402

MS = 1e6        # the planes' unit is the nanosecond


def ev(name, a, b):
    return Event(name, a * MS, b * MS, None)


def planes(device_shift=0.0, program_a=(100, 200), late_insert=False):
    """One device, two decode dispatches with an admission between
    them.  Device busy 100-200, 300-400, 450-500: idle 200-300 and
    400-450.  With ``late_insert`` the admission's insertion runs on
    the device only at 292.5-293.5, inside the second dispatch's
    section and before its enqueue."""
    d = device_shift
    engine = [
        ev("ptpu/decode", 90, 210), ev("ptpu_step", 95, 205),
        ev("ptpu/upload", 95, 97), ev("ptpu/enqueue", 97, 99),
        ev("ptpu/sync", 99, 205), ev("ptpu/commit", 205, 210),
        ev("ptpu/prefill", 215, 265), ev("ptpu/admit", 220, 260),
        ev("ptpu/decode", 290, 520), ev("ptpu_step", 292, 510),
        ev("ptpu/upload", 292, 294), ev("ptpu/enqueue", 294, 296),
        ev("ptpu/sync", 296, 510), ev("ptpu/commit", 510, 520),
        ev("PjitFunction(program)", 97, 99),
    ]
    insert = [ev("jit__insert(2)", 292.5 + d, 293.5 + d)] \
        if late_insert else []
    return {
        "/device:TPU:0": {
            "XLA Ops": [ev("%fusion.1 fusion", 100 + d, 200 + d),
                        ev("%fusion.1 fusion", 300 + d, 400 + d),
                        ev("%copy.2 copy", 450 + d, 500 + d)] + insert,
            "XLA Modules": [
                ev("jit_program(1)", program_a[0] + d, program_a[1] + d),
                ev("jit_program(1)", 300 + d, 500 + d)] + insert,
        },
        "/host:CPU": {
            "engine": engine,
            # a thread with fewer spans is not the one attributed to
            "handler": [ev("ptpu/board", 0, 1000)],
        },
    }


def test_each_gap_goes_to_the_innermost_span_and_the_rest_is_unattributed():
    rep = host_spans.attribute(planes())
    assert rep["thread"] == "engine" and rep["devices"] == 1
    assert rep["window_s"] == pytest.approx(0.400)
    assert rep["idle_s"] == pytest.approx(0.150)
    want = {"ptpu/sync": 59, "ptpu/admit": 40, "unattributed": 30,
            "ptpu/prefill": 10, "ptpu/commit": 5, "ptpu/decode": 2,
            "ptpu/upload": 2, "ptpu/enqueue": 2}
    assert {k: round(1e3 * v, 6) for k, v in rep["by_owner"].items()} \
        == want
    assert list(rep["by_owner"])[:3] == ["ptpu/sync", "ptpu/admit",
                                         "unattributed"]
    # a gap's owner is the one that holds most of it
    assert rep["top_gaps"] == [["ptpu/admit", pytest.approx(0.100)],
                               ["ptpu/sync", pytest.approx(0.050)]]
    assert rep["clock"]["violations"] == 0
    assert rep["clock"]["shift_ns"] == 0.0
    text = "\n".join(host_spans.lines(rep))
    assert 'idle_gaps: [["ptpu/admit", 0.1' in text
    assert "shift applied 0.000 ms, violations left 0" in text


def test_one_shift_cures_a_device_clock_that_runs_ahead():
    rep = host_spans.attribute(planes(device_shift=8.0))
    clock = rep["clock"]
    assert clock["sections"] == 2
    assert clock["violations_unshifted"] == 1   # program 1 ends at 208
    # program ends 3 ms after its sync at the latest, begins 11 ms after
    # its enqueue at the earliest: the shift is the middle, 7 ms
    assert clock["shift_ns"] == pytest.approx(7.0 * MS)
    assert clock["violations"] == 0
    # the gaps are placed on the host's clock after the shift: the
    # device is left 1 ms late, so the first gap is 201-301
    assert rep["by_owner"]["ptpu/admit"] == pytest.approx(0.040)
    assert rep["by_owner"]["ptpu/sync"] == pytest.approx(0.004 + 0.005
                                                         + 0.050)


def test_a_program_an_admission_enqueued_is_not_the_sections_own():
    # it begins before the section's enqueue began, which is no fault
    # of the clock: the device came late to what _admit had enqueued
    rep = host_spans.attribute(planes(late_insert=True))
    clock = rep["clock"]
    assert clock["sections"] == 2
    assert clock["violations_unshifted"] == 0 and clock["shift_ns"] == 0.0
    assert rep["idle_s"] == pytest.approx(0.149)
    assert rep["by_owner"]["ptpu/upload"] == pytest.approx(0.001)


def test_a_planted_violation_no_shift_can_cure_is_counted():
    # a program that begins before its section's enqueue AND ends after
    # its sync: longer than the section
    rep = host_spans.attribute(planes(program_a=(90, 215)))
    clock = rep["clock"]
    assert clock["violations_unshifted"] == 1
    assert clock["violations"] == 1
    assert "violations left 1" in "\n".join(host_spans.lines(rep))


def test_nothing_to_attribute_without_spans_or_device():
    bare = planes()
    bare["/host:CPU"] = {"engine": [ev("PjitFunction(program)", 97, 99)]}
    assert host_spans.attribute(bare) is None       # the parent's trace
    no_device = planes()
    del no_device["/device:TPU:0"]
    assert host_spans.attribute(no_device) is None
    job = planes()                  # a job's trace has no ptpu_step
    job["/host:CPU"]["engine"] = [
        ev("ptpu/train_step", 90 + 200 * i, 280 + 200 * i)
        for i in range(3)] + [ev("ptpu/enqueue", 95, 99)]
    rep = host_spans.attribute(job)
    assert rep["clock"] is None and rep["train_steps"] == 3
    assert rep["by_owner"]["ptpu/train_step"] == pytest.approx(0.140)
    assert rep["by_owner"]["unattributed"] == pytest.approx(0.010)
    assert "not checked" in "\n".join(host_spans.lines(rep))


def step(window, **fields):
    return {"name": "step", "args": dict(window=window, device_s=0.07,
                                         **fields)}


@pytest.mark.parametrize("metric, field", [
    ("engine_admit_ms", "admit_s"), ("engine_upload_ms", "upload_s"),
    ("engine_commit_ms", "commit_s"),
    ("engine_lock_wait_ms", "lock_wait_s")])
def test_engine_readers_per_decode_step_or_nothing(metric, field):
    reader = load_module("layer_metrics", metric)
    ctx = types.SimpleNamespace(collected={"engine_steps": [
        step(1, **{field: 0.004}), step(8, **{field: 0.005})]})
    assert reader.read(ctx) == pytest.approx(1.0)   # 9 ms over 9 steps
    # the parent's records have no such field; an empty ring has none
    ctx.collected["engine_steps"] = [step(1), step(8)]
    assert reader.read(ctx) is None
    ctx.collected["engine_steps"] = []
    assert reader.read(ctx) is None


def test_idle_attributed_reader():
    reader = load_module("layer_metrics", "idle_attributed_pct.serve")
    ctx = types.SimpleNamespace(
        host_spans_report=host_spans.attribute(planes()))
    assert reader.read(ctx) == pytest.approx(80.0)  # 30 of 150 ms lost
    ctx.host_spans_report = None                    # the parent
    assert reader.read(ctx) is None


def test_job_readers_from_the_blocks_or_nothing():
    wait = load_module("layer_metrics", "input_wait_ms")
    busy = load_module("layer_metrics", "train_host_busy_pct")
    ctx = types.SimpleNamespace(
        host_spans_report=None,
        host_spans_blocks={"steps": 100, "span_s": 10.0,
                           "host_data_wait_s": 0.02,
                           "host_enqueue_s": 0.25, "host_log_s": 0.03})
    assert wait.read(ctx) == pytest.approx(0.2)
    assert busy.read(ctx) == pytest.approx(3.0)
    ctx.host_spans_blocks = None                    # the parent
    assert wait.read(ctx) is None and busy.read(ctx) is None
