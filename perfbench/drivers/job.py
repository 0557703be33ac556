"""The ``job`` driver: ``ptpu run -f <polyaxonfile>`` -> LocalExecutor ->
``python -m polyaxon_tpu.train``, watched from its run store.

``train.py`` has no time limit, so the job is given far more steps than
the window needs and is stopped when the window has closed.  Set-up ends
at the ``setup_blocks``-th logged block (the first holds the first-step
stall); the window is the next ``--seconds`` seconds of the events' own
``timestamp``s (taken by ``train.py`` right after the ``float()`` that
syncs the device), so events that reach the store late lose nothing.
"""

from __future__ import annotations

import math
import os
import signal
import sys
import time

import procs
from procs import say


def window_rate(events, setup_blocks: int, seconds: float,
                tokens_per_step: int, chips: int):
    """The window arithmetic over ``[(step, timestamp, loss)]`` in step
    order.  Returns None until an event stamped after the window's end
    has been read; then ``{"t0", "t1", "blocks", "rate", "span_s"}``:
    ``blocks`` are the events inside the window (the one that opens it
    included), the rate is the steps between the first and the last of
    them, times tokens per step, over the difference of their
    timestamps, per chip."""
    if len(events) < setup_blocks:
        return None
    t0 = events[setup_blocks - 1][1]
    t1 = t0 + seconds
    if events[-1][1] <= t1:
        return None
    blocks = [e for e in events[setup_blocks - 1:] if e[1] <= t1]
    first, last = blocks[0], blocks[-1]
    span = last[1] - first[1]
    rate = (last[0] - first[0]) * tokens_per_step / span / chips \
        if span > 0 else None
    return {"t0": t0, "t1": t1, "blocks": blocks, "rate": rate,
            "span_s": span}


def run(ctx) -> dict:
    from polyaxon_tpu.client import FileRunStore

    config, mix, cell = ctx.config, ctx.mix, ctx.cell
    job = config["job"]
    chips = cell["chips"]
    rehearse = ctx.rehearse
    model = config["rehearse_model"] if rehearse else config["model"]
    seq = job["rehearse_seq"] if rehearse else job["seq"]
    batch = job["batch_size_per_chip"] * chips
    tokens_per_step = batch * seq
    home = os.path.join(ctx.out, "home")
    params = {"model": model, "steps": mix["steps"],
              "log_every": mix["log_every"], "seed": ctx.child_seed,
              "strategy": mix["strategy"], "batch_size": batch,
              **job["params"]}
    if ctx.trace:
        params.update(profile_at=mix["profile_at"],
                      profile_steps=mix["profile_steps"])
    cmd = [sys.executable, "-m", "polyaxon_tpu.cli", "run", "-f",
           os.path.join("perfbench", "configs", job["polyaxonfile"])]
    for key, value in params.items():
        cmd += ["-P", f"{key}={value}"]
    log_path = os.path.join(ctx.out, "job.stdout")
    before = procs.cache_entries()
    proc = procs.start(cmd, log_path,
                       ctx.child_env(POLYAXON_TPU_HOME=home))
    store = FileRunStore(home)
    uuid = None
    window = None
    events = []
    trace_done_step = mix["profile_at"] + mix["profile_steps"] \
        if ctx.trace else 0
    deadline = time.time() + ctx.setup_limit_s + ctx.seconds
    try:
        while time.time() < deadline:
            if proc.poll() is not None:
                say(f"job: `ptpu run` ended by itself with code "
                    f"{proc.returncode}")
                break
            if not ctx.check_device():
                break
            if uuid is None:
                runs = store.list_runs()
                uuid = runs[0]["uuid"] if runs else None
            if uuid is not None:
                events = [(e["step"], e["timestamp"], e["value"])
                          for e in store.read_events(uuid, "metric", "loss")]
                window = window_rate(events, mix["setup_blocks"],
                                     ctx.seconds, tokens_per_step, chips)
                if window is not None and events[-1][0] >= trace_done_step:
                    break
            time.sleep(0.25)
        ctx.read_device()
    finally:
        # SIGKILL to the group: SIGTERM would make train.py write its
        # preemption checkpoint (2.8 GB) before it goes.
        t = time.time()
        procs.stop(proc, signal.SIGKILL, grace=30)
        say(f"job: stopped `ptpu run` and its worker with SIGKILL in "
            f"{time.time() - t:.1f}s")
    logs = (store.read_logs(uuid) or "") if uuid else ""
    procs.cache_report("job", before, logs)
    for line in logs.splitlines():
        if "reshaped flat" in line or "bytes_in_use after init" in line \
                or "compiled train step" in line:
            say(f"job: {line.split('] ', 1)[-1]}")
    if window is None:
        say("job: no window was measured; end of the job's output:\n"
            + procs.tail(log_path) + "\n" + logs[-3000:])
        return {"ok": False}

    def series(name):
        return [e["value"] for e in store.read_events(uuid, "metric", name)]

    run_rec = store.get_run(uuid)
    inputs = run_rec.get("inputs") or {}
    losses = [e[2] for e in window["blocks"]]
    checks = ctx.checks
    checks.add(inputs.get("backend") == ctx.device["platform"],
               f"logged backend {inputs.get('backend')!r}, the device "
               f"probe said {ctx.device['platform']!r}")
    checks.add(inputs.get("n_chips") == chips,
               f"mesh over {inputs.get('n_chips')} chips, cell wants "
               f"{chips}")
    checks.add(len(losses) >= 2 and all(map(math.isfinite, losses)),
               f"losses in the window not finite: {losses[:5]}...")
    checks.add(all(a != b for a, b in zip(losses, losses[1:])),
               "loss did not move between two blocks of the window")
    ln_v = math.log(config["published"]["vocab_size"])
    tol = config["correct"]
    if not rehearse:
        checks.add(abs(events[0][2] - ln_v) < tol["first_loss_rtol"] * ln_v,
                   f"first logged loss {events[0][2]} far from ln(vocab) "
                   f"{ln_v:.2f}")
        pallas = series("pallas_calls")
        checks.add(bool(pallas) and pallas[0] >= 1,
                   f"no tpu_custom_call in the compiled train step "
                   f"(pallas_calls {pallas})")
    checks.add(losses[-1] <= losses[0] + tol["loss_rise_atol"],
               f"loss rose over the window: {losses[0]} -> {losses[-1]}")
    say(f"job: window {window['span_s']:.3f}s of {ctx.seconds}s between "
        f"steps {window['blocks'][0][0]} and {window['blocks'][-1][0]}, "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, first logged loss "
        f"{events[0][2]:.4f}")
    # Where a stall sits, should a window hold one (PERF.md section 6:
    # one run in fifteen read 1.7 s long and its store was gone).
    blocks = window["blocks"]
    spans = sorted((b[1] - a[1], b[0]) for a, b in zip(blocks, blocks[1:]))
    say(f"job: the window's blocks took {spans[0][0]:.3f}s at least, "
        f"{spans[len(spans) // 2][0]:.3f}s in the middle, "
        f"{spans[-1][0]:.3f}s at most (the block that ended at step "
        f"{spans[-1][1]})")
    failed = sum(1 for v in losses if not math.isfinite(v))
    compile_s = series("compile_s")
    trace_dir = os.path.join(store.artifacts_path(uuid), "traces") \
        if ctx.trace else None
    return {
        "ok": True, "t_window": window["t0"],
        "attempted": len(losses), "failed": failed,
        "end_to_end": {"train_tok_s_chip": window["rate"]},
        "trace_dir": trace_dir,
        "collected": {
            "compile_s": compile_s[0] if compile_s else None,
            "trace_steps": mix["profile_steps"],
            "batch": batch, "chips": chips,
        },
    }
