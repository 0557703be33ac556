"""The ``traffic`` driver: ``ptpu serve`` under a mix's load.

Start the server, wait for ``/healthz``, warm every shape the window
will use (the mix's warm-up set, twice; the second pass may compile
nothing), run the mix's loop for ``ramp_s`` seconds so the window opens
on a full server, measure for ``--seconds`` (a traced run then keeps the
load on for a few seconds under the profiler), let the callers take the
replies still owed and send nothing more, read ``/info``, replay some of
the window's requests on the now idle server (token-identical, the
engine's own contract), stop the server.

The window's rate counts every reply's tokens for the part of its wait
that lies inside the window (``loadgen.tokens_in_window``), so the
replies that straddle an edge are neither lost nor counted whole; that
is why the callers are waited for.  Latencies are those of the replies
that came inside the window.
"""

from __future__ import annotations

import asyncio
import os
import random
import signal
import sys
import time

import loadgen
import procs
import stats
from procs import say

HOST = "127.0.0.1"
DRAIN_LIMIT_S = 60.0        # a caller still owed its reply then has failed
REPLAY_MAX_NEW_TOKENS = 128     # 9 s of replay on the v5e; longer ones add
#                                 17 s for nothing a shorter one would miss


def wait_healthy(ctx, proc, port: int) -> bool:
    deadline = time.time() + ctx.setup_limit_s
    while time.time() < deadline and proc.poll() is None:
        if not ctx.check_device():
            return False
        try:
            if procs.http("GET", f"http://{HOST}:{port}/healthz",
                          timeout=5)[0] == 200:
                return True
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    return False


async def info(port: int) -> dict:
    status, body = await loadgen.call_json(HOST, port, "GET", "/info")
    return body if status == 200 else {}


async def engine_steps(port: int, since: float) -> list:
    """The engine's step records (``GET /trace``: the telemetry ring's
    ``step`` spans, one per decode dispatch, with ``window``, ``tokens``
    and ``device_s``) that began after the host-clock time ``since``.
    The ring's clock starts with the server, so records are placed by
    their distance from the newest one, which is as good as now."""
    fetched = time.time()
    status, body = await loadgen.call_json(HOST, port, "GET", "/trace")
    steps = [e for e in body.get("traceEvents", [])
             if e.get("name") == "step" and e.get("ph") == "X"
             and "device_s" in e.get("args", {})]
    if not steps:
        return []
    newest = max(e["ts"] + e["dur"] for e in steps)
    return [e for e in steps
            if e["ts"] >= newest - 1e6 * (fetched - since)]


async def drive(ctx, port: int, mix: dict, vocab: int) -> dict:
    """Warm-up, ramp, window, drain, replay: everything between
    ``/healthz`` and the server's stop, on one event loop."""
    out = {"warm_misses": [], "polls": []}
    rng_warm = random.Random(ctx.seed ^ 0x5EED)
    warm = [loadgen.make_request(s, mix, vocab, rng_warm, ctx.trace)
            for s in loadgen.shapes(mix)]
    for n in (1, 2):
        t = time.time()
        # First one at a time: alone on the server, a request walks
        # through every decode window on its way to its budget.  Then
        # all at once, which may compile nothing new.
        recs = [(await loadgen.send_all(HOST, port, [r]))[0]
                for r in warm] if n == 1 \
            else await loadgen.send_all(HOST, port, warm)
        bad = [r for r in recs if not loadgen.well_formed(r, vocab)]
        ctx.checks.add(not bad, f"warm-up pass {n}: {len(bad)} of "
                       f"{len(recs)} requests failed (first status "
                       f"{bad[0]['status'] if bad else None})")
        out["info_warm"] = await info(port)
        misses = out["info_warm"].get("compile_cache_misses")
        out["warm_misses"].append(misses)
        say(f"traffic: warm-up pass {n}: {len(warm)} requests in "
            f"{time.time() - t:.1f}s, compile_cache_misses {misses}")
    ctx.checks.add(out["warm_misses"][0] == out["warm_misses"][1],
                   f"the second warm-up pass compiled: misses "
                   f"{out['warm_misses']}")

    records = []
    stream = loadgen.requests(mix, ctx.seed, vocab, timings=ctx.trace)
    t0 = time.time() + float(mix["ramp_s"])
    t1 = t0 + ctx.seconds
    out["t0"], out["t1"] = t0, t1
    stop = asyncio.Event()
    callers = loadgen.start_closed_loop(HOST, port, stream,
                                        int(mix["clients"]), records, stop)

    async def at(when):
        await asyncio.sleep(max(0.0, when - time.time()))

    await at(t0)
    out["info_open"] = await info(port)
    say(f"traffic: ramp {mix['ramp_s']}s: compile_cache_misses "
        f"{out['info_open'].get('compile_cache_misses')} at the window's "
        f"start")
    if ctx.trace:
        while time.time() < t1 - 0.5:      # the counters once a second
            out["polls"].append((time.time(), await info(port)))
            await at(min(t1, time.time() + 1.0))
    await at(t1)
    if not ctx.trace:
        stop.set()      # a traced run keeps the load on for the profiler
    out["info_close"] = await info(port)
    # The memory reading: the window has closed, the load is still on.
    await asyncio.get_running_loop().run_in_executor(None, ctx.read_device)
    if ctx.trace:
        # The load runs on under the profiler for a few seconds AFTER
        # the window: stopping a trace stalls the server for longer
        # than a window lasts.
        out["engine_steps"] = await engine_steps(port, t0)
        out["trace_open"] = await info(port)
        status, body = await loadgen.call_json(HOST, port, "POST",
                                               "/profile/start")
        say(f"traffic: POST /profile/start -> {status} {body}")
        await asyncio.sleep(float(mix["trace_s"]))
        out["trace_close"] = await info(port)
        t = time.time()
        status, body = await loadgen.call_json(HOST, port, "POST",
                                               "/profile/stop")
        say(f"traffic: POST /profile/stop -> {status} {body} after "
            f"{time.time() - t:.1f}s")
    # No caller sends another request now; each takes the reply it is
    # owed (the longest budget's worth of steps, and the queue before).
    stop.set()
    t = time.time()
    _, owed = await asyncio.wait(callers, timeout=DRAIN_LIMIT_S)
    for task in owed:
        task.cancel()
    await asyncio.gather(*callers, return_exceptions=True)
    say(f"traffic: the callers took the replies still owed in "
        f"{time.time() - t:.1f}s ({len(owed)} given up after "
        f"{DRAIN_LIMIT_S:.0f}s)")
    out["records"] = records

    # The replay, now alone on the server: of the requests answered in
    # the window, one of each output budget from the shortest up, half
    # greedy, half sampled with their seeds, all at once.
    inside = [r for r in records if t0 <= r.get("done", 0) < t1
              and loadgen.well_formed(r, vocab)]
    picked = []
    for sampled in (False, True):
        by_budget = {}
        for r in inside:
            if ("seed" in r["request"]) == sampled:
                by_budget.setdefault(r["request"]["max_new_tokens"], r)
        picked += [by_budget[b] for b in sorted(by_budget)
                   if b <= REPLAY_MAX_NEW_TOKENS]
    t = time.time()
    again = await loadgen.send_all(HOST, port,
                                   [r["request"] for r in picked])
    same = sum(1 for a, b in zip(picked, again)
               if b.get("status") == 200
               and a["response"]["new_tokens"]
               == b["response"].get("new_tokens"))
    out["replay"] = (same, len(picked))
    say(f"traffic: replayed budgets "
        f"{[r['request']['max_new_tokens'] for r in picked]} in "
        f"{time.time() - t:.1f}s")
    out["info_end"] = await info(port)
    return out


def run(ctx) -> dict:
    config, mix = ctx.config, dict(ctx.mix)
    serve = config["serve"]
    if ctx.rehearse:
        mix.update(mix.get("rehearse", {}))
    model = config["rehearse_model"] if ctx.rehearse else config["model"]
    args = serve["rehearse_args"] if ctx.rehearse else serve["args"]
    port = procs.free_port()
    profile_dir = os.path.join(ctx.out, "profile")
    cmd = [sys.executable, "-m", "polyaxon_tpu.cli", "serve", "--model",
           model, "--port", str(port), *args]
    if ctx.trace:
        cmd += ["--profile-dir", profile_dir]
    log_path = os.path.join(ctx.out, "serve.log")
    before = procs.cache_entries()
    t_start = time.time()
    proc = procs.start(cmd, log_path, ctx.child_env(
        POLYAXON_TPU_HOME=os.path.join(ctx.out, "home")))
    result = {"ok": False}
    try:
        if not wait_healthy(ctx, proc, port):
            say(f"traffic: /healthz never answered (server exit code "
                f"{proc.poll()})")
            return result
        say(f"traffic: `ptpu serve {' '.join(cmd[4:])}` healthy after "
            f"{time.time() - t_start:.1f}s")
        first = procs.http("GET", f"http://{HOST}:{port}/info")[1]
        vocab = first["config"]["vocab_size"]
        if not ctx.rehearse:
            ctx.checks.add(vocab == config["published"]["vocab_size"],
                           f"serving vocab {vocab}, not the published one")
        got = asyncio.run(drive(ctx, port, mix, vocab))
        result = reduce_window(ctx, got, vocab)
        result["trace_dir"] = profile_dir if ctx.trace else None
    finally:
        clean = procs.stop(proc, signal.SIGTERM, grace=60)
        ctx.checks.add(clean, "the server ignored SIGTERM")
        with open(log_path, errors="replace") as f:
            text = f.read()
        procs.cache_report("traffic", before, text)
        if not result["ok"] or ctx.checks.failures:
            say("traffic: end of the server's log:\n" + text[-3000:])
    return result


def reduce_window(ctx, got: dict, vocab: int) -> dict:
    t0, t1 = got["t0"], got["t1"]
    # The window's requests: those whose wait overlaps it.  One that was
    # given up on, or failed, is a failure of the window.
    over = [r for r in got["records"]
            if r["sent"] < t1 and r.get("done", t1) >= t0]
    bad = [r for r in over if not loadgen.well_formed(r, vocab)]
    bad_ids = {r["index"] for r in bad}
    whole = [r for r in over if r["index"] not in bad_ids]
    tokens = sum(loadgen.tokens_in_window(r, t0, t1) for r in whole)
    done = [r for r in whole if t0 <= r["done"] < t1]
    landed = sum(r["request"]["max_new_tokens"] for r in done)
    late = [1e3 * (r["sent"] - r["due"]) for r in over]
    say(f"traffic: window {ctx.seconds}s: {len(over)} requests overlap "
        f"it, {len(bad)} failed; {tokens:.1f} output tokens fall inside "
        f"({len(done)} replies of {landed} tokens landed in it); "
        f"generator lateness ms p50 {stats.median(late)} max "
        f"{max(late, default=None)}")
    # Where a stall sits, should a window hold one (PERF.md section 6).
    ends = sorted(r["done"] for r in done)
    quiet, when = max((b - a, b - t0)
                      for a, b in zip([t0] + ends, ends + [t1]))
    say(f"traffic: the longest the window went without a reply: "
        f"{quiet:.2f}s, ending {when:.1f}s into it")
    if bad:
        say(f"traffic: first failure: status {bad[0].get('status')} "
            f"{str(bad[0].get('response'))[:300]}")
    end = got["info_end"]
    checks = ctx.checks
    checks.add(end.get("backend") == ctx.device["platform"],
               f"/info backend {end.get('backend')!r}")
    routing = end.get("routing") or {}
    checks.add(end.get("solo_fallbacks") == {}
               and routing.get("greedy") == "engine"
               and routing.get("sampled") == "engine",
               f"requests left the engine: routing {routing}, "
               f"solo_fallbacks {end.get('solo_fallbacks')}")
    same, of = got["replay"]
    say(f"traffic: replay on the idle server: {same} of {of} "
        f"token-identical")
    checks.add(of >= 2 and same == of,
               f"replayed requests differ: {same} of {of} identical")
    compiles = got["info_close"].get("compile_cache_misses", 0) \
        - got["info_open"].get("compile_cache_misses", 0)
    say(f"traffic: compile_cache_misses over the window: {compiles} "
        f"(at its start {got['info_open'].get('compile_cache_misses')})")
    checks.add(compiles == 0, f"{compiles} programs compiled inside the "
                              f"window")
    if compiles or got["warm_misses"][0] != got["warm_misses"][1]:
        for label, rec in (("after the warm-up", got["info_warm"]),
                           ("at the window's start", got["info_open"]),
                           ("at its close", got["info_close"])):
            say(f"traffic: programs compiled by kind {label}: "
                f"{rec.get('compile_cache', {}).get('compile_cache_by_kind')}")
    return {
        "ok": True, "t_window": t0,
        "attempted": len(over), "failed": len(bad),
        "end_to_end": {"serve_out_tok_s": tokens / ctx.seconds},
        "collected": {
            "done": done, "info_open": got["info_open"],
            "info_close": got["info_close"], "polls": got["polls"],
            "trace_open": got.get("trace_open"),
            "trace_close": got.get("trace_close"),
            "engine_steps": got.get("engine_steps", []),
            "window_compiles": compiles,
        },
    }
