"""The ``traffic_ref`` driver: ``ptpu serve`` under a mix's load, then
held to a plain reference.

``drivers/traffic.py``'s run as it stands (``wait_healthy``, ``drive``,
``reduce_window``, by import: start, warm-up twice, ramp, window,
(trace,) drain, replay, ``/info``), with three differences:

- the served vocabulary is checked against the configuration's
  ``held`` slice, not the published one;
- a mix may name a ``hand``: the deck is then dealt in hands of that
  many requests, each holding the mix's weights exactly (``dealt``);
- after the replay the idle server is asked again for a few of the
  window's prompts with ``{"logits": true}`` (the mix's ``reference``:
  how many, how many tokens each): the float32 logits every new token
  was chosen from, out of the SAME compiled prefill and decode programs
  the window ran (the decode program takes its step count as an
  operand; a dispatch of one step keeps that step's logits).  When the
  server has gone, ``reference/<config>.py`` runs as a child on the
  freed chip: the program's own weights, the benchmark's own float32
  forward pass over prompt ++ new tokens, and the relative error of
  every served row.  ``correct`` is false past either tolerance of the
  configuration's ``correct`` block.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import loadgen
import procs
import stats
from procs import say
from run import load_module

traffic = load_module("drivers", "traffic")
HOST = traffic.HOST
REFERENCE_LIMIT_S = 900.0


def dealt(mix: dict) -> dict:
    """The mix as ``loadgen.requests`` is handed it.  A mix that names
    a ``hand`` has its deck dealt in hands: every ``hand`` requests in
    a row hold the mix's weights exactly, paired and ordered by the
    seed, where a whole deck shuffled at once lets a seed bunch its
    long prompts or its short answers at one edge of the window.  The
    generator does that when its deck IS one hand; the hands must add
    up to the deck, size for size."""
    hand = int(mix.get("hand", mix["deck"]))
    hands, rest = divmod(int(mix["deck"]), hand)
    one = dict(mix, deck=hand)
    if rest or any(sorted(part * hands) != sorted(whole) for part, whole
                   in zip(loadgen.deck(one), loadgen.deck(mix))):
        raise ValueError(f"hands of {hand} do not add up to the deck "
                         f"of {mix['deck']}")
    return one


def pick(records: list, t0: float, t1: float, vocab: int, n: int) -> list:
    """``n`` of the window's requests for the reference: the longest
    prompt first (it ends past the window), then one of each other
    length, then the longest again; greedy and sampled in turn, as the
    window sent them."""
    inside = [r for r in records if t0 <= r.get("done", 0) < t1
              and loadgen.well_formed(r, vocab)]
    lengths = sorted({len(r["request"]["prompt"]) for r in inside},
                     reverse=True)
    order = (lengths + lengths)[:max(n, 1)] if lengths else []
    picked, used = [], set()
    for i, length in enumerate(order):
        want_sampled = bool(i % 2)
        pool = [r for r in inside if r["index"] not in used
                and len(r["request"]["prompt"]) == length]
        pool.sort(key=lambda r: ("seed" in r["request"]) != want_sampled)
        if pool:
            picked.append(pool[0])
            used.add(pool[0]["index"])
    return picked


async def served_logits(port: int, picked: list, new: int) -> list:
    """The picked prompts again on the idle server, ``new`` tokens each,
    with the logits they were chosen from."""
    out = []
    for rec in picked:
        req = dict(rec["request"], max_new_tokens=new, logits=True)
        req.pop("timings", None)
        status, resp = await loadgen.call_json(HOST, port, "POST",
                                               "/generate", req)
        out.append((req, status, resp))
    return out


def engine_stalls(port: int, t0: float, t1: float) -> None:
    """Where the engine stood still inside the window, for a run that
    reads far off: of the telemetry ring's step and prefill records
    (``GET /trace``; placed by their distance from the newest one, which
    has just ended) the longest one, and the longest stretch that none
    of them covers.  The ring holds the last 4 096 events, so the
    window's first seconds may have left it."""
    fetched = time.time()
    try:
        body = procs.http("GET", f"http://{HOST}:{port}/trace")[1]
    except (OSError, ValueError):
        return
    spans = [e for e in body.get("traceEvents", [])
             if e.get("ph") == "X" and e.get("name") in ("step", "prefill")]
    if not spans:
        return
    newest = max(e["ts"] + e["dur"] for e in spans)

    def at(us):
        return fetched - 1e-6 * (newest - us) - t0

    spans = sorted((at(e["ts"]), at(e["ts"] + e["dur"]), e) for e in spans)
    spans = [x for x in spans if x[0] >= 0 and x[1] <= t1 - t0]
    if len(spans) < 2:
        return
    dur, start, ev = max((b - a, a, e) for a, b, e in spans)
    gap, end, covered = 0.0, 0.0, spans[0][1]
    for a, b, _ in spans[1:]:
        if a - covered > gap:
            gap, end = a - covered, a
        covered = max(covered, b)
    args = {k: v for k, v in ev.get("args", {}).items()
            if k in ("window", "occupancy", "device_s", "piece", "filled")}
    say(f"engine: {len(spans)} step and prefill records from "
        f"{spans[0][0]:.1f}s into the window on: the longest, a "
        f"{ev['name']} of {dur:.3f}s, began {start:.1f}s into it {args}; "
        f"the longest stretch under no record {gap:.3f}s, ending "
        f"{end:.1f}s into it")


def run_reference(ctx, served: list) -> list:
    """The child on the freed chip; returns its per-request errors
    ([] if it failed)."""
    config = ctx.config
    ref = config["reference"]
    pre = "rehearse_" if ctx.rehearse else ""
    job = {"model": config["rehearse_model" if ctx.rehearse
                           else "model"],
           "cfg": ref[pre + "cfg"],
           "experts_held": ref[pre + "experts_held"],
           "expert_offset": ref[pre + "expert_offset"],
           "degrade": os.environ.get("PERFBENCH_REFERENCE_DEGRADE")
           or None,
           "requests": [
               {"prompt": req["prompt"],
                "new_tokens": resp["new_tokens"][0],
                "logits_b64": resp["logits"]["b64"],
                "shape": resp["logits"]["shape"]}
               for req, _, resp in served]}
    job_path = os.path.join(ctx.out, "reference_job.json")
    out_path = os.path.join(ctx.out, "reference_out.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    cmd = [sys.executable, os.path.join(procs.HERE, ref["file"]),
           job_path, out_path]
    t = time.time()
    log_path = os.path.join(ctx.out, "reference.log")
    env = ctx.child_env()
    env.pop("PERFBENCH_PROBE_PREFIX")   # the device line is the server's
    env.pop("JAX_DEBUG_LOG_MODULES")    # an eager run is all compiles
    proc = procs.start(cmd, log_path, env)
    try:
        proc.wait(timeout=REFERENCE_LIMIT_S)
    except subprocess.TimeoutExpired:
        pass
    finally:
        procs.stop(proc, signal.SIGKILL, grace=30)
    say(f"reference: child exit {proc.returncode} after "
        f"{time.time() - t:.1f}s"
        + (f", degraded to {job['degrade']}" if job["degrade"] else ""))
    say("reference: " + procs.tail(log_path, 1500).replace(
        "\n", "\nreference: "))
    try:
        with open(out_path) as f:
            return json.load(f)["requests"]
    except (OSError, ValueError, KeyError):
        return []


def check_reference(ctx, served: list, results: list) -> dict:
    """The comparison that decides ``correct`` beside the traffic
    driver's own checks."""
    tol = ctx.config["correct"]
    checks = ctx.checks
    want = int(ctx.mix["reference"]["requests"])
    checks.add(len(results) == len(served) >= min(want, 2),
               f"the reference compared {len(results)} of {len(served)} "
               f"requests (wanted {want})")
    medians, worst = [], 0.0
    for r in results:
        med = stats.median(r["rel_err"])
        medians.append(med)
        worst = max(worst, max(r["rel_err"]))
        say(f"reference: prompt {r['prompt_tokens']}: rel_err median "
            f"{med:.5f} max {max(r['rel_err']):.5f} over "
            f"{len(r['rel_err'])} rows, argmax same {r['argmax_same']}")
        checks.add(r["finite"], f"prompt {r['prompt_tokens']}: logits "
                                f"not finite")
        if not ctx.rehearse:
            checks.add(
                med <= tol["logits_rel_err_request_median_max"],
                f"prompt {r['prompt_tokens']}: median relative error "
                f"of the logits {med:.5f} over "
                f"{tol['logits_rel_err_request_median_max']}")
    if results and not ctx.rehearse:
        checks.add(worst <= tol["logits_rel_err_max"],
                   f"largest relative error of a logits row "
                   f"{worst:.5f} over {tol['logits_rel_err_max']}")
        longest = max(r["prompt_tokens"] for r in results)
        checks.add(longest > ctx.config["sliding_window"],
                   f"no compared request passes the window (longest "
                   f"prompt {longest})")
    say("reference: " + json.dumps(
        {"request_medians": medians, "max": worst}))
    return {"request_medians": medians, "max": worst}


def run(ctx) -> dict:
    config, mix = ctx.config, dict(ctx.mix)
    serve = config["serve"]
    if ctx.rehearse:
        mix.update(mix.get("rehearse", {}))
        ctx.mix = mix
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
    served = []
    try:
        if not traffic.wait_healthy(ctx, proc, port):
            say(f"traffic: /healthz never answered (server exit code "
                f"{proc.poll()})")
            return result
        say(f"traffic: `ptpu serve {' '.join(cmd[4:])}` healthy after "
            f"{time.time() - t_start:.1f}s")
        first = procs.http("GET", f"http://{HOST}:{port}/info")[1]
        vocab = first["config"]["vocab_size"]
        if not ctx.rehearse:
            ctx.checks.add(vocab == config["held"]["vocab_size"],
                           f"serving vocab {vocab}, not the held slice "
                           f"of {config['held']['vocab_size']}")
        got = asyncio.run(traffic.drive(ctx, port, dealt(mix), vocab))
        picked = pick(got["records"], got["t0"], got["t1"], vocab,
                      int(mix["reference"]["requests"]))
        t = time.time()
        served = asyncio.run(served_logits(
            port, picked, int(mix["reference"]["new_tokens"])))
        bad = [s for _, s, resp in served
               if s != 200 or not resp.get("logits")]
        ctx.checks.add(not bad, f"{len(bad)} of {len(served)} requests "
                                f"for logits failed (first status "
                                f"{bad[0] if bad else None})")
        served = [x for x in served
                  if x[1] == 200 and x[2].get("logits")]
        say(f"reference: {len(served)} of the window's prompts "
            f"({[len(r['prompt']) for r, _, _ in served]} tokens) "
            f"served again with logits in {time.time() - t:.1f}s")
        engine_stalls(port, got["t0"], got["t1"])
        got["info_end"] = procs.http(
            "GET", f"http://{HOST}:{port}/info")[1]
        result = traffic.reduce_window(ctx, got, vocab)
        result["trace_dir"] = profile_dir if ctx.trace else None
    finally:
        clean = procs.stop(proc, signal.SIGTERM, grace=60)
        ctx.checks.add(clean, "the server ignored SIGTERM")
        with open(log_path, errors="replace") as f:
            text = f.read()
        procs.cache_report("traffic", before, text)
        if not result["ok"] or ctx.checks.failures:
            say("traffic: end of the server's log:\n" + text[-3000:])
    if result["ok"]:
        result["collected"]["reference"] = check_reference(
            ctx, served, run_reference(ctx, served) if served else [])
    return result
