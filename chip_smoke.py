#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives both hot paths once, through the entry points a user calls, at
the full published width of ``gpt2-medium`` (24 layers, d 1024, 16
heads, vocab 50 257, seq 1024; random weights from ``--seed``):

- **train**: ``ptpu run -f examples/gpt2/onechip.yaml`` -> LocalExecutor
  -> ``python -m polyaxon_tpu.train`` for a handful of steps, checked
  from the run store (status, backend, compile seconds, finite moving
  losses, throughput metric, the Pallas kernel in the executable);
- **serve**: ``ptpu serve --model gpt2-medium`` with default batching,
  a few ``POST /generate`` (mixed lengths, two concurrent, one repeated
  with its seed), then ``GET /info`` (backend, engine routing, no
  recompiles once warm).

``--chips 4`` runs INSTEAD the two paths that exist only across chips,
each with what it is compared with: dp=4 training against one chip of
the same machine, and ``ptpu serve --mesh tp=4`` against the unmeshed
server.

One process holds a chip, so this parent never imports JAX: every
phase is a child process that has exited before the next one starts.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
``ok`` is true only on a TPU with every phase passed, and the exit code
is 0 only then.  Any time or rate printed on an earlier line is one
smoke run, not a benchmark.

``--rehearse`` (CPU rehearsal of the control flow, never a result) swaps
in ``gpt2-tiny`` and carries on without a TPU; ``ok`` stays false there.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
POLYAXONFILE = os.path.join("examples", "gpt2", "onechip.yaml")
VOCAB = {"gpt2-medium": 50257, "gpt2-tiny": 1024}
STEPS = 6
NEW_TOKENS = 32
# dp=4 against one chip: same seed, same global batch, same data.  The
# two programs differ in matmul tiling and in the order the gradient is
# summed, so bf16 losses agree closely but not bitwise.
DP_LOSS_RTOL = 1e-2

PROBE = ("import json, jax; d = jax.devices(); "
         "print(json.dumps({'platform': d[0].platform, "
         "'kind': d[0].device_kind, 'count': len(d)}))")


def say(msg: str) -> None:
    # One write per line: two request threads report at once.
    sys.stdout.write(msg + "\n")
    sys.stdout.flush()


class Smoke:
    """One run's state: where things go, what the device is, what
    failed.  ``check`` records; nothing here lets a failure pass."""

    def __init__(self, args):
        self.args = args
        self.model = "gpt2-tiny" if args.rehearse else "gpt2-medium"
        self.out = os.path.abspath(args.out)
        self.failures = []
        self.device = None
        # Where config.enable_compilation_cache puts the cache: from
        # outside when JAX_COMPILATION_CACHE_DIR is set, else in the
        # checkout.  Same rule, restated so this parent stays off JAX.
        self.cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or os.path.join(ROOT, ".jax_cache")

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            say(f"FAIL {what}")
        return bool(ok)

    def env(self, **extra: str) -> dict:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
            else "")
        # JAX's own cache-hit/miss lines (debug level) onto the
        # child's stderr: counted below, nothing added to the program.
        env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
        env.update(extra)
        return env

    def cache_entries(self) -> int:
        try:
            return len(os.listdir(self.cache_dir))
        except OSError:
            return 0


def cache_report(smoke: Smoke, label: str, before: int, log_text: str):
    hits = log_text.count("Persistent compilation cache hit")
    misses = log_text.count("PERSISTENT COMPILATION CACHE MISS")
    say(f"{label}: compile cache {smoke.cache_dir} entries "
        f"{before} -> {smoke.cache_entries()}, hits {hits}, "
        f"misses {misses}")


def one_chip_env() -> dict:
    """Limit a child to ONE of the host's chips (libtpu's own
    variables; the CPU rehearsal's twin is the device-count flag)."""
    one = {name: "1,1,1" for name in (
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
        # the same two bounds under their older names, which the
        # machine may come with already set for the whole host
        "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")}
    return {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0", **one,
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def probe_device(smoke: Smoke):
    """What JAX finds, asked in a child that exits at once."""
    proc = subprocess.run([sys.executable, "-c", PROBE],
                          capture_output=True, text=True, timeout=300,
                          env=smoke.env(JAX_DEBUG_LOG_MODULES=""))
    if proc.returncode != 0:
        say(proc.stderr[-2000:])
        return None
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def run_train(smoke: Smoke, label: str, strategy: str, child_env=None):
    """``ptpu run -f onechip.yaml`` in its own store; returns the run's
    facts read back from that store (None if there is no run)."""
    from polyaxon_tpu.client import FileRunStore

    home = os.path.join(smoke.out, f"home-{label}")
    before = smoke.cache_entries()
    cmd = [sys.executable, "-m", "polyaxon_tpu.cli", "run",
           "-f", POLYAXONFILE,
           "-P", f"model={smoke.model}", "-P", f"steps={STEPS}",
           "-P", "log_every=1", "-P", f"seed={smoke.args.seed}",
           "-P", f"strategy={strategy}"]
    t0 = time.time()
    with open(os.path.join(smoke.out, f"{label}.stdout"), "w") as sink:
        proc = subprocess.run(
            cmd, cwd=ROOT, stdout=sink, stderr=subprocess.STDOUT,
            timeout=900,
            env=smoke.env(POLYAXON_TPU_HOME=home, **(child_env or {})))
    say(f"{label}: `ptpu run -f {POLYAXONFILE}` exited "
        f"{proc.returncode} after {time.time() - t0:.0f}s")
    smoke.check(proc.returncode == 0, f"{label}: ptpu run exit code "
                                      f"{proc.returncode}")
    store = FileRunStore(home)
    runs = store.list_runs()
    if not smoke.check(len(runs) == 1,
                       f"{label}: {len(runs)} runs in the store, not 1"):
        return None
    run = runs[0]
    uuid = run["uuid"]
    logs = store.read_logs(uuid) or ""
    inputs = run.get("inputs") or {}
    platform = smoke.device["platform"]

    def series(name):
        return [e["value"] for e in store.read_events(uuid, "metric",
                                                      name)]

    facts = {"loss": series("loss"), "grad_norm": series("grad_norm"),
             "compile_s": series("compile_s"),
             "tok_per_sec_per_chip": series("tok_per_sec_per_chip"),
             "pallas_calls": series("pallas_calls"), "logs": logs,
             "n_chips": inputs.get("n_chips")}
    if not smoke.check(run.get("status") == "succeeded",
                       f"{label}: run status {run.get('status')!r}"):
        say(logs[-3000:])
    smoke.check(inputs.get("backend") == platform,
                f"{label}: logged backend {inputs.get('backend')!r}, "
                f"device probe said {platform!r}")
    smoke.check(len(facts["compile_s"]) == 1,
                f"{label}: compile_s not logged")
    loss = facts["loss"]
    smoke.check(len(loss) == STEPS and all(map(math.isfinite, loss)),
                f"{label}: want {STEPS} finite losses, got {loss}")
    smoke.check(all(a != b for a, b in zip(loss, loss[1:])),
                f"{label}: loss did not move between steps: {loss}")
    # Untrained weights on uniform random tokens: cross-entropy sits
    # near ln(vocab).
    ln_v = math.log(VOCAB[smoke.model])
    smoke.check(bool(loss) and abs(loss[0] - ln_v) < 0.15 * ln_v,
                f"{label}: first loss {loss[:1]} far from ln(vocab) "
                f"{ln_v:.2f}")
    gn = facts["grad_norm"]
    smoke.check(bool(gn) and all(math.isfinite(g) and g > 0 for g in gn),
                f"{label}: grad_norm not finite and positive: {gn}")
    smoke.check(len(facts["tok_per_sec_per_chip"]) == STEPS,
                f"{label}: tok_per_sec_per_chip not logged every step")
    if platform == "tpu":
        # ops/attention.py drops to the fused-XLA path without a word;
        # train.py counts the kernel in the executable's own text.
        smoke.check(bool(facts["pallas_calls"])
                    and facts["pallas_calls"][0] >= 1,
                    f"{label}: no tpu_custom_call in the compiled train "
                    f"step (pallas_calls {facts['pallas_calls']})")
    say(f"{label}: compile_s {facts['compile_s']}, pallas_calls "
        f"{facts['pallas_calls']}, n_chips {facts['n_chips']}, losses "
        f"{[round(v, 4) for v in loss]}")
    say(f"{label}: tok/s/chip by step (one smoke run, not a benchmark) "
        f"{facts['tok_per_sec_per_chip']}")
    cache_report(smoke, label, before, logs)
    for line in logs.splitlines():
        if "reshaped flat" in line or "bytes_in_use after init" in line:
            say(f"{label}: {line.split('] ', 1)[-1]}")
    return facts


def train_phase(smoke: Smoke) -> None:
    run_train(smoke, "train", "dp:-1")


def dp4_phase(smoke: Smoke) -> None:
    """dp=4, one process driving four chips, against one chip of the
    same machine: same seed, same global batch."""
    four = run_train(smoke, "train-dp4", "dp:4")
    one = run_train(smoke, "train-one-chip", "dp:1",
                    child_env=one_chip_env())
    if four is None or one is None:
        return
    smoke.check(four["n_chips"] == 4 and one["n_chips"] == 1,
                f"dp4: meshes over {four['n_chips']} and "
                f"{one['n_chips']} chips, wanted 4 and 1")
    pairs = list(zip(four["loss"], one["loss"]))
    worst = max((abs(a - b) / abs(b) for a, b in pairs), default=None)
    say(f"dp4: per-step loss, dp=4 vs one chip: {pairs}; worst "
        f"relative difference {worst} (tolerance {DP_LOSS_RTOL})")
    smoke.check(len(pairs) == STEPS and worst is not None
                and worst <= DP_LOSS_RTOL,
                f"dp4: losses differ from the one-chip run by {worst} "
                f"(> {DP_LOSS_RTOL})")
    if smoke.device["platform"] == "tpu":
        in_use = None
        for line in four["logs"].splitlines():
            if "bytes_in_use after init: " in line:
                in_use = json.loads(line.split("after init: ", 1)[1])
        # Params and optimizer state are replicated under dp: every
        # device holds as much as the first, not a sliver.
        smoke.check(bool(in_use) and len(in_use) == 4
                    and min(in_use) > 0.9 * max(in_use),
                    f"dp4: state is not on all four devices "
                    f"(bytes_in_use {in_use})")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def make_requests(seed: int, vocab: int):
    """The smoke's traffic, made from the seed: two prompt lengths,
    greedy and sampled; r3/r4 go out together; r5 repeats r2."""
    rng = random.Random(seed)

    def prompt(n):
        return [rng.randrange(vocab) for _ in range(n)]

    short, long_ = 24, 77
    r1 = {"prompt": prompt(short), "max_new_tokens": NEW_TOKENS}
    r2 = {"prompt": prompt(long_), "max_new_tokens": NEW_TOKENS,
          "temperature": 0.8, "seed": seed + 7}
    r3 = {"prompt": prompt(short), "max_new_tokens": NEW_TOKENS}
    r4 = {"prompt": prompt(long_), "max_new_tokens": NEW_TOKENS,
          "temperature": 0.8, "seed": seed + 11}
    return r1, r2, r3, r4, dict(r2)


def serve_once(smoke: Smoke, label: str, extra_args=(), child_env=None):
    """Start ``ptpu serve``, send the smoke's traffic, read ``/info``,
    SIGTERM it and wait for it to be gone.  Returns ``(tokens of the
    five responses, final /info)`` or None."""
    port = free_port()
    # The rehearsed fault: ask a port nobody listens on.
    ask = free_port() if smoke.args.rehearse_fault == "closed-port" \
        else port
    base = f"http://127.0.0.1:{ask}"
    log_path = os.path.join(smoke.out, f"{label}.log")
    before = smoke.cache_entries()
    cmd = [sys.executable, "-m", "polyaxon_tpu.cli", "serve",
           "--model", smoke.model, "--port", str(port), *extra_args]
    t0 = time.time()
    with open(log_path, "w") as sink:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, stdout=sink, stderr=subprocess.STDOUT,
            env=smoke.env(**(child_env or {})))
    try:
        deadline = time.time() + (90 if smoke.args.rehearse else 600)
        up = False
        while time.time() < deadline and proc.poll() is None:
            try:
                up = http("GET", base + "/healthz", timeout=5)[0] == 200
            except (OSError, ValueError):
                up = False
            if up:
                break
            time.sleep(1.0)
        if not smoke.check(up, f"{label}: /healthz never answered on "
                               f"{base} (server exit code "
                               f"{proc.poll()})"):
            return None
        say(f"{label}: `ptpu serve {' '.join(cmd[4:])}` healthy after "
            f"{time.time() - t0:.0f}s")
        vocab = http("GET", base + "/info")[1]["config"]["vocab_size"]
        smoke.check(vocab == VOCAB[smoke.model],
                    f"{label}: serving vocab {vocab}, not the "
                    f"published {VOCAB[smoke.model]}")
        r1, r2, r3, r4, r5 = make_requests(smoke.args.seed, vocab)

        def generate(req):
            t = time.time()
            status, resp = http("POST", base + "/generate", req)
            new = resp["new_tokens"][0]
            smoke.check(
                status == 200 and len(new) == NEW_TOKENS
                and all(isinstance(x, int) and 0 <= x < vocab
                        for x in new),
                f"{label}: /generate gave HTTP {status} and "
                f"{len(new)} new tokens, wanted 200 and {NEW_TOKENS} "
                f"in [0, {vocab})")
            say(f"{label}: prompt {len(req['prompt'])} + {len(new)} new "
                f"tokens in {time.time() - t:.2f}s (one smoke run, not "
                f"a benchmark)")
            return new

        out = [generate(r1), generate(r2)]
        warm = http("GET", base + "/info")[1]["compile_cache_misses"]
        both = [None, None]

        def worker(i, req):
            both[i] = generate(req)

        threads = [threading.Thread(target=worker, args=(i, r))
                   for i, r in enumerate((r3, r4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        smoke.check(all(b is not None for b in both),
                    f"{label}: a concurrent request did not come back")
        out += both
        out.append(generate(r5))
        smoke.check(out[4] == out[1],
                    f"{label}: the repeated request (same prompt, same "
                    f"seed) gave different tokens")
        info = http("GET", base + "/info")[1]
        smoke.check(info["backend"] == smoke.device["platform"],
                    f"{label}: /info backend {info['backend']!r}")
        smoke.check(info["solo_fallbacks"] == {}
                    and info["routing"]["greedy"] == "engine"
                    and info["routing"]["sampled"] == "engine",
                    f"{label}: requests left the engine: routing "
                    f"{info['routing']}, solo_fallbacks "
                    f"{info['solo_fallbacks']}")
        smoke.check(info["compile_cache_misses"] == warm,
                    f"{label}: compile_cache_misses grew from {warm} "
                    f"to {info['compile_cache_misses']} after the "
                    f"second request")
        say(f"{label}: /info backend {info['backend']}, "
            f"compile_cache_misses {warm} after two requests and "
            f"{info['compile_cache_misses']} after five, attention "
            f"routes {info.get('attention_routes')}")
        return out, info
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
                smoke.check(False, f"{label}: server ignored SIGTERM")
        with open(log_path, errors="replace") as f:
            text = f.read()
        cache_report(smoke, label, before, text)
        if smoke.failures:
            say(f"{label}: end of the server's log:\n{text[-2000:]}")


def serve_phase(smoke: Smoke) -> None:
    serve_once(smoke, "serve")


def meshed_phase(smoke: Smoke) -> None:
    """``--mesh tp=4`` against the unmeshed server, which starts only
    after the meshed one has exited.  The repo's contract is bitwise
    token equality per seed (README "Meshed serving")."""
    meshed = serve_once(smoke, "serve-tp4", ("--mesh", "tp=4"))
    plain = serve_once(smoke, "serve-one-chip", child_env=one_chip_env())
    if meshed is None or plain is None:
        return
    smoke.check(meshed[0] == plain[0],
                "meshed: tp=4 tokens differ from the unmeshed server's")
    info = meshed[1]
    pools = info.get("kv_pool_shardings") or []
    say(f"meshed: mesh {info.get('mesh')}, KV pool leaves "
        f"{json.dumps(pools)}")
    # From the live arrays' shardings: every KV leaf on four devices,
    # its heads axis (second to last) cut in four.
    kv = [p for p in pools if len(p["shape"]) >= 4]
    smoke.check(
        bool(kv) and all(
            p["devices"] == 4 and "tp" in p["spec"]
            and p["shard_shape"][-2] * 4 == p["shape"][-2] for p in kv),
        f"meshed: KV pools are not sharded over heads on four devices: "
        f"{pools}")
    smoke.check(info.get("mesh_devices") == 4,
                f"meshed: mesh over {info.get('mesh_devices')} devices")


# ---------------------------------------------------------------------------


def finish(smoke: Smoke) -> int:
    device = smoke.device or {"platform": None, "kind": None, "count": 0}
    ok = (not smoke.failures and device["platform"] == "tpu"
          and device["count"] == smoke.args.chips)
    for what in smoke.failures:
        say(f"failed: {what}")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: only the paths that span four chips, "
                             "each with what it is compared with")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(ROOT,
                                                      "chip_smoke_out"),
                        help="run store, logs (emptied first)")
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal: gpt2-tiny, carry on without "
                             "a TPU; never ok")
    parser.add_argument("--rehearse-fault", choices=("closed-port",),
                        default=None,
                        help="rehearse a failing phase: the serve phase "
                             "asks a port nobody listens on")
    args = parser.parse_args(argv)
    smoke = Smoke(args)
    shutil.rmtree(smoke.out, ignore_errors=True)
    os.makedirs(smoke.out)

    smoke.device = probe_device(smoke)
    say(f"device: {smoke.device}; compile cache: {smoke.cache_dir} "
        f"({smoke.cache_entries()} entries)")
    if smoke.device is None or not (
            args.rehearse or (smoke.device["platform"] == "tpu"
                              and smoke.device["count"] == args.chips)):
        smoke.check(False, f"wanted {args.chips} TPU chip(s), JAX found "
                           f"{smoke.device}")
        return finish(smoke)

    phases = (train_phase, serve_phase) if args.chips == 1 \
        else (dp4_phase, meshed_phase)
    for phase in phases:
        say(f"--- {phase.__name__} ---")
        try:
            phase(smoke)
        except Exception as e:  # recorded: the run then ends non-zero
            traceback.print_exc()
            smoke.check(False, f"{phase.__name__}: {type(e).__name__}: "
                               f"{e}")
    return finish(smoke)


if __name__ == "__main__":
    sys.exit(main())
