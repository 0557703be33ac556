"""Process control for the benchmark's children.

Copied from ``chip_smoke.py`` (proven on the v5e in PR 22) and cut to
what the harness needs: a child environment, a child started in its own
process group, a stop that waits until the whole group has gone, the
HTTP helper, and the compile-cache report.

One process holds a chip, so nothing here imports JAX.  What the device
is, and how full it got, comes from the child itself: ``probe/`` is put
on the child's ``PYTHONPATH`` and its ``sitecustomize`` writes what the
child's own JAX reports (see ``probe/sitecustomize.py``).
"""

from __future__ import annotations

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROBE_DIR = os.path.join(HERE, "probe")


def say(msg: str) -> None:
    """An earlier line of the run's output (never the last)."""
    sys.stdout.write(msg + "\n")
    sys.stdout.flush()


def cache_dir() -> str:
    """Where ``config.enable_compilation_cache`` puts the cache: from
    outside when ``JAX_COMPILATION_CACHE_DIR`` is set, else inside the
    checkout.  Restated here so the parent stays off JAX."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")


def cache_entries() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except OSError:
        return 0


def cache_report(label: str, before: int, log_text: str) -> dict:
    """JAX's own hit/miss lines, counted from the child's log."""
    hits = log_text.count("Persistent compilation cache hit")
    misses = log_text.count("PERSISTENT COMPILATION CACHE MISS")
    say(f"{label}: compile cache {cache_dir()} entries {before} -> "
        f"{cache_entries()}, hits {hits}, misses {misses}")
    return {"hits": hits, "misses": misses}


def child_env(probe_prefix: str, **extra: str) -> dict:
    """The environment of a child that may hold the chip."""
    env = dict(os.environ)
    path = [PROBE_DIR, ROOT]
    if env.get("PYTHONPATH"):
        path.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(path)
    # JAX's own cache hit/miss lines (debug level) onto the child's
    # stderr: counted by cache_report, nothing added to the program.
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    # Persist sub-second compiles too, also where the cache directory
    # comes from outside (config.py leaves JAX's 1 s threshold alone
    # there, and a server is ~90 sub-second programs).
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["PERFBENCH_PROBE_PREFIX"] = probe_prefix
    env.pop("BENCH_RUN", None)
    env.update(extra)
    return env


def start(cmd, log_path: str, env: dict) -> subprocess.Popen:
    """Start ``cmd`` from the checkout's root as the leader of a new
    process group, its output in ``log_path``."""
    with open(log_path, "w") as sink:
        return subprocess.Popen(cmd, cwd=ROOT, stdout=sink,
                                stderr=subprocess.STDOUT, env=env,
                                start_new_session=True)


def _group_members(pgid: int) -> list:
    """Live (non-zombie) pids of the process group, from /proc."""
    out = []
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        # after "pid (comm)": state ppid pgrp ...
        if int(fields[2]) == pgid and fields[0] != "Z":
            out.append(int(stat.split("/")[2]))
    return out


def stop(proc: subprocess.Popen, sig: int, grace: float = 60.0) -> bool:
    """Send ``sig`` to the child's whole group, wait until every
    process of it has ended, SIGKILL what outlives ``grace``.  True if
    the first signal was enough."""
    pgid = proc.pid
    clean = True

    def send(s):
        try:
            os.killpg(pgid, s)
        except ProcessLookupError:
            pass

    send(sig)
    deadline = time.time() + grace
    while time.time() < deadline:
        if proc.poll() is not None and not _group_members(pgid):
            break
        time.sleep(0.05)
    else:
        clean = False
        send(signal.SIGKILL)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        clean = False
    end = time.time() + 30
    while _group_members(pgid) and time.time() < end:
        send(signal.SIGKILL)
        time.sleep(0.1)
    return clean


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 600.0):
    """``(status, parsed JSON)``; an HTTP error status is returned, not
    raised."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except ValueError:
            return e.code, {}


def read_probe(prefix: str):
    """What the children's own JAX reported: the device (from the child
    that sees the most devices), the fullest child's memory readings,
    and what the probes cost.  None until a child has initialised a
    backend."""
    records = []
    for path in glob.glob(prefix + ".*.json"):
        try:
            with open(path) as f:
                records.append(json.load(f))
        except (OSError, ValueError):
            continue        # being replaced
    if not records:
        return None
    device = max(records, key=lambda r: r["count"])
    fullest = max(records, key=lambda r: r["memory_peak_bytes"])
    return dict(fullest, platform=device["platform"], kind=device["kind"],
                count=device["count"],
                samples=sum(r["samples"] for r in records),
                cost_s=sum(r["cost_s"] for r in records))


def ask_probes(prefix: str, wait: float = 3.0) -> None:
    """Have every reporting child read its allocator once more, and
    wait until each has (or ``wait`` seconds)."""
    asks = [path[:-len("json")] + "ask"
            for path in glob.glob(prefix + ".*.json")]
    for ask in asks:
        open(ask, "w").close()
    deadline = time.time() + wait
    while any(map(os.path.exists, asks)) and time.time() < deadline:
        time.sleep(0.05)


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""
