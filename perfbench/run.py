#!/usr/bin/env python3
"""perfbench/run.py — one cell of the benchmark, once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, prints earlier lines for a
reader and, as the LAST line of stdout, one JSON object for the driver:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in
a traced run, ``breakdown``.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Everything that belongs to one cell, configuration, mix, driver or
per-layer metric is a file found by its name in ``BENCHMARK.json``
(perfbench/README.md).  This parent never imports JAX while a child
holds the chip; it finds a TPU with the cell's chips or exits non-zero
with no result.  ``--rehearse`` walks the control flow on the CPU with
tiny presets: ``correct`` is never true there and the numbers mean
nothing.
"""

from __future__ import annotations

import time

T_START = time.time()   # set-up counts from process start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import procs  # noqa: E402
from procs import say  # noqa: E402

SEED_FOLD = 2 ** 31 - 1   # the children's --seed is a signed 32-bit int


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py``, found by its name (which may hold
    dots and dashes)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}".replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Checks:
    """What decides ``correct``: ``add`` records, nothing lets a failure
    pass."""

    def __init__(self):
        self.failures = []

    def add(self, ok, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            say(f"FAIL {what}")
        return bool(ok)


class Context:
    """One run's state, handed to the driver and then to the per-layer
    readers."""

    def __init__(self, args, bench):
        self.bench = bench
        self.cell = next(w for w in bench["workloads"]
                         if w["name"] == args.workload)
        manifest = load_json("workloads", self.cell["name"] + ".json")
        for key in ("config", "traffic", "chips"):
            if manifest[key] != self.cell[key]:
                raise SystemExit(f"workloads/{self.cell['name']}.json and "
                                 f"BENCHMARK.json differ on {key}")
        self.config = load_json("configs", self.cell["config"] + ".json")
        self.mix = load_json("traffic", self.cell["traffic"] + ".json")
        self.seed = args.seed
        self.child_seed = args.seed % SEED_FOLD
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.out = os.path.join(HERE, "out",
                                f"{self.cell['name']}.t{args.trace}")
        self.probe_prefix = os.path.join(self.out, "probe")
        self.setup_limit_s = 1100.0
        self.device = None
        self.checks = Checks()
        self.collected = {}
        self.reduced = None

    def metrics_of(self, group: str):
        name = self.cell["name"]
        return [m for m in self.bench[group]
                if "workloads" not in m or name in m["workloads"]]

    def child_env(self, **extra: str) -> dict:
        """The environment of this run's children; a rehearsal's are
        held to the CPU whatever the machine has."""
        if self.rehearse:
            extra["JAX_PLATFORMS"] = "cpu"
        return procs.child_env(self.probe_prefix, **extra)

    def check_device(self) -> bool:
        """False once the child's JAX has reported a device that is not
        the cell's: another platform than a TPU, or another count."""
        if self.device is None:
            self.device = procs.read_probe(self.probe_prefix)
            if self.device is not None:
                say(f"device: {self.device['platform']} "
                    f"{self.device['kind']!r} x{self.device['count']} "
                    f"(as the child's JAX reports it); compile cache "
                    f"{procs.cache_dir()}")
        if self.device is None or self.rehearse:
            return True
        return self.device["platform"] == "tpu" \
            and self.device["count"] == self.cell["chips"]

    def read_device(self) -> None:
        """Called when the window has closed, the load still on: the
        children read their allocators once more.  The peak of device
        memory with the readings it is made of, and what the probe's own
        calls cost the child."""
        procs.ask_probes(self.probe_prefix)
        self.device = procs.read_probe(self.probe_prefix) or self.device
        d = self.device
        if d is None:
            return
        say(f"memory: peak {d['memory_peak_bytes']} B on the fullest "
            f"chip = the larger of peak_bytes_in_use "
            f"{d.get('peak_bytes_in_use')} and bytes_in_use "
            f"{d.get('bytes_in_use')} + bytes_reserved "
            f"{d.get('bytes_reserved')} at the fullest sample (limit "
            f"{d.get('bytes_limit')}); probe: {d.get('samples')} readings "
            f"outside the window cost the children "
            f"{d.get('cost_s', 0.0):.3f}s")


def reduce_trace(ctx, trace_dir):
    """The traced run's reduction, after every child has gone."""
    import trace_reduce

    file = trace_reduce.find_xplane(trace_dir) if trace_dir else None
    if file is None:
        say(f"trace: no *.xplane.pb under {trace_dir}")
        return None
    t = time.time()
    reduced = trace_reduce.reduce(trace_reduce.load(file),
                                  rehearse=ctx.rehearse)
    say(f"trace: {file} ({os.path.getsize(file)} bytes) reduced in "
        f"{time.time() - t:.1f}s")
    if reduced is not None:
        say("trace: " + json.dumps(
            {k: v for k, v in reduced.items() if k != "top_ops"}))
    return reduced


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal with tiny presets; never "
                             "correct, never a result")
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if not os.path.isdir(os.path.join(ROOT, "polyaxon_tpu")):
        say("no polyaxon_tpu/ beside perfbench/: nothing to measure")
        return 2
    ctx = Context(args, bench)
    shutil.rmtree(ctx.out, ignore_errors=True)
    os.makedirs(ctx.out)
    say(f"cell {ctx.cell['name']}: {ctx.cell['config']} x "
        f"{ctx.cell['traffic']} on {ctx.cell['chips']} chip(s), seed "
        f"{ctx.seed}, {ctx.seconds}s, trace {int(ctx.trace)}"
        + (" [REHEARSAL: not a result]" if ctx.rehearse else ""))

    driver = load_module("drivers", ctx.mix["driver"])
    try:
        result = driver.run(ctx)
    except Exception:
        traceback.print_exc()
        result = {"ok": False}
    if ctx.device is None or not ctx.check_device():
        say(f"wanted {ctx.cell['chips']} TPU chip(s), the child's JAX "
            f"found {ctx.device}: no result")
        return 3
    if not result.get("ok"):
        say("the driver measured nothing: no result")
        return 1

    setup_s = result["t_window"] - T_START
    ctx.collected = result.get("collected", {})
    values = dict(result["end_to_end"], setup_s=setup_s)
    say("end to end: " + json.dumps(values))
    device = {"platform": ctx.device["platform"],
              "kind": ctx.device["kind"], "count": ctx.device["count"],
              "memory_peak_bytes": ctx.device["memory_peak_bytes"]}
    line = {"correct": False, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}

    if ctx.trace:
        ctx.reduced = reduce_trace(ctx, result.get("trace_dir"))
        if ctx.reduced is None:
            say("the traced run holds no device operation: no result")
            return 1
        if not ctx.rehearse:    # a CPU's trace under no device's name
            device["busy_s"] = ctx.reduced["busy_s"]
            device["window_s"] = ctx.reduced["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, s]
                               for n, s in ctx.reduced["top_ops"][:10]],
                # The program puts no TraceAnnotation on its host
                # sections yet, so an idle gap cannot be given to what
                # the host did.
                "idle_gaps": [],
            }
        for metric in ctx.metrics_of("per_layer"):
            if ctx.rehearse and metric["source"] == "device_trace":
                continue
            try:
                value = load_module("layer_metrics",
                                    metric["name"]).read(ctx)
            except Exception as e:     # a reader with nothing to read
                say(f"{metric['name']}: {type(e).__name__}: {e}")
                value = None
            if value is not None:
                line["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        for metric in ctx.metrics_of("end_to_end"):
            value = values.get(metric["name"])
            if not ctx.checks.add(value is not None,
                                  f"{metric['name']} could not be "
                                  f"computed over this window"):
                continue
            line["metrics"][metric["name"]] = {"value": value,
                                               "unit": metric["unit"]}
    ctx.checks.add(line["failed"] == 0,
                   f"{line['failed']} of {line['attempted']} failed")
    for what in ctx.checks.failures:
        say(f"failed: {what}")
    line["correct"] = not ctx.checks.failures and not ctx.rehearse
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
