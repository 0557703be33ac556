"""BASELINE config 4: Hyperband CIFAR-ConvNet sweep through the FULL
stack, measured (VERDICT r1 #10).

Stack exercised: client submit -> control plane queue -> agent claim ->
LocalBackend -> tuner controller (hyperband brackets/rungs, concurrency
control) -> child runs = real ``polyaxon_tpu.train --model convnet``
subprocesses logging ``loss`` through tracking -> controller joins on
the metric and promotes.

Chaos is part of the measurement: trials drawing ``lr > FAIL_LR`` exit
1 (injected child failure — the divergent-learning-rate analogue); the
sweep must complete and produce a surviving best run anyway.

Emits one JSON line to stdout and appends the full record to
``benchmarks/results.jsonl``:

    {"bench": "sweep-hyperband", "trials": .., "failed": ..,
     "wall_s": .., "max_observed_concurrent": .., "best_metric": ..}

Run: python benchmarks/bench_sweep.py [--max-iterations 8] [--eta 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import textwrap
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAIL_LR = 1.0  # trials above this injected-failure threshold exit 1

# Real training child: tiny CIFAR-shaped ConvNet on the CPU backend.
# The compilation cache is shared across trials (train.py places it:
# config.enable_compilation_cache) so only the first trial at each
# step-count pays XLA.
CHILD_CODE = textwrap.dedent(f"""
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    lr = float(sys.argv[1])
    epochs = int(float(sys.argv[2]))
    if lr > {FAIL_LR}:
        print("injected failure: lr diverges", file=sys.stderr)
        sys.exit(1)
    sys.argv = ["train", "--model", "convnet", "--lr", str(lr),
                "--steps", str(3 * epochs), "--batch-size", "16",
                "--optimizer", "sgd", "--log-every", "3"]
    from polyaxon_tpu.train import main
    sys.exit(main() or 0)
""")


def sweep_operation(max_iterations: int, eta: int, concurrency: int,
                    algo: str = "hyperband", num_runs: int = 16):
    if algo == "hyperband":
        matrix = {
            "kind": "hyperband",
            "maxIterations": max_iterations,
            "eta": eta,
        }
    else:  # asha: barrier-free promotions, same budget semantics
        matrix = {
            "kind": "asha",
            "numRuns": num_runs,
            "maxIterations": max_iterations,
            "eta": eta,
            "minResource": 1,
        }
    matrix.update({
        "resource": {"name": "epochs", "type": "int"},
        "metric": {"name": "loss", "optimization": "minimize"},
        "params": {"lr": {"kind": "loguniform", "value": [1e-4, 3.0]}},
        "seed": 7,
        "concurrency": concurrency,
    })
    return {
        "kind": "operation",
        "name": f"cifar-{algo}",
        "matrix": matrix,
        "component": {
            "kind": "component",
            "inputs": [
                {"name": "lr", "type": "float"},
                {"name": "epochs", "type": "int", "value": 1,
                 "isOptional": True},
            ],
            "run": {
                "kind": "job",
                "container": {
                    "command": [sys.executable, "-c", CHILD_CODE],
                    "args": ["{{ lr }}", "{{ epochs }}"],
                },
            },
        },
    }


def max_concurrent(children) -> int:
    """Peak overlap of child [start, end] execution windows."""
    events = []
    for child in children:
        start = child.get("created_at")
        duration = child.get("duration") or 0
        if start is None:
            continue
        events.append((start, 1))
        events.append((start + duration, -1))
    peak = live = 0
    for _, delta in sorted(events):
        live += delta
        peak = max(peak, live)
    return peak


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--max-iterations", type=int, default=8)
    parser.add_argument("--eta", type=int, default=2)
    parser.add_argument("--concurrency", type=int, default=32)
    parser.add_argument("--timeout", type=float, default=3600)
    parser.add_argument("--algo", default="hyperband",
                        choices=("hyperband", "asha"))
    parser.add_argument("--num-runs", type=int, default=16,
                        help="asha: configs sampled at rung 0")
    args = parser.parse_args()

    # Children inherit forced-CPU jax; they share the compilation cache
    # that train.py places itself (config.enable_compilation_cache).
    home = tempfile.mkdtemp(prefix="ptpu-sweep-")
    os.environ["POLYAXON_TPU_HOME"] = home

    from polyaxon_tpu.client.store import FileRunStore
    from polyaxon_tpu.lifecycle import V1Statuses
    from polyaxon_tpu.polyaxonfile import get_op_from_files
    from polyaxon_tpu.runner.agent import Agent, LocalBackend
    from polyaxon_tpu.scheduler.api import ControlPlane

    store = FileRunStore(home)
    plane = ControlPlane(store)
    op_dict = sweep_operation(args.max_iterations, args.eta,
                              args.concurrency, algo=args.algo,
                              num_runs=args.num_runs)
    operation = get_op_from_files([op_dict])

    record = store.create_run(name=f"cifar-{args.algo}", project="bench",
                              content=operation.to_dict(),
                              kind="tuner")
    store.set_status(record["uuid"], V1Statuses.QUEUED)

    agent = Agent(plane, backend=LocalBackend(store, project="bench"),
                  name="bench-agent", poll_interval=0.05)
    agent_thread = threading.Thread(target=agent.run_forever, daemon=True)

    t0 = time.perf_counter()
    agent_thread.start()
    deadline = time.time() + args.timeout
    final = None
    while time.time() < deadline:
        final = store.get_run(record["uuid"])
        if final.get("status") in V1Statuses.DONE:
            break
        time.sleep(0.5)
    wall = time.perf_counter() - t0
    agent.stop()

    children = store.list_runs(pipeline=record["uuid"])
    failed = [c for c in children
              if c.get("status") == V1Statuses.FAILED]
    outputs = (final or {}).get("outputs") or {}
    best_uuid = outputs.get("best_run")
    best_survived = bool(
        best_uuid
        and store.get_run(best_uuid).get("status")
        == V1Statuses.SUCCEEDED) if best_uuid else None

    result = {
        "bench": f"sweep-{args.algo}",
        "model": "convnet",
        "backend": "cpu",
        "status": (final or {}).get("status"),
        "trials": len(children),
        "failed": len(failed),
        "wall_s": round(wall, 1),
        "sec_per_trial": round(wall / max(1, len(children)), 2),
        "concurrency": args.concurrency,
        "max_observed_concurrent": max_concurrent(children),
        "host_cpus": os.cpu_count(),
        "num_succeeded": outputs.get("num_succeeded"),
        "best_metric": outputs.get("best_metric"),
        "best_params": outputs.get("best_params"),
        "best_run_succeeded": best_survived,
        "ts": time.time(),
    }
    print(json.dumps(result))
    out = os.path.join(REPO, "benchmarks", "results.jsonl")
    with open(out, "a") as f:
        f.write(json.dumps(result) + "\n")
    # Success gate scales with the algorithm's actual budget: ASHA at
    # --num-runs 16 tops out at 16+8+4+2 = 30 jobs, so hyperband's 32
    # floor can never pass; and with only num_runs loguniform draws the
    # injected-failure assertion is ~17% flaky (P(no lr > 1) ≈
    # 0.89^16), so chaos is asserted only where the draw count makes
    # it near-certain (hyperband's 35 draws).
    min_trials = 32 if args.algo == "hyperband" else args.num_runs
    chaos_ok = result["failed"] > 0 if args.algo == "hyperband" \
        else True
    ok = (result["status"] == V1Statuses.SUCCEEDED
          and result["trials"] >= min_trials and chaos_ok
          and result["best_metric"] is not None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
