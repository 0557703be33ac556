"""GPT-2-medium single-chip MFU sweep (round-4 follow-on to the ResNet
sweep).

The offline v5e harness pinned gpt2-medium b4/seq-1024 at 15.46 G of the
chip's 15.75 G HBM — the un-remattered config sits against the
memory wall, so batch (the main MFU lever for LMs on the MXU) cannot
move.  Remat trades ~30 % more FLOPs for O(layers) less activation HBM;
a selective policy (``dots_saveable``: keep matmul outputs, recompute
the cheap elementwise chain) costs far less recompute than full remat.
This sweep walks that frontier on the real chip:

- b4  base        — the committed regime (sanity anchor).
- b8  remat+dots  — selective remat should fit b8 and amortize
  bandwidth/launch overhead over 2x the MXU work.
- b16 remat+dots  — bigger still; whether MFU keeps climbing tells us
  if the model is compute- or bandwidth-bound at this size.
- b8  remat-full  — isolates the recompute tax of full vs selective.

Each point appends a ``{"bench": "gpt2-medium-mfu-sweep"}`` row to
``benchmarks/results.jsonl`` as it is measured, and the best point updates ``.bench_baseline.json`` under
``gpt2-medium:tpu`` with its full config so the default bench replays
it.

Run: python benchmarks/bench_gpt2_mfu.py [--steps 20] [--quick]
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench as B  # noqa: E402


def sweep_configs(quick: bool):
    # (batch, variant, JSON-safe overrides, optimizer name) — see
    # bench.run_mfu_sweep for the encoding contract.
    # Offline memory predictions (bench_offline_v5e, round 5): b8
    # remat-dots peaks at 9.67 GB (fits), b16 at 15.80 GB — OVER the
    # 15.75 GB chip, so b12 is the committed fallback and b16 runs
    # LAST (an OOM there costs nothing already banked).  The b4 no-
    # remat bridged roofline caps at MFU 0.436 (memory-bound): batch
    # scaling under remat is the only path past it.
    # Value-per-minute order: the b8 remat-dots point (predicted
    # ceiling 0.753) runs FIRST; the b4 anchor was
    # already measured live in round 4 (0.375) and drops to third;
    # b16 stays last (predicted to brush the 15.75 GB limit — an OOM
    # there costs nothing already banked).
    cfgs = [
        (8, "remat-dots",
         {"remat": True, "remat_policy": "dots_saveable"}, None),
        (12, "remat-dots",
         {"remat": True, "remat_policy": "dots_saveable"}, None),
        (4, "base", None, None),
        (8, "remat-full", {"remat": True}, None),
        (16, "remat-dots",
         {"remat": True, "remat_policy": "dots_saveable"}, None),
    ]
    return cfgs[:2] if quick else cfgs


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()
    return B.run_mfu_sweep("gpt2-medium", sweep_configs(args.quick),
                           steps=args.steps, warmup=args.warmup)


if __name__ == "__main__":
    sys.exit(main())
