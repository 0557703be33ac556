"""BERT-base single-chip MFU sweep (round-5 follow-up).

The committed bert-base TPU row (b16) predates the flash 512-block fix
and the analytic-MFU numerator; beyond refreshing it, b16 is also the
model's memory wall — offline compiles measured b32 un-remattered at
16.49 GB (> the 15.75 GB chip).  BertConfig.remat is all-or-nothing
(the encoder block is one scan'd layer; no dots_saveable split), so
the frontier here is full-remat batch scaling, exactly the gpt2-medium
playbook with one fewer knob:

- b16 base   — refresh the stale committed regime under the current
  numerator (sanity anchor + honest headline row).
- b32 remat  — offline-predicted to fit; recompute tax vs 2x MXU work.
- b64 remat  — whether MFU keeps climbing says compute- or
  bandwidth-bound at encoder shapes (seq 512).

Each point appends a ``{"bench": "bert-base-mfu-sweep"}`` row
IMMEDIATELY and the best point updates ``.bench_baseline.json`` under
``bert-base:tpu`` so the default bench replays it.

Run: python benchmarks/bench_bert_mfu.py [--steps 20] [--quick]
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench as B  # noqa: E402


def sweep_configs(quick: bool):
    # b32 remat is the predicted win (offline ceiling 0.631 vs the b16
    # wall) — run it first so a short window banks the headline point;
    # the b16 refresh anchors second, b64 (flat predicted ceiling,
    # diminishing returns) last.
    cfgs = [
        (32, "remat", {"remat": True}, None),
        (16, "base", None, None),
        (64, "remat", {"remat": True}, None),
    ]
    return cfgs[:2] if quick else cfgs


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()
    return B.run_mfu_sweep("bert-base", sweep_configs(args.quick),
                           steps=args.steps, warmup=args.warmup)


if __name__ == "__main__":
    sys.exit(main())
