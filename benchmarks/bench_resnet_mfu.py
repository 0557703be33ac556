"""ResNet-50 single-chip MFU sweep (VERDICT r2 task 2).

r2's committed number — 2008 img/sec/chip, MFU 0.244, batch 128 — left
the chip idle ~75% of the time.  This sweep walks the knobs that move
conv-net MFU on a v5e chip:

- **batch** 128/256/512: bigger batches amortize BN/elementwise
  bandwidth and per-step launch overhead over more MXU work.
- **optimizer** momentum vs plain SGD: momentum reads+writes a second
  f32 param-sized buffer every step (pure HBM bandwidth).
- **BN dtype** f32 vs bf16: the normalize-scale-shift chain in bf16
  halves its HBM traffic and fuses into the conv epilogue.

Each point appends a ``{"bench": "resnet50-mfu-sweep"}`` row to
``benchmarks/results.jsonl`` as it is measured, and the best point updates
``.bench_baseline.json`` under ``resnet50:tpu`` with its full config
(batch/overrides/optimizer) so the default bench replays it.

Run: python benchmarks/bench_resnet_mfu.py [--steps 30] [--quick]
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
    cfgs = [
        (128, "base", None, None),
        (256, "base", None, None),
        (512, "base", None, None),
        (256, "sgd-nomom", None, "sgd-nomom"),
        (256, "bn-bf16", {"norm_dtype": "bf16"}, None),
        (512, "bn-bf16", {"norm_dtype": "bf16"}, None),
        (512, "bn-bf16+nomom", {"norm_dtype": "bf16"}, "sgd-nomom"),
        # MLPerf space-to-depth stem: the 7x7/s2-on-3-channels conv is
        # the lowest-occupancy MXU op in the net (exact-equivalence
        # pinned in tests/test_models.py::TestSpaceToDepthStem).
        (256, "s2d-stem", {"stem": "space_to_depth"}, None),
        (512, "s2d-stem+bn-bf16",
         {"stem": "space_to_depth", "norm_dtype": "bf16"}, None),
    ]
    return cfgs[:3] if quick else cfgs


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--quick", action="store_true")
    parser.add_argument(
        "--only", default=None,
        help="comma list of batch:variant legs to run (e.g. "
             "'512:bn-bf16,256:s2d-stem') — lets a re-run carry "
             "only the still-missing rows")
    args = parser.parse_args()
    cfgs = sweep_configs(args.quick)
    if args.only:
        wanted = {tuple(x.strip().split(":", 1))
                  for x in args.only.split(",")}
        known = {(str(c[0]), c[1]) for c in cfgs}
        bad = {":".join(w) for w in wanted if w not in known}
        if bad:
            # A typo'd leg silently running an empty sweep would
            # spend chip time measuring nothing.
            raise SystemExit(
                f"--only entries match no sweep config: "
                f"{sorted(bad)}; known legs: "
                f"{sorted(':'.join(k) for k in known)}")
        cfgs = [c for c in cfgs if (str(c[0]), c[1]) in wanted]
    return B.run_mfu_sweep("resnet50", cfgs,
                           steps=args.steps, warmup=args.warmup)


if __name__ == "__main__":
    sys.exit(main())
