"""Windowed-attention O(W) kv-grid remap: measured win (VERDICT r2 #4).

A/B of the SAME sliding-window flash kernels with the kv-grid remap on
vs off (POLYAXON_TPU_FLASH_NO_REMAP) at long sequence / short window —
the regime windowed attention exists for.  Without the remap the
BlockSpec pipeline DMAs every KV tile (O(S) HBM per q block) even
though masked blocks skip their MXU work; with it, only the
ceil(W/block)+2 tiles that can intersect the window are visited.

Each point times fwd+bwd (grad of sum-of-squares) through the jitted
kernel and appends a ``{"bench": "windowed-attention"}`` row.

Run on TPU: python benchmarks/bench_windowed.py
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "benchmarks", "results.jsonl")

# (seq, window, batch, heads, dim)
POINTS = [(8192, 1024, 2, 8, 64), (8192, 1024, 2, 16, 128),
          (16384, 1024, 1, 8, 128)]


def _measure(seq, window, batch, heads, dim, steps=10):
    """Runs in a CHILD process so the remap env var is set before jax
    traces anything (printed as one JSON line on stdout)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.ops.flash import flash_attention

    if jax.default_backend() != "tpu":
        raise SystemExit("bench_windowed: JAX found no TPU")
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(batch, seq, heads, dim),
                           jnp.bfloat16) for _ in range(3))

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              scale=dim ** -0.5)
        return (out.astype(jnp.float32) ** 2).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    grads = step(q, k, v)
    jax.block_until_ready(grads)
    t0 = time.perf_counter()
    for _ in range(steps):
        grads = step(q, k, v)
    jax.block_until_ready(grads)
    dt = (time.perf_counter() - t0) / steps
    print(json.dumps({"ms": round(dt * 1e3, 3)}))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--child", nargs=5, type=int, default=None,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        _measure(*args.child)
        return 0

    # Each leg is a child that holds the chip while it runs, so this
    # parent stays off JAX: a chip belongs to one process at a time.
    for point in POINTS:
        row = {"bench": "windowed-attention", "backend": "tpu",
               "ts": time.time(), "seq": point[0], "window": point[1],
               "batch": point[2], "heads": point[3], "dim": point[4]}
        # Base env with the A/B switch REMOVED: a stray exported
        # POLYAXON_TPU_FLASH_NO_REMAP would otherwise disable the remap
        # in both legs and record a bogus ~1.0 speedup.
        base_env = {k: v for k, v in os.environ.items()
                    if k != "POLYAXON_TPU_FLASH_NO_REMAP"}
        for label, env in (("remap_ms", {}),
                           ("no_remap_ms",
                            {"POLYAXON_TPU_FLASH_NO_REMAP": "1"})):
            try:
                out = subprocess.run(
                    [sys.executable, __file__, "--child",
                     *map(str, point)],
                    env={**base_env, **env}, capture_output=True,
                    text=True, timeout=900, cwd=REPO)
                row[label] = json.loads(
                    out.stdout.strip().splitlines()[-1])["ms"]
            except Exception as e:
                row[label] = None
                print(f"# {label} {point} failed: {type(e).__name__}",
                      file=sys.stderr)
        if row.get("remap_ms") and row.get("no_remap_ms"):
            row["speedup"] = round(row["no_remap_ms"] / row["remap_ms"], 2)
        print(json.dumps(row))
        with open(RESULTS, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
