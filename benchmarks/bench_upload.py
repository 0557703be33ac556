"""Host cost of handing ONE decode launch's host operands to the device.

A sampled decode dispatch of the slot pool (serving/slots.py) carries
nine small host arrays: the step count, tokens, the fresh mask,
positions, PRNG keys, token indices, temperatures, top-k and top-p.
This times, on the host clock, the two ways of handing them over:

- ``nine``:   one ``jnp.asarray(a.copy())`` an array (the launch before
              packing);
- ``packed``: every array's words laid into ONE int32 buffer
              (``serving.slots.LaunchPacking.pack``: bool as 0/1,
              float32 and uint32 bit for bit) and one
              ``jax.device_put``, what a launch does.

Each is timed with the device idle and with a program of a few
milliseconds in flight, which is where a serving launch runs (the
engine keeps one dispatch ahead).  Rounds alternate between the two
paths.  Prints one JSON line of medians and 90th percentiles in
microseconds a launch.

    python benchmarks/bench_upload.py [--slots 24] [--rounds 600]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def operands(slots: int, rng):
    """The nine host arrays of a sampled launch over ``slots`` slots."""
    return [np.int32(8),
            rng.randint(0, 50257, slots).astype(np.int32),
            rng.rand(slots) < 0.5,
            rng.randint(0, 1024, slots).astype(np.int32),
            rng.randint(0, 2 ** 32, (slots, 2), dtype=np.uint64)
            .astype(np.uint32),
            rng.randint(0, 256, slots).astype(np.int32),
            rng.rand(slots).astype(np.float32),
            rng.randint(0, 64, slots).astype(np.int32),
            rng.rand(slots).astype(np.float32)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, default=24)
    ap.add_argument("--rounds", type=int, default=600)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.serving.slots import LaunchPacking

    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    ops = operands(args.slots, rng)

    def nine():
        return [jnp.asarray(a.copy()) for a in ops]

    def packed():
        return [jax.device_put(LaunchPacking.pack(ops)[0])]

    @jax.jit
    def busy(x):
        return jax.lax.fori_loop(0, 24, lambda i, y: (y @ x) * 0.5, x)

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    t = time.perf_counter()
    busy(x).block_until_ready()
    one = time.perf_counter() - t
    t = time.perf_counter()
    busy(x).block_until_ready()
    busy_ms = (time.perf_counter() - t) * 1e3

    for path in (nine, packed):         # warm every path's first call
        jax.block_until_ready(path())

    times = {f"{name}_{load}": [] for name in ("nine", "packed")
             for load in ("idle", "busy")}
    for r in range(args.rounds):
        for name, path in (("nine", nine), ("packed", packed)) \
                if r % 2 == 0 else (("packed", packed), ("nine", nine)):
            for load in ("idle", "busy"):
                pending = busy(x) if load == "busy" else None
                t = time.perf_counter()
                out = path()
                times[f"{name}_{load}"].append(time.perf_counter() - t)
                jax.block_until_ready(out)
                if pending is not None:
                    pending.block_until_ready()

    def q(v, p):
        return round(float(np.percentile(v, p)) * 1e6, 1)

    print(json.dumps({
        "bench": "upload",
        "device": f"{dev.platform} {dev.device_kind}",
        "slots": args.slots, "rounds": args.rounds,
        "arrays": len(ops),
        "packed_words": LaunchPacking(ops).size,
        "busy_program_ms": round(busy_ms, 3),
        "first_call_s": round(one, 2),
        **{f"{k}_p50_us": q(v, 50) for k, v in times.items()},
        **{f"{k}_p90_us": q(v, 90) for k, v in times.items()},
    }))


if __name__ == "__main__":
    main()
