"""On-chip roofline probe: measured HBM bandwidth + MXU throughput.

VERDICT r3 task 2 accepts "0.40 MFU or a written profile-backed ceiling
analysis" for ResNet-50.  The offline v5e harness derived the ceiling
from the XLA cost model's bytes_accessed — analytic, not profiled.  This
probe closes the loop ON THE REAL CHIP:

1. **HBM bandwidth**: stream a multi-GiB bf16 saxpy (read x, read y,
   write out → 3 arrays of traffic) and report achieved GB/s.  This is
   the classic STREAM-triad number; XLA fuses the multiply-add into one
   kernel so the measurement is pure memory throughput.
2. **MXU throughput**: a big bf16 matmul chain (8k^3, f32 accumulation
   — the training regime) and report achieved TFLOP/s.  This calibrates
   what "peak" really means on this chip (clock throttling, padding
   losses) instead of trusting the spec sheet.
3. **Per-model ceilings**: for every ``offline-v5e`` row in
   results.jsonl (which carries the optimized-HLO ``bytes_accessed`` and
   analytic FLOPs of the REAL train step), compute the roofline step
   time  t_min = max(F / flops_meas, B / bw_meas)  and the implied MFU
   ceiling  F / t_min / peak_spec.  A model whose measured MFU sits on
   this ceiling is bandwidth-bound — more tuning cannot move it; only a
   traffic reduction (fusion, dtype, layout) can.

Appends ``{"bench": "roofline-probe"}`` rows to results.jsonl.

Run: python benchmarks/bench_roofline_probe.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench as B  # noqa: E402

RESULTS = os.path.join(REPO, "benchmarks", "results.jsonl")


def _sync(jax, x):
    jax.block_until_ready(x)


def measure_hbm_bw(jax, gib: float = 2.0, iters: int = 10):
    """STREAM-triad: out = a * x + y over bf16 arrays (~gib each)."""
    import jax.numpy as jnp

    n = int(gib * (1 << 30) / 2)  # bf16 elements per array
    x = jnp.ones((n,), jnp.bfloat16)
    y = jnp.ones((n,), jnp.bfloat16)

    @jax.jit
    def triad(x, y):
        return 2.0 * x + y

    out = triad(x, y)
    _sync(jax, out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = triad(out, y)
    _sync(jax, out)
    dt = (time.perf_counter() - t0) / iters
    bytes_moved = 3 * n * 2  # read out, read y, write out
    return bytes_moved / dt, dt


def measure_mxu(jax, m: int = 8192, iters: int = 10):
    """Achieved bf16 matmul TFLOP/s with f32 accumulation (train regime)."""
    import jax.numpy as jnp

    a = jnp.ones((m, m), jnp.bfloat16)
    b = jnp.ones((m, m), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        # Chain keeps the MXU busy across iters without host round-trips;
        # preferred_element_type pins the training accumulation dtype.
        c = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return c.astype(jnp.bfloat16)

    c = mm(a, b)
    _sync(jax, c)
    t0 = time.perf_counter()
    for _ in range(iters):
        c = mm(c, b)
    _sync(jax, c)
    dt = (time.perf_counter() - t0) / iters
    return 2.0 * m * m * m / dt, dt


def model_ceilings(flops_meas: float, bw_meas: float, peak_spec: float):
    """Roofline ceiling per offline-v5e row (real train-step HLO)."""
    rows = []
    try:
        with open(RESULTS) as f:
            for raw in f:
                try:
                    row = json.loads(raw)
                except ValueError:
                    continue
                if row.get("bench") != "offline-v5e":
                    continue
                # Scanned transformers' HLO bytes miss ~(L-1)/L of layer
                # traffic (XLA counts the nn.scan body once) — their
                # rows carry cost_model_valid:false and must not become
                # "compute-bound" ceilings here (same gate as
                # bench_offline_v5e.analyze).
                if row.get("cost_model_valid") is not True:
                    continue
                flops = row.get("step_flops_analytic")
                bytes_acc = row.get("hlo_bytes_accessed")
                if not flops or not bytes_acc:
                    continue
                t_compute = flops / flops_meas
                t_memory = bytes_acc / bw_meas
                t_min = max(t_compute, t_memory)
                rows.append({
                    "model": row.get("model"),
                    "variant": row.get("variant"),
                    "batch": row.get("batch"),
                    "arithmetic_intensity": round(flops / bytes_acc, 1),
                    "bound": ("memory" if t_memory > t_compute
                              else "compute"),
                    "t_min_ms": round(t_min * 1e3, 2),
                    "mfu_ceiling": round(flops / t_min / peak_spec, 4),
                })
    except OSError:
        pass
    # Newest row per (model, variant, batch) wins.
    dedup = {}
    for r in rows:
        dedup[(r["model"], r["variant"], r["batch"])] = r
    return list(dedup.values())


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gib", type=float, default=2.0)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    jax, backend = B.init_backend(False)

    from polyaxon_tpu.chips import peak_bf16_flops

    peak_spec = peak_bf16_flops(jax.devices()[0].device_kind)
    bw, bw_dt = measure_hbm_bw(jax, args.gib, args.iters)
    print(f"# HBM triad: {bw / 1e9:.0f} GB/s ({bw_dt * 1e3:.1f} ms/iter)",
          file=sys.stderr)
    flops_meas, mm_dt = measure_mxu(jax, iters=args.iters)
    print(f"# MXU bf16: {flops_meas / 1e12:.1f} TFLOP/s "
          f"({mm_dt * 1e3:.1f} ms/iter)", file=sys.stderr)

    row = {
        "bench": "roofline-probe", "ts": time.time(), "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "hbm_bw_gbs": round(bw / 1e9, 1),
        "mxu_bf16_tflops": round(flops_meas / 1e12, 2),
        "peak_spec_tflops": round(peak_spec / 1e12, 2),
        "mxu_fraction_of_spec": round(flops_meas / peak_spec, 4),
        "ceilings": model_ceilings(flops_meas, bw, peak_spec),
    }
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    main()
