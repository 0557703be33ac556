"""Train-step benchmark: prints ONE JSON line.

Measures the headline BASELINE metric — ResNet-50 training throughput in
img/sec/chip (BASELINE.json: "ResNet-50 img/sec/chip via `polyaxon run`")
— plus MFU (model FLOPs utilization: analytic FLOPs per step ÷ measured
step time ÷ chip peak bf16 FLOPs, ``polyaxon_tpu/chips.py``).

Without ``--cpu`` it finds a TPU or exits non-zero: a CPU number is
never written under the metric's name.  One process holds the chip, so
``--all`` runs its jobs in this process, one after the other.

``vs_baseline`` is reported against the framework's own recorded best
(``.bench_baseline.json``); None until a baseline exists for this
model+backend.

Usage: python bench.py [--model resnet50] [--batch N] [--steps N]
       python bench.py --all     # bench every headline model, append
                                 # benchmarks/results.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def init_backend(force_cpu: bool):
    """``(jax, backend)``: the CPU when asked for, else a TPU or exit.

    No probe, no retry, no fallback: a run that cannot reach its chip
    fails, and says so."""
    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    from polyaxon_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    backend = jax.default_backend()
    if not force_cpu and backend != "tpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU (backend {backend!r}); a CPU "
            f"run must be asked for with --cpu")
    return jax, backend


def compile_step(step_fn, state, batch, rng):
    """AOT-compile via TrainStep.precompile; return (flops, compile_s).

    precompile installs the executable so the timed loop reuses it
    (lower().compile() does not share jit's cache, and a second full XLA
    compile of gpt2-medium costs minutes on TPU).  cost_analysis()
    describes the post-SPMD per-device module, so the returned FLOPs are
    per chip.
    """
    flops = None
    compile_s = None
    try:
        compiled, compile_s = step_fn.precompile(state, batch, rng)
        flops = _module_flops(compiled) or None
    except Exception as e:
        print(f"# cost analysis unavailable: {type(e).__name__}",
              file=sys.stderr)
    return flops, compile_s


def _setup_step(jax, spec, batch_size: int, overrides, optimizer):
    """One benchable train step: (model, mesh, step, state, batch, rng).
    Single source of truth for the bench mesh/optimizer defaults —
    bench_model and reconcile_flops's probes MUST measure the same
    kind of module."""
    import optax

    from polyaxon_tpu.parallel import MeshSpec, build_mesh, \
        make_train_step

    model, params = spec.init_params(batch_size=2, **(overrides or {}))
    mesh = build_mesh(MeshSpec(dp=-1))
    step = make_train_step(spec.loss_fn(model),
                           optimizer or optax.sgd(0.1, momentum=0.9),
                           mesh)
    state = step.init_state(params)
    batch = spec.make_batch(batch_size)
    batch = jax.device_put(batch, step.batch_sharding)
    return model, mesh, step, state, batch, jax.random.PRNGKey(0)


def _module_flops(compiled) -> float:
    """Per-chip FLOPs from a compiled module's cost analysis."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return float(cost.get("flops", 0.0))


def scan_bridge(probes, num_layers: int):
    """The ONE place that owns the scanned-transformer bridge
    arithmetic (shared by reconcile_flops and
    benchmarks/bench_offline_v5e.bridge_scanned — keep the two
    callers' corrections consistent by changing it HERE).

    ``probes``: per-depth measurements ``[(value_at_L1, ...),
    (value_at_L2, ...)]`` — any number of parallel quantities (flops,
    bytes).  Returns the full-depth reconstruction
    ``v1 + (L-1)*(v2-v1)`` per quantity, or None if any probe value
    is falsy (cost analysis unavailable).
    """
    (p1, p2) = probes
    out = []
    for v1, v2 in zip(p1, p2):
        if not v1 or not v2:
            return None
        out.append(v1 + (num_layers - 1) * (v2 - v1))
    return tuple(out)


def _probe_cost_flops(jax, spec, batch_size: int, overrides,
                      optimizer) -> float:
    """Per-chip XLA cost-analysis FLOPs of one train step compiled
    with the given config overrides (used by reconcile_flops's
    unrolled L=1/L=2 probes; never executed)."""
    _, _, step, state, batch, rng = _setup_step(
        jax, spec, batch_size, overrides, optimizer)
    compiled, _ = step.precompile(state, batch, rng)
    return _module_flops(compiled)


def reconcile_flops(jax, spec, batch_size: int, overrides, optimizer,
                    backend: str, n_chips: int = 1):
    """Bridge XLA's compiled-module FLOP count to the analytic MFU
    numerator (VERDICT r4 weak #3; docs/SCALING.md "MFU accounting").

    Two systematic undercounts make the raw ``cost_analysis`` number
    useless for scanned transformers:

    1. **Scan bodies count once.**  The layer stack runs under
       ``nn.scan`` and XLA reports the body's FLOPs once, not
       x num_layers (verified: gpt2-tiny scanned 219M vs unrolled
       327M).  Measured bridge: compile the SAME config unrolled at
       L=1 and L=2; their difference is one layer's FLOPs as XLA
       actually counts it (fusions included), so
       ``f1 + (L-1) * (f2 - f1)`` reconstructs the full-depth count.
    2. **Pallas kernels are invisible.**  On TPU the flash-attention
       custom call reports zero FLOPs; the registry's analytic
       attention term (``spec.attn_flops``) is added back.  Off-TPU
       the reference XLA attention path runs and is already counted.

    Returns a dict with the reconstructed per-chip count and the
    bridge components, or None when the model can't be probed (no
    scan_layers/num_layers config).  Note the reconstruction counts
    HARDWARE flops: for remat configs it includes recompute, so it
    legitimately EXCEEDS the analytic model-flops numerator — that
    gap is the remat tax, not an accounting error.
    """
    model = spec.make_model(**(overrides or {}))
    cfg = getattr(model, "cfg", None)
    L = getattr(cfg, "num_layers", None)
    if not L or not hasattr(cfg, "scan_layers"):
        return None
    ov = dict(overrides or {})
    ov["scan_layers"] = False
    f1 = _probe_cost_flops(jax, spec, batch_size,
                           {**ov, "num_layers": 1}, optimizer)
    f2 = _probe_cost_flops(jax, spec, batch_size,
                           {**ov, "num_layers": 2}, optimizer)
    bridged = scan_bridge([(f1,), (f2,)], L)
    if bridged is None:
        return None
    (xla_unrolled,) = bridged
    body = f2 - f1
    attn = 0.0
    if backend == "tpu":
        if spec.attn_flops is None:
            # Flash (pallas) carries the attention FLOPs on TPU and
            # they're invisible to the probes too; without a
            # registered analytic term the "repaired" number would
            # still be missing attention — don't emit a half-bridge.
            return None
        # The analytic term is global and must reflect the OVERRIDDEN
        # config (sweeps patch num_layers/hidden); normalize to
        # per-chip like the post-SPMD module the probes measured.
        attn = spec.attn_flops(batch_size, cfg) / max(1, n_chips)
    return {
        "probe_l1": f1,
        "body_per_layer": body,
        "attn_added": attn,
        "xla_adjusted": xla_unrolled + attn,
    }


def bench_model(jax, model_name: str, batch_size: int, steps: int,
                warmup: int, backend: str, overrides=None, variant=None,
                optimizer=None):
    from polyaxon_tpu.models.registry import get_model

    spec = get_model(model_name)
    _, mesh, step, state, batch, rng = _setup_step(
        jax, spec, batch_size, overrides, optimizer)
    n_chips = mesh.devices.size

    flops, compile_s = compile_step(step, state, batch, rng)

    for _ in range(warmup):
        state, metrics = step(state, batch, rng)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch, rng)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    if final_loss != final_loss:  # NaN guard
        return None

    sec_per_step = dt / steps
    # Unit: tokens/sec for LMs, img/sec for vision models.
    tokens = batch["inputs"].shape
    is_lm = batch["inputs"].ndim == 2
    per_sec = (tokens[0] * tokens[1] if is_lm else batch_size) / sec_per_step

    from polyaxon_tpu.chips import peak_bf16_flops

    peak = peak_bf16_flops(mesh.devices.flat[0].device_kind)
    # MFU numerator: analytic model FLOPs/step when the registry has a
    # closed form (XLA cost_analysis can't see pallas kernel FLOPs);
    # the XLA count is kept as a
    # cross-check (mfu_xla), and for scanned transformers the
    # reconciled count (scan-depth + pallas bridge — reconcile_flops)
    # is emitted as mfu_xla_adjusted.
    analytic = spec.train_flops(batch_size) if spec.train_flops else None
    bridge = None
    if peak:  # two probe compiles buy nothing without a known peak
        try:
            bridge = reconcile_flops(jax, spec, batch_size, overrides,
                                     optimizer, backend, n_chips)
        except Exception as e:
            print(f"# flop reconciliation unavailable: "
                  f"{type(e).__name__}: {str(e)[:120]}", file=sys.stderr)
    mfu = mfu_xla = mfu_xla_adjusted = None
    if peak:
        if analytic:
            mfu = analytic / n_chips / sec_per_step / peak
        if flops:
            # flops is per-chip (post-SPMD module): per-chip work / time
            # / per-chip peak.
            mfu_xla = flops / sec_per_step / peak
        if bridge:
            mfu_xla_adjusted = (bridge["xla_adjusted"]
                                / sec_per_step / peak)
        if mfu is None:
            mfu = mfu_xla_adjusted or mfu_xla

    return {
        "model": model_name,
        "backend": backend,
        "batch": batch_size,
        **({"variant": variant} if variant else {}),
        "n_chips": n_chips,
        "sec_per_step": round(sec_per_step, 5),
        "per_sec_per_chip": round(per_sec / n_chips, 2),
        "unit": ("tok" if is_lm else "img") + "/sec/chip",
        # Global (all-chip) FLOPs per step.  flops_src marks the MFU
        # numerator regime: rows before 2026-07-30 used the per-chip
        # XLA count (which can't see pallas-kernel FLOPs) and have no
        # flops_src field.
        "step_flops": analytic or (flops * n_chips if flops else None),
        "flops_src": ("analytic" if analytic
                      else ("xla" if flops else None)),
        "step_flops_per_chip_xla": flops,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_xla": round(mfu_xla, 4) if mfu_xla is not None else None,
        **({"mfu_xla_adjusted": round(mfu_xla_adjusted, 4),
            "xla_bridge": {k: round(v, 1) for k, v in bridge.items()}}
           if mfu_xla_adjusted is not None else {}),
        # VERDICT r1 #3 criterion: scanned stacks keep compile time
        # flat in depth (gpt2-medium well under 30s on the chip).
        "compile_s": round(compile_s, 1) if compile_s else None,
        "loss": final_loss,
    }


def load_baseline():
    path = os.path.join(os.path.dirname(__file__) or ".",
                        ".bench_baseline.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def baseline_entry(baseline, model, backend):
    """One baseline entry: ``(value, config_dict_or_None)``.

    Entries are either a bare number (legacy) or a dict ``{"value",
    "batch", "overrides", "variant"}`` recording the CONFIG the best
    number was measured at.  The config matters: once an MFU sweep
    commits a faster variant (e.g. resnet50 b512 s2d+bf16-BN) as the
    baseline, a driver-run default bench measuring the STOCK config
    would score vs_baseline < 1 — a phantom regression.  The default
    run replays the recorded config instead (main()).
    """
    e = baseline.get(f"{model}:{backend}")
    if isinstance(e, dict):
        return e.get("value"), e
    return e, None


def decode_overrides(ov):
    """JSON-stored model overrides -> constructor values.

    Dtype-valued config fields are stored by name ("bf16"/"f32") since
    baselines live in a JSON file; everything else passes through.
    """
    if not ov:
        return None
    import jax.numpy as jnp

    dtypes = {"bf16": jnp.bfloat16, "f32": jnp.float32}
    return {k: dtypes.get(v, v) if isinstance(v, str) else v
            for k, v in ov.items()}


def decode_optimizer(name):
    """JSON-stored optimizer name -> optax optimizer (None = bench
    default, sgd+momentum).  Recorded alongside the winning config so a
    nomom-variant baseline is replayed with the optimizer it was
    actually measured with."""
    if name is None:
        return None
    import optax

    if name == "sgd-nomom":
        return optax.sgd(0.1)
    raise ValueError(f"unknown recorded optimizer {name!r}")


def config_matches(result, cfg):
    """Did this run measure the baseline's recorded config?

    vs_baseline against a DIFFERENT config (stock fallback after the
    recorded one failed, or an explicit --batch) is the phantom
    regression baseline_entry exists to avoid — suppress it instead.
    Legacy numeric entries recorded no config; treat as matching.
    """
    if cfg is None:
        return True
    return (result.get("batch") == cfg.get("batch")
            and (result.get("variant") or None)
            == (cfg.get("variant") or None))


def emit(result) -> None:
    baseline = load_baseline()
    # vs_baseline only means something against the baseline recorded
    # for this backend AND this config (config_matches).
    vs = None
    base_val, base_cfg = baseline_entry(baseline, result["model"],
                                        result["backend"])
    if base_val and config_matches(result, base_cfg):
        vs = round(result["per_sec_per_chip"] / base_val, 4)
    variant = result.get("variant")
    line = {
        "metric": (f"{result['model']} {result['unit']} "
                   f"({result['backend']}, batch {result['batch']}"
                   + (f", {variant}" if variant else "") + ")"),
        "value": result["per_sec_per_chip"],
        "unit": result["unit"],
        "vs_baseline": vs,
        "mfu": result["mfu"],
        "backend": result["backend"],
        "sec_per_step": result["sec_per_step"],
    }
    print(json.dumps(line))


def run_mfu_sweep(model_name: str, configs, *, steps: int = 20,
                  warmup: int = 3) -> int:
    """Shared driver for the per-model MFU sweeps
    (benchmarks/bench_resnet_mfu.py, bench_gpt2_mfu.py).

    ``configs``: ``(batch, variant, overrides, optimizer_name)`` tuples.
    Overrides are JSON-safe (dtypes by name — see decode_overrides) and
    the optimizer is a name decode_optimizer resolves, so the WINNING
    config can be recorded verbatim in ``.bench_baseline.json`` and the
    default bench replays exactly what was measured (incl. the
    optimizer — a nomom variant is meaningless under the default
    momentum SGD).

    Appends one ``{"bench": "<model>-mfu-sweep"}`` row per point to
    benchmarks/results.jsonl as it is measured and updates the baseline
    entry if the best point beats it.
    """
    tag = f"{model_name}-mfu-sweep"
    here = os.path.dirname(os.path.abspath(__file__))
    results_path = os.path.join(here, "benchmarks", "results.jsonl")
    baseline_path = os.path.join(here, ".bench_baseline.json")

    def _rank_key(mfu, per_sec):
        # ONE ranking for best-point selection and the commit guard:
        # MFU first when known, throughput as tiebreak.  Guarding the
        # commit on raw throughput while ranking by MFU would let an
        # early high-throughput/low-MFU leg permanently block the
        # MFU-best config from being banked.
        return (mfu is not None, mfu or 0.0, per_sec or 0.0)

    def _commit_baseline(path, model, r, overrides, opt_name):
        try:
            with open(path) as f:
                baseline = json.load(f)
        except (OSError, ValueError):
            baseline = {}
        prev, prev_cfg = baseline_entry(baseline, model, "tpu")
        prev_key = _rank_key((prev_cfg or {}).get("mfu"), prev)
        if _rank_key(r["mfu"], r["per_sec_per_chip"]) > prev_key:
            baseline[f"{model}:tpu"] = {
                "value": r["per_sec_per_chip"],
                "mfu": r["mfu"],
                "batch": r["batch"],
                "variant": r.get("variant"),
                "overrides": overrides,
                "optimizer": opt_name,
            }
            # Atomic replace: these commits happen mid-sweep, exactly
            # where the leg-timeout SIGKILL lands — an in-place write
            # killed mid-json.dump would truncate the file and wipe
            # every model's baseline.
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(baseline, f, indent=1, sort_keys=True)
            os.replace(tmp, path)

    jax, backend = init_backend(False)

    best = best_key = None
    for batch, variant, overrides, opt_name in configs:
        t0 = time.time()
        try:
            r = bench_model(jax, model_name, batch, steps, warmup,
                            backend,
                            overrides=decode_overrides(overrides),
                            variant=variant,
                            optimizer=decode_optimizer(opt_name))
        except Exception as e:
            r = None
            print(f"# {variant} b{batch} failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", file=sys.stderr)
        if not r:
            row = {"bench": tag, "ts": time.time(), "model": model_name,
                   "batch": batch, "variant": variant, "failed": True}
        else:
            row = {"bench": tag, "ts": time.time(),
                   "wall_s": round(time.time() - t0, 1), **r}
            print(f"# b{batch} {variant}: {r['per_sec_per_chip']} "
                  f"{r['unit']} mfu={r['mfu']}", file=sys.stderr)
            # Rank by MFU when the chip's peak is known, else by raw
            # throughput (mfu=None on unrecognized device kinds must
            # not make the FIRST point win every 0>0 tie).
            key = _rank_key(r["mfu"], r["per_sec_per_chip"])
            if best is None or key > best_key:
                best, best_key = r, key
                # Bank the winning config IMMEDIATELY, not after the
                # loop: sweeps get SIGKILLed at the leg timeout and an
                # end-of-sweep commit loses every point already
                # measured (this round's bn-bf16 row beat the baseline
                # by 26% and was dropped exactly this way).
                _commit_baseline(baseline_path, model_name, r,
                                 overrides, opt_name)
        with open(results_path, "a") as f:
            f.write(json.dumps(row) + "\n")

    if best:
        print(json.dumps({"bench": tag, "best_mfu": best["mfu"],
                          "best_batch": best["batch"],
                          "best_variant": best.get("variant"),
                          "per_sec_per_chip":
                          best["per_sec_per_chip"]}))
    return 0


def bench_decode_row(jax, model_name: str, backend: str):
    """One decode/serving row via benchmarks/bench_decode.py's logic."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "bench_decode.py")
    spec = importlib.util.spec_from_file_location("_bench_decode", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.bench_decode(jax, model_name, backend)


def _append_results(rows) -> None:
    """Append evidence rows to benchmarks/results.jsonl (one writer —
    the --all CPU and accelerator paths must not drift apart)."""
    if not rows:
        return
    out = os.path.join(os.path.dirname(__file__) or ".",
                       "benchmarks", "results.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for r in rows:
            f.write(json.dumps({"bench": "headline", "ts": time.time(),
                                **r}) + "\n")


def _append_decode_row(row) -> None:
    """Decode rows carry their own bench tag (not "headline")."""
    out = os.path.join(os.path.dirname(__file__) or ".",
                       "benchmarks", "results.jsonl")
    with open(out, "a") as f:
        f.write(json.dumps({"bench": "decode", "ts": time.time(),
                            **row}) + "\n")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default=None)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument(
        "--variant", default=None,
        help="Label stamped on the result row — marks env-driven A/B "
             "legs (e.g. bwd flash-block tuning) whose config is not "
             "visible in the row otherwise.")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--all", action="store_true",
                        help="Bench every headline model; append each "
                             "result to benchmarks/results.jsonl.")
    parser.add_argument("--cpu", action="store_true",
                        help="Force the CPU backend (a smoke run; the "
                             "metric line says so).")
    parser.add_argument(
        "--decode", default=None, metavar="MODEL",
        help="Run the decode/serving bench for MODEL instead of a "
             "train-step bench.")
    parser.add_argument(
        "--append", action="store_true",
        help="Append the result row(s) to benchmarks/results.jsonl even "
             "without --all.")
    args = parser.parse_args()

    jax, backend = init_backend(args.cpu)
    on_accel = backend == "tpu"

    if args.decode:
        r = bench_decode_row(jax, args.decode, backend)
        if r and args.append:
            _append_decode_row(r)
        print(json.dumps({"metric": f"decode bench ({backend})", "value":
                          (r or {}).get("tok_per_sec_per_chip", 0),
                          "unit": "tok/sec/chip", "vs_baseline": None,
                          "backend": backend}))
        return 0 if r else 1

    if args.all and on_accel:
        models = ["resnet50", "gpt2-medium", "bert-base",
                  "tinyllama-1.1b"]
    elif args.all:
        models = ["resnet50-tiny", "gpt2-tiny", "bert-tiny"]
    else:
        models = [args.model or ("resnet50" if on_accel else
                                 "resnet50-tiny")]

    results = []
    for name in models:
        # gpt2-medium: batch 4 is the recorded config; tinyllama at seq
        # 2048 needs a small batch (f32 optimizer state for 1.1B params
        # on a 16 GB chip).
        batch = args.batch or (
            {"resnet50": 128, "gpt2-medium": 4, "bert-base": 16,
             "tinyllama-1.1b": 2}.get(name, 16) if on_accel else 8)
        # The committed baseline records the CONFIG its best number was
        # measured at; replay it first (see baseline_entry), then the
        # stock config if it fails (e.g. the best batch no longer fits
        # after an unrelated model change).
        attempts = []
        _, base_cfg = baseline_entry(load_baseline(), name, backend)
        if not args.batch and base_cfg and base_cfg.get("batch"):
            attempts.append(
                (base_cfg["batch"],
                 decode_overrides(base_cfg.get("overrides")),
                 base_cfg.get("variant"),
                 decode_optimizer(base_cfg.get("optimizer"))))
        if not any(b == batch and not ov and not var
                   for b, ov, var, _o in attempts):
            attempts.append((batch, None, None, None))
        r = None
        for try_batch, overrides, variant, optimizer in attempts:
            if args.variant:
                # env-driven A/B tag composes with the replayed
                # baseline variant (e.g. "bn-bf16+bwd-block-512")
                variant = (f"{variant}+{args.variant}" if variant
                           else args.variant)
            try:
                r = bench_model(jax, name, try_batch, args.steps,
                                args.warmup, backend,
                                overrides=overrides, variant=variant,
                                optimizer=optimizer)
            except Exception as e:  # next attempt / next model
                print(f"# bench {name} b{try_batch}"
                      f"{' ' + variant if variant else ''} failed: "
                      f"{type(e).__name__}: {str(e)[:300]}",
                      file=sys.stderr)
                r = None
            if r:
                break
        if r:
            results.append(r)
            print(f"# {r['model']}: {r['per_sec_per_chip']} {r['unit']} "
                  f"mfu={r['mfu']}", file=sys.stderr)
            if args.all or args.append:
                # Row by row: a later model's failure must not lose
                # what was already measured.
                _append_results([r])

    if args.all and on_accel:
        # One process holds the chip, so the decode row runs here too.
        try:
            row = bench_decode_row(jax, "gpt2-medium", backend)
        except Exception as e:
            print(f"# decode bench gpt2-medium failed: "
                  f"{type(e).__name__}: {str(e)[:300]}", file=sys.stderr)
        else:
            if row:
                _append_decode_row(row)

    if not results:
        print("bench.py: no model produced a result", file=sys.stderr)
        return 1
    emit(results[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
