"""Decode/serving benchmark (VERDICT r2 task 7).

The zoo ships KV-cache decoding (greedy + beam) but nothing measured
it.  This records, per model, a ``{"bench": "decode"}`` row with:

- **tok/sec/chip** for the jitted end-to-end ``generate()`` (chunked
  prefill + one lax.scan over positions — one compiled program, no
  per-token dispatch; see models/generate.py).
- **kv_cache_mb**: the stacked cache footprint at the benched batch.
- **ttft_ms** at two prompt lengths, and their ratio: chunked prefill
  does ONE parallel forward over the prompt, so time-to-first-token
  must grow sublinearly in prompt length (the sequential-decode
  alternative is exactly linear in wall time).  ``ttft_ratio`` <
  len_ratio is the pass criterion recorded with the row.

Run: python benchmarks/bench_decode.py [--models gpt2-medium,...]
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

# model -> (batch, prompt_len, new_tokens, ttft_prompts)
CONFIGS = {
    "gpt2-medium": (8, 128, 256, (128, 512)),
    "tinyllama-1.1b": (8, 128, 256, (128, 1024)),
    "t5-small": (8, 128, 256, (128, 512)),  # seq2seq: prompt = encoder
    "gpt2-tiny": (4, 16, 32, (8, 32)),      # CI-sized smoke config
    "t5-tiny": (4, 16, 32, (8, 32)),        # CI-sized seq2seq smoke
    "mistral-tiny": (4, 16, 32, (8, 32)),   # windowed: ring A/B leg
}


def _cache_bytes(jax, model, batch: int) -> int:
    """KV-cache footprint of one decode session at ``batch``."""
    from polyaxon_tpu.models.generate import init_cache

    shapes = jax.eval_shape(lambda: init_cache(model, batch))
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes))


def bench_decode(jax, model_name: str, backend: str, checkpoint=None):
    import numpy as np

    from polyaxon_tpu.models.generate import (generate,
                                              generate_seq2seq,
                                              init_cache)
    from polyaxon_tpu.models.registry import get_model

    batch, p_len, new_toks, ttft_lens = CONFIGS[model_name]
    spec = get_model(model_name)
    model, variables = spec.init_params(batch_size=1)
    vocab = model.cfg.vocab_size
    rng = np.random.RandomState(0)

    # Build the row incrementally and checkpoint after EVERY measured
    # variant, so a failure loses the variant in flight and nothing
    # already measured.
    fields = {"model": model_name, "backend": backend, "batch": batch,
              "prompt_len": p_len, "new_tokens": new_toks}

    def ck(**kw):
        fields.update(kw)
        if checkpoint is not None:
            checkpoint(dict(fields))

    # Seq2seq (T5-style) models decode through generate_seq2seq: the
    # "prompt" is the ENCODER input, TTFT = encode + one prefill step.
    # Their cache (self-attn ring + computed cross K/V) is sized from
    # a decode-method init; decoder-only models use init_cache.
    seq2seq = hasattr(model, "encode")
    if seq2seq:
        import jax.numpy as jnp

        def cache_shapes_fn():
            enc = jax.eval_shape(
                lambda t: model.apply(
                    {"params": variables["params"]}, t,
                    method="encode"),
                jax.ShapeDtypeStruct((batch, p_len), jnp.int32))
            return jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((batch, 1), jnp.int32),
                                   jnp.zeros(enc.shape, enc.dtype),
                                   decode=True, decode_position=0,
                                   method="decode"))["cache"]
        cache_shapes = cache_shapes_fn()
    else:
        cache_shapes = jax.eval_shape(lambda: init_cache(model, batch))
    kv_bytes = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(cache_shapes))

    def timed(fn, *args):
        out = fn(*args)          # compile + run
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    gen_fn = generate_seq2seq if seq2seq else generate
    gen = jax.jit(lambda p: gen_fn(model, variables, p,
                                   max_new_tokens=new_toks))
    prompt = rng.randint(0, vocab, size=(batch, p_len)).astype("int32")
    total_s = timed(gen, prompt)
    tok_per_sec = batch * new_toks / total_s
    ck(tok_per_sec_per_chip=round(tok_per_sec, 1),
       decode_ms_per_token=round(1000 * total_s / new_toks, 3),
       kv_cache_mb=round(kv_bytes / 2**20, 1))

    # Weight-only int8 A/B (ops/quant.py): decode at small batch is
    # weight-bandwidth-bound, so halving the weight bytes should show
    # directly in tok/sec.  Same jitted program shape — the dequant
    # sits inside the scan body (generate._params).
    from polyaxon_tpu.ops.quant import quantize_params, quantized_bytes
    qvars = {"params": quantize_params(variables["params"])}
    stored_b, full_b = quantized_bytes(qvars["params"])
    gen_q = jax.jit(lambda p: gen_fn(model, qvars, p,
                                     max_new_tokens=new_toks))
    int8_s = timed(gen_q, prompt)
    tok_per_sec_int8 = batch * new_toks / int8_s
    ck(tok_per_sec_per_chip_int8=round(tok_per_sec_int8, 1),
       int8_speedup=round(tok_per_sec_int8 / tok_per_sec, 3),
       weights_mb=round(full_b / 2**20, 1),
       weights_mb_int8=round(stored_b / 2**20, 1))

    # Ring-cache A/B for sliding-window models: O(window) cache vs
    # O(max_position), same tokens (exactness pinned in
    # tests/test_ring_kv_cache.py) — the long-context serving mode.
    ring_tok_per_sec = ring_kv_bytes = None
    if getattr(model.cfg, "sliding_window", None) is not None and \
            hasattr(model.cfg, "kv_cache_ring") and not seq2seq:
        ring_model = spec.make_model(kv_cache_ring=True)
        ring_kv_bytes = _cache_bytes(jax, ring_model, batch)
        gen_r = jax.jit(lambda p: gen_fn(ring_model, variables, p,
                                         max_new_tokens=new_toks))
        ring_s = timed(gen_r, prompt)
        ring_tok_per_sec = batch * new_toks / ring_s
        ck(tok_per_sec_per_chip_ring=round(ring_tok_per_sec, 1),
           kv_cache_mb_ring=round(ring_kv_bytes / 2**20, 2))

    # Fully quantized serving: int8 weights AND int8 KV cache
    # (models/kv_cache.py) — the same params drive a model rebuilt with
    # kv_cache_int8, halving BOTH bandwidth streams of the decode loop.
    tok_per_sec_int8_kv = kv_bytes_int8 = None
    if hasattr(model.cfg, "kv_cache_int8"):
        kv_model = spec.make_model(kv_cache_int8=True)
        kv_bytes_int8 = None if seq2seq else \
            _cache_bytes(jax, kv_model, batch)
        gen_qkv = jax.jit(lambda p: gen_fn(kv_model, qvars, p,
                                           max_new_tokens=new_toks))
        qkv_s = timed(gen_qkv, prompt)
        tok_per_sec_int8_kv = batch * new_toks / qkv_s
        ck(tok_per_sec_per_chip_int8_kv=round(tok_per_sec_int8_kv, 1),
           int8_kv_speedup=round(tok_per_sec_int8_kv / tok_per_sec, 3),
           **({"kv_cache_mb_int8": round(kv_bytes_int8 / 2**20, 1)}
              if kv_bytes_int8 else {}))

    # TTFT = prefill + first sampled token (max_new_tokens=1).
    # Measured BEFORE the speculative A/B: its two jits are cheap next
    # to the speculative-loop compiles, so the latency evidence is
    # banked first.
    ttft = {}
    for L in ttft_lens:
        first = jax.jit(lambda p: gen_fn(model, variables, p,
                                         max_new_tokens=1))
        pr = rng.randint(0, vocab, size=(batch, L)).astype("int32")
        ttft[L] = timed(first, pr)
    l_small, l_big = ttft_lens
    ratio = ttft[l_big] / ttft[l_small]
    ck(ttft_ms={str(k): round(v * 1e3, 1) for k, v in ttft.items()},
       ttft_ratio=round(ratio, 2),
       ttft_len_ratio=round(l_big / l_small, 2),
       ttft_sublinear=bool(ratio < l_big / l_small))

    # Speculative decoding A/B (models/generate.generate_speculative):
    # tokens are pinned bit-identical to greedy, so the only question
    # hardware can answer is the SCHEDULE's cost.  Two honest numbers:
    # - spec_speedup_draft: gpt2-small draft with random weights —
    #   acceptance is chance-level, so this measures pure round
    #   overhead (realistic lower bound for an untrained pair).
    # - spec_speedup_full_accept: the target drafting for itself —
    #   every proposal verifies, so each round commits k tokens; this
    #   is the committed-schedule win at full acceptance (with a draft
    #   as expensive as the target, i.e. a conservative ceiling — a
    #   real 4x-smaller trained draft sits between the two).
    if model_name == "gpt2-medium" and not seq2seq:
        from polyaxon_tpu.models.generate import generate_speculative

        draft_spec = get_model("gpt2-small")
        draft_model, draft_vars = draft_spec.init_params(batch_size=1)
        k = 4
        gen_sp = jax.jit(lambda p: generate_speculative(
            model, variables, draft_model, draft_vars, p,
            max_new_tokens=new_toks, k=k))
        sp_s = timed(gen_sp, prompt)
        gen_self = jax.jit(lambda p: generate_speculative(
            model, variables, model, variables, p,
            max_new_tokens=new_toks, k=k))
        self_s = timed(gen_self, prompt)
        spec_fields = {
            "spec_k": k,
            "spec_draft": "gpt2-small",
            "spec_tok_per_sec_draft":
                round(batch * new_toks / sp_s, 1),
            "spec_speedup_draft": round(total_s / sp_s, 3),
            "spec_tok_per_sec_full_accept":
                round(batch * new_toks / self_s, 1),
            "spec_speedup_full_accept": round(total_s / self_s, 3),
        }
        ck(**spec_fields)

    return fields


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--models", default="gpt2-medium,tinyllama-1.1b,t5-small")
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()

    jax, backend = B.init_backend(args.cpu)

    def tpu_partial_writer(f):
        # Partial rows are superseded by any later row for the same
        # model without "partial": true; only TPU measurements are
        # worth checkpointing (cpu-smoke reruns in seconds).
        row = {"bench": "decode", "ts": time.time(), "partial": True,
               **f}
        with open(RESULTS, "a") as fh:
            fh.write(json.dumps(row) + "\n")

    for name in args.models.split(","):
        name = name.strip()
        try:
            r = bench_decode(
                jax, name, backend,
                checkpoint=tpu_partial_writer if backend == "tpu"
                else None)
        except Exception as e:
            print(f"# decode {name} failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", file=sys.stderr)
            continue
        row = {"bench": "decode", "ts": time.time(),
               # Non-TPU rows are smoke evidence, not perf (same
               # machine-tag convention as run_bench.py).
               **({"regime": "cpu-smoke"} if backend != "tpu" else {}),
               **r}
        print(json.dumps(row))
        with open(RESULTS, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
