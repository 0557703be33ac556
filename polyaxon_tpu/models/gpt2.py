"""GPT-2 — BASELINE config 5 (MPIJob ring-allreduce -> ICI) and the
flagship model for ``__graft_entry__``.

TPU-first decoder: pre-LN blocks, fused QKV, bf16 MXU matmuls with f32
softmax/layernorm, causal flash attention via ``ops.attention`` (pallas
on TPU), weight-tied LM head.  The layer stack runs under ``nn.scan``
(default) so XLA traces ONE block and compiles a rolled loop — compile
time stays flat in depth and the stacked ``[layers, ...]`` params are
exactly the shape pipeline parallelism consumes.  Param names match
``parallel.strategies.TP_RULES`` (``qkv``/``o_proj``/``fc1``/``fc2``/
``wte``) — ``{tp: N}`` "just works" — and activations are pinned with
``parallel.constrain`` so mixed dp×fsdp×tp meshes never hit XLA's
involuntary-full-rematerialization fallback (VERDICT r1 #2).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.constraints import BATCH, constrain
from .attention import dot_product_attention
from .kv_cache import attend_kv_cache
from .scan_stack import remat_policy as _remat_policy
from .scan_stack import scan_stack


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    max_position: int = 1024
    layer_norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    # What the Dense and Embed leaves REST in.  float32 for training
    # and checkpoints; a served tree rests in ``dtype``, rounded once
    # at start-up (serving/weights.py), where float32 leaves are
    # rounded on every use.  The LayerNorms compute in float32 and
    # keep float32 leaves.
    param_dtype: jnp.dtype = jnp.float32
    # Rematerialize each block in the backward pass: trades ~30% more
    # FLOPs for O(layers) less activation HBM — the standard TPU knob
    # for long sequences / big batches.
    remat: bool = False
    # Selective remat: name of a jax.checkpoint_policies member (e.g.
    # "dots_with_no_batch_dims_saveable" keeps the MXU matmul outputs
    # and recomputes only elementwise/attention — much cheaper backward
    # than full remat at a fraction of no-remat's activation HBM).
    # None = save nothing (full remat).  Ignored unless remat=True.
    remat_policy: Optional[str] = None
    # Roll the layer stack into one nn.scan'd block (compile-time and
    # PP-friendly).  False unrolls a Python loop (per-layer param names,
    # kept for checkpoint/debug compatibility).
    scan_layers: bool = True
    # Serve-time option: store the decode KV cache as int8 with
    # per-(token, head) bf16 scales (kv_cache.py) — halves the
    # KV bytes each decoded token streams from HBM.
    kv_cache_int8: bool = False

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config()  # 1024h/24L/16H == gpt2-medium (~355M)

    @staticmethod
    def small() -> "GPT2Config":
        return GPT2Config(hidden_size=768, num_layers=12, num_heads=12)

    @staticmethod
    def mini() -> "GPT2Config":
        # Between tiny and small: big enough that one decode step's
        # compute dominates per-dispatch overhead on a CPU backend
        # (the regime real accelerators are in — what the serving
        # load benchmark needs to compare batching POLICIES rather
        # than dispatch counts), small enough to stay CI-sized.
        # f32 compute: CPU has no native bf16 MXU (emulated = slower),
        # and bf16's coarse logit grid makes a random-init model's
        # greedy argmax tie at one ulp — which differently-shaped XLA
        # programs (vmapped slot decode, split vs one-shot prefill)
        # may round apart, breaking the serving benches' cross-path
        # token-equality asserts on ties that carry no signal.
        return GPT2Config(vocab_size=4096, hidden_size=256,
                          num_layers=4, num_heads=8, max_position=512,
                          dtype=jnp.float32)

    @staticmethod
    def tiny() -> "GPT2Config":
        return GPT2Config(vocab_size=1024, hidden_size=64, num_layers=2,
                          num_heads=4, max_position=128)


class GPT2Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, decode: bool = False, layer=None):
        # ``layer``: this block's index where the scanned stack
        # carries the whole KV cache (scan_stack.LayerScanBody).
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln1")(x).astype(cfg.dtype)
        qkv = nn.Dense(3 * cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="qkv")(h)
        # Column-parallel output: heads land sharded over tp.
        qkv = constrain(qkv, BATCH, None, "tp")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = h.shape[:-1] + (cfg.num_heads, head_dim)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        if decode:
            # KV-cache step (GPT-2 has no RoPE — positions enter via
            # wpe at the embedding): the append, and the attention over
            # the plane as far as it is written.
            a = attend_kv_cache(
                self, lambda k, v, mask, _: dot_product_attention(
                    q, k, v, mask=mask),
                k, v, cfg.max_position, quantize=cfg.kv_cache_int8,
                layer=layer)
        else:
            a = dot_product_attention(q, k, v, causal=True)
        a = a.reshape(h.shape)
        a = constrain(a, BATCH, None, "tp")
        # Row-parallel o_proj: XLA inserts the partial-sum allreduce and
        # the residual returns to the canonical batch-sharded layout.
        x = x + nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         name="o_proj")(a)
        x = constrain(x, BATCH, None, None)

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln2")(x).astype(cfg.dtype)
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="fc1")(h)
        h = constrain(h, BATCH, None, "tp")
        h = nn.gelu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="fc2")(h)
        x = x + h
        return constrain(x, BATCH, None, None)


class GPT2Model(nn.Module):
    """setup()-style so the forward decomposes into ``embed_tokens`` /
    ``run_blocks`` / ``head`` methods — pipeline parallelism runs the
    block stack through ``parallel.pipeline_apply`` while embedding and
    head execute on every pipeline rank (they are small next to the
    stack).  ``apply(..., method="embed_tokens")`` etc. reuse the same
    param tree as ``__call__``."""

    cfg: GPT2Config

    def setup(self):
        cfg = self.cfg
        self.wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                            dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name="wte")
        self.wpe = nn.Embed(cfg.max_position, cfg.hidden_size,
                            dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name="wpe")
        if cfg.scan_layers:
            # One traced block, rolled over the layer axis; params carry
            # a leading [num_layers] dim (what pipeline_apply stacks
            # over).
            self.h = scan_stack(GPT2Block, cfg, name="h")
        else:
            block_cls = nn.remat(
                GPT2Block, policy=_remat_policy(cfg.remat_policy)) \
                if cfg.remat else GPT2Block
            self.h_blocks = tuple(block_cls(cfg, name=f"h_{i}")
                                  for i in range(cfg.num_layers))
        self.ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                 dtype=jnp.float32, name="ln_f")

    def embed_tokens(self, input_ids, position=None):
        # Pin the gather output before any arithmetic: the vocab-sharded
        # table otherwise leaves the lookup in a table-derived layout
        # that conflicts with the batch-sharded residual stream.
        x = constrain(self.wte(input_ids), BATCH, None, None)
        pos = jnp.arange(input_ids.shape[-1])
        if position is not None:  # decode: absolute position of token 0
            pos = pos + position
        x = x + self.wpe(pos)
        return constrain(x, BATCH, None, None)

    def run_blocks(self, x, decode: bool = False):
        if self.cfg.scan_layers:
            x, _ = self.h.run(x, decode)
            return x
        for block in self.h_blocks:
            # `decode or None`: under nn.remat a literal False would be
            # traced as a bool[] operand and `if decode:` inside the
            # block raises TracerBoolConversionError; None stays a
            # static python literal (same trick as the scanned call).
            x = block(x, decode=decode or None)
        return x

    def head(self, x):
        x = self.ln_f(x)
        # Pin the attend input's hidden dim REPLICATED: without this,
        # the partitioner propagates an fsdp-on-hidden preference
        # into the tied embedding's transpose, whose vocab dim is
        # committed to (tp, fsdp) by the param rules — the two device
        # orders can't be resharded in place and XLA falls back to
        # involuntary full rematerialization of the weight
        # (test_spmd_layout pins the warning away).
        x = constrain(x.astype(self.cfg.dtype), BATCH, None, None)
        logits = self.wte.attend(x)
        # LM head shards the vocab dim with the tied embedding.
        return constrain(logits.astype(jnp.float32), BATCH, None, "tp")

    def __call__(self, input_ids, *, train: bool = False,
                 decode: bool = False, decode_position=None,
                 last_only: bool = False):
        if decode and decode_position is None:
            # Unlike Llama (whose RoPE reads the per-layer cache index),
            # GPT-2's learned wpe needs the absolute position — omitting
            # it would silently give every token position 0.
            raise ValueError(
                "GPT-2 decode needs decode_position (the absolute "
                "position of this token; generate() supplies it)")
        x = self.embed_tokens(
            input_ids, position=decode_position if decode else None)
        x = self.run_blocks(x, decode=decode)
        if last_only:  # prefill: one row of logits, not [B, P, V]
            x = x[:, -1:]
        return self.head(x)
