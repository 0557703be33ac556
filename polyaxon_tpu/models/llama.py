"""Llama-family decoder — the zoo's modern-LLM flagship.

The reference orchestrates user-supplied torch Llama code (SURVEY.md
§0/§2.5); here the architecture is TPU-native: RMSNorm (f32 stats),
RoPE (``ops.rotary``), SwiGLU MLP, grouped-query attention, bf16 MXU
matmuls, flash attention via ``ops.attention``, and an ``nn.scan``'d
layer stack (one traced block; stacked ``[layers, ...]`` params feed
pipeline parallelism directly).

Param names line up with ``parallel.strategies.TP_RULES``
(``q_proj``/``k_proj``/``v_proj``/``o_proj`` column/row,
``gate_proj``/``up_proj`` column, ``down_proj`` row, ``embed`` vocab-
sharded) so ``strategy: {tp: N}`` works with no per-model config, and
activations are pinned with ``parallel.constrain`` to keep mixed
dp×fsdp×tp meshes off XLA's replicate-then-repartition fallback.

GQA note: K/V heads are repeated up to the query head count right
before attention, so the repeated K/V *activations* are materialized
at full head count for the kernel (a head-sharing BlockSpec in the
flash kernel would avoid that; future work).  What GQA does shrink
here is the K/V params, their gradients, and optimizer state — at
``num_kv_heads``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.constraints import BATCH, constrain
from ..ops.rotary import apply_rotary
from .attention import dot_product_attention
from .kv_cache import append_ring_kv_cache, attend_kv_cache
from .scan_stack import remat_policy, scan_stack


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    max_position: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    # Sliding-window (local) attention: position i attends to
    # [i-window, i] — window+1 visible keys.  NOTE: HF transformers'
    # Mistral masking keeps W keys ((i-W, i]); when importing an HF
    # checkpoint with sliding_window=W, set this to W-1 for logit
    # parity.  None = full causal attention.
    sliding_window: Optional[int] = None
    dtype: jnp.dtype = jnp.bfloat16
    # Llama-family checkpoints use an UNTIED lm_head (unlike GPT-2's
    # weight-tied wte.attend); tie only for small-vocab experiments.
    tie_embeddings: bool = False
    remat: bool = False
    remat_policy: Optional[str] = None
    scan_layers: bool = True
    # Serve-time option: store the decode KV cache as int8 with
    # per-(token, head) bf16 scales (kv_cache.py) — halves the
    # KV bytes each decoded token streams from HBM.
    kv_cache_int8: bool = False
    # Serve-time option for sliding-window models: O(window) RING
    # cache instead of O(max_position) — sessions stream indefinitely
    # past max_position (RoPE needs no table).  See
    # kv_cache.append_ring_kv_cache.
    kv_cache_ring: bool = False
    # Extra ring slots beyond window+1.  Speculative decoding with
    # draft length k overwrites up to k-1 still-in-window slots on a
    # partial-acceptance rollback — set >= k-1 (generate_speculative
    # enforces it); plain decode needs 0.
    kv_cache_ring_slack: int = 0

    def __post_init__(self):
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1 or None; got "
                f"{self.sliding_window} (0 would silently disable "
                "windowing)")
        if self.kv_cache_ring and self.sliding_window is None:
            raise ValueError(
                "kv_cache_ring requires sliding_window (a full-"
                "attention model needs every past position — there is "
                "no window to ring over)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be divisible by "
                f"num_kv_heads ({self.num_kv_heads}) for GQA sharing")
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"hidden_size ({self.hidden_size}) must be divisible "
                f"by num_heads ({self.num_heads})")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def tinyllama() -> "LlamaConfig":
        # remat=True is load-bearing: the b2/seq-2048 train step needs
        # 21.0 GiB of HBM without remat and 14.7 GiB with it (measured
        # via the deviceless v5e compile, benchmarks/bench_offline_v5e
        # rationale) — a single 16 GiB v5e chip cannot run the headline
        # config at all un-remattered.  Remat trades ~30% more FLOPs
        # for fitting; multi-chip fsdp runs that fit anyway can build
        # LlamaConfig(remat=False) directly.
        return LlamaConfig(remat=True)  # TinyLlama-1.1B dims

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig(vocab_size=512, hidden_size=64,
                           intermediate_size=128, num_layers=2,
                           num_heads=4, num_kv_heads=2, max_position=128)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, layer=None):
        cfg = self.cfg
        hd = cfg.head_dim
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype, name=name)
        q = dense(cfg.num_heads * hd, "q_proj")(x)
        k = dense(cfg.num_kv_heads * hd, "k_proj")(x)
        v = dense(cfg.num_kv_heads * hd, "v_proj")(x)
        q = constrain(q, BATCH, None, "tp")
        b, s = x.shape[:2]
        q = q.reshape(b, s, cfg.num_heads, hd)
        k = k.reshape(b, s, cfg.num_kv_heads, hd)
        v = v.reshape(b, s, cfg.num_kv_heads, hd)

        def attention(q, k, v, mask=None):
            """Over the cache (``mask``: the causal-append one, window-
            clipped) or, without one, causal over the sequence."""
            if cfg.num_kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.num_kv_heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            return dot_product_attention(
                q, k, v, causal=mask is None, mask=mask,
                window=cfg.sliding_window if mask is None else None)

        if decode:
            # KV-cache step (single token or chunked prefill): keys
            # rotate at their absolute cache positions inside the
            # append (stored pre-rotated); q rotates to match with the
            # append's positions.  The causal-append mask handles both
            # S == 1 and whole-prompt chunks, window-clipped.
            rot = lambda p, kk: apply_rotary(  # noqa: E731
                kk, kk, theta=cfg.rope_theta, positions=p)[1]
            rot_q = lambda p: apply_rotary(  # noqa: E731
                q, q, theta=cfg.rope_theta, positions=p)[0]
            if cfg.kv_cache_ring:
                # O(window) ring — unbounded streaming decode.
                k, v, mask, pos = append_ring_kv_cache(
                    self, k, v, cfg.sliding_window, rotate=rot,
                    quantize=cfg.kv_cache_int8,
                    slack=cfg.kv_cache_ring_slack, layer=layer)
                a = attention(rot_q(pos), k, v, mask)
            else:
                # The plane, read as far as it is written.
                a = attend_kv_cache(
                    self, lambda k, v, mask, pos: attention(
                        rot_q(pos), k, v, mask),
                    k, v, cfg.max_position, window=cfg.sliding_window,
                    quantize=cfg.kv_cache_int8, rotate=rot,
                    layer=layer)
        else:
            q, k = apply_rotary(q, k, theta=cfg.rope_theta)
            a = attention(q, k, v)
        a = constrain(a.reshape(b, s, cfg.num_heads * hd),
                      BATCH, None, "tp")
        return dense(cfg.hidden_size, "o_proj")(a)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, layer=None):
        # ``layer``: this block's index where the scanned stack
        # carries the whole KV cache (scan_stack.LayerScanBody).
        cfg = self.cfg
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=cfg.rms_norm_eps, dtype=jnp.float32, name=name)
        x = x + LlamaAttention(cfg, name="attn")(
            norm("input_norm")(x).astype(cfg.dtype), decode=decode,
            layer=layer)
        x = constrain(x, BATCH, None, None)
        h = norm("post_attn_norm")(x).astype(cfg.dtype)
        gate = nn.Dense(cfg.intermediate_size, use_bias=False,
                        dtype=cfg.dtype, name="gate_proj")(h)
        up = nn.Dense(cfg.intermediate_size, use_bias=False,
                      dtype=cfg.dtype, name="up_proj")(h)
        h = constrain(nn.silu(gate) * up, BATCH, None, "tp")
        x = x + nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                         name="down_proj")(h)
        return constrain(x, BATCH, None, None)


class LlamaModel(nn.Module):
    """Same setup()-decomposition as GPT2Model (``embed_tokens`` /
    ``run_blocks`` / ``head``) so pipeline parallelism and the trainer
    treat every decoder in the zoo uniformly."""

    cfg: LlamaConfig

    def setup(self):
        cfg = self.cfg
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                              dtype=cfg.dtype, name="embed")
        if cfg.scan_layers:
            self.layers = scan_stack(LlamaBlock, cfg, name="h")
        else:
            cls = nn.remat(LlamaBlock,
                           policy=remat_policy(cfg.remat_policy)) \
                if cfg.remat else LlamaBlock
            self.blocks = tuple(cls(cfg, name=f"h_{i}")
                                for i in range(cfg.num_layers))
        self.final_norm = nn.RMSNorm(epsilon=cfg.rms_norm_eps,
                                     dtype=jnp.float32, name="final_norm")
        if not cfg.tie_embeddings:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    dtype=cfg.dtype, name="lm_head")

    def embed_tokens(self, input_ids):
        return constrain(self.embed(input_ids), BATCH, None, None)

    def run_blocks(self, x, decode: bool = False):
        if self.cfg.scan_layers:
            x, _ = self.layers.run(x, decode)
            return x
        for block in self.blocks:
            # `decode or None`: a literal False would be traced under
            # nn.remat (TracerBoolConversionError); None stays static
            # — same convention as the scanned call above.
            x = block(x, decode=decode or None)
        return x

    def head(self, x):
        x = self.final_norm(x).astype(self.cfg.dtype)
        # Pin the head input's hidden dim REPLICATED: the partitioner
        # otherwise propagates an fsdp-on-hidden preference into the
        # vocab-committed head weight and falls back to involuntary
        # full rematerialization (see gpt2.head / test_spmd_layout).
        x = constrain(x, BATCH, None, None)
        if self.cfg.tie_embeddings:
            logits = self.embed.attend(x)
        else:
            logits = self.lm_head(x)
        return constrain(logits.astype(jnp.float32), BATCH, None, "tp")

    def __call__(self, input_ids, *, train: bool = False,
                 decode: bool = False, decode_position=None,
                 last_only: bool = False):
        # decode_position is accepted for generate()'s uniform calling
        # convention; RoPE positions come from the per-layer cache
        # index, so it is unused here.  last_only projects ONLY the
        # final position through the vocab head (prefill wants one
        # row of logits, not [B, P, V]).
        if input_ids.shape[-1] > self.cfg.max_position:
            raise ValueError(
                f"sequence length {input_ids.shape[-1]} exceeds "
                f"max_position {self.cfg.max_position}; raise it (RoPE "
                f"needs no new params) or shorten the batch")
        x = self.run_blocks(self.embed_tokens(input_ids), decode=decode)
        if last_only:
            x = x[:, -1:]
        return self.head(x)
