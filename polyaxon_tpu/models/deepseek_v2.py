"""``deepseek_v2`` decoder (DeepSeek-V2 family) — multi-head LATENT
attention beside softmax-routed experts.

What no other decoder in the zoo has: an attention layer whose decode
cache holds ONE row a position for all its heads.  A token's keys and
values are functions of a compressed latent ``c`` (``kv_lora_rank``
wide) and of one rope key ``k_pe`` shared by the heads::

    q            = x W_q           -> per head [q_nope | q_pe]
    [c | k_pe]   = x W_kva ;  c <- RMSNorm(c)
    [k_nope | v] = c W_kvb         per head
    q_pe, k_pe   <- RoPE (YaRN frequencies; the cached k_pe ROTATED)
    score_h(i,j) = (q_nope_h(i).k_nope_h(j) + q_pe_h(i).k_pe(j)) * s
    o            = concat_h(sum_j p_h(i,j) v_h(j)) W_o

so the cache keeps ``[RMSNorm(c) | rotated k_pe]`` and nothing else
(``kv_cache.attend_latent_cache``: one plane ``[B, positions, rank +
rope]``, kind ``latent``), and a call attends over it by one of TWO
PATHS that give the same numbers (``ops.attention`` counts which was
traced):

- EXPANDED (a call of more than one position: a prefill piece, a
  speculative verify chunk): the rows read are sent through ``W_kvb``
  to a key and a value a head, and the attention is the usual one.
  Cheapest a query-key pair (``nope + rope + v`` multiply-adds a
  head), at one expansion of the rows read a call.
- ABSORBED (one position: a decode step): ``W_UK`` (the key half of
  ``W_kvb``) is folded into the query, ``q~_h = q_nope_h W_UK,h^T``,
  the scores are ``q~_h . c(j) + q_pe_h . k_pe(j)`` over the rows as
  they lie, the weighted sum is of latents, and ``W_UV`` (the value
  half) expands that one sum a head.  The plane is read once for all
  the heads and nothing of it is expanded: a decode step that expanded
  a long cache would spend its time on rows it already had.

Which path a call takes is this static rule on its length, nobody's
option.

The block: ``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``; the FFN a
dense SwiGLU in the leading ``first_k_dense`` layers, then expert
layers: ``g = softmax(x W_g)`` over all experts (float32), greedy
top-k, the weights the chosen ``g`` as they are (the source's
``norm_topk_prob`` is false: not renormalised) times ``routed_scaling_factor``, the routed sum over the
experts HELD here (``parallel/moe.py``) plus ONE shared SwiGLU of
``n_shared_experts`` experts' width.  Final RMSNorm, untied head, no
embedding scale.  The layers are unrolled (``h_0`` ... ``h_{n-1}``) as
in ``afmoe.py``.

The softmax scale is ``(nope + rope)^-1/2 * m^2``, ``m = 0.1
mscale_all_dim ln(factor) + 1`` (YaRN's temperature, folded into the
scale as the published code does); cos and sin are multiplied by
``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``,
which is 1 where the two are equal.  RoPE pairs are HALF-SPLIT (pair
``i`` is dims ``i`` and ``i + rope/2``, ``ops/rotary.py``): the
published code de-interleaves ``(2i, 2i + 1)`` into that order before
rotating, which for weights made here is a fixed permutation of
``W_q``'s and ``W_kva``'s rope columns.

Parameter tree (what ``reference/deepseek_v2.py`` reads)::

    embed/embedding [V, d]      final_norm/scale [d]
    lm_head/kernel [d, V]
    h_<i>/{input,pre_ffn}_norm/scale [d]
    h_<i>/attn/q_proj/kernel [d, H*(nope+rope)]
    h_<i>/attn/kv_a_proj/kernel [d, rank+rope]   kv_a_norm/scale [rank]
    h_<i>/attn/kv_b_proj [rank, H, nope+v]       o_proj/kernel [H*v, d]
    h_<i>/mlp/{gate,up}_proj/kernel [d, I], down_proj/kernel [I, d]
    h_<i>/moe/router_kernel [d, E] f32
    h_<i>/moe/experts_{gate,up} [E_h, d, f]     experts_down [E_h, f, d]
    h_<i>/moe/shared/{gate,up}_proj/kernel [d, n_shared*f], down_proj

``param_dtype`` is what the matrices REST in (bfloat16 for the served
cut); norm scales and the router stay float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import note_latent_route
from ..ops.rotary import apply_rotary, yarn_inv_freq, yarn_mscale
from ..parallel.moe import (held_experts_ffn, held_pair_counts,
                            softmax_topk_route)
from ..spans import scope
from .afmoe import SwiGLU
from .generate import STATS
from .kv_cache import attend_latent_cache

F32 = jnp.float32


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944          # the leading dense layers
    moe_intermediate_size: int = 1408       # one expert
    num_layers: int = 27
    first_k_dense: int = 1
    num_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 64                   # the router's width
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    # This chip's share: experts [expert_offset, +experts_held).
    experts_held: int = 64
    expert_offset: int = 0
    routed_scaling_factor: float = 1.0
    rope_theta: float = 10000.0
    # YaRN (ops/rotary.yarn_inv_freq); factor 1 is plain RoPE.
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    # A latent plane holds this many positions.
    max_position: int = 16896
    rms_norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, +{self.experts_held}) "
                f"are not among {self.num_experts}")

    @property
    def latent_width(self) -> int:
        """What a position keeps in a layer's cache: the normed latent
        and the rotated rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)

    @property
    def rope_table_scale(self) -> float:
        return yarn_mscale(self.rope_factor, self.rope_mscale) \
            / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)

    def inv_freq(self):
        return yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_position, self.rope_beta_fast,
            self.rope_beta_slow)

    @staticmethod
    def tiny() -> "DeepseekV2Config":
        """CPU tests and rehearsals: 1 dense + 2 expert layers, 8
        experts top 2 + 2 shared, YaRN by 4 over an original 8."""
        return DeepseekV2Config(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_layers=3, first_k_dense=1,
            num_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, num_experts=8,
            num_experts_per_tok=2, n_shared_experts=2, experts_held=8,
            rope_factor=4.0, rope_original_max_position=8,
            max_position=64)

    @staticmethod
    def v2_lite_stage0() -> "DeepseekV2Config":
        """DeepSeek-V2-Lite cut to the first of four pipeline stages
        (perfbench/configs/deepseek-v2-lite.json has the arithmetic):
        the leading dense layer and 6 expert layers of 27, all 64
        experts of each, every width and the whole vocabulary as
        published, the head beside them, bfloat16 at rest, planes of
        16 896 positions."""
        return DeepseekV2Config(num_layers=7, max_position=16896,
                                param_dtype=jnp.bfloat16)


def _rms(cfg: DeepseekV2Config, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=F32, name=name)


def _dense(cfg: DeepseekV2Config, features: int, name: str):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


def _softmax(scores, allowed, scale, dtype):
    scores = jnp.where(allowed, scores * scale, -1e30)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def expanded_attention(q_nope, q_pe, rows, w_kvb, allowed, cfg):
    """Queries [B, S, H, nope] and [B, S, H, rope] over the latent
    ``rows`` [B, T, rank + rope], every row read EXPANDED through
    ``w_kvb`` [rank, H, nope + v] to its key and value a head.
    ``allowed`` broadcasts to [B, H, S, T].  Scores and softmax in
    float32.  Returns [B, S, H * v].  Traced under the scopes
    ``ptpu_latent_expand`` (the expansion) and ``ptpu_attend`` (the
    rest; spans.py)."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with scope("ptpu_latent_expand"):
        kv = jnp.einsum("btr,rhd->bthd", rows[..., :r], w_kvb)
    with scope("ptpu_attend"):
        scores = jnp.einsum("bshd,bthd->bhst", q_nope, kv[..., :dn],
                            preferred_element_type=F32) \
            + jnp.einsum("bshd,btd->bhst", q_pe, rows[..., r:],
                         preferred_element_type=F32)
        p = _softmax(scores, allowed, cfg.softmax_scale, rows.dtype)
        out = jnp.einsum("bhst,bthd->bshd", p, kv[..., dn:])
        return out.reshape(out.shape[:2] + (-1,))


def absorbed_attention(q_nope, q_pe, rows, w_kvb, allowed, cfg):
    """The same attention with nothing of ``rows`` expanded: the key
    half of ``w_kvb`` folded into the query, the scores and the
    weighted sum over the rows as they lie (every head reads the same
    ones), the value half applied to the one sum a head.  Traced
    under the scope ``ptpu_attend`` (spans.py), the two folded
    projections with it."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with scope("ptpu_attend"):
        q = jnp.concatenate([
            jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb[..., :dn]), q_pe],
            axis=-1)                            # [B, S, H, rank + rope]
        scores = jnp.einsum("bshw,btw->bhst", q, rows,
                            preferred_element_type=F32)
        p = _softmax(scores, allowed, cfg.softmax_scale, rows.dtype)
        mixed = jnp.einsum("bhst,btr->bshr", p, rows[..., :r])
        out = jnp.einsum("bshr,rhd->bshd", mixed, w_kvb[..., dn:])
        return out.reshape(out.shape[:2] + (-1,))


def takes_absorbed(positions: int) -> bool:
    """The static rule: a call of ONE position attends over the
    latents as they lie, a longer one expands the rows it reads."""
    return positions == 1


class LatentAttention(nn.Module):
    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        h, r = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        b, s = x.shape[:2]
        q = _dense(cfg, h * (dn + dr), "q_proj")(x).reshape(
            b, s, h, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        kva = _dense(cfg, r + dr, "kv_a_proj")(x)
        c = _rms(cfg, "kv_a_norm")(kva[..., :r]).astype(cfg.dtype)
        w_kvb = self.param(
            "kv_b_proj", nn.initializers.lecun_normal(
                in_axis=0, out_axis=(1, 2)),
            (r, h, dn + dv), cfg.param_dtype).astype(cfg.dtype)

        inv_freq, table = cfg.inv_freq(), cfg.rope_table_scale

        def rot(positions, t):
            """[B, S, heads, rope] rotated at ``positions``."""
            out = apply_rotary(t, t, positions=positions,
                               inv_freq=inv_freq)[0]
            return out if table == 1.0 else (out * table).astype(t.dtype)

        def rot_rows(positions, rows):
            return jnp.concatenate(
                [rows[..., :r],
                 rot(positions, rows[..., None, r:])[..., 0, :]], axis=-1)

        rows = jnp.concatenate([c, kva[..., r:]], axis=-1)
        attend, route = (absorbed_attention, "latent_absorbed") \
            if takes_absorbed(s) else (expanded_attention,
                                       "latent_expanded")
        note_latent_route(route)
        if decode:
            a = attend_latent_cache(
                self, lambda read, allowed, pos: attend(
                    q_nope, rot(pos, q_pe), read, w_kvb, allowed, cfg),
                rows, cfg.max_position, rotate=rot_rows)
        else:
            pos = jnp.arange(s)
            a = attend(q_nope, rot(pos, q_pe), rot_rows(pos, rows),
                       w_kvb, pos[None, :] <= pos[:, None], cfg)
        return _dense(cfg, cfg.hidden_size, "o_proj")(a)


class DeepseekMoE(nn.Module):
    """The shared SwiGLU + the held experts' part of the routed sum."""

    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, f, held = (cfg.hidden_size, cfg.moe_intermediate_size,
                      cfg.experts_held)
        router = self.param("router_kernel",
                            nn.initializers.lecun_normal(),
                            (d, cfg.num_experts), F32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w_gate = self.param("experts_gate", init, (held, d, f),
                            cfg.param_dtype)
        w_up = self.param("experts_up", init, (held, d, f),
                          cfg.param_dtype)
        w_down = self.param("experts_down", init, (held, f, d),
                            cfg.param_dtype)
        flat = x.reshape(-1, d)
        chosen, weights = softmax_topk_route(
            flat, router, cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor)
        # [pairs on each held expert ..., pairs routed], as afmoe.py
        # sows them, for the serving programs.
        self.sow(STATS, "expert_pairs", jnp.concatenate([
            held_pair_counts(chosen, held, cfg.expert_offset),
            jnp.full((1,), chosen.size, jnp.int32)]),
            reduce_fn=jnp.add,
            init_fn=lambda: jnp.zeros((held + 1,), jnp.int32))
        routed = held_experts_ffn(
            flat, chosen, weights, w_gate.astype(cfg.dtype),
            w_up.astype(cfg.dtype), w_down.astype(cfg.dtype),
            expert_offset=cfg.expert_offset)
        shared = SwiGLU(cfg, cfg.n_shared_experts * f, name="shared")(x)
        return shared + routed.reshape(x.shape).astype(cfg.dtype)


class DeepseekV2Block(nn.Module):
    cfg: DeepseekV2Config
    index: int

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        x = x + LatentAttention(cfg, name="attn")(
            _rms(cfg, "input_norm")(x).astype(cfg.dtype), decode=decode)
        h = _rms(cfg, "pre_ffn_norm")(x).astype(cfg.dtype)
        if self.index < cfg.first_k_dense:
            return x + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + DeepseekMoE(cfg, name="moe")(h)


class DeepseekV2Model(nn.Module):
    cfg: DeepseekV2Config

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 decode: bool = False, decode_position=None,
                 last_only: bool = False):
        # ``decode_position`` belongs to generate()'s uniform calling
        # convention: positions come from each layer's cache index.
        cfg = self.cfg
        if input_ids.shape[-1] > cfg.max_position:
            raise ValueError(
                f"sequence length {input_ids.shape[-1]} exceeds "
                f"max_position {cfg.max_position}")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(
            input_ids)
        for i in range(cfg.num_layers):
            x = DeepseekV2Block(cfg, i, name=f"h_{i}")(x, decode=decode)
        if last_only:
            x = x[:, -1:]
        x = _rms(cfg, "final_norm")(x).astype(cfg.dtype)
        logits = _dense(cfg, cfg.vocab_size, "lm_head")(x)
        return logits.astype(F32)
