"""``afmoe`` decoder (Arcee Trinity family) — a stack whose layers differ.

What no other decoder in the zoo has, all in one block design:

- window layers and full layers MIXED (three ``sliding_attention``
  layers, then one ``full_attention`` layer, ...): window layers rotate
  q and k (RoPE) and see the last ``sliding_window`` keys, full layers
  use no positions and see everything.  Decoding keeps TWO kinds of
  cache side by side in one cache collection: a window layer a ring of
  ``sliding_window + kv_ring_chunk`` positions
  (``kv_cache.append_ring_kv_cache``), a full layer a plane of
  ``max_position`` (``kv_cache.attend_kv_cache``: read as far as it is
  written).
- leading dense-FFN layers, then expert layers: token-choice top-k of
  ``num_experts`` by sigmoid scores plus a selection bias, a shared
  expert, and the routed sum over the experts HELD here
  (``parallel/moe.py``): ``experts_held`` experts from
  ``expert_offset`` on, one chip's share of an expert-parallel
  deployment.  The router keeps its published width.
- four RMSNorms a block (sandwich), per-head RMSNorm of q and k, an
  elementwise sigmoid gate on the attention output, grouped-query
  attention computed grouped (K and V are never repeated to the query
  heads: the cache read is the decode step's largest).

The layers are UNROLLED (``h_0`` ... ``h_{n-1}``): they differ in
parameters and in cache shape, so there is no stacked cache and nothing
to carry through a layer scan — every layer's cache variables are its
own leaves of the cache tree, which the serving step program carries
through its step loop and updates in place, a row a step
(serving/slots.py).

Parameter tree (what ``reference/afmoe.py`` reads)::

    embed/embedding [V, d]      final_norm/scale [d]
    lm_head/kernel [d, V]
    h_<i>/{input,post_attn,pre_ffn,post_ffn}_norm/scale [d]
    h_<i>/attn/{q,gate}_proj/kernel [d, Hq*D]   o_proj/kernel [Hq*D, d]
    h_<i>/attn/{k,v}_proj/kernel [d, Hkv*D]     {q,k}_norm/scale [D]
    h_<i>/mlp/{gate,up}_proj/kernel [d, I], down_proj/kernel [I, d]
    h_<i>/moe/router_kernel [d, E] f32          router_bias [E] f32
    h_<i>/moe/experts_{gate,up} [E_h, d, f]     experts_down [E_h, f, d]
    h_<i>/moe/shared/{gate,up}_proj/kernel [d, f], down_proj/kernel

``param_dtype`` is what the matrices REST in (bfloat16 for the served
cut: in float32 it does not fit the chip); norm scales and the router
stay float32.  The published description's readings are listed in
``reference/afmoe.py``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.rotary import apply_rotary
from ..parallel.moe import (held_experts_ffn, held_pair_counts,
                            sigmoid_topk_route)
from ..spans import scope
from .generate import STATS
from .kv_cache import append_ring_kv_cache, attend_kv_cache

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the leading dense layers
    moe_intermediate_size: int = 3072       # an expert, the shared one
    # One entry a layer held; ``num_dense_layers`` of them lead.
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    num_dense_layers: int = 1
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256                  # the router's width
    num_experts_per_tok: int = 4
    # This chip's share: experts [expert_offset, +experts_held).
    experts_held: int = 256
    expert_offset: int = 0
    route_scale: float = 2.448
    route_norm: bool = True
    # Window layers see the last ``sliding_window`` keys, (i - W, i].
    sliding_window: int = 4096
    # A window layer's ring holds ``sliding_window + kv_ring_chunk``
    # positions: a prefill chunk up to that long is written BEFORE it
    # is read, so the attention reads the ring alone
    # (kv_cache.append_ring_kv_cache).  Longer chunks still decode
    # right, through a copy.
    kv_ring_chunk: int = 512
    max_position: int = 8192
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    # Drawn small and non-zero so that choosing by s + b and weighing
    # by s can be told apart.
    router_bias_init_std: float = 0.01
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (WINDOW, FULL)]
        if bad:
            raise ValueError(f"layer_types holds {bad[0]!r}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of "
                             "num_kv_heads")
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, +{self.experts_held}) "
                f"are not among {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @staticmethod
    def tiny() -> "AfmoeConfig":
        """CPU tests and rehearsals: window 8, 8 experts top 2, the
        second of 2 shares of 4."""
        return AfmoeConfig(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24,
            layer_types=(WINDOW, WINDOW, WINDOW, WINDOW, FULL),
            num_dense_layers=1, num_heads=4, num_kv_heads=2,
            head_dim=8, num_experts=8, num_experts_per_tok=2,
            experts_held=4, expert_offset=4, sliding_window=8,
            kv_ring_chunk=4, max_position=64,
            router_bias_init_std=0.05)

    @staticmethod
    def trinity_large_ep8() -> "AfmoeConfig":
        """Trinity-Large-Preview cut to one chip's share of an 8-way
        expert-parallel deployment (perfbench/configs/
        trinity-large-preview.json has the arithmetic): the leading
        dense layer and one period of expert layers, experts 0-31 of
        256, an eighth of the vocabulary, bfloat16 at rest."""
        return AfmoeConfig(
            vocab_size=25024,
            layer_types=(WINDOW, WINDOW, WINDOW, WINDOW, FULL),
            num_dense_layers=1, experts_held=32, expert_offset=0,
            max_position=8192, param_dtype=jnp.bfloat16)


def _rms(cfg: AfmoeConfig, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                      name=name)


def _dense(cfg: AfmoeConfig, features: int, name: str):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


def grouped_attention(q, k, v, allowed):
    """``q`` [B, S, Hq, D] over ``k``/``v`` [B, T, Hkv, D], the query
    heads of one KV head computed as a group against the KV head as it
    lies: nothing of K or V is repeated.  ``allowed`` broadcasts to
    [B, 1, 1, S, T].  Scores and softmax in float32.  Traced under
    the scope ``ptpu_attend`` (spans.py)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    with scope("ptpu_attend"):
        q = q.reshape(b, s, hkv, hq // hkv, d)
        scores = jnp.einsum("bsngd,btnd->bngst", q, k,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(allowed, scores / math.sqrt(d), -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bngst,btnd->bsngd", p, v)
        return out.reshape(b, s, hq * d)


class AfmoeAttention(nn.Module):
    cfg: AfmoeConfig
    layer_type: str

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        b, s = x.shape[:2]
        window = self.layer_type == WINDOW
        q = _dense(cfg, hq * d, "q_proj")(x).reshape(b, s, hq, d)
        k = _dense(cfg, hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
        v = _dense(cfg, hkv * d, "v_proj")(x).reshape(b, s, hkv, d)
        q = _rms(cfg, "q_norm")(q).astype(cfg.dtype)
        k = _rms(cfg, "k_norm")(k).astype(cfg.dtype)
        rot = lambda p, t: apply_rotary(  # noqa: E731
            t, t, theta=cfg.rope_theta, positions=p)[0]
        if decode:
            # The cache helpers count a window as the keys BEHIND the
            # query (i - w .. i): the published W keys are w = W - 1.
            # ``allowed`` comes [1, 1, S, T]: one more axis for the
            # query heads of a group.
            if window:
                k, v, allowed, pos = append_ring_kv_cache(
                    self, k, v, cfg.sliding_window - 1, rotate=rot,
                    slack=cfg.kv_ring_chunk)
                a = grouped_attention(rot(pos, q), k, v,
                                      allowed[:, :, None])
            else:
                # The plane, read as far as it is written.
                a = attend_kv_cache(
                    self, lambda k, v, allowed, _: grouped_attention(
                        q, k, v, allowed[:, :, None]),
                    k, v, cfg.max_position)
        else:
            pos = jnp.arange(s)
            allowed = pos[None, :] <= pos[:, None]
            if window:
                q, k = rot(pos, q), rot(pos, k)
                allowed &= pos[None, :] > pos[:, None] \
                    - cfg.sliding_window
            a = grouped_attention(q, k, v, allowed)
        a = a * jax.nn.sigmoid(_dense(cfg, hq * d, "gate_proj")(x))
        return _dense(cfg, cfg.hidden_size, "o_proj")(a)


class SwiGLU(nn.Module):
    cfg: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.silu(_dense(cfg, self.width, "gate_proj")(x)) \
            * _dense(cfg, self.width, "up_proj")(x)
        return _dense(cfg, cfg.hidden_size, "down_proj")(h)


class AfmoeMoE(nn.Module):
    """Shared expert + the held experts' part of the routed sum."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, f, held = (cfg.hidden_size, cfg.moe_intermediate_size,
                      cfg.experts_held)
        router = self.param("router_kernel",
                            nn.initializers.lecun_normal(),
                            (d, cfg.num_experts), jnp.float32)
        bias = self.param(
            "router_bias",
            nn.initializers.normal(cfg.router_bias_init_std),
            (cfg.num_experts,), jnp.float32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w_gate = self.param("experts_gate", init, (held, d, f),
                            cfg.param_dtype)
        w_up = self.param("experts_up", init, (held, d, f),
                          cfg.param_dtype)
        w_down = self.param("experts_down", init, (held, f, d),
                            cfg.param_dtype)
        flat = x.reshape(-1, d)
        chosen, weights = sigmoid_topk_route(
            flat, router, bias, cfg.num_experts_per_tok,
            scale=cfg.route_scale, normalize=cfg.route_norm)
        # [pairs on each held expert ..., pairs routed], for whoever
        # makes the collection mutable (the serving programs).
        self.sow(STATS, "expert_pairs", jnp.concatenate([
            held_pair_counts(chosen, held, cfg.expert_offset),
            jnp.full((1,), chosen.size, jnp.int32)]),
            reduce_fn=jnp.add,
            init_fn=lambda: jnp.zeros((held + 1,), jnp.int32))
        routed = held_experts_ffn(
            flat, chosen, weights, w_gate.astype(cfg.dtype),
            w_up.astype(cfg.dtype), w_down.astype(cfg.dtype),
            expert_offset=cfg.expert_offset)
        shared = SwiGLU(cfg, f, name="shared")(x)
        return shared + routed.reshape(x.shape).astype(cfg.dtype)


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    index: int

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        a = AfmoeAttention(cfg, cfg.layer_types[self.index],
                           name="attn")(
            _rms(cfg, "input_norm")(x).astype(cfg.dtype), decode=decode)
        x = x + _rms(cfg, "post_attn_norm")(a).astype(cfg.dtype)
        h = _rms(cfg, "pre_ffn_norm")(x).astype(cfg.dtype)
        if self.index < cfg.num_dense_layers:
            f = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        else:
            f = AfmoeMoE(cfg, name="moe")(h)
        return x + _rms(cfg, "post_ffn_norm")(f).astype(cfg.dtype)


class AfmoeModel(nn.Module):
    cfg: AfmoeConfig

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
        if cfg.mup_enabled:
            x = x * math.sqrt(cfg.hidden_size)
        for i in range(cfg.num_layers):
            x = AfmoeBlock(cfg, i, name=f"h_{i}")(x, decode=decode)
        if last_only:
            x = x[:, -1:]
        x = _rms(cfg, "final_norm")(x).astype(cfg.dtype)
        logits = _dense(cfg, cfg.vocab_size, "lm_head")(x)
        return logits.astype(jnp.float32)
