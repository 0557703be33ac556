"""``jamba`` decoder (AI21 Jamba family) — Mamba-1 state-space layers
with an attention layer among every ``attn_layer_period``.

What no other decoder in the zoo has: a layer whose per-sequence decode
state has NO POSITION AXIS.  A Mamba layer keeps, in the ``cache``
collection, ``ssm_state`` — ``h`` ``[B, d_state, d_inner]`` float32 —
and ``conv_tail`` — the last ``d_conv - 1`` inputs of its causal
convolution ``[B, d_conv - 1, d_inner]`` — and no ``cache_index``: the
state after ``t`` tokens is all of the past there is, whatever ``t``
was.  It is a SNAPSHOT: it can be stored, inserted into a slot and
carried from one prefill piece to the next, but it cannot be rewound
to an earlier position (models/kv_cache.py, "the kinds of leaf").  The
attention layers keep full-length K/V planes through
``kv_cache.attend_kv_cache`` (one KV head, no positional encoding), so
one cache tree holds two kinds of leaf, ``state`` and ``full``.

The block: ``x += mixer(norm1(x)); x += mlp(norm2(x))``, the MLP a
dense SwiGLU in every layer (``num_experts`` 1), then a final RMSNorm
and the TIED head (the embedding's transpose).  The layers are
unrolled (``h_0`` ... ``h_{n-1}``) as in ``afmoe.py``: they differ.

The Mamba mixer (``d_inner = expand * hidden``)::

    [u, z] = x W_in
    u      = silu(conv1d_causal(u) + b_conv)       depthwise, d_conv taps
    [dt, B, C] = u W_x                             dt_rank + 2 d_state
    dt, B, C   = rms(dt), rms(B), rms(C)           each its own scale
    delta  = softplus(dt W_dt + b_dt)              float32
    A      = -exp(A_log)                           [d_state, d_inner]
    h_t    = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) (x) B_t
    y_t    = h_t . C_t + D * u_t
    out    = (y * silu(z)) W_out

``delta``, ``A``, ``h`` and the scan are float32; the matrices rest in
``param_dtype``.  A piece of more than one position goes through
``ops/selective_scan.selective_scan`` (the Pallas kernel on a TPU), a
single position through ``selective_step``.

Parameter tree (what ``reference/jamba.py`` reads)::

    embed/embedding [V, d]      final_norm/scale [d]
    h_<i>/{input,pre_ffn}_norm/scale [d]
    h_<i>/mlp/{gate,up}_proj/kernel [d, I], down_proj/kernel [I, d]
    h_<i>/attn/q_proj/kernel [d, Hq*D]   o_proj/kernel [Hq*D, d]
    h_<i>/attn/{k,v}_proj/kernel [d, Hkv*D]
    h_<i>/mamba/in_proj/kernel [d, 2*d_inner]   out_proj/kernel
    h_<i>/mamba/conv_kernel [d_conv, d_inner]   conv_bias [d_inner]
    h_<i>/mamba/x_proj/kernel [d_inner, dt_rank + 2*d_state]
    h_<i>/mamba/{dt,b,c}_norm/scale             f32
    h_<i>/mamba/dt_proj_kernel [dt_rank, d_inner]
    h_<i>/mamba/dt_bias [d_inner] f32   D [d_inner] f32
    h_<i>/mamba/A_log [d_state, d_inner] f32   (HF: its transpose)
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.selective_scan import selective_scan, selective_step, silu
from ..spans import scope
from .afmoe import SwiGLU, grouped_attention
from .kv_cache import attend_kv_cache

F32 = jnp.float32


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    # Layer i is attention where i % period == offset; else Mamba.
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    # The step size a fresh layer starts from: dt_bias is the inverse
    # softplus of a draw log-uniform in [dt_min, dt_max] (the Mamba
    # convention), so that decays are neither 0 nor 1.
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    # The attention layers' planes hold this many positions.
    max_position: int = 1024
    rms_norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of "
                             "num_kv_heads")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    @staticmethod
    def tiny() -> "JambaConfig":
        """CPU tests and rehearsals: 4 layers, attention at layer 1."""
        return JambaConfig(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            num_layers=4, attn_layer_period=4, attn_layer_offset=1,
            num_heads=4, num_kv_heads=1, head_dim=8, mamba_d_state=4,
            mamba_d_conv=4, mamba_dt_rank=8, max_position=64)

    @staticmethod
    def jamba2_3b() -> "JambaConfig":
        """AI21-Jamba2-3B as published, uncut (perfbench/configs/
        ai21-jamba2-3b.json has the arithmetic), served with planes of
        1 024 positions and bfloat16 at rest."""
        return JambaConfig(max_position=1024, param_dtype=jnp.bfloat16)


def _rms(cfg: JambaConfig, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=F32, name=name)


def _dense(cfg: JambaConfig, features: int, name: str):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


def _dt_bias_init(cfg: JambaConfig):
    def init(key, shape, dtype=F32):
        lo, hi = math.log(cfg.dt_min), math.log(cfg.dt_max)
        dt = jnp.exp(jax.random.uniform(key, shape, F32) * (hi - lo) + lo)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class JambaAttention(nn.Module):
    """Grouped-query attention (multi-query where there is one KV
    head), no bias, no positional encoding; also the attention layer
    of ``nemotron_h.py``."""

    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        b, s = x.shape[:2]
        q = _dense(cfg, hq * d, "q_proj")(x).reshape(b, s, hq, d)
        k = _dense(cfg, hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
        v = _dense(cfg, hkv * d, "v_proj")(x).reshape(b, s, hkv, d)
        if decode:
            a = attend_kv_cache(
                self, lambda k, v, allowed, _: grouped_attention(
                    q, k, v, allowed[:, :, None]),
                k, v, cfg.max_position)
        else:
            pos = jnp.arange(s)
            a = grouped_attention(q, k, v, pos[None, :] <= pos[:, None])
        return _dense(cfg, cfg.hidden_size, "o_proj")(a)


class JambaMamba(nn.Module):
    """The Mamba-1 mixer with Jamba's norms on dt, B and C."""

    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        di, n, taps, r = (cfg.d_inner, cfg.mamba_d_state,
                          cfg.mamba_d_conv, cfg.mamba_dt_rank)
        b, s = x.shape[:2]
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (taps, di), cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.normal(0.02),
                            (di,), cfg.param_dtype)
        w_dt = self.param("dt_proj_kernel", nn.initializers.lecun_normal(),
                          (r, di), cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (di,), F32)
        a_log = self.param(
            "A_log", lambda *_: jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=F32))[:, None], (n, di)), (n, di), F32)
        d_skip = self.param("D", nn.initializers.ones, (di,), F32)

        u, z = jnp.split(_dense(cfg, 2 * di, "in_proj")(x), 2, axis=-1)
        # The causal convolution sees the last ``taps - 1`` inputs of
        # the piece before (zeros before the first).
        if decode:
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (b, taps - 1, di), cfg.dtype)
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (b, n, di), F32)
            past = tail.value
        else:
            past = jnp.zeros((b, taps - 1, di), u.dtype)
        seen = jnp.concatenate([past.astype(u.dtype), u], axis=1)
        u = sum(seen[:, k:k + s].astype(F32) * conv_w[k].astype(F32)
                for k in range(taps)) + conv_b.astype(F32)
        u = silu(u).astype(cfg.dtype)

        dbc = _dense(cfg, r + 2 * n, "x_proj")(u)
        dt = _rms(cfg, "dt_norm")(dbc[..., :r]).astype(cfg.dtype)
        bmat = _rms(cfg, "b_norm")(dbc[..., r:r + n])
        cmat = _rms(cfg, "c_norm")(dbc[..., r + n:])
        delta = jax.nn.softplus(
            jnp.dot(dt, w_dt.astype(cfg.dtype),
                    preferred_element_type=F32) + dt_bias)
        a = -jnp.exp(a_log)
        h0 = state.value if decode else jnp.zeros((b, n, di), F32)
        # What the device does for the state is named (spans.py): a
        # decode step's one-position update with the shift of the
        # convolution's tail ``ptpu_state_step``, a piece's scan
        # ``ptpu_scan`` (inside selective_scan) and its tail's store
        # ``ptpu_kv_write``.
        if s == 1:
            with scope("ptpu_state_step"):
                y, h = selective_step(u[:, 0], delta[:, 0], a,
                                      bmat[:, 0], cmat[:, 0], d_skip, h0)
                y = (y * silu(z[:, 0].astype(F32)))[:, None]
        else:
            y, h = selective_scan(u, delta, a, bmat, cmat, d_skip, h0, z)
        if decode:
            with scope("ptpu_state_step" if s == 1 else "ptpu_kv_write"):
                state.value = h
                tail.value = seen[:, s:].astype(cfg.dtype)
        return _dense(cfg, cfg.hidden_size, "out_proj")(
            y.astype(cfg.dtype))


class JambaBlock(nn.Module):
    cfg: JambaConfig
    index: int

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        h = _rms(cfg, "input_norm")(x).astype(cfg.dtype)
        if cfg.is_attention(self.index):
            x = x + JambaAttention(cfg, name="attn")(h, decode=decode)
        else:
            x = x + JambaMamba(cfg, name="mamba")(h, decode=decode)
        h = _rms(cfg, "pre_ffn_norm")(x).astype(cfg.dtype)
        return x + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)


class JambaModel(nn.Module):
    cfg: JambaConfig

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 decode: bool = False, decode_position=None,
                 last_only: bool = False):
        # ``decode_position`` belongs to generate()'s uniform calling
        # convention: an attention layer's positions come from its
        # cache index, a Mamba layer has none.
        cfg = self.cfg
        if input_ids.shape[-1] > cfg.max_position:
            raise ValueError(
                f"sequence length {input_ids.shape[-1]} exceeds "
                f"max_position {cfg.max_position}")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(input_ids)
        for i in range(cfg.num_layers):
            x = JambaBlock(cfg, i, name=f"h_{i}")(x, decode=decode)
        if last_only:
            x = x[:, -1:]
        x = _rms(cfg, "final_norm")(x).astype(cfg.dtype)
        # The tied head: the table's transpose, accumulated in float32.
        return jnp.einsum("bsd,vd->bsv", x,
                          embed.embedding.astype(cfg.dtype),
                          preferred_element_type=F32)
