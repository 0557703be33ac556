"""``nemotron_h`` decoder (NVIDIA Nemotron-H / Nemotron 3 family) —
Mamba-2 layers beside relu² experts and attention, one mixer a layer.

What no other decoder in the zoo has: a recurrent layer whose state is
a MATRIX a head, ``[mamba_head_dim, ssm_state_size]``, decayed by one
scalar a head and position (Mamba-2, ``ops/ssd.py``), where Jamba's
Mamba-1 state is a vector a channel decayed by a matrix a position.
A Mamba-2 layer keeps, in the ``cache`` collection, ``ssm_state``
``[B, heads, head_dim, state]`` float32 and ``conv_tail`` — the last
``conv_kernel - 1`` inputs of its causal convolution, ``[B, taps - 1,
conv_dim]`` — and no ``cache_index``: kind ``state``
(models/kv_cache.py), as Jamba's are.  And an expert layer whose
experts are UNGATED: ``W_down relu(x W_up)^2`` (``parallel/moe.
held_experts_ffn`` without ``w_gate``).

The block: ``x += mixer(norm(x))`` with ONE mixer a layer, the letter
of ``pattern`` (the published ``hybrid_override_pattern``, cut to the
layers held) at its index — ``M`` Mamba-2, ``E`` experts, ``*``
attention — then a final RMSNorm and an untied head.  The layers are
unrolled (``h_0`` ... ``h_{n-1}``), as in ``afmoe.py``: they differ.

The Mamba-2 mixer (``di = heads * head_dim``, ``conv_dim = di + 2 G
N``)::

    [z, xBC, dt] = x W_in                       di, conv_dim, heads
    xBC    = silu(conv1d_causal(xBC) + b_conv)  depthwise, taps
    [x, B, C] = xBC                    heads x head_dim, G x N, G x N
    dt     = softplus(dt + dt_bias)             float32, [heads]
    A      = -exp(A_log)                        [heads]
    h_t    = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t    [head_dim, N]
    y_t    = h_t C_t + D x_t
    out    = GroupRMSNorm(y * silu(z)) W_out    G groups of di / G

Head ``h`` reads group ``h // (heads / G)``.  The state, ``dt`` and
the decays are float32; the matrices rest in ``param_dtype``.  A piece
of more than one position goes through ``ops/ssd.ssd_scan`` (blocks of
``chunk_size``, scope ``ptpu_scan``), a single position through
``ssd_step`` (scope ``ptpu_state_step``).

The expert layer: sigmoid scores over all ``num_experts`` + a
selection bias for the choice (``parallel/moe.sigmoid_topk_route``),
the chosen scores renormalised and scaled by ``routed_scaling_factor``;
the part of the routed sum the ``experts_held`` experts from
``expert_offset`` give (one chip's share of an expert-parallel
deployment), plus one shared relu² expert.  The attention: Jamba's
(``jamba.JambaAttention``), grouped-query without positions.

Parameter tree (what ``reference/nemotron_h.py`` reads)::

    embed/embedding [V, d]   final_norm/scale [d]   lm_head/kernel [d, V]
    h_<i>/norm/scale [d]
    M: h_<i>/mixer/in_proj/kernel [d, 2 di + 2 G N + heads]
       h_<i>/mixer/conv_kernel [taps, conv_dim]  conv_bias [conv_dim]
       h_<i>/mixer/{dt_bias, A_log, D} [heads] f32
       h_<i>/mixer/norm_scale [di] f32   out_proj/kernel [di, d]
    E: h_<i>/mixer/router_kernel [d, E] f32   router_bias [E] f32
       h_<i>/mixer/experts_up [E_h, d, f]   experts_down [E_h, f, d]
       h_<i>/mixer/shared/up_proj/kernel [d, f_sh], down_proj/kernel
    *: h_<i>/mixer/{q,k,v,o}_proj/kernel
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.selective_scan import silu
from ..ops.ssd import ssd_scan, ssd_step
from ..parallel.moe import (held_experts_ffn, held_pair_counts,
                            sigmoid_topk_route)
from ..spans import scope
from .generate import STATS
from .jamba import JambaAttention

F32 = jnp.float32
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    # One letter a layer held: M Mamba-2, E experts, * attention.
    pattern: str = "MEMEM*EMEMEM*"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # dt_bias is the inverse softplus of a step log-uniform in
    # [time_step_min, time_step_max], no smaller than time_step_floor
    # (the Mamba-2 convention), so that decays are neither 0 nor 1.
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    num_experts: int = 128                  # the router's width
    num_experts_per_tok: int = 6
    # This chip's share: experts [expert_offset, +experts_held).
    experts_held: int = 128
    expert_offset: int = 0
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # Drawn small and non-zero so that choosing by s + b and weighing
    # by s can be told apart.
    router_bias_init_std: float = 0.01
    # The attention layers' planes hold this many positions.
    max_position: int = 4096
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        bad = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if bad:
            raise ValueError(f"pattern holds {sorted(bad)}")
        if self.num_heads % self.num_kv_heads \
                or self.mamba_num_heads % self.n_groups:
            raise ValueError("query heads must be a multiple of KV "
                             "heads, Mamba heads of n_groups")
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, +{self.experts_held}) "
                f"are not among {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def tiny() -> "NemotronHConfig":
        """CPU tests and rehearsals: every kind of layer, 8 heads of 4
        in 2 groups, blocks of 4, 8 experts top 2 of which the second
        of 2 shares (4) is held."""
        return NemotronHConfig(
            vocab_size=256, hidden_size=32, pattern="MEM*E",
            num_heads=4, num_kv_heads=2, head_dim=8, mamba_num_heads=8,
            mamba_head_dim=4, ssm_state_size=8, n_groups=2,
            chunk_size=4, num_experts=8, num_experts_per_tok=2,
            experts_held=4, expert_offset=4, moe_intermediate_size=16,
            shared_intermediate_size=24, max_position=64,
            router_bias_init_std=0.05)

    @staticmethod
    def nano_stage0_ep8() -> "NemotronHConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B cut to one chip's share of
        EP 8 x PP 4 (perfbench/configs/nemotron-3-nano-30b-a3b.json has
        the arithmetic): pipeline stage 0, layers 0-12, experts 0-15 of
        128, an eighth of the vocabulary, planes of 4 096 positions,
        bfloat16 at rest."""
        return NemotronHConfig(vocab_size=16384, experts_held=16,
                               expert_offset=0, max_position=4096,
                               param_dtype=jnp.bfloat16)


def _rms(cfg: NemotronHConfig, name: str):
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=F32, name=name)


def _dense(cfg: NemotronHConfig, features: int, name: str, **kw):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name, **kw)


def _dt_bias_init(cfg: NemotronHConfig):
    def init(key, shape, dtype=F32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, F32)
                                 * (hi - lo) + lo), cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        heads, p, n, g = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                          cfg.ssm_state_size, cfg.n_groups)
        di, cd, taps = cfg.d_inner, cfg.conv_dim, cfg.conv_kernel
        b, s = x.shape[:2]
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (taps, cd), cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.normal(0.02),
                            (cd,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,), F32)
        a_log = self.param("A_log", lambda *_: jnp.log(jnp.arange(
            1, heads + 1, dtype=F32)), (heads,), F32)
        d_skip = self.param("D", nn.initializers.ones, (heads,), F32)
        norm_w = self.param("norm_scale", nn.initializers.ones, (di,), F32)

        zxbcdt = _dense(cfg, di + cd + heads, "in_proj")(x)
        z, xbc, dt = jnp.split(zxbcdt, [di, di + cd], axis=-1)
        # The causal convolution sees the last ``taps - 1`` inputs of
        # the piece before (zeros before the first).
        if decode:
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (b, taps - 1, cd), cfg.dtype)
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (b, heads, p, n), F32)
            past = tail.value
        else:
            past = jnp.zeros((b, taps - 1, cd), xbc.dtype)
        seen = jnp.concatenate([past.astype(xbc.dtype), xbc], axis=1)
        xbc = silu(sum(seen[:, k:k + s].astype(F32) * conv_w[k].astype(F32)
                       for k in range(taps)) + conv_b.astype(F32))
        xs, bm, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
        xs = xs.reshape(b, s, heads, p)
        bm, cm = bm.reshape(b, s, g, n), cm.reshape(b, s, g, n)
        dt = jax.nn.softplus(dt.astype(F32) + dt_bias)
        a = -jnp.exp(a_log)
        h0 = state.value if decode else jnp.zeros((b, heads, p, n), F32)
        # What the device does for the state is named (spans.py): a
        # decode step's one-position update with the shift of the
        # convolution's tail ``ptpu_state_step``, a piece's blocks
        # ``ptpu_scan`` (inside ssd_scan) and its stores
        # ``ptpu_kv_write``.
        if s == 1:
            with scope("ptpu_state_step"):
                y, h = ssd_step(xs[:, 0], dt[:, 0], a, bm[:, 0],
                                cm[:, 0], d_skip, h0)
                y = y[:, None]
        else:
            y, h = ssd_scan(xs, dt, a, bm, cm, d_skip, h0,
                            chunk=cfg.chunk_size)
        if decode:
            with scope("ptpu_state_step" if s == 1 else "ptpu_kv_write"):
                state.value = h
                tail.value = seen[:, s:].astype(cfg.dtype)
        # RMSNorm of the gated output within each of the G groups.
        y = y.reshape(b, s, g, di // g) \
            * silu(z.astype(F32)).reshape(b, s, g, di // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        y = y.reshape(b, s, di) * norm_w
        return _dense(cfg, cfg.hidden_size, "out_proj")(
            y.astype(cfg.dtype))


class Relu2MLP(nn.Module):
    cfg: NemotronHConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = jnp.square(nn.relu(_dense(cfg, self.width, "up_proj")(x)))
        return _dense(cfg, cfg.hidden_size, "down_proj")(h)


class NemotronHMoE(nn.Module):
    """Shared expert + the held experts' part of the routed sum."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, f, held = (cfg.hidden_size, cfg.moe_intermediate_size,
                      cfg.experts_held)
        router = self.param("router_kernel",
                            nn.initializers.lecun_normal(),
                            (d, cfg.num_experts), F32)
        bias = self.param(
            "router_bias",
            nn.initializers.normal(cfg.router_bias_init_std),
            (cfg.num_experts,), F32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w_up = self.param("experts_up", init, (held, d, f),
                          cfg.param_dtype)
        w_down = self.param("experts_down", init, (held, f, d),
                            cfg.param_dtype)
        flat = x.reshape(-1, d)
        chosen, weights = sigmoid_topk_route(
            flat, router, bias, cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor,
            normalize=cfg.norm_topk_prob)
        # [pairs on each held expert ..., pairs routed], for whoever
        # makes the collection mutable (the serving programs).
        self.sow(STATS, "expert_pairs", jnp.concatenate([
            held_pair_counts(chosen, held, cfg.expert_offset),
            jnp.full((1,), chosen.size, jnp.int32)]),
            reduce_fn=jnp.add,
            init_fn=lambda: jnp.zeros((held + 1,), jnp.int32))
        routed = held_experts_ffn(
            flat, chosen, weights, None, w_up.astype(cfg.dtype),
            w_down.astype(cfg.dtype), expert_offset=cfg.expert_offset)
        shared = Relu2MLP(cfg, cfg.shared_intermediate_size,
                          name="shared")(x)
        return shared + routed.reshape(x.shape).astype(cfg.dtype)


class NemotronHBlock(nn.Module):
    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        h = _rms(cfg, "norm")(x).astype(cfg.dtype)
        if self.kind == MAMBA:
            return x + Mamba2Mixer(cfg, name="mixer")(h, decode=decode)
        if self.kind == EXPERTS:
            return x + NemotronHMoE(cfg, name="mixer")(h)
        return x + JambaAttention(cfg, name="mixer")(h, decode=decode)


class NemotronHModel(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 decode: bool = False, decode_position=None,
                 last_only: bool = False):
        # ``decode_position`` belongs to generate()'s uniform calling
        # convention: an attention layer's positions come from its
        # cache index, a Mamba-2 layer has none.
        cfg = self.cfg
        if input_ids.shape[-1] > cfg.max_position:
            raise ValueError(
                f"sequence length {input_ids.shape[-1]} exceeds "
                f"max_position {cfg.max_position}")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(
            input_ids)
        for i, kind in enumerate(cfg.pattern):
            x = NemotronHBlock(cfg, kind, name=f"h_{i}")(x, decode=decode)
        if last_only:
            x = x[:, -1:]
        x = _rms(cfg, "final_norm")(x).astype(cfg.dtype)
        # The head accumulates AND returns float32.
        return _dense(cfg, cfg.vocab_size, "lm_head",
                      dot_general=functools.partial(
                          jax.lax.dot_general,
                          preferred_element_type=F32))(x)
