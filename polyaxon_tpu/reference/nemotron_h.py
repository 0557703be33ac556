"""Plain reference for the ``nemotron_h`` decoder (NVIDIA Nemotron-H /
Nemotron 3 family).

The forward pass of ``models/nemotron_h.py`` written straight from the
published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
batching, one sequence at a time, layer by layer and expert by expert,
so that the weights of one layer (one expert) are all that is ever held
in float32 and a cut of the model fits one chip, or the host, beside
its bfloat16 parameters.  The Mamba-2 recurrence is SEQUENTIAL — a
``lax.scan`` over positions from a zero state, a ``[head_dim, state]``
matrix a head — and not the chunked form the program computes a
prefill piece in (``ops/ssd.py``), so the comparison does not hold that
algebra to itself.  Independent of the code under test: it shares the
parameter TREE (names and shapes, ``models/nemotron_h.py``'s
docstring) and nothing else.

``cfg`` is a dict under the published ``config.json``'s key names::

    hidden_size, hybrid_override_pattern (one letter a layer HELD),
    num_attention_heads, num_key_value_heads, head_dim,
    mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
    conv_kernel, layer_norm_epsilon, n_routed_experts (the router's
    width, as published), num_experts_per_tok, routed_scaling_factor,
    norm_topk_prob

The share of an expert-parallel deployment is ``(experts_held,
expert_offset)``: the parameter tree holds the weights of experts
``[expert_offset, expert_offset + experts_held)`` only; every token is
routed over all ``n_routed_experts`` and what the absent experts would
have added is left out.  The vocabulary slice is whatever rows the
tree's ``embed`` and ``lm_head`` hold: ids index the slice.

Departures from, and readings of, the published description (the
catalog row carries no modelling code; each is listed under
``assumed`` in ``perfbench/configs/nemotron-3-nano-30b-a3b.json``):

- no positional encoding anywhere: the family's attention layers apply
  none (``rope_theta`` and ``partial_rotary_factor`` are in the config
  and unused); causal GQA, scale ``head_dim^-1/2``, no bias.
- the Mamba-2 mixer as HF ``nemotron_h`` writes it: ``in_proj`` gives
  ``z``, ``xBC``, ``dt`` in that order; a depthwise causal convolution
  of ``conv_kernel`` taps WITH a bias (``use_conv_bias``) and silu over
  ``xBC``; head ``h`` reads group ``h // (heads / n_groups)``;
  ``time_step_limit`` (0, inf) clamps nothing; ``D`` a head; the
  output ``RMSNorm(y * silu(z))`` within ``n_groups`` groups of
  ``heads * head_dim / n_groups`` channels, eps ``layer_norm_epsilon``.
- the experts: ``W_down relu(W_up x)^2`` (``mlp_hidden_act`` relu2),
  no gate matrix; the shared expert the same at its own width.
- the router: sigmoid scores in float32; the CHOICE by ``scores +
  e_score_correction_bias`` (``router_bias``), the weights the chosen
  scores over their sum (+ 1e-20) times ``routed_scaling_factor``;
  ``n_group`` = ``topk_group`` = 1 groups nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, scale, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def dense(x, w):
    """``x`` times a weight matrix ``w``, raised to float32: every
    product with a weight goes through here."""
    return x @ _f32(w)


@jax.jit
def relu2_mlp(x, up_w, down_w):
    h = jnp.maximum(dense(x, up_w), 0.0)
    return dense(h * h, down_w)


@jax.jit
def one_head(q, k, v):
    """One query head [T, D] over its KV head: causal, f32 scores."""
    t = q.shape[0]
    pos = jnp.arange(t)
    s = (q @ k.T) / math.sqrt(q.shape[-1])
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def attention(p, x, cfg):
    """One sequence ``x`` [T, hidden] -> [T, hidden]."""
    t = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = dense(x, p["q_proj"]["kernel"]).reshape(t, hq, d)
    k = dense(x, p["k_proj"]["kernel"]).reshape(t, hkv, d)
    v = dense(x, p["v_proj"]["kernel"]).reshape(t, hkv, d)
    group = hq // hkv
    # A head at a time, each taken by a traced index: one compiled
    # program serves every head.
    heads = [one_head(jnp.take(q, h, axis=1),
                      jnp.take(k, h // group, axis=1),
                      jnp.take(v, h // group, axis=1))
             for h in range(hq)]
    return dense(jnp.stack(heads, axis=1).reshape(t, hq * d),
                 p["o_proj"]["kernel"])


@jax.jit
def recurrence(x, dt, a, b, c, d_skip):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t
    + D x_t`` a head, position by position from ``h = 0``: ``x`` [T,
    heads, head_dim], ``dt`` [T, heads], ``a``, ``d_skip`` [heads],
    ``b``, ``c`` [T, heads, state] (each head its group's)."""
    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) \
            + d_skip[:, None] * x_t

    heads, p = x.shape[1:]
    _, y = jax.lax.scan(step, jnp.zeros((heads, p, b.shape[-1]), F32),
                        (x, dt, b, c))
    return y


def mamba2(p, x, cfg):
    """One sequence ``x`` [T, hidden] -> [T, hidden]."""
    t = x.shape[0]
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, groups, taps = (cfg["ssm_state_size"], cfg["n_groups"],
                       cfg["conv_kernel"])
    di = heads * hd
    proj = dense(x, p["in_proj"]["kernel"])
    z, xbc, dt = proj[:, :di], proj[:, di:-heads], proj[:, -heads:]
    # Depthwise causal convolution: position t sees xbc[t-taps+1 .. t].
    w = _f32(p["conv_kernel"])                      # [taps, conv_dim]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[-1]), F32), xbc])
    xbc = silu(sum(padded[k:k + t] * w[k] for k in range(taps))
               + _f32(p["conv_bias"]))
    xs = xbc[:, :di].reshape(t, heads, hd)
    per_group = lambda m: jnp.repeat(  # noqa: E731
        m.reshape(t, groups, n), heads // groups, axis=1)
    b = per_group(xbc[:, di:di + groups * n])
    c = per_group(xbc[:, di + groups * n:])
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    a = -jnp.exp(_f32(p["A_log"]))
    y = recurrence(xs, dt, a, b, c, _f32(p["D"])).reshape(t, di)
    y = (y * silu(z)).reshape(t, groups, di // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    y = y.reshape(t, di) * _f32(p["norm_scale"])
    return dense(y, p["out_proj"]["kernel"])


def route(p, x, cfg):
    """``(chosen [T, k], weights [T, k])`` over all the router's
    experts."""
    s = jax.nn.sigmoid(dense(x, p["router_kernel"]))
    _, chosen = jax.lax.top_k(s + _f32(p["router_bias"]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def moe(p, x, cfg, experts_held, expert_offset):
    """Shared expert + the HELD experts' part of the routed sum."""
    chosen, w = route(p, x, cfg)
    y = relu2_mlp(x, p["shared"]["up_proj"]["kernel"],
                  p["shared"]["down_proj"]["kernel"])
    for e in range(experts_held):     # an expert at a time, every token
        w_e = jnp.sum(jnp.where(chosen == expert_offset + e, w, 0.0),
                      axis=-1, keepdims=True)                 # [T, 1]
        # by a traced index, as the heads are: one program an expert
        y = y + w_e * relu2_mlp(x, jnp.take(p["experts_up"], e, axis=0),
                                jnp.take(p["experts_down"], e, axis=0))
    return y


def block(p, x, cfg, kind, experts_held, expert_offset):
    h = rms_norm(x, p["norm"]["scale"], cfg["layer_norm_epsilon"])
    mixer = p["mixer"]
    if kind == "M":
        return x + mamba2(mixer, h, cfg)
    if kind == "E":
        return x + moe(mixer, h, cfg, experts_held, expert_offset)
    return x + attention(mixer, h, cfg)


def forward(params, ids, cfg, *, experts_held, expert_offset=0,
            rows=None):
    """Logits [len(rows) or T, vocab slice] of ONE sequence ``ids``
    [T] (ids index the vocabulary slice the tree holds).  ``rows``: the
    positions whose logits are wanted (the head is applied to those
    alone)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(params["embed"]["embedding"], ids, axis=0))
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            x = block(params[f"h_{i}"], x, cfg, kind, experts_held,
                      expert_offset)
        if rows is not None:
            x = x[jnp.asarray(np.asarray(rows, np.int32))]
        x = rms_norm(x, params["final_norm"]["scale"],
                     cfg["layer_norm_epsilon"])
        return dense(x, params["lm_head"]["kernel"])
