"""Plain reference for the ``afmoe`` decoder (Arcee Trinity family).

The forward pass of ``models/afmoe.py`` written straight from the
published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching, one sequence at a time, layer by layer and expert by expert,
so that the weights of one layer (one expert) are all that is ever held
in float32 and a cut of the model fits one chip, or the host, beside
its bfloat16 parameters.  Independent of the code under test: it
shares the parameter TREE (names and shapes, ``models/afmoe.py``'s
docstring) and nothing else.

``cfg`` is a dict under the published ``config.json``'s key names::

    hidden_size, num_attention_heads, num_key_value_heads, head_dim,
    rms_norm_eps, rope_theta, sliding_window, layer_types (one entry a
    layer HELD), num_dense_layers, num_experts (the router's width, as
    published), num_experts_per_tok, route_scale, route_norm,
    mup_enabled

The share of an expert-parallel deployment is ``(experts_held,
expert_offset)``: the parameter tree holds the weights of experts
``[expert_offset, expert_offset + experts_held)`` only; every token is
routed over all ``num_experts`` and what the absent experts would have
added is left out.  The vocabulary slice is whatever rows the tree's
``embed`` and ``lm_head`` hold: ids index the slice.

Departures from, and readings of, the published description (the
catalog row carries no modelling code, so each is an ASSUMPTION, listed
under ``assumed`` in ``perfbench/configs/trinity-large-preview.json``):

- ``mup_enabled``: the embedding's output is scaled by sqrt(hidden).
- "sandwich norm": four RMSNorms a block — before and after the
  attention, before and after the FFN, the second of each pair on the
  branch's OUTPUT before the residual add.  "Depth-scaled" (an init or
  a per-layer factor) is not modelled: weights are random here.
- per-head RMSNorm of q and k over ``head_dim`` (one learned scale of
  ``head_dim`` shared by the heads), before RoPE.
- RoPE (half-split, theta ``rope_theta``) on ``sliding_attention``
  layers only; ``full_attention`` layers use no positions at all.
- window layers see the last ``sliding_window`` keys, ``(i - W, i]``.
- the attention output is gated elementwise by ``sigmoid(x W_g)``,
  ``W_g: hidden -> heads * head_dim``, before ``W_o``.
- the router's selection bias enters the top-k choice only, never the
  weights; weights are the chosen sigmoid scores, normalised
  (``route_norm``, + 1e-20) and scaled by ``route_scale``.
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


def rope(x, positions, theta):
    """x [T, H, D], half-split rotation at ``positions`` [T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@jax.jit
def swiglu(x, gate_w, up_w, down_w):
    h = jax.nn.silu(x @ _f32(gate_w)) * (x @ _f32(up_w))
    return h @ _f32(down_w)


@jax.jit
def one_head(q, k, v, allowed):
    """One query head [T, D] over its KV head: [T, T] scores in f32."""
    s = (q @ k.T) / math.sqrt(q.shape[-1])
    s = jnp.where(allowed, s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def attention(p, x, cfg, layer_type):
    """One sequence ``x`` [T, hidden] -> [T, hidden]."""
    t = x.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(t, hq, d)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(t, hkv, d)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(t, hkv, d)
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    pos = jnp.arange(t)
    allowed = pos[None, :] <= pos[:, None]
    if layer_type == "sliding_attention":
        q = rope(q, pos, cfg["rope_theta"])
        k = rope(k, pos, cfg["rope_theta"])
        allowed &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    group = hq // hkv
    # A head at a time, each taken by a traced index (jnp.take): one
    # compiled program serves every head, where ``q[:, h]`` would
    # compile one a head.
    heads = [one_head(jnp.take(q, h, axis=1),
                      jnp.take(k, h // group, axis=1),
                      jnp.take(v, h // group, axis=1), allowed)
             for h in range(hq)]
    a = jnp.stack(heads, axis=1).reshape(t, hq * d)
    a = a * jax.nn.sigmoid(x @ _f32(p["gate_proj"]["kernel"]))
    return a @ _f32(p["o_proj"]["kernel"])


def route(p, x, cfg):
    """Scores, choice and weights of every token over ALL experts:
    ``(chosen [T, k] expert ids, weights [T, k])``."""
    s = jax.nn.sigmoid(x @ _f32(p["router_kernel"]))       # [T, E]
    _, chosen = jax.lax.top_k(s + _f32(p["router_bias"]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["route_scale"]


def moe_ffn(p, x, cfg, experts_held, expert_offset):
    """Shared expert + the HELD experts' part of the routed sum."""
    chosen, w = route(p, x, cfg)
    y = swiglu(x, p["shared"]["gate_proj"]["kernel"],
               p["shared"]["up_proj"]["kernel"],
               p["shared"]["down_proj"]["kernel"])
    for e in range(experts_held):     # an expert at a time, every token
        w_e = jnp.sum(jnp.where(chosen == expert_offset + e, w, 0.0),
                      axis=-1, keepdims=True)                 # [T, 1]
        # by a traced index, as the heads are: one program an expert
        y = y + w_e * swiglu(x, jnp.take(p["experts_gate"], e, axis=0),
                             jnp.take(p["experts_up"], e, axis=0),
                             jnp.take(p["experts_down"], e, axis=0))
    return y


def block(p, x, cfg, index, experts_held, expert_offset):
    eps = cfg["rms_norm_eps"]
    a = attention(p["attn"], rms_norm(x, p["input_norm"]["scale"], eps),
                  cfg, cfg["layer_types"][index])
    x = x + rms_norm(a, p["post_attn_norm"]["scale"], eps)
    h = rms_norm(x, p["pre_ffn_norm"]["scale"], eps)
    if index < cfg["num_dense_layers"]:
        m = p["mlp"]
        f = swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                   m["down_proj"]["kernel"])
    else:
        f = moe_ffn(p["moe"], h, cfg, experts_held, expert_offset)
    return x + rms_norm(f, p["post_ffn_norm"]["scale"], eps)


def forward(params, ids, cfg, *, experts_held, expert_offset=0,
            rows=None):
    """Logits [len(rows) or T, vocab slice] of ONE sequence ``ids``
    [T] (ids index the vocabulary slice the tree holds).  ``rows``: the
    positions whose logits are wanted (the head is applied to those
    alone)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(params["embed"]["embedding"], ids, axis=0))
        if cfg.get("mup_enabled", True):
            x = x * math.sqrt(cfg["hidden_size"])
        for i in range(len(cfg["layer_types"])):
            x = block(params[f"h_{i}"], x, cfg, i, experts_held,
                      expert_offset)
        if rows is not None:
            x = x[jnp.asarray(np.asarray(rows, np.int32))]
        x = rms_norm(x, params["final_norm"]["scale"],
                     cfg["rms_norm_eps"])
        return x @ _f32(params["lm_head"]["kernel"])
