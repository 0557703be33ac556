"""Plain reference for the ``jamba`` decoder (AI21 Jamba family).

The forward pass of ``models/jamba.py`` written straight from the
published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
batching, one sequence at a time, layer by layer, the state-space
recurrence a ``lax.scan`` over positions from a zero state.  Only one
layer's weights are ever held in float32, so the whole model fits one
chip, or the host, beside its bfloat16 parameters.  Independent of the
code under test: it shares the parameter TREE (names and shapes,
``models/jamba.py``'s docstring) and nothing else.

``cfg`` is a dict under the published ``config.json``'s key names::

    hidden_size, num_hidden_layers, attn_layer_period,
    attn_layer_offset, num_attention_heads, num_key_value_heads,
    mamba_d_state, mamba_d_conv, mamba_dt_rank, rms_norm_eps

(``head_dim`` is ``hidden_size / num_attention_heads``, the widths of
the projections are read off the tree.)

Departures from, and readings of, the published description (the
catalog row carries no modelling code; each is listed under ``assumed``
in ``perfbench/configs/ai21-jamba2-3b.json``):

- ``num_experts`` 1: every layer's FFN is the dense SwiGLU; the
  ``expert_layer_*`` keys select nothing.
- no positional encoding anywhere (Jamba's attention layers use none).
- Jamba's ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``: an
  RMSNorm with its own scale on each of the three slices of ``u W_x``,
  before ``dt`` is projected up.
- ``A_log`` is stored ``[d_state, d_inner]``, the TRANSPOSE of the HF
  tensor (the channel axis last tiles the chip's registers whole); the
  mathematics is the same.
- ``mamba_conv_bias`` true, ``mamba_proj_bias`` false: the convolution
  has a bias, ``in_proj``, ``x_proj``, ``out_proj`` have none;
  ``dt_proj`` has its bias (``dt_bias``), as in every Mamba.
- the head is the embedding's transpose (``tie_word_embeddings``).
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


@jax.jit
def swiglu(x, gate_w, up_w, down_w):
    h = silu(x @ _f32(gate_w)) * (x @ _f32(up_w))
    return h @ _f32(down_w)


@jax.jit
def one_head(q, k, v):
    """One query head [T, D] over the KV head: causal, f32 scores."""
    t = q.shape[0]
    pos = jnp.arange(t)
    s = (q @ k.T) / math.sqrt(q.shape[-1])
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def attention(p, x, cfg):
    """One sequence ``x`` [T, hidden] -> [T, hidden]."""
    t = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(t, hq, d)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(t, hkv, d)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(t, hkv, d)
    group = hq // hkv
    # A head at a time, each taken by a traced index: one compiled
    # program serves every head.
    heads = [one_head(jnp.take(q, h, axis=1),
                      jnp.take(k, h // group, axis=1),
                      jnp.take(v, h // group, axis=1))
             for h in range(hq)]
    return jnp.stack(heads, axis=1).reshape(t, hq * d) \
        @ _f32(p["o_proj"]["kernel"])


@jax.jit
def recurrence(u, delta, a, b, c, d_skip):
    """``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) (x) B_t``, ``y_t =
    h_t C_t + D u_t`` from ``h = 0``: ``u, delta`` [T, d_inner], ``a``
    [d_inner, d_state], ``b, c`` [T, d_state]."""
    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return h, h @ c_t + d_skip * u_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32),
                        (u, delta, b, c))
    return y


def mamba(p, x, cfg):
    """One sequence ``x`` [T, hidden] -> [T, hidden]."""
    t = x.shape[0]
    n, taps, r = (cfg["mamba_d_state"], cfg["mamba_d_conv"],
                  cfg["mamba_dt_rank"])
    eps = cfg["rms_norm_eps"]
    uz = x @ _f32(p["in_proj"]["kernel"])
    d_inner = uz.shape[-1] // 2
    u, z = uz[:, :d_inner], uz[:, d_inner:]
    # Depthwise causal convolution: position t sees u[t-taps+1 .. t].
    w = _f32(p["conv_kernel"])                       # [taps, d_inner]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d_inner), F32), u])
    u = sum(padded[k:k + t] * w[k] for k in range(taps)) \
        + _f32(p["conv_bias"])
    u = silu(u)
    dbc = u @ _f32(p["x_proj"]["kernel"])
    dt = rms_norm(dbc[:, :r], p["dt_norm"]["scale"], eps)
    b = rms_norm(dbc[:, r:r + n], p["b_norm"]["scale"], eps)
    c = rms_norm(dbc[:, r + n:], p["c_norm"]["scale"], eps)
    delta = jax.nn.softplus(dt @ _f32(p["dt_proj_kernel"])
                            + _f32(p["dt_bias"]))
    a = -jnp.exp(_f32(p["A_log"])).T                 # [d_inner, d_state]
    y = recurrence(u, delta, a, b, c, _f32(p["D"]))
    return (y * silu(z)) @ _f32(p["out_proj"]["kernel"])


def block(p, x, cfg, index):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["input_norm"]["scale"], eps)
    if index % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
        x = x + attention(p["attn"], h, cfg)
    else:
        x = x + mamba(p["mamba"], h, cfg)
    m = p["mlp"]
    return x + swiglu(rms_norm(x, p["pre_ffn_norm"]["scale"], eps),
                      m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                      m["down_proj"]["kernel"])


def forward(params, ids, cfg, *, rows=None):
    """Logits [len(rows) or T, vocab] of ONE sequence ``ids`` [T].
    ``rows``: the positions whose logits are wanted (the head is
    applied to those alone)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        table = params["embed"]["embedding"]
        x = _f32(jnp.take(table, ids, axis=0))
        for i in range(cfg["num_hidden_layers"]):
            x = block(params[f"h_{i}"], x, cfg, i)
        if rows is not None:
            x = x[jnp.asarray(np.asarray(rows, np.int32))]
        x = rms_norm(x, params["final_norm"]["scale"],
                     cfg["rms_norm_eps"])
        return x @ _f32(table).T
