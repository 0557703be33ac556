"""Plain reference for the ``deepseek_v2`` decoder (DeepSeek-V2 family).

The forward pass of ``models/deepseek_v2.py`` written straight from the
published description, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching, one sequence at a time, layer by layer and expert by expert,
the attention ALWAYS in its materialised form — every position's
latent expanded through ``W_kvb`` to a key and a value a head, the
usual causal attention over them — so that the program's absorbed
decode path is held to a formulation that shares nothing with it.  The
attention runs a head at a time in blocks of query rows, so that a
sequence of 16 k positions fits beside the bfloat16 parameters; only
one layer's (one expert's) weights are ever held in float32.
Independent of the code under test: it shares the parameter TREE (names
and shapes, ``models/deepseek_v2.py``'s docstring) and nothing else.

``cfg`` is a dict under the published ``config.json``'s key names::

    num_hidden_layers (the layers HELD), first_k_dense_replace,
    num_attention_heads, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, num_experts_per_tok, norm_topk_prob,
    routed_scaling_factor, rope_theta, rope_scaling {factor,
    original_max_position_embeddings, beta_fast, beta_slow, mscale,
    mscale_all_dim}, rms_norm_eps

(the router's width, the experts' and the shared SwiGLU's widths are
read off the tree.)  The share of an expert-parallel deployment is
``(experts_held, expert_offset)``, as in ``reference/afmoe.py``.

Departures from, and readings of, the published description
(``modeling_deepseek.py`` as the catalog row's ``config`` fixes it;
each is listed under ``assumed`` in
``perfbench/configs/deepseek-v2-lite.json``):

- ``q_lora_rank`` null: the query is one projection, no compression.
- RoPE pairs are HALF-SPLIT: pair ``i`` of the rope part is dims ``i``
  and ``i + rope/2``.  The published code stores the pair as dims
  ``(2i, 2i + 1)`` and de-interleaves into the half-split order before
  it rotates; for weights made at random that is a fixed permutation of
  the rope columns of ``W_q`` and ``W_kva``, and the same mathematics.
- YaRN: frequencies blended by a linear ramp over the pair index
  between ``floor(cd(beta_fast))`` and ``ceil(cd(beta_slow))``; the
  softmax scale carries ``m^2``, ``m = 0.1 mscale_all_dim ln(factor) +
  1``; cos and sin are multiplied by ``yarn_mscale(factor, mscale) /
  yarn_mscale(factor, mscale_all_dim)`` (1 for the published values).
- ``n_group`` 1, ``topk_group`` 1, ``topk_method`` greedy: the top-k
  is over all experts with no group limit; ``norm_topk_prob`` false:
  the weights are the chosen softmax scores as they are, times
  ``routed_scaling_factor``.
- the ``n_shared_experts`` shared experts are ONE SwiGLU of their
  summed width, as the published code builds them.
- ``seq_aux`` and the auxiliary loss concern training and are absent.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 2048      # query rows a call of the attention scores


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, scale, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim, theta, scaling):
    """The ``dim // 2`` rope frequencies under YaRN, float64 numpy."""
    half = dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    if not scaling or scaling["factor"] <= 1:
        return f
    original = scaling["original_max_position_embeddings"]

    def cd(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(cd(scaling["beta_fast"])), 0)
    high = min(math.ceil(cd(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + f / scaling["factor"] * ramp


def rope(x, positions, freqs, table_scale):
    """x [T, ..., D], half-split rotation at ``positions`` [T]."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, None] * jnp.asarray(freqs, F32)[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * table_scale, jnp.sin(ang) * table_scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@jax.jit
def swiglu(x, gate_w, up_w, down_w):
    h = jax.nn.silu(x @ _f32(gate_w)) * (x @ _f32(up_w))
    return h @ _f32(down_w)


@jax.jit
def head_rows(q, k, v, first, scale):
    """Query rows ``[first, first + R)`` of one head, ``q`` [R, D], over
    the whole sequence's ``k`` [T, D] and ``v`` [T, Dv]: causal, [R, T]
    scores in f32."""
    rows = first + jnp.arange(q.shape[0])
    s = (q @ k.T) * scale
    s = jnp.where(jnp.arange(k.shape[0])[None, :] <= rows[:, None], s,
                  -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ v


def attention(p, x, cfg):
    """One sequence ``x`` [T, hidden] -> [T, hidden], materialised."""
    t = x.shape[0]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    scaling = cfg.get("rope_scaling")
    freqs = yarn_frequencies(dr, cfg["rope_theta"], scaling)
    m = table = 1.0
    if scaling and scaling["factor"] > 1:
        m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
        table = yarn_mscale(scaling["factor"], scaling["mscale"]) / m
    scale = m * m / math.sqrt(dn + dr)
    pos = jnp.arange(t)

    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(t, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos, freqs, table)], axis=-1)
    kva = x @ _f32(p["kv_a_proj"]["kernel"])
    c = rms_norm(kva[:, :r], p["kv_a_norm"]["scale"],
                 cfg["rms_norm_eps"])
    k_pe = rope(kva[:, r:], pos, freqs, table)              # [T, rope]
    kv = jnp.einsum("tr,rhd->thd", c, _f32(p["kv_b_proj"]))
    heads = []
    for head in range(h):       # by a traced index: one program a block
        k_h = jnp.concatenate(
            [jnp.take(kv, head, axis=1)[:, :dn], k_pe], axis=-1)
        v_h = jnp.take(kv, head, axis=1)[:, dn:]
        q_h = jnp.take(q, head, axis=1)
        heads.append(jnp.concatenate(
            [head_rows(q_h[a:a + QUERY_BLOCK], k_h, v_h, a, scale)
             for a in range(0, t, QUERY_BLOCK)]))
    return jnp.stack(heads, axis=1).reshape(t, h * dv) \
        @ _f32(p["o_proj"]["kernel"])


def route(p, x, cfg):
    """Scores, choice and weights of every token over ALL experts:
    ``(chosen [T, k] expert ids, weights [T, k])``."""
    g = jax.nn.softmax(x @ _f32(p["router_kernel"]), axis=-1)  # [T, E]
    w, chosen = jax.lax.top_k(g, cfg["num_experts_per_tok"])
    assert not cfg.get("norm_topk_prob", False)  # the source's: as they are
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def moe_ffn(p, x, cfg, experts_held, expert_offset):
    """The shared SwiGLU + the HELD experts' part of the routed sum."""
    chosen, w = route(p, x, cfg)
    y = swiglu(x, p["shared"]["gate_proj"]["kernel"],
               p["shared"]["up_proj"]["kernel"],
               p["shared"]["down_proj"]["kernel"])
    for e in range(experts_held):     # an expert at a time, every token
        w_e = jnp.sum(jnp.where(chosen == expert_offset + e, w, 0.0),
                      axis=-1, keepdims=True)                 # [T, 1]
        y = y + w_e * swiglu(x, jnp.take(p["experts_gate"], e, axis=0),
                             jnp.take(p["experts_up"], e, axis=0),
                             jnp.take(p["experts_down"], e, axis=0))
    return y


def block(p, x, cfg, index, experts_held, expert_offset):
    eps = cfg["rms_norm_eps"]
    x = x + attention(p["attn"],
                      rms_norm(x, p["input_norm"]["scale"], eps), cfg)
    h = rms_norm(x, p["pre_ffn_norm"]["scale"], eps)
    if index < cfg["first_k_dense_replace"]:
        m = p["mlp"]
        return x + swiglu(h, m["gate_proj"]["kernel"],
                          m["up_proj"]["kernel"],
                          m["down_proj"]["kernel"])
    return x + moe_ffn(p["moe"], h, cfg, experts_held, expert_offset)


def forward(params, ids, cfg, *, experts_held, expert_offset=0,
            rows=None):
    """Logits [len(rows) or T, vocab] of ONE sequence ``ids`` [T].
    ``rows``: the positions whose logits are wanted (the head is
    applied to those alone)."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(params["embed"]["embedding"], ids, axis=0))
        for i in range(cfg["num_hidden_layers"]):
            x = block(params[f"h_{i}"], x, cfg, i, experts_held,
                      expert_offset)
        if rows is not None:
            x = x[jnp.asarray(np.asarray(rows, np.int32))]
        x = rms_norm(x, params["final_norm"]["scale"],
                     cfg["rms_norm_eps"])
        return x @ _f32(params["lm_head"]["kernel"])
