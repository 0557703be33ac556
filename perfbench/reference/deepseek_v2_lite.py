"""The benchmark's own copy of the plain ``deepseek_v2`` reference, and
the child that ``drivers/traffic_ref.py`` runs after the server has
gone.

Everything from ``import math`` down to ``forward`` is
``polyaxon_tpu/reference/deepseek_v2.py`` as this benchmark accepted it
(``perfbench/tests`` hold the two to the same text), kept here so that
a later change to the program cannot move the yardstick: the forward
pass of the architecture in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernels, one
sequence at a time, layer by layer and expert by expert, the attention
ALWAYS materialised (every latent expanded to a key and a value a
head), a head at a time in blocks of query rows.  Its readings of the
published description are listed in that file's docstring and under
``assumed`` in ``configs/deepseek-v2-lite.json``.

As a program (the child)::

    python3 perfbench/reference/deepseek_v2_lite.py <job.json> <out.json>

``job.json``: ``{"model": zoo name, "cfg": the reference's cfg dict,
"experts_held", "expert_offset", "degrade": null | "int8_weights" |
"bf16_compute", "requests": [{"prompt": [...], "new_tokens": [...],
"logits_b64": ..., "shape": [n, V]}]}``.  The child makes the weights
exactly as ``ptpu serve`` does (the zoo's ``init_params``: the same
code, seed and backend give the same bits), keeps them bfloat16 and
raises them to float32 a matrix at a time inside ``forward``, runs each
request's prompt ++ new tokens through :func:`forward`, and writes for
every request the relative error of each served logits row against the
reference's row at the same position: ``||served - ref|| / ||ref||``
over the vocabulary.  ``degrade`` computes the REFERENCE in the nearest
precision below the configuration's (the bfloat16 matrices rounded to
int8 per output channel): the reading that has to come out as not
correct (PERF.md).  ``bf16_compute`` is no limit but a witness of what
the configuration's OWN precision costs: the reference once as it is
and once with every activation a program of bfloat16 modules rounds
(the norms' outputs, queries, keys, values and probabilities, the
SwiGLU's product, every branch's output and the residual stream after
each addition) through bfloat16, float32 accumulation kept; it writes
the rounded reference's error against the float32 one beside the
served rows' error against the rounded one.
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


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------


def _int8_round(w, axis):
    """Symmetric int8 per output channel (the scale over the input
    ``axis``), back in the dtype it came in: what weight-only int8
    serving would hold."""
    w32 = _f32(w)
    scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / 127.0
    q = jnp.round(w32 / jnp.where(scale > 0, scale, 1.0))
    return (q * scale).astype(w.dtype)


def _degrade(params, how):
    """``params`` with every bfloat16 matrix through int8 and back, a
    leaf at a time, the old leaf freed as the new one is made.  The
    input axis is the last but one ([d, f], [E, d, f]) except in
    ``kv_b_proj`` [rank, H, nope + v], where it is the first."""
    if how != "int8_weights":
        return params
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    for path, w in flat:
        if w.ndim >= 2 and w.dtype == jnp.bfloat16:
            first = "kv_b_proj" in jax.tree_util.keystr(path)
            new = _int8_round(w, 0 if first else -2)
            w.delete()
            w = new
        leaves.append(w)
    return jax.tree.unflatten(treedef, leaves)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _rounded(fn):
    return lambda *args, **kw: _bf16(fn(*args, **kw))


@jax.jit
def _head_rows_bf16(q, k, v, first, scale):
    """:func:`head_rows` on rounded ``q``, ``k``, ``v``, the
    probabilities rounded before they weigh the values."""
    q, k, v = _bf16(q), _bf16(k), _bf16(v)
    rows = first + jnp.arange(q.shape[0])
    s = (q @ k.T) * scale
    s = jnp.where(jnp.arange(k.shape[0])[None, :] <= rows[:, None], s,
                  -jnp.inf)
    return _bf16(_bf16(jax.nn.softmax(s, axis=-1)) @ v)


def _swiglu_bf16(x, gate_w, up_w, down_w):
    h = _bf16(jax.nn.silu(_bf16(x @ _f32(gate_w)))
              * _bf16(x @ _f32(up_w)))
    return _bf16(h @ _f32(down_w))


def _block_bf16(p, x, cfg, index, experts_held, expert_offset):
    """:func:`block` with the stream rounded after each addition."""
    eps = cfg["rms_norm_eps"]
    x = _bf16(x + attention(
        p["attn"], rms_norm(x, p["input_norm"]["scale"], eps), cfg))
    h = rms_norm(x, p["pre_ffn_norm"]["scale"], eps)
    if index < cfg["first_k_dense_replace"]:
        m = p["mlp"]
        return _bf16(x + swiglu(h, m["gate_proj"]["kernel"],
                                m["up_proj"]["kernel"],
                                m["down_proj"]["kernel"]))
    return _bf16(x + moe_ffn(p["moe"], h, cfg, experts_held,
                             expert_offset))


def _bf16_compute():
    """The module's functions as a bfloat16 program rounds; the route's
    scores stay float32, as the program's do."""
    return {"rms_norm": _rounded(rms_norm), "rope": _rounded(rope),
            "attention": _rounded(attention),
            "moe_ffn": _rounded(moe_ffn), "head_rows": _head_rows_bf16,
            "swiglu": _swiglu_bf16, "block": _block_bf16}


def main(argv):
    import base64
    import json
    import os
    import sys
    import time

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    with open(argv[1]) as f:
        job = json.load(f)
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from polyaxon_tpu.config import enable_compilation_cache
    from polyaxon_tpu.models.registry import get_model

    enable_compilation_cache()
    t = time.time()
    _, variables = get_model(job["model"]).init_params(batch_size=1)
    params = _degrade(variables.pop("params"), job.get("degrade"))
    print(f"reference: weights of {job['model']} made in "
          f"{time.time() - t:.1f}s on {jax.devices()[0].platform}",
          flush=True)
    out = []
    plain = dict(globals())
    rounded = _bf16_compute() if job.get("degrade") == "bf16_compute" \
        else None

    def reference(ids, rows):
        return np.asarray(forward(
            params, np.asarray(ids, np.int32), job["cfg"],
            experts_held=job["experts_held"],
            expert_offset=job["expert_offset"], rows=rows))

    for req in job["requests"]:
        t = time.time()
        served = np.frombuffer(base64.b64decode(req["logits_b64"]),
                               "<f4").reshape(req["shape"])
        ids = req["prompt"] + req["new_tokens"][:-1]
        first = len(req["prompt"]) - 1
        rows = list(range(first, first + served.shape[0]))
        ref = reference(ids, rows)
        witness = {}
        if rounded:
            exact = ref
            globals().update(rounded)
            ref = reference(ids, rows)
            globals().update({k: plain[k] for k in rounded})
            gap = np.linalg.norm(ref - exact, axis=-1) \
                / np.linalg.norm(exact, axis=-1)
            witness = {"rounded_against_float32":
                       [float(e) for e in gap]}
            print(f"reference: bf16_compute against float32: rel_err "
                  f"median {np.median(gap):.4g} max {gap.max():.4g}",
                  flush=True)
        err = np.linalg.norm(served - ref, axis=-1) \
            / np.linalg.norm(ref, axis=-1)
        out.append({"prompt_tokens": len(req["prompt"]),
                    "rel_err": [float(e) for e in err], **witness,
                    "finite": bool(np.isfinite(served).all()
                                   and np.isfinite(ref).all()),
                    "argmax_same": int(np.sum(
                        served.argmax(-1) == ref.argmax(-1))),
                    "seconds": round(time.time() - t, 2)})
        print(f"reference: prompt {len(req['prompt'])} + "
              f"{served.shape[0]} rows in {out[-1]['seconds']}s: "
              f"rel_err max {max(out[-1]['rel_err']):.4g}", flush=True)
    with open(argv[2], "w") as f:
        json.dump({"requests": out}, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
