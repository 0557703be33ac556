"""The benchmark's own copy of the plain ``afmoe`` reference, and the
child that ``drivers/traffic_ref.py`` runs after the server has gone.

Everything from ``import math`` down to ``forward`` is
``polyaxon_tpu/reference/afmoe.py`` as this benchmark accepted it
(``perfbench/tests`` hold the two to the same text), kept here so that
a later change to the program cannot move the yardstick: the forward
pass of the architecture in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernels, one
sequence at a time, layer by layer and expert by expert.  Its readings
of the published description are listed in that file's docstring and
under ``assumed`` in ``configs/trinity-large-preview.json``.

As a program (the child)::

    python3 perfbench/reference/trinity_large_preview.py <job.json> <out.json>

``job.json``: ``{"model": zoo name, "cfg": the reference's cfg dict,
"experts_held", "expert_offset", "degrade": null | "int8_weights",
"requests": [{"prompt": [...], "new_tokens": [...],
"logits_b64": ..., "shape": [n, V]}]}``.  The child makes the weights
exactly as ``ptpu serve`` does (the zoo's ``init_params``: the same
code, seed and backend give the same bits), runs each request's prompt
++ new tokens through :func:`forward`, and writes for every request the
relative error of each served logits row against the reference's row at
the same position: ``||served - ref|| / ||ref||`` over the vocabulary
slice.  ``degrade`` computes the REFERENCE in the nearest precision
below the configuration's (the bfloat16 matrices rounded to int8 per
output channel): the reading that has to come out as not correct
(PERF.md).
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


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------


def _int8_round(w):
    """Symmetric int8 per output channel (last axis), back in the
    dtype it came in: what weight-only int8 serving would hold."""
    w32 = _f32(w)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
    q = jnp.round(w32 / jnp.where(scale > 0, scale, 1.0))
    return (q * scale).astype(w.dtype)


def _degrade(params, how):
    """``params`` with every bfloat16 matrix through int8 and back, a
    leaf at a time, the old leaf freed as the new one is made: two
    copies of the weights do not fit the chip."""
    if how != "int8_weights":
        return params
    leaves, treedef = jax.tree.flatten(params)
    for i, w in enumerate(leaves):
        if w.ndim >= 2 and w.dtype == jnp.bfloat16:
            leaves[i] = _int8_round(w)
            w.delete()
    return jax.tree.unflatten(treedef, leaves)


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
    for req in job["requests"]:
        t = time.time()
        served = np.frombuffer(base64.b64decode(req["logits_b64"]),
                               "<f4").reshape(req["shape"])
        ids = req["prompt"] + req["new_tokens"][:-1]
        first = len(req["prompt"]) - 1
        rows = list(range(first, first + served.shape[0]))
        ref = np.asarray(forward(
            params, np.asarray(ids, np.int32), job["cfg"],
            experts_held=job["experts_held"],
            expert_offset=job["expert_offset"], rows=rows))
        err = np.linalg.norm(served - ref, axis=-1) \
            / np.linalg.norm(ref, axis=-1)
        out.append({"prompt_tokens": len(req["prompt"]),
                    "rel_err": [float(e) for e in err],
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
