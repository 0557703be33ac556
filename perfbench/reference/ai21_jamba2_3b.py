"""The benchmark's own copy of the plain ``jamba`` reference, and the
child that ``drivers/traffic_ref.py`` runs after the server has gone.

Everything from ``import math`` down to ``forward`` is
``polyaxon_tpu/reference/jamba.py`` as this benchmark accepted it
(``perfbench/tests`` hold the two to the same text), kept here so that
a later change to the program cannot move the yardstick: the forward
pass of the architecture in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, one
sequence at a time, layer by layer, the state-space recurrence a
``lax.scan`` over positions from a zero state.  Its readings of the
published description are listed in that file's docstring and under
``assumed`` in ``configs/ai21-jamba2-3b.json``.

As a program (the child)::

    python3 perfbench/reference/ai21_jamba2_3b.py <job.json> <out.json>

``job.json``: ``{"model": zoo name, "cfg": the reference's cfg dict,
"degrade": null | "int8_weights" | "bf16_state", "requests":
[{"prompt": [...], "new_tokens": [...], "logits_b64": ..., "shape":
[n, V]}]}`` (``experts_held`` and ``expert_offset``, which
``traffic_ref`` also writes, are not read: the model has no experts).
The child makes the weights exactly as ``ptpu serve`` does (the zoo's
``init_params``: the same code, seed and backend give the same bits),
keeps them bfloat16 and raises them to float32 a layer at a time
inside ``forward``, so that 3.03 B parameters fit the chip; runs each
request's prompt ++ new tokens through :func:`forward`; and writes for
every request the relative error of each served logits row against the
reference's row at the same position: ``||served - ref|| / ||ref||``
over the vocabulary.  ``degrade`` computes the REFERENCE in a lower
precision than the configuration states: ``int8_weights`` rounds its
bfloat16 matrices to int8 per output channel (the nearest precision
below: the reading that has to come out as not correct, PERF.md);
``bf16_state`` rounds the recurrence's state ``h`` to bfloat16 after
every position (what a bfloat16 ``h`` would read: reported, not a
limit).
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
    leaf at a time, the old leaf freed as the new one is made."""
    if how != "int8_weights":
        return params
    leaves, treedef = jax.tree.flatten(params)
    for i, w in enumerate(leaves):
        if w.ndim >= 2 and w.dtype == jnp.bfloat16:
            leaves[i] = _int8_round(w)
            w.delete()
    return jax.tree.unflatten(treedef, leaves)


@jax.jit
def _recurrence_bf16_state(u, delta, a, b, c, d_skip):
    """:func:`recurrence` with ``h`` rounded to bfloat16 after every
    position."""
    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        h = h.astype(jnp.bfloat16).astype(F32)
        return h, h @ c_t + d_skip * u_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32),
                        (u, delta, b, c))
    return y


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
    if job.get("degrade") == "bf16_state":
        globals()["recurrence"] = _recurrence_bf16_state
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
        ref = np.asarray(forward(params, np.asarray(ids, np.int32),
                                 job["cfg"], rows=rows))
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
