"""The benchmark's own copy of the plain ``nemotron_h`` reference, and
the child that ``drivers/traffic_ref.py`` runs after the server has
gone.

Everything from ``import math`` down to ``forward`` is
``polyaxon_tpu/reference/nemotron_h.py`` as this benchmark accepted it
(``perfbench/tests`` hold the two to the same text), kept here so that
a later change to the program cannot move the yardstick: the forward
pass of the architecture in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, one
sequence at a time, layer by layer and expert by expert, the Mamba-2
recurrence SEQUENTIAL (a ``lax.scan`` over positions from a zero
state), not the chunked form the program runs a prefill piece in.  Its
readings of the published description are listed in that file's
docstring, under ``assumed`` in ``configs/nemotron-3-nano-30b-a3b.json``
and in ``reference/README-nemotron-3-nano-30b-a3b.md``.

As a program (the child)::

    python3 perfbench/reference/nemotron_3_nano.py <job.json> <out.json>

``job.json``: ``{"model": zoo name, "cfg": the reference's cfg dict,
"experts_held": n, "expert_offset": n, "degrade": null |
"int8_weights" | "int8_weights_activations", "requests": [{"prompt":
[...], "new_tokens": [...], "logits_b64": ..., "shape": [n, V]}]}``.
The child makes the weights exactly as ``ptpu serve`` does (the zoo's
``init_params``: the same code, seed and backend give the same bits)
and, before it uses them, holds the tree to what the published widths
and the Mamba-2 convention say without asking the model's code
(:func:`check_tree`: every leaf's path, shape and dtype; ``A_log`` =
log(1..heads), ``D`` = 1, every norm's scale 1, ``softplus(dt_bias)``
inside ``[time_step_min, time_step_max]``), and exits non-zero where
it does not hold; keeps them bfloat16 and raises them to float32 a
layer (an expert) at a time inside ``forward``; runs each request's
prompt ++ new tokens through :func:`forward` with the held experts and
the vocabulary slice the tree holds; and writes for every request the
relative error of each served logits row against the reference's row
at the same position: ``||served - ref|| / ||ref||`` over the
vocabulary slice.

``degrade`` computes the REFERENCE in a lower precision than the
configuration's bfloat16, for the reading that has to come out as not
correct (PERF.md): ``int8_weights`` rounds its bfloat16 matrices to
int8 per output channel; ``int8_weights_activations`` does that AND
rounds the rows each of them multiplies to int8 per row (per token) —
W8A8, what an int8 matrix unit computes, the nearest precision below
bfloat16 that a v5e serves in.
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


INT8 = ("int8_weights", "int8_weights_activations")


def _degrade(params, how):
    """``params`` with every bfloat16 matrix through int8 and back, a
    leaf at a time, the old leaf freed as the new one is made."""
    if how not in INT8:
        return params
    leaves, treedef = jax.tree.flatten(params)
    for i, w in enumerate(leaves):
        if w.ndim >= 2 and w.dtype == jnp.bfloat16:
            leaves[i] = _int8_round(w)
            w.delete()
    return jax.tree.unflatten(treedef, leaves)


def _dense_int8(x, w):
    """:func:`dense` with the rows a bfloat16 matrix multiplies rounded
    to int8 first, symmetric a row (a token) over the contraction: the
    activations' half of W8A8.  A float32 matrix (the router) is
    multiplied as :func:`dense` does."""
    if w.dtype == jnp.bfloat16:
        x = _f32(x)
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        x = jnp.round(x / jnp.where(scale > 0, scale, 1.0)) * scale
    return x @ _f32(w)


def expected_tree(cfg, experts_held, vocab) -> dict:
    """``{path: shape}`` of the parameter tree, from the published
    widths alone (the tree ``models/nemotron_h.py``'s docstring
    names)."""
    d = cfg["hidden_size"]
    heads, n, g = (cfg["mamba_num_heads"], cfg["ssm_state_size"],
                   cfg["n_groups"])
    di = heads * cfg["mamba_head_dim"]
    conv = di + 2 * g * n
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    f, fsh = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    want = {"embed/embedding": (vocab, d), "final_norm/scale": (d,),
            "lm_head/kernel": (d, vocab)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        want[f"h_{i}/norm/scale"] = (d,)
        layer = {
            "M": {"in_proj/kernel": (d, 2 * di + 2 * g * n + heads),
                  "conv_kernel": (cfg["conv_kernel"], conv),
                  "conv_bias": (conv,), "dt_bias": (heads,),
                  "A_log": (heads,), "D": (heads,),
                  "norm_scale": (di,), "out_proj/kernel": (di, d)},
            "E": {"router_kernel": (d, cfg["n_routed_experts"]),
                  "router_bias": (cfg["n_routed_experts"],),
                  "experts_up": (experts_held, d, f),
                  "experts_down": (experts_held, f, d),
                  "shared/up_proj/kernel": (d, fsh),
                  "shared/down_proj/kernel": (fsh, d)},
            "*": {"q_proj/kernel": (d, q), "k_proj/kernel": (d, kv),
                  "v_proj/kernel": (d, kv), "o_proj/kernel": (q, d)},
        }[kind]
        want.update({f"h_{i}/mixer/{k}": v for k, v in layer.items()})
    return want


FLOAT32 = ("A_log", "D", "dt_bias", "scale", "norm_scale",
           "router_kernel", "router_bias")


def check_tree(params, cfg, experts_held, vocab) -> list:
    """What of the parameter tree breaks :func:`expected_tree` or the
    closed forms of the Mamba-2 convention (HF ``nemotron_h``):
    ``A_log`` = log(1..heads) (``-exp(A_log)`` = -(1..heads) to 2 %:
    the device computes the log), ``D`` = 1, a norm's scale 1,
    ``softplus(dt_bias)`` in ``[time_step_min, time_step_max]``; the
    small vectors, norms and router float32, the matrices of one
    dtype.  [] where all holds."""
    got = {"/".join(str(k.key) for k in path): leaf for path, leaf
           in jax.tree_util.tree_flatten_with_path(params)[0]}
    want = expected_tree(cfg, experts_held, vocab)
    bad = [f"leaves {sorted(set(got) ^ set(want))[:4]} differ"] \
        if set(got) != set(want) else []
    heads = jnp.arange(1, cfg["mamba_num_heads"] + 1, dtype=F32)
    matrices = set()
    for path, leaf in sorted(got.items()):
        name = path.rsplit("/", 1)[-1]
        if path in want and tuple(leaf.shape) != want[path]:
            bad.append(f"{path} {tuple(leaf.shape)}, not {want[path]}")
            continue
        if name in FLOAT32:
            if leaf.dtype != F32:
                bad.append(f"{path} {leaf.dtype}, not float32")
        else:
            matrices.add(str(leaf.dtype))
        if name == "A_log":
            # A = -exp(A_log) = -(1..heads) to 2 %: a device's log is
            # not numpy's, and any other convention is off by far more
            dev = float(np.max(np.abs(np.exp(np.asarray(leaf, np.float64))
                                      / np.asarray(heads) - 1.0)))
            ok = dev <= 2e-2
        elif name in ("D", "scale", "norm_scale"):
            ok = bool(jnp.all(leaf == 1.0))
        elif name == "dt_bias":
            dt = np.logaddexp(0.0, np.asarray(leaf, np.float64))
            ok = bool(np.all(dt >= cfg["time_step_min"] * (1 - 1e-4))
                      and np.all(dt <= cfg["time_step_max"] * (1 + 1e-4)))
        else:
            # on the device: a matrix is not copied to the host
            ok = bool(jnp.isfinite(leaf).all())
        if not ok:
            bad.append(f"{path} breaks its closed form"
                       + (f" (A off by {dev:.3g})" if name == "A_log"
                          else ""))
    if len(matrices) > 1:
        bad.append(f"matrices of dtypes {sorted(matrices)}")
    return bad


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
    if job.get("degrade") == "int8_weights_activations":
        globals()["dense"] = _dense_int8
    t = time.time()
    _, variables = get_model(job["model"]).init_params(batch_size=1)
    params = variables.pop("params")
    vocab = job["requests"][0]["shape"][-1] if job["requests"] \
        else params["lm_head"]["kernel"].shape[-1]
    bad = check_tree(params, job["cfg"], job["experts_held"], vocab)
    if bad:
        print("reference: the weights are not the published tree: "
              + "; ".join(bad[:8]), flush=True)
        return 3
    params = _degrade(params, job.get("degrade"))
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
