"""Operations and bytes of the hybrid state-space decoder
``ai21-jamba2-3b``, from the configuration file's sizes: what
``ssm_serve_mfu_pct`` divides by the peak, and the selective-scan
kernel's least traffic for ``selective_scan_roofline_pct``.

Counted for a token, 2 FLOP a multiply-add: in every Mamba layer the
four projections (in, x, dt, out), the convolution's taps and the
scan's three multiply-adds a (channel, state) pair — ``exp(dA) * h``,
``(delta u) * B`` and ``h * C``; in every attention layer the four
projections (q, k, v, o); in every layer the SwiGLU; for every row that
went through the head, the tied table.  NOT counted: the attention over
the cache, the exponentials and the softplus, norms, the gate, the
sampler — so the share under-reads and cannot flatter.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    h = config["hidden_size"]
    layers = config["num_hidden_layers"]
    attn = len([i for i in range(layers)
                if i % config["attn_layer_period"]
                == config["attn_layer_offset"]])
    return {"h": h, "layers": layers, "attn": attn,
            "mamba": layers - attn,
            "d_inner": config["mamba_expand"] * h,
            "n": config["mamba_d_state"], "r": config["mamba_dt_rank"],
            "taps": config["mamba_d_conv"]}


def mamba_token_flops(config: dict) -> float:
    """One token through one Mamba mixer."""
    s = sizes(config)
    di, n, r = s["d_inner"], s["n"], s["r"]
    matmuls = s["h"] * 2 * di + di * (r + 2 * n) + r * di + di * s["h"]
    return 2.0 * (matmuls + s["taps"] * di + 3 * di * n)


def attention_token_flops(config: dict) -> float:
    """One token through one attention mixer's projections."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"] * (h // heads)
    return 2.0 * (2 * h * h + 2 * h * kv)


def token_flops(config: dict) -> float:
    """One token through every layer."""
    s = sizes(config)
    return (s["mamba"] * mamba_token_flops(config)
            + s["attn"] * attention_token_flops(config)
            + s["layers"] * 2.0 * 3 * s["h"] * config["intermediate_size"])


def head_flops(config: dict) -> float:
    """One row through the tied head."""
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def serve_flops(config: dict, *, tokens: float, head_rows: float) -> float:
    return tokens * token_flops(config) + head_rows * head_flops(config)


def scan_kernel_flops(config: dict, *, layer_tokens: float) -> float:
    """The kernel's multiply-adds (and the skip and the gate) for
    ``layer_tokens`` positions x layers."""
    s = sizes(config)
    return layer_tokens * 2.0 * s["d_inner"] * (3 * s["n"] + 2)


def scan_kernel_bytes(config: dict, *, layer_tokens: float,
                      calls: float) -> float:
    """The kernel's least HBM traffic, float32: per position ``u``,
    ``delta``, ``z`` in and ``y`` out (``d_inner`` each) and ``B``,
    ``C`` (``d_state`` each); per call the state in and out and ``A``
    (``d_state x d_inner`` each) and ``D``."""
    s = sizes(config)
    di, n = s["d_inner"], s["n"]
    return 4.0 * (layer_tokens * (4 * di + 2 * n)
                  + calls * (3 * n * di + di))
