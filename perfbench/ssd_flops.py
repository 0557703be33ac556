"""Operations and least traffic of the hybrid Mamba-2 decoder
``nemotron-3-nano-30b-a3b`` as it is held on one chip, from the
configuration file's sizes: what ``ssd_serve_mfu_pct`` divides by the
peak, the chunked (SSD) form's least time for ``ssd_scan_roofline_pct``
and the one-position update's least bytes for
``state_step_roofline_pct``.

Counted, 2 FLOP a multiply-add.  For a token: in every Mamba-2 layer
``in_proj`` (``z``, ``xBC``, ``dt``), the convolution's taps, the
recurrence's two products a (head, head_dim, state) triple — the
state's update ``dt x (x) B`` and its read-out ``h C`` — and
``out_proj``; in every expert layer the router and the shared relu²
expert (two matrices); in every attention layer the four projections;
for every token-expert pair that fell on a HELD expert (the program's
counter) one expert's two matrices; for every row through the head,
the vocabulary slice.  NOT counted: the attention over the cache, the
decays' exponentials, the softplus, the norms, the gate, the sampler,
and the chunked form's work beyond the recurrence's (its masked
halves) — so the share under-reads and cannot flatter.
"""

from __future__ import annotations


def has_pattern(config: dict) -> bool:
    """Whether ``config`` holds a layer pattern with Mamba-2 layers."""
    return "M" in config.get("held", {}).get("hybrid_override_pattern", "")


def sizes(config: dict) -> dict:
    pattern = config["held"]["hybrid_override_pattern"]
    heads, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    n, groups = config["ssm_state_size"], config["n_groups"]
    return {"h": config["hidden_size"], "heads": heads, "hd": hd,
            "n": n, "groups": groups, "di": heads * hd,
            "conv_dim": heads * hd + 2 * groups * n,
            "taps": config["conv_kernel"], "q": config["chunk_size"],
            "mamba": pattern.count("M"), "experts": pattern.count("E"),
            "attention": pattern.count("*")}


def mamba_token_flops(config: dict) -> float:
    """One token through one Mamba-2 mixer."""
    s = sizes(config)
    proj = s["h"] * (s["di"] + s["conv_dim"] + s["heads"]) \
        + s["di"] * s["h"]
    return 2.0 * (proj + s["taps"] * s["conv_dim"]
                  + 2 * s["heads"] * s["hd"] * s["n"])


def token_flops(config: dict) -> float:
    """One token through everything every token passes."""
    s = sizes(config)
    h = s["h"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    # the router keeps its published width; the shared expert
    expert_layer = h * config["published"]["n_routed_experts"] \
        + 2 * h * config["moe_shared_expert_intermediate_size"]
    return (s["mamba"] * mamba_token_flops(config)
            + s["experts"] * 2.0 * expert_layer
            + s["attention"] * 2.0 * h * (2 * q + 2 * kv))


def pair_flops(config: dict) -> float:
    """One token through one routed relu² expert (up and down)."""
    return 2.0 * 2 * config["hidden_size"] \
        * config["moe_intermediate_size"]


def head_flops(config: dict) -> float:
    """One row through the head's vocabulary slice."""
    return 2.0 * config["hidden_size"] * config["held"]["vocab_size"]


def serve_flops(config: dict, *, tokens: float, held_pairs: float,
                head_rows: float) -> float:
    return (tokens * token_flops(config)
            + held_pairs * pair_flops(config)
            + head_rows * head_flops(config))


def scan_flops(config: dict, *, layer_tokens: float) -> float:
    """The chunked form's least multiply-adds for ``layer_tokens``
    positions x Mamba-2 layers in whole blocks of ``chunk_size``: the
    causal half of each block's ``C B^T`` (a group) and of its scores
    times ``x`` (a head), the carried state's read-out and the state's
    update."""
    s = sizes(config)
    pairs = (s["q"] + 1) / 2.0          # causal pairs a position, a block
    return layer_tokens * 2.0 * (
        pairs * (s["groups"] * s["n"] + s["heads"] * s["hd"])
        + 2 * s["heads"] * s["hd"] * s["n"])


def scan_bytes(config: dict, *, layer_tokens: float,
               calls: float) -> float:
    """The chunked form's least HBM traffic, float32: a position's
    ``x``, ``B``, ``C``, ``dt`` in and ``y`` out; a call's state in and
    out."""
    s = sizes(config)
    row = 2 * s["di"] + 2 * s["groups"] * s["n"] + s["heads"]
    return 4.0 * (layer_tokens * row
                  + calls * 2 * s["heads"] * s["hd"] * s["n"])


def state_step_bytes(config: dict, *, lane_steps: float) -> float:
    """The one-position update's least HBM traffic for ``lane_steps``
    lanes x Mamba-2 layers: the float32 state read and written, the
    convolution's tail (bfloat16) read and written, and the lane's
    ``x``, ``B``, ``C``, ``dt`` rows in and ``y`` out (float32)."""
    s = sizes(config)
    state = 4.0 * 2 * s["heads"] * s["hd"] * s["n"]
    tail = 2.0 * 2 * (s["taps"] - 1) * s["conv_dim"]
    rows = 4.0 * (2 * s["di"] + 2 * s["groups"] * s["n"] + s["heads"])
    return lane_steps * (state + tail + rows)
