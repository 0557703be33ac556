"""Operations of the latent-attention sparse decoder
``deepseek-v2-lite`` as it is held on one chip, from the configuration
file's sizes: what ``mla_serve_mfu_pct`` divides by the peak.  (The
one Pallas kernel on this configuration's path, the expert layers'
grouped matmul, is counted in ``perfbench/grouped_matmul_flops.py``;
both attention paths are XLA's.)

Counted, 2 FLOP a multiply-add, at the MODEL's form — the cheapest way
the equations can be computed, whatever the program does:

- for every token the five projections of every layer's attention (the
  query, the compression ``W_kva``, ``W_UK`` and ``W_UV`` — ONE
  expansion of the token's own latent through ``W_kvb`` — and the
  output), the dense layers' SwiGLU, the shared SwiGLU of every expert
  layer;
- for every token-expert pair that fell on a HELD expert (the program's
  counter, not an expectation) one expert's SwiGLU;
- for every causal query-key pair of a latent layer (the program's two
  pair counters, which come multiplied by the layers) the scores over
  ``nope + rope`` and the weighted sum over ``v``, a head;
- for every row that went through the head, the vocabulary.

NOT counted: what the program spends beyond that form — the
re-expansion of cached rows in every later prefill piece, the absorbed
path's wider pairs (``rank + rope`` and ``rank`` a head where the model
needs ``nope + rope`` and ``v``) — nor the router, norms, RoPE and the
sampler.  So the share cannot flatter: a program that re-expands less
reads higher.
"""

from __future__ import annotations


def attention_token_flops(config: dict) -> float:
    """One token through one layer's five projections."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    return 2.0 * (h * heads * (nope + rope) + h * (rank + rope)
                  + rank * heads * (nope + v) + heads * v * h)


def token_flops(config: dict) -> float:
    """One token through everything every token passes."""
    h = config["hidden_size"]
    layers = config["held"]["num_hidden_layers"]
    dense = config["held"]["first_k_dense_replace"]
    shared = config["n_shared_experts"] * config["moe_intermediate_size"]
    return (layers * attention_token_flops(config)
            + 2.0 * 3 * h * (dense * config["intermediate_size"]
                             + (layers - dense) * shared))


def pair_flops(config: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"]


def attention_pair_flops(config: dict) -> float:
    """One causal query-key pair of one layer, every head: the score
    and the weighted value."""
    return 2.0 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def head_flops(config: dict) -> float:
    """One row through the head."""
    return 2.0 * config["hidden_size"] * config["held"]["vocab_size"]


def serve_flops(config: dict, *, tokens: float, held_pairs: float,
                attention_pairs: float, head_rows: float) -> float:
    return (tokens * token_flops(config)
            + held_pairs * pair_flops(config)
            + attention_pairs * attention_pair_flops(config)
            + head_rows * head_flops(config))
