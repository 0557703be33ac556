"""Operations of the sparse decoder ``trinity-large-preview`` as it is
held on one chip, from the configuration file's sizes: what
``serve_mfu_pct`` divides by the peak.  (No Pallas kernel of this PR to
count: the grouped expert matmul is ``jax.lax.ragged_dot``, which the
TPU compiler lowers to its own custom call.)

Counted, 2 FLOP a multiply-add: for every token the attention's five
projections (q, k, v, gate, o) of every layer, the dense layers' SwiGLU,
the shared expert of every expert layer; for every token-expert pair
that fell on a HELD expert (the program's counter, not an expectation)
one expert's SwiGLU; for every row that went through the head, the
vocabulary slice.  NOT counted: the attention over the cache (scores
and weighted values), the router, norms, RoPE, the sampler — so the
share under-reads and cannot flatter.
"""

from __future__ import annotations


def token_flops(config: dict) -> float:
    """One token through everything every token passes."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    layers = config["held"]["num_hidden_layers"]
    dense = config["held"]["num_dense_layers"]
    attention = h * (3 * heads + 2 * kv)            # q, gate, o; k, v
    return 2.0 * (layers * attention
                  + dense * 3 * h * config["intermediate_size"]
                  + (layers - dense) * 3 * h
                  * config["moe_intermediate_size"])


def pair_flops(config: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"]


def head_flops(config: dict) -> float:
    """One row through the head's vocabulary slice."""
    return 2.0 * config["hidden_size"] * config["held"]["vocab_size"]


def serve_flops(config: dict, *, tokens: float, held_pairs: float,
                head_rows: float) -> float:
    return (tokens * token_flops(config)
            + held_pairs * pair_flops(config)
            + head_rows * head_flops(config))
