"""Operations and bytes from shapes: the benchmark's own arithmetic.

``train_flops`` is a copy of ``models/registry.py``
``_transformer_train_flops`` (what ``_gpt2_train_flops`` and
``_bert_train_flops`` call); the attention-kernel functions are new.
All take the configuration file's ``published`` sizes.
"""

from __future__ import annotations


def train_flops(batch: int, *, layers: int, hidden: int, seq: int,
                vocab: int, intermediate: int, causal: bool) -> float:
    """Model FLOPs of one training step, forward + backward (2x), no
    recompute.

    dense = 6 * N_matmul * tokens (qkv/o/mlp kernels + the output head;
    embedding lookups are gathers, not matmuls).  attention = 12 *
    layers * tokens * seq * hidden (the two S^2 matmuls: 4*S*h per token
    per layer forward, x3 for training), halved for a causal model: only
    the lower triangle is needed work, whatever the kernel computes.
    """
    n_matmul = layers * (4 * hidden * hidden + 2 * hidden * intermediate) \
        + hidden * vocab
    tokens = batch * seq
    attn = 12.0 * layers * tokens * seq * hidden
    if causal:
        attn /= 2.0
    return 6.0 * n_matmul * tokens + attn


def config_train_flops(config: dict, batch: int) -> float:
    """``train_flops`` from a configuration file (``published`` +
    ``job``)."""
    pub = config["published"]
    return train_flops(
        batch, layers=pub["num_hidden_layers"], hidden=pub["hidden_size"],
        seq=config["job"]["seq"], vocab=pub["vocab_size"],
        intermediate=pub["intermediate_size"],
        causal=config["causal"])


def attention_kernels_flops(config: dict, batch: int) -> float:
    """Needed FLOPs of one step's attention-kernel calls (all layers):
    forward QK^T and PV (4*S*S*h a sequence), backward dq and dkv
    kernels (dS, dQ, dK, dV plus the recomputed QK^T the backward cannot
    avoid: 10*S*S*h), halved under a causal mask.  The recomputed scores
    count here, unlike in ``train_flops``: this is the kernels' least
    work, not the model's."""
    pub = config["published"]
    seq = config["job"]["seq"]
    per_layer = 14.0 * batch * seq * seq * pub["hidden_size"]
    if config["causal"]:
        per_layer /= 2.0
    return pub["num_hidden_layers"] * per_layer


def attention_kernels_bytes(config: dict, batch: int,
                            dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the same calls: forward reads q, k, v and
    writes o (4 tensors of B*S*h); backward reads q, k, v, o, do and
    writes dq, dk, dv (8 tensors).  Row statistics (f32, B*S*heads) are
    left out: under 2 % of the tensors."""
    pub = config["published"]
    seq = config["job"]["seq"]
    tensor = batch * seq * pub["hidden_size"] * dtype_bytes
    return pub["num_hidden_layers"] * 12.0 * tensor
