"""Operations and least traffic of the grouped-matmul kernel
(``polyaxon_tpu/ops/grouped_matmul.py``) where the experts are
UNGATED — ``W_down relu(x W_up)^2``, two matrices an expert — for
``ungated_experts_roofline_pct``.  ``grouped_matmul_flops.py`` counts
the three matrices of a SwiGLU expert, which would read a relu² layer
1.5 x high.  The kernel's own sizes only — the width ``h`` of a token
and ``f`` of an expert (``hidden_size``, ``moe_intermediate_size``);
how many experts are held, how many layers have experts and how many a
token chooses come in through the program's counters.

An expert layer is two grouped matmuls — up ``[h, f]``, down ``[f,
h]`` an expert — over the token-expert pairs that fell on a held
expert.  Counted, 2 FLOP a multiply-add, bfloat16 at rest.
"""

from __future__ import annotations


def flops(*, h: int, f: int, held_pairs: float) -> float:
    """Up and down of one expert for every held pair (growth of
    ``moe_pairs_held_total``)."""
    return held_pairs * 2.0 * 2 * h * f


def least_bytes(*, h: int, f: int, held_pairs: float,
                touched: float) -> float:
    """The least HBM traffic: the two matrices of every expert that
    took at least one pair in a layer of a program run, read ONCE
    (growth of ``moe_experts_touched_total``), and each held pair's row
    in and out: ``h`` in and ``f`` out for up, ``f`` in and ``h`` out
    for down."""
    return 2.0 * (touched * 2.0 * h * f
                  + held_pairs * (2.0 * h + 2.0 * f))
