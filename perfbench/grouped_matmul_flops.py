"""Operations and least traffic of the expert layers' grouped-matmul
kernel (``polyaxon_tpu/ops/grouped_matmul.py``), for
``grouped_matmul_roofline_pct`` in whichever configuration runs it.
The kernel's own sizes only — the width ``h`` of a token and ``f`` of
an expert (``hidden_size``, ``moe_intermediate_size`` in every
configuration's file); how many experts are held, how many layers have
experts and how many a token chooses come in through the program's
counters, not through a configuration's key names.

An expert layer is three grouped matmuls — gate and up ``[h, f]``,
down ``[f, h]`` an expert — over the token-expert pairs that fell on a
held expert.  Counted, 2 FLOP a multiply-add, bfloat16 at rest.
"""

from __future__ import annotations


def flops(*, h: int, f: int, held_pairs: float) -> float:
    """Gate, up and down of one expert for every held pair (growth of
    ``moe_pairs_held_total``)."""
    return held_pairs * 2.0 * 3 * h * f


def least_bytes(*, h: int, f: int, held_pairs: float,
                touched: float) -> float:
    """The least HBM traffic: the three matrices of every expert that
    took at least one pair in a layer of a program run, read ONCE
    (growth of ``moe_experts_touched_total``: counted in the program,
    a decode step's lanes summed first, not an expectation), and each
    held pair's row in and out: ``h`` in and ``f`` out for gate and
    for up, ``f`` in and ``h`` out for down."""
    return 2.0 * (touched * 3.0 * h * f
                  + held_pairs * (3.0 * h + 3.0 * f))
