"""Grouped matmul of the expert layers: Pallas kernel on TPU, XLA's
``ragged_dot`` elsewhere.

``rows`` ``[M, k]``, sorted by group, through ``w`` ``[G, k, n]``: row
``i`` is multiplied by the matrix of the group it lies in, groups lying
one behind the other, ``sizes`` ``[G]`` rows each; rows past the last
group belong to none and what the result holds there is undefined
(``parallel/moe.held_experts_ffn`` masks them).  bfloat16 or float32
in, the same out, accumulation in float32.

Two routes behind :func:`grouped_matmul`, counted when a call is TRACED
(:func:`route_counts`, the server's ``/info`` ``grouped_matmul_routes``):

- ``pallas`` (a TPU, a row count that a row tile divides:
  :func:`tiling`): ``jax.experimental.pallas.ops.tpu.megablox.gmm``,
  which walks the row tiles group by group and visits a (tile, group)
  pair only where rows of that group lie in that tile.  With the few
  rows a group that a prefill piece or a decode step gives an expert
  (3 072 rows over 64 experts: 48 a group) XLA's own ``ragged-dot``
  ran 2.97 ms where this kernel runs 0.79 ms (``[3072, 2048] x [64,
  2048, 1408]``, v5e, PERF.md section 6, PR 35); the floor is the
  experts' weights read once, 0.45 ms.
- ``xla``: ``jax.lax.ragged_dot``.  The CPU path, the path of odd row
  counts, and the kernel's test oracle.
"""

from __future__ import annotations

import collections
import os
import threading
from typing import Dict, Optional, Tuple

import jax

_ROUTES: "collections.Counter[str]" = collections.Counter()
_ROUTES_LOCK = threading.Lock()


def route_counts() -> Dict[str, int]:
    """``{"pallas": n, "xla": n}``: grouped matmuls traced so far in
    this process, by the route they took."""
    with _ROUTES_LOCK:
        return {"pallas": _ROUTES["pallas"], "xla": _ROUTES["xla"]}


def tiling(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    """The kernel's ``(rows, k, n)`` tile for ``[m, k] x [G, k, n]``, or
    None where no row tile divides ``m`` (the kernel asks that; ``k``
    and ``n`` may leave a ragged last tile).  Rows: 256 where they
    divide ``m``, else 128, else ``m`` itself up to 256 (a decode
    step's pairs; whole 16-row bfloat16 tiles).  The matrix tile is
    wide over ``n`` and 512 deep, or the whole of a short ``k`` under a
    1 024-wide strip of a long ``n``: the two that read fastest of
    those tried on the chip at ``(2048, 1408)`` and ``(1408, 2048)``
    (PERF.md section 6, PR 35); about 6-8 MB of VMEM with the double
    buffers."""
    rows = next((t for t in (256, 128) if m % t == 0),
                m if m <= 256 and m % 16 == 0 else None)
    if rows is None:
        return None
    if n <= 1536:
        return rows, min(k, 512), n
    return min(rows, 128), k if k <= 1536 else 512, 1024


def kernel_eligible(m: int, k: int, n: int) -> bool:
    """Whether the Pallas kernel takes this matmul: a TPU (or a
    deviceless compile for one: the switch ``ops/flash.py`` reads) and
    a row count it can tile."""
    on_tpu = jax.default_backend() == "tpu" \
        or os.environ.get("POLYAXON_TPU_ASSUME_TPU")
    return bool(on_tpu) and tiling(m, k, n) is not None


def grouped_matmul(rows, w, sizes, *, interpret: bool = False):
    """``rows`` [M, k] through ``w`` [G, k, n] by groups of ``sizes``
    [G] int32 rows -> [M, n] in ``rows``' dtype.  ``interpret`` forces
    the kernel's route through the Pallas interpreter (tests, on a
    CPU)."""
    m, k = rows.shape
    n = w.shape[-1]
    kernel = interpret or kernel_eligible(m, k, n)
    with _ROUTES_LOCK:
        _ROUTES["pallas" if kernel else "xla"] += 1
    if not kernel:
        return jax.lax.ragged_dot(rows, w, group_sizes=sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(rows, w, sizes, preferred_element_type=rows.dtype,
               tiling=tiling(m, k, n), interpret=interpret)
