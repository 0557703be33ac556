"""Selective scan (Mamba-1): Pallas kernel on TPU, ``lax.scan`` elsewhere.

The recurrence of a state-space mixer over a piece of ``L`` positions,
with the state it was handed and the state it leaves::

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) (x) B_t
    y_t = h_t . C_t + D * u_t            [* silu(z_t) where z is given]

``delta_t * A`` is one decay per channel AND per state (``[d, n]``), so
the recurrence folds into no matrix product; everything is float32.

Shapes: ``u, delta, z, y`` ``[B, L, D]``; ``A`` and the state ``h``
``[N, D]`` / ``[B, N, D]`` (the channel axis LAST: 16 x 5 120 tiles the
chip's (8, 128) registers whole, where ``[D, 16]`` would pad every row
of 16 to 128 lanes); ``B, C`` ``[B, L, N]``; ``D`` ``[D]``.

Two routes behind :func:`selective_scan`, counted when a call is TRACED
(:func:`route_counts`, the server's ``/info`` ``scan_routes``):

- ``pallas`` (a TPU, ``D`` a multiple of 1 024): the kernel holds ``h``
  in VMEM for a block of 1 024 channels laid out ``[N, 8, 128]``, walks
  time inside the kernel — one step is ``N`` full-register updates, the
  step's ``B_t[n]``, ``C_t[n]`` read as scalars from SMEM — and streams
  ``u, delta, z -> y`` through in pieces of ``BLOCK_L`` positions.  A
  piece that ``BLOCK_L`` does not divide is padded with ``delta = 0``:
  ``exp(0) = 1`` and ``0 * u = 0``, so a padded position leaves ``h``
  as it was, exactly.
- ``xla``: a ``lax.scan`` over positions, a handful of fusions a trip.
  The CPU path, the path of odd widths, and the kernel's test oracle.

:func:`selective_step` is the decode step's one-position update in
plain ``jnp`` (elementwise; XLA fuses it into the step program).
"""

from __future__ import annotations

import collections
import functools
import os
import threading
from typing import Dict

import jax
import jax.numpy as jnp

from ..spans import scope

F32 = jnp.float32
SUBLANES, LANES = 8, 128
CHANNEL_BLOCK = SUBLANES * LANES    # channels a kernel instance owns
BLOCK_L = 128                       # positions a grid step streams

_ROUTES: "collections.Counter[str]" = collections.Counter()
_ROUTES_LOCK = threading.Lock()


def route_counts() -> Dict[str, int]:
    """``{"pallas": n, "xla": n}``: scans traced so far in this
    process, by the route they took."""
    with _ROUTES_LOCK:
        return {"pallas": _ROUTES["pallas"], "xla": _ROUTES["xla"]}


def scan_eligible(d: int) -> bool:
    """Whether the Pallas kernel takes a scan over ``d`` channels: a
    TPU (or a deviceless compile for one: the switch ``ops/flash.py``
    reads) and whole channel blocks."""
    on_tpu = jax.default_backend() == "tpu" \
        or os.environ.get("POLYAXON_TPU_ASSUME_TPU")
    return bool(on_tpu) and d % CHANNEL_BLOCK == 0


def silu(x):
    return x * jax.nn.sigmoid(x)


def selective_step(u, delta, A, B, C, D, h):
    """ONE position: ``u, delta`` ``[..., D]``, ``B, C`` ``[..., N]``,
    ``h`` ``[..., N, D]`` -> ``(y [..., D], h)``.  float32."""
    u, delta = u.astype(F32), delta.astype(F32)
    h = jnp.exp(delta[..., None, :] * A) * h \
        + (delta * u)[..., None, :] * B.astype(F32)[..., :, None]
    y = jnp.sum(h * C.astype(F32)[..., :, None], axis=-2) + D * u
    return y, h


def selective_scan_xla(u, delta, A, B, C, D, h0, z=None):
    """The recurrence as a ``lax.scan`` over positions."""
    A, D = A.astype(F32), D.astype(F32)

    def step(h, x):
        u_t, delta_t, b_t, c_t = x
        y, h = selective_step(u_t, delta_t, A, b_t, c_t, D, h)
        return h, y

    h, y = jax.lax.scan(step, h0.astype(F32), tuple(
        jnp.moveaxis(a.astype(F32), 1, 0) for a in (u, delta, B, C)))
    y = jnp.moveaxis(y, 0, 1)
    if z is not None:
        y = y * silu(z.astype(F32))
    return y, h


def _scan_kernel(b_ref, c_ref, u_ref, dt_ref, z_ref, a_ref, d_ref,
                 h0_ref, y_ref, h_ref, *, steps: int, n: int,
                 gated: bool):
    """One block of 1 024 channels, one piece of ``steps`` positions.
    ``h_ref`` (the OUTPUT block ``[N, 8, 128]``, the same block for
    every piece of the time axis, so it stays in VMEM across them)
    carries the state from piece to piece."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    a = a_ref[...]                                  # [N, 8, 128]
    d_skip = d_ref[...]                             # [8, 128]

    def step(t, h):
        u = u_ref[t]                                # [8, 128]
        dt = dt_ref[t]
        du = dt * u
        y = d_skip * u
        new = []
        for i in range(n):                          # N register updates
            h_i = jnp.exp(dt * a[i]) * h[i] + du * b_ref[t * n + i]
            y = y + h_i * c_ref[t * n + i]
            new.append(h_i)
        if gated:
            zt = z_ref[t]
            y = y * (zt / (1.0 + jnp.exp(-zt)))
        y_ref[t] = y
        return tuple(new)

    h = jax.lax.fori_loop(
        0, steps, step, tuple(h_ref[i] for i in range(n)))
    for i in range(n):
        h_ref[i] = h[i]


def selective_scan_pallas(u, delta, A, B, C, D, h0, z=None, *,
                          block_l: int = None, interpret: bool = False):
    """The recurrence as the Pallas kernel (``D`` a multiple of
    1 024); ``interpret`` runs it in the Pallas interpreter (the CPU
    tests)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, length, d = u.shape
    n = A.shape[0]
    if d % CHANNEL_BLOCK:
        raise ValueError(f"{d} channels are not whole blocks of "
                         f"{CHANNEL_BLOCK}")
    block_l = min(block_l or BLOCK_L, length)
    pieces = -(-length // block_l)
    pad = pieces * block_l - length
    gated = z is not None

    def rows(x):
        """``[B, L, D]`` float32, padded with zeros to whole pieces,
        channels as ``[8, D / 8]``: a position is whole registers."""
        x = x.astype(F32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x.reshape(bsz, pieces * block_l, SUBLANES, d // SUBLANES)

    def scalars(x):
        """``[B, L, N]`` -> ``[B * pieces, block_l * N]`` for SMEM."""
        x = x.astype(F32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x.reshape(bsz * pieces, block_l * n)

    grid = (bsz, d // CHANNEL_BLOCK, pieces)
    smem = pl.BlockSpec((None, block_l * n),
                        lambda b, j, t: (b * pieces + t, 0),
                        memory_space=pltpu.SMEM)
    row = pl.BlockSpec((None, block_l, SUBLANES, LANES),
                       lambda b, j, t: (b, t, 0, j))
    state = pl.BlockSpec((None, n, SUBLANES, LANES),
                         lambda b, j, t: (b, 0, 0, j))
    u4 = rows(u)
    operands = [scalars(B), scalars(C), u4, rows(delta),
                rows(z) if gated else u4,
                A.astype(F32).reshape(n, SUBLANES, d // SUBLANES),
                D.astype(F32).reshape(SUBLANES, d // SUBLANES),
                h0.astype(F32).reshape(bsz, n, SUBLANES, d // SUBLANES)]
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, steps=block_l, n=n, gated=gated),
        grid=grid,
        in_specs=[smem, smem, row, row, row,
                  pl.BlockSpec((n, SUBLANES, LANES),
                               lambda b, j, t: (0, 0, j)),
                  pl.BlockSpec((SUBLANES, LANES),
                               lambda b, j, t: (0, j)),
                  state],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct(u4.shape, F32),
                   jax.ShapeDtypeStruct(
                       (bsz, n, SUBLANES, d // SUBLANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(*operands)
    return (y.reshape(bsz, pieces * block_l, d)[:, :length],
            h.reshape(bsz, n, d))


def selective_scan(u, delta, A, B, C, D, h0, z=None):
    """``(y [B, L, D], h_L [B, N, D])`` of the recurrence in the
    module's docstring over one piece, from the carried-in state
    ``h0``; float32.  The one entry point: the kernel where
    :func:`scan_eligible`, the ``lax.scan`` otherwise; either way
    traced under the scope ``ptpu_scan`` (spans.py)."""
    route = "pallas" if scan_eligible(u.shape[-1]) else "xla"
    with _ROUTES_LOCK:
        _ROUTES[route] += 1
    fn = selective_scan_pallas if route == "pallas" \
        else selective_scan_xla
    with scope("ptpu_scan"):
        return fn(u, delta, A, B, C, D, h0, z)
