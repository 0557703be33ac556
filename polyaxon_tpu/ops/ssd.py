"""State-space duality (Mamba-2): a matrix-valued state a head.

The recurrence of a Mamba-2 mixer, head ``h`` of group ``g`` (the
heads share their ``B`` and ``C`` within a group)::

    h_t = exp(dt_t A_h) h_{t-1} + dt_t x_t (x) B_t     [P, N] a head
    y_t = h_t C_t + D_h x_t

The decay ``exp(dt_t A_h)`` is ONE scalar a head and position (where
Mamba-1's is a ``[d, n]`` matrix a position, ``ops/selective_scan.py``),
so a block of positions folds into matrix products: the chunked (SSD)
form of Dao & Gu, arXiv:2405.21060, section 6.  Within a block of
``Q`` positions the outputs are ``(C B^T o L) X`` with the decay mask
``L_ij = exp(sum_{j < k <= i} dt_k A)`` (``i >= j``), and the state the
block hands on is the carried one decayed over the block plus
``X^T (decay to the block's end * dt o B)``.  The state, ``dt``, the
decays and every operand are float32; the block's products go to the
MXU at the default matmul precision and accumulate in float32.

Shapes: ``x`` ``[B, L, H, P]``, ``dt`` ``[B, L, H]`` (after the
softplus), ``A``, ``D`` ``[H]``, ``B``, ``C`` ``[B, L, G, N]``, the
state ``[B, H, P, N]``; ``H`` a multiple of ``G``, head ``h`` reading
group ``h // (H / G)``.

- :func:`ssd_scan`: a prefill piece, the state carried in and out:
  blocks of ``chunk`` positions, a ``lax.scan`` over the blocks (XLA:
  einsums on the MXU, no Pallas kernel), traced under the scope
  ``ptpu_scan`` (spans.py).  A piece that ``chunk`` does not divide is
  padded with ``dt = 0``: a padded position decays nothing (``exp(0) =
  1``) and adds nothing (``0 * x``), so the state leaves the piece
  exactly as its last real position left it.
- :func:`ssd_step`: a decode step's one-position update, elementwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..spans import scope

F32 = jnp.float32


def _grouped(a, groups: int):
    """``[..., H]`` -> ``[..., G, H / G]``: the heads by group."""
    return a.reshape(a.shape[:-1] + (groups, a.shape[-1] // groups))


def ssd_step(x, dt, A, B, C, D, h):
    """ONE position: ``x`` ``[b, H, P]``, ``dt`` ``[b, H]``, ``B``,
    ``C`` ``[b, G, N]``, ``h`` ``[b, H, P, N]`` -> ``(y [b, H, P], h)``.
    float32."""
    b, heads, p = x.shape
    g, n = B.shape[-2:]
    x = x.astype(F32).reshape(b, g, heads // g, p)
    dt = _grouped(dt.astype(F32), g)                      # [b, G, k]
    h = h.reshape(b, g, heads // g, p, n)
    decay = jnp.exp(dt * _grouped(A.astype(F32), g))
    h = decay[..., None, None] * h \
        + (dt[..., None] * x)[..., None] * B.astype(F32)[:, :, None, None]
    y = jnp.sum(h * C.astype(F32)[:, :, None, None], axis=-1) \
        + _grouped(D.astype(F32), g)[..., None] * x
    return y.reshape(b, heads, p), h.reshape(b, heads, p, n)


def _block(h, blk, A):
    """One block of ``q`` positions from the carried state ``h``
    ``[b, G, k, P, N]``: ``(h', y [b, q, G, k, P])`` without the skip."""
    x, dt, bm, cm = blk            # [b,q,G,k,P] [b,q,G,k] [b,q,G,N] x2
    a = dt * A                                            # log decays
    cum = jnp.cumsum(a, axis=1)                           # [b, q, G, k]
    q = x.shape[1]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    # L_ij = exp(cum_i - cum_j) for j <= i: masked BEFORE the exponent,
    # where the difference of a later j is positive and may overflow.
    seg = cum[:, :, None] - cum[:, None, :]               # [b,i,j,G,k]
    decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                              -jnp.inf))
    cb = jnp.einsum("bign,bjgn->bijg", cm, bm,
                    preferred_element_type=F32)
    scores = cb[..., None] * decay * dt[:, None]          # [b,i,j,G,k]
    y = jnp.einsum("bijgk,bjgkp->bigkp", scores, x,
                   preferred_element_type=F32)
    # what the carried state gives each position, decayed to it
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bign,bgkpn->bigkp", cm, h, preferred_element_type=F32)
    to_end = jnp.exp(cum[:, -1:] - cum) * dt              # [b, q, G, k]
    h = jnp.exp(cum[:, -1])[..., None, None] * h + jnp.einsum(
        "bjgkp,bjgn->bgkpn", x * to_end[..., None], bm,
        preferred_element_type=F32)
    return h, y


def ssd_scan(x, dt, A, B, C, D, h0, *, chunk: int = 128):
    """``(y [B, L, H, P], h_L [B, H, P, N])`` of the recurrence in the
    module's docstring over one piece, from the carried-in state
    ``h0``, in blocks of ``min(chunk, L)`` positions; float32.  Traced
    under the scope ``ptpu_scan`` (spans.py)."""
    with scope("ptpu_scan"):
        b, length, heads, p = x.shape
        g, n = B.shape[-2:]
        k = heads // g
        q = min(chunk, length)
        blocks = -(-length // q)
        pad = blocks * q - length

        def blocked(a, tail):
            """``[b, L, ...]`` float32, padded with zeros to whole
            blocks, as ``[blocks, b, q, *tail]`` for the scan."""
            a = a.astype(F32)
            if pad:
                a = jnp.pad(a, [(0, 0), (0, pad)]
                            + [(0, 0)] * (a.ndim - 2))
            a = a.reshape((b, blocks, q) + tail)
            return jnp.moveaxis(a, 1, 0)

        xs = (blocked(x, (g, k, p)), blocked(dt, (g, k)),
              blocked(B, (g, n)), blocked(C, (g, n)))
        a_g = _grouped(A.astype(F32), g)
        h, y = jax.lax.scan(
            lambda h, blk: _block(h, blk, a_g),
            h0.astype(F32).reshape(b, g, k, p, n), xs)
        y = jnp.moveaxis(y, 0, 1).reshape(b, blocks * q, heads, p)
        y = y[:, :length] + D.astype(F32)[:, None] * x.astype(F32)
        return y, h.reshape(b, heads, p, n)
