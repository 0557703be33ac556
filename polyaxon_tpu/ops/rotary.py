"""Rotary position embeddings (RoPE) — Su et al., RoFormer.

The reference delegates model code entirely to user containers
(SURVEY.md §0); the TPU build's zoo owns its ops.  RoPE is implemented
the TPU-friendly way: the half-split convention (rotate_half) over the
head dim, precomputing cos/sin once per (seq, head_dim) at trace time so
XLA hoists them out of the layer scan and fuses the elementwise rotation
into the surrounding matmul epilogues.  No gather/scatter, no dynamic
shapes — everything is iota-based and static.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_correction_range(dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """``(low, high)``: the pair indices between which YaRN blends.
    Pair ``i`` of ``dim // 2`` turns ``original / (2 pi theta^(2i/dim))``
    times over the original context; ``cd(r)`` is the (real) index of
    the pair that turns ``r`` times, ``low = floor(cd(beta_fast))`` and
    ``high = ceil(cd(beta_slow))``, clipped to the pairs there are."""
    def cd(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(cd(beta_fast)), 0),
            min(math.ceil(cd(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float = 32, beta_slow: float = 1):
    """YaRN's frequencies of the ``dim // 2`` pairs (Peng et al.,
    arXiv:2309.00071), float32 numpy: pairs that turn more than
    ``beta_fast`` times over the ``original`` context keep their
    frequency ``f_i = theta^(-2i/dim)`` (extrapolated), pairs that turn
    less than ``beta_slow`` times take ``f_i / factor`` (interpolated),
    and a linear ramp over the pair index blends the ones between."""
    half = dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) / half)
    low, high = yarn_correction_range(dim, theta, original, beta_fast,
                                      beta_slow)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / factor * ramp).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _cos_sin(dim: int, theta: float, positions: jax.Array,
             inv_freq=None):
    # [S, dim/2] angle table in f32; bf16 angles lose too much precision
    # for long sequences (position 8191 * smallest freq needs ~13 bits).
    # ``positions`` may be traced (the decode path's cache index) — ONE
    # formula serves train and decode, so they cannot drift.
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(q: jax.Array, k: jax.Array, *,
                 theta: float = 10000.0,
                 position_offset: int = 0,
                 positions: jax.Array = None,
                 inv_freq=None):
    """Rotate q/k ([B, S, H, D]) by their positions; returns (q, k).

    ``position_offset`` (static int) shifts positions; ``positions``
    ([S] int array, may be traced — the decode path's cache index)
    overrides it.  ``inv_freq`` ([D/2], e.g. :func:`yarn_inv_freq`)
    replaces the frequencies ``theta`` would give.  The rotation
    preserves dtype (bf16 in, bf16 out) while the trig and the
    rotation arithmetic run in f32.
    """
    seq, d = q.shape[1], q.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head dim; got {d}")
    if k.shape[1] != seq:
        # One angle table serves both tensors; rotating a short q
        # against a long k (decode against a cache) must go through two
        # calls — the cached k are already rotated at their positions.
        raise ValueError(
            f"apply_rotary needs matching q/k seq lengths (got "
            f"{seq} vs {k.shape[1]}); rotate new k at its own "
            f"position_offset and reuse the cached rotated keys")
    if positions is None:
        positions = position_offset + jnp.arange(seq)
    cos, sin = _cos_sin(d, theta, positions, inv_freq)
    cos = cos[None, :, None, :]  # [1, S, 1, D/2]
    sin = sin[None, :, None, :]

    def rot(x):
        x = x.astype(jnp.float32)
        x1, x2 = jnp.split(x, 2, axis=-1)
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out

    return rot(q).astype(q.dtype), rot(k).astype(k.dtype)
