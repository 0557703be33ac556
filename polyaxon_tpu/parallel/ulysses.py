"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

Second context-parallel strategy (SURVEY.md 5.7): instead of rotating K/V
(ring), reshard so each device sees the FULL sequence for a subset of
heads — one all-to-all before attention, one after.  On TPU the
``all_to_all`` lowers to ICI all-to-all; cost is 2 reshards of activations
vs the ring's (n-1) K/V hops, favoring Ulysses when heads >> sp and
attention kernels want the whole sequence (e.g. flash attention on-chip).

Requires num_heads % sp == 0.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import active_batch_axes


def _ulysses_shard(q, k, v, mask, *, axis_name: str, attn_fn):
    """Per-shard body: inputs [B, S/sp, H, D] -> output [B, S/sp, H, D].

    ``mask``: None or boolean [B, H?, Sq, Sk] replicated across the sp
    axis (full sequence dims); when it carries a real head dim, each
    rank slices its own head range after the all-to-all.
    """

    def seq2head(x):
        # [B, S/sp, H, D] -> [B, S, H/sp, D]: split heads, gather sequence.
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head2seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    q_full = seq2head(q)
    k_full = seq2head(k)
    v_full = seq2head(v)
    if mask is None:
        # Unmasked: keep the original 3-arg attn_fn contract so existing
        # custom kernels (attn_fn=lambda q, k, v: ...) stay valid.
        o_full = attn_fn(q_full, k_full, v_full)
    else:
        mask_local = mask
        if mask.shape[1] > 1:
            n = jax.lax.psum(1, axis_name)
            idx = jax.lax.axis_index(axis_name)
            h_per = mask.shape[1] // n
            mask_local = jax.lax.dynamic_slice_in_dim(
                mask, idx * h_per, h_per, axis=1)
        o_full = attn_fn(q_full, k_full, v_full, mask_local)
    return head2seq(o_full)


def _default_inner(q, k, v, mask=None, *, causal: bool,
                   scale: Optional[float], window: Optional[int] = None):
    """Per-shard attention after the all-to-all: each rank holds the
    FULL sequence for a head subset — exactly the flash kernel's shape,
    so route through it when eligible (TPU or the interpret-mode tests,
    lane-aligned seq, MXU-aligned head dim, at most a key-padding
    mask); otherwise the fused-XLA fallback."""
    from ..ops.flash import flash_attention, flash_eligible, \
        narrow_kv_mask

    if flash_eligible(q.shape[1], k.shape[1], q.shape[-1], mask):
        kvm = None if mask is None else \
            narrow_kv_mask(mask, q.shape[0], k.shape[1])
        return flash_attention(
            q, k, v, causal=causal,
            scale=q.shape[-1] ** -0.5 if scale is None else scale,
            kv_mask=kvm, window=window)
    return _plain_attention(q, k, v, mask, causal=causal, scale=scale,
                            window=window)


def _plain_attention(q, k, v, mask=None, *, causal: bool,
                     scale: Optional[float],
                     window: Optional[int] = None):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:  # window implies causal (validated at every driver)
        # Post-all-to-all each rank holds the FULL sequence, so local
        # indices ARE global positions; the window composes directly.
        sq, sk = q.shape[1], k.shape[1]
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(sk)[None, :]
        cmask = qi >= ki
        if window is not None:
            cmask &= qi - ki <= window
        scores = jnp.where(cmask[None, :, None, :], scores, -1e30)
    if mask is not None:
        # [B, H?, Sq, Sk] -> scores' [B, Sq, H, Sk]
        scores = jnp.where(jnp.transpose(mask, (0, 2, 1, 3)),
                           scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    mask: Optional[jax.Array] = None,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    attn_fn: Optional[Callable] = None,
    batch_axes=("dp", "fsdp"),
):
    """Ulysses attention over a mesh axis; q/k/v GLOBAL [B, S, H, D].

    ``mask``: optional boolean [B, H?, Sq, Sk] (True = attend; padded
    batches keep sequence parallelism — VERDICT r1 #8).  The mask's
    sequence dims stay full (post-all-to-all each rank sees the whole
    sequence); a real head dim must divide the sp axis like q's.

    ``attn_fn``: custom kernel called as ``attn_fn(q, k, v)`` when no
    mask is given (the original contract) and ``attn_fn(q, k, v, mask)``
    when one is — a 3-arg kernel stays valid for unmasked use.
    """
    from jax import shard_map

    sp = mesh.shape.get(axis_name, 1)
    n_heads = q.shape[2]
    if n_heads % sp:
        raise ValueError(
            f"Ulysses needs heads ({n_heads}) divisible by {axis_name} "
            f"axis size ({sp}); use ring attention otherwise"
        )
    if mask is not None:
        if mask.ndim != 4:
            raise ValueError(
                f"mask must be 4-d [B,H,Sq,Sk]; got {mask.shape}")
        if mask.shape[1] > 1 and mask.shape[1] % sp:
            raise ValueError(
                f"mask head dim ({mask.shape[1]}) must divide sp ({sp})")
    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1; got {window}")
        if attn_fn is not None:
            raise ValueError(
                "window with a custom attn_fn would be silently "
                "ignored; apply the window inside your kernel instead")
    inner = attn_fn or functools.partial(_default_inner, causal=causal,
                                         scale=scale, window=window)
    batch = active_batch_axes(mesh, batch_axes)
    spec = P(batch, axis_name, None, None)
    body = functools.partial(_ulysses_shard, axis_name=axis_name,
                             attn_fn=inner)
    if mask is None:
        return shard_map(
            lambda q, k, v: body(q, k, v, None), mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    mask_spec = P(batch if mask.shape[0] > 1 else None, None, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, mask_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, mask)
