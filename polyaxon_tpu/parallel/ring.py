"""Ring attention: sequence/context parallelism over the ICI torus.

Long-context capability the reference lacks entirely (SURVEY.md 5.7).
Design follows the blockwise/ring-attention literature (see PAPERS.md):
each device owns one sequence block of Q/K/V; K/V blocks rotate around the
``sp`` axis via ``ppermute`` (on TPU this maps onto nearest-neighbor ICI
hops — the hardware *is* the ring), while each device accumulates its
local Q's attention with a numerically-stable running log-sum-exp.
Compute of block r overlaps with the DMA of block r+1 (XLA schedules the
ppermute async); the attention never materializes the full [S, S] matrix.

All functions are written per-shard and meant to be wrapped by
``shard_map`` (see ``ring_attention`` for the driver).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import active_batch_axes

BIG_NEG = -1e30


def _ring_flash_eligible(q, s_blk: int, mask) -> bool:
    """Static routing: run per-rotation blocks through the pallas flash
    kernel?  Shared predicate; the kernels see s_blk-length q/kv blocks
    while the key-padding mask keeps FULL kv columns (sliced per
    rotation), hence mask_kv_len."""
    from ..ops.flash import flash_eligible

    return flash_eligible(s_blk, s_blk, q.shape[-1], mask,
                          mask_kv_len=q.shape[1])


def _block_attend(q, k, v, *, scale, q_offset, kv_offset, causal,
                  mask_blk=None, window=None):
    """One blockwise attention contribution.

    q: [B, Sq, H, D], k/v: [B, Sk, H, D] -> (scores-derived partials)
    Returns (p @ v) unnormalized [B, Sq, H, D], row max m [B, Sq, H],
    row sum l [B, Sq, H] — all in f32 for stable accumulation.
    ``mask_blk``: optional boolean broadcastable to [B, H, Sq, Sk]
    (True = attend) covering exactly this KV block.
    """
    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bqhk", q32, k32) * scale  # [B,Sq,H,Sk]
    if causal:  # window implies causal (validated at every driver)
        sq, sk = q.shape[1], k.shape[1]
        q_ids = q_offset + jnp.arange(sq)[:, None]
        k_ids = kv_offset + jnp.arange(sk)[None, :]
        mask = q_ids >= k_ids  # [Sq, Sk]
        if window is not None:
            mask &= q_ids - k_ids <= window
        scores = jnp.where(mask[None, :, None, :], scores, BIG_NEG)
    if mask_blk is not None:
        # [B, H, Sq, Sk] (broadcast dims allowed) -> scores' B,Sq,H,Sk.
        scores = jnp.where(jnp.transpose(mask_blk, (0, 2, 1, 3)),
                           scores, BIG_NEG)
    m = jnp.max(scores, axis=-1)  # [B,Sq,H]
    p = jnp.exp(scores - m[..., None])
    # Fully-masked rows: zero contribution (m stays BIG_NEG, p -> 1.0 rows
    # must not pollute the sum).
    valid = m > BIG_NEG / 2
    p = jnp.where(valid[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,Sq,H]
    pv = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32))
    return pv, m, l


def _ring_attention_shard(q, k, v, mask, *, axis_name: str, causal: bool,
                          scale: Optional[float], axis_size: int,
                          use_flash: bool = False, window=None):
    """Per-shard body: q/k/v are the LOCAL sequence blocks [B, Sblk, H, D].

    ``mask``: None, or boolean with kv dim FULL-length (each shard holds
    its q-rows but every key column, so each rotation slices the arriving
    block's columns out of it): broadcastable to [B, H, Sq_blk, S_full].

    ``use_flash``: run each block contribution through the pallas flash
    kernel (MXU path; decided statically by the driver) and combine the
    normalized per-block outputs exactly via their logsumexp:
    o = sum_r o_r * exp(lse_r - lse_total).  Future blocks of a causal
    ring skip their kernels entirely (lax.switch), which is where ring
    attention's causal FLOP saving actually materializes.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = axis_size
    my_idx = jax.lax.axis_index(axis_name)
    s_blk = q.shape[1]
    perm = [(j, (j + 1) % n) for j in range(n)]
    if use_flash:
        return _ring_flash_shard(q, k, v, mask, scale=scale, causal=causal,
                                 n=n, my_idx=my_idx, perm=perm,
                                 axis_name=axis_name, window=window)

    def attend(acc, k_cur, v_cur, r):
        o, m, l = acc
        src = (my_idx - r) % n  # which block k_cur/v_cur originated from
        mask_blk = None
        if mask is not None:
            kv_len = k_cur.shape[1]
            if mask.shape[-1] in (1, kv_len):
                mask_blk = mask  # broadcast kv, or per-block (sp == 1)
            else:
                mask_blk = jax.lax.dynamic_slice_in_dim(
                    mask, src * s_blk, kv_len, axis=3)
        pv, m_blk, l_blk = _block_attend(
            q, k_cur, v_cur, scale=scale,
            q_offset=my_idx * s_blk, kv_offset=src * s_blk, causal=causal,
            mask_blk=mask_blk, window=window,
        )
        new_m = jnp.maximum(m, m_blk)
        corr_old = jnp.exp(m - new_m)
        corr_new = jnp.exp(m_blk - new_m)
        # exp(BIG_NEG - BIG_NEG) = 1 on never-touched rows: guard with the
        # validity of each side instead.
        corr_old = jnp.where(m > BIG_NEG / 2, corr_old, 0.0)
        corr_new = jnp.where(m_blk > BIG_NEG / 2, corr_new, 0.0)
        o = o * corr_old[..., None] + pv * corr_new[..., None]
        l = l * corr_old + l_blk * corr_new
        return o, new_m, l

    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:2] + q.shape[2:3], BIG_NEG, jnp.float32)  # [B,Sq,H]
    l = jnp.zeros(q.shape[:2] + q.shape[2:3], jnp.float32)

    def step(carry, r):
        o, m, l, k_cur, v_cur = carry
        o, m, l = attend((o, m, l), k_cur, v_cur, r)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    # n-1 rotations only: the last block is consumed without a further
    # ppermute (it would be dead ICI traffic on every forward).
    k_cur, v_cur = k, v
    if n > 1:
        (o, m, l, k_cur, v_cur), _ = jax.lax.scan(
            step, (o, m, l, k, v), jnp.arange(n - 1))
    o, m, l = attend((o, m, l), k_cur, v_cur, n - 1)

    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
    return (o / l[..., None]).astype(q.dtype)


def _ring_flash_shard(q, k, v, mask, *, scale, causal, n, my_idx, perm,
                      axis_name, window=None):
    """Flash-kernel ring body.  ``mask`` here is None or a key-padding
    mask [B, S_full] bool (the driver narrows the 4-d form).

    ``window`` (sliding window, causal only): rotation r's KV block sits
    a STATIC r*s_blk positions behind the local q block, so each
    rotation runs the kernel with a static local window of
    ``window - r*s_blk`` — and the ring STOPS after
    ceil(window/s_blk) rotations instead of n-1: windowed
    long-context pays O(W) communication, not O(S)."""
    from ..ops.flash import flash_attention_lse

    s_blk = q.shape[1]

    def block(k_cur, v_cur, src, diag: bool, skip: bool = False,
              win=None):
        if skip:
            o = jnp.zeros(q.shape, jnp.float32)
            lse = jnp.full(q.shape[:2] + q.shape[2:3], BIG_NEG,
                           jnp.float32)
            return o, lse
        kvm = None
        if mask is not None:
            kvm = jax.lax.dynamic_slice_in_dim(mask, src * s_blk, s_blk,
                                               axis=1)
        o, lse = flash_attention_lse(q, k_cur, v_cur, causal=diag,
                                     scale=scale, kv_mask=kvm,
                                     window=win)
        # flash lse is [B, H, Sq] -> ring's [B, Sq, H] accumulator
        # convention.
        return o.astype(jnp.float32), jnp.transpose(lse, (0, 2, 1))

    def combine(acc, o_r, lse_r):
        o, lse_acc = acc
        new_lse = jnp.logaddexp(lse_acc, lse_r)
        w_old = jnp.where(lse_acc > BIG_NEG / 2,
                          jnp.exp(lse_acc - new_lse), 0.0)
        w_new = jnp.where(lse_r > BIG_NEG / 2,
                          jnp.exp(lse_r - new_lse), 0.0)
        o = o * w_old[..., None] + o_r * w_new[..., None]
        return o, jnp.where(new_lse > BIG_NEG / 2, new_lse, BIG_NEG)

    if window is not None:
        # Unrolled: the per-rotation window is static, and rotations
        # beyond the window do not happen at all.
        r_max = min(n - 1, (window + s_blk - 1) // s_blk)
        acc = block(k, v, my_idx, diag=True, win=window)
        k_cur, v_cur = k, v
        for r in range(1, r_max + 1):
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            src = (my_idx - r) % n
            o_r, lse_r = jax.lax.cond(
                my_idx >= r,  # otherwise src wrapped to a FUTURE block
                lambda kc, vc, sx: block(kc, vc, sx, diag=False,
                                         win=window - r * s_blk),
                lambda kc, vc, sx: block(kc, vc, sx, diag=False,
                                         skip=True),
                k_cur, v_cur, src)
            acc = combine(acc, o_r, lse_r)
        o, _ = acc
        return o.astype(q.dtype)

    def attend(acc, k_cur, v_cur, r):
        src = (my_idx - r) % n
        if causal:
            # past -> full attend; diagonal -> causal kernel; future ->
            # no kernel at all (the causal FLOP saving).
            idx = jnp.where(src == my_idx, 1,
                            jnp.where(src < my_idx, 0, 2)).astype(jnp.int32)
            o_r, lse_r = jax.lax.switch(
                idx,
                [lambda kc, vc, s: block(kc, vc, s, diag=False),
                 lambda kc, vc, s: block(kc, vc, s, diag=True),
                 lambda kc, vc, s: block(kc, vc, s, diag=False,
                                         skip=True)],
                k_cur, v_cur, src)
        else:
            o_r, lse_r = block(k_cur, v_cur, src, diag=False)
        return combine(acc, o_r, lse_r)

    o = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full(q.shape[:2] + q.shape[2:3], BIG_NEG, jnp.float32)

    def step(carry, r):
        o, lse, k_cur, v_cur = carry
        o, lse = attend((o, lse), k_cur, v_cur, r)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, lse, k_nxt, v_nxt), None

    k_cur, v_cur = k, v
    if n > 1:
        (o, lse, k_cur, v_cur), _ = jax.lax.scan(
            step, (o, lse, k, v), jnp.arange(n - 1))
    o, lse = attend((o, lse), k_cur, v_cur, n - 1)
    return o.astype(q.dtype)


def ring_attention(
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
    batch_axes=("dp", "fsdp"),
):
    """Ring attention over a mesh axis.

    q/k/v: GLOBAL arrays [B, S, H, D]; S must divide by mesh.shape[axis_name].
    ``mask``: optional boolean broadcastable to [B, H, S, S] (True =
    attend) — padded batches keep sequence parallelism (VERDICT r1 #8).
    Its q dim shards with q when full-size; the kv dim stays full and is
    sliced per rotation.  Returns output with the same sharding as q.

    ``window`` (sliding window >= 1; requires causal): the flash ring
    stops rotating after ceil(window/block) hops — communication is O(W),
    not O(S).
    """
    from jax import shard_map

    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1; got {window}")
    batch = active_batch_axes(mesh, batch_axes)
    spec = P(batch, axis_name, None, None)
    sp = mesh.shape.get(axis_name, 1)
    use_flash = _ring_flash_eligible(q, q.shape[1] // max(sp, 1), mask)
    body = functools.partial(_ring_attention_shard, axis_name=axis_name,
                             causal=causal, scale=scale,
                             axis_size=sp, use_flash=use_flash,
                             window=window)
    if mask is None:
        return shard_map(
            lambda q, k, v: body(q, k, v, None), mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    if mask.ndim != 4:
        raise ValueError(f"mask must be 4-d [B,H,Sq,Sk]; got {mask.shape}")
    if use_flash:
        from ..ops.flash import narrow_kv_mask

        # Key-padding mask: the flash body consumes the narrow [B, S]
        # bool form (kv dim full on every shard; sliced per rotation).
        kvm = narrow_kv_mask(mask, q.shape[0], k.shape[1])
        return shard_map(
            body, mesh=mesh,
            in_specs=(spec, spec, spec, P(batch, None)),
            out_specs=spec,
            check_vma=False,
        )(q, k, v, kvm)
    mask_spec = P(batch if mask.shape[0] > 1 else None,
                  None,
                  axis_name if mask.shape[2] > 1 else None,
                  None)  # kv dim full on every shard; sliced per rotation
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, mask_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, mask)
