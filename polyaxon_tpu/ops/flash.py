"""Pallas flash attention for TPU — forward AND backward kernels.

Forward: classic online-softmax blocking, grid = (B, H, q_blocks,
kv_blocks) with the kv axis innermost; VMEM scratch accumulator/row
stats persist across the innermost grid dimension (TPU grids execute
sequentially per core), so the [S, S] score matrix never exists.  The
row logsumexp (LSE) is emitted as a second output for the backward.

Backward (FlashAttention-2 style, two kernels — neither materializes
[S, S]):

- ``dq``:  grid (B, H, q_blocks, kv_blocks); streams K/V blocks per Q
  block, recomputes P = exp(S - LSE), accumulates
  dQ += (P * (dO V^T - delta)) K * scale.
- ``dkv``: grid (B, H, kv_blocks, q_blocks); streams Q/dO blocks per
  KV block, accumulates dV += P^T dO and dK += dS^T Q * scale.

``delta = rowsum(dO * O)`` is precomputed in XLA (one fused elementwise
pass).  Fully-masked causal blocks are skipped via ``pl.when`` in all
three kernels.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block sizes bound the per-program VMEM footprint (scores block is
# BLOCK_Q x BLOCK_KV f32).  Large blocks matter on TPU: at 128x128 the
# per-program work (a [128, 64] @ [64, 128] dot) is so small that grid
# overhead dominated — measured 52% of a gpt2-medium step; 1024-blocks
# cut the whole train step 215 -> 125 ms on v5e.  1024x1024 f32 scores
# (4 MB) + q/k/v/acc still fit VMEM comfortably.  Sequences must be
# 128-multiples (the lane tile); each call picks the largest 128-multiple
# block that divides the seq and stays under these caps (_pick_block).
BLOCK_Q = int(os.environ.get("POLYAXON_TPU_FLASH_BLOCK_Q", 1024))
BLOCK_KV = int(os.environ.get("POLYAXON_TPU_FLASH_BLOCK_KV", 1024))
# The backward kernels hold more live operands per program (q/k/v/o/do
# + two output accumulators), so their VMEM sweet spot can sit below
# the forward's — tunable independently for an on-chip A/B.  None =
# follow the LIVE forward caps at call time, so tests that monkeypatch
# BLOCK_Q/BLOCK_KV keep shrinking the backward tiling too.
_env_q_bwd = os.environ.get("POLYAXON_TPU_FLASH_BLOCK_Q_BWD")
_env_kv_bwd = os.environ.get("POLYAXON_TPU_FLASH_BLOCK_KV_BWD")
BLOCK_Q_BWD = int(_env_q_bwd) if _env_q_bwd else None
BLOCK_KV_BWD = int(_env_kv_bwd) if _env_kv_bwd else None
NEG_INF = -1e30


def _interpret() -> bool:
    return bool(os.environ.get("POLYAXON_TPU_FLASH_INTERPRET"))


def flash_eligible(sq: int, sk: int, head_dim: int, mask=None, *,
                   mask_kv_len: int = None) -> bool:
    """Single routing predicate for every flash consumer (the local
    attention router, ring's per-rotation blocks, Ulysses' post-all-to-
    all inner): env kill-switch, TPU backend (or the interpret-mode
    tests), 128-lane seq alignment, MXU-aligned head dim, and at most a
    key-padding mask [B, 1, 1, kv_len].  ``mask_kv_len`` overrides the
    expected mask column count when the kernel consumes kv in slices of
    a longer mask (ring)."""
    if os.environ.get("POLYAXON_TPU_NO_FLASH"):
        return False
    # POLYAXON_TPU_ASSUME_TPU: deviceless AOT compiles for a TPU
    # topology (jax.experimental.topologies) run with a CPU default
    # backend, but the lowering target IS the TPU compiler — without
    # this override they would silently trace the plain-attention path
    # and report S^2-score memory the real program never allocates
    # (benchmarks/bench_offline_v5e.py).
    if not (jax.default_backend() == "tpu"
            or os.environ.get("POLYAXON_TPU_ASSUME_TPU")
            or os.environ.get("POLYAXON_TPU_FLASH_INTERPRET")):
        return False
    if sq % 128 or sk % 128 or head_dim % 64:
        return False
    return mask is None or (
        mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1
        and mask.shape[3] == (mask_kv_len if mask_kv_len is not None
                              else sk))


def narrow_kv_mask(mask, batch: int, sk: int):
    """[B?, 1, 1, Sk] boolean -> the [batch, sk] form the kernels take."""
    return jnp.broadcast_to(mask[:, 0, 0, :], (batch, sk))


def _pick_block(seq: int, cap: int) -> int:
    """Largest 128-multiple block that divides ``seq`` and is <= cap."""
    best = 128
    for b in range(128, min(cap, seq) + 1, 128):
        if seq % b == 0:
            best = b
    return best


def _win_tiles(span: int, block: int, total: int) -> int:
    """#blocks of size ``block`` that can intersect ANY contiguous span
    of ``span`` positions (unaligned), capped at ``total``."""
    return min(total, (span - 1) // block + 2)


def _kv_base(iq, block_q, block_kv, q_shift, window, n_kv, n_vis):
    """First kv block the remapped grid visits for q-block ``iq``: the
    tile holding position q_lo - window, clamped so the n_vis-tile
    visit window stays inside [0, n_kv)."""
    first = (iq * block_q + q_shift - window) // block_kv
    return jnp.clip(first, 0, n_kv - n_vis)


def _q_base(ikv, block_q, block_kv, q_shift, window, n_q, n_vis):
    """dkv twin of :func:`_kv_base`: for kv-block ``ikv`` the needed q
    blocks span global positions [kv_lo, kv_hi + window]; the first is
    the q tile whose last row reaches kv_lo."""
    first = (ikv * block_kv - q_shift) // block_q
    return jnp.clip(first, 0, n_q - n_vis)


def _block_needed(iq, ikv, block_q, block_kv, q_shift, causal: bool,
                  window: int):
    """Does (q-block iq, kv-block ikv) contain any unmasked position?

    Causal skips blocks entirely in the future; a sliding window
    (``window`` > 0: position i attends to [i-window, i]) additionally
    skips blocks entirely in the past.  The skip removes the MXU work;
    for causal windowed calls the kv grid axis is ALSO remapped to the
    ceil(W/block)+2 tiles that can intersect the window (_kv_base /
    _q_base), so the BlockSpec pipeline only DMAs O(W) KV bytes per q
    block instead of O(S) — the check here still guards the clamped
    boundary tiles the remap over-visits near the sequence edges.
    (Non-causal windowed calls — ring's boundary rotations — keep the
    full grid: without the causal upper bound the needed kv range is
    unbounded above.)
    """
    q_lo = iq * block_q + q_shift
    q_hi = q_lo + block_q - 1
    kv_lo = ikv * block_kv
    kv_hi = ikv * block_kv + block_kv - 1
    conds = []  # iq/ikv are traced program ids: combine with &, not and
    if causal:
        conds.append(kv_lo <= q_hi)
    if window is not None:
        conds.append(kv_hi >= q_lo - window)
    if not conds:
        return True
    needed = conds[0]
    for c in conds[1:]:
        needed = needed & c
    return needed


def _block_ids(iq, ikv, block_q, block_kv, q_shift):
    q_ids = q_shift + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_ids = ikv * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    return q_ids, k_ids


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, causal: bool, scale: float,
                block_q: int, block_kv: int, q_shift: int,
                padded: bool = False, window=None, n_kv_total=None):
    # Optional key-padding mask rides as a 4th input ref ([1, 8,
    # block_kv] f32; row 0 = 1.0 for valid keys).
    if padded:
        kvm_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        kvm_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    iq = pl.program_id(2)
    j = pl.program_id(3)   # grid index along the (possibly remapped) axis
    n_kv = pl.num_programs(3)
    ikv = j
    if n_kv_total is not None:
        # Windowed remap: grid axis 3 runs over the visited tiles only;
        # recover the TRUE kv block index for the mask math (must match
        # the BlockSpec index_map exactly).  Init/finalize stay on the
        # grid index j — the scratch accumulator lifecycle follows grid
        # execution order, not kv position.
        ikv = _kv_base(iq, block_q, block_kv, q_shift, window,
                       n_kv_total, n_kv) + j

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    needed = _block_needed(iq, ikv, block_q, block_kv, q_shift,
                           causal, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]  # [block_q, D]
        k = k_ref[0, 0]  # [block_kv, D]
        v = v_ref[0, 0]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal or window is not None:
            q_ids, k_ids = _block_ids(iq, ikv, block_q, block_kv, q_shift)
            if causal:
                scores = jnp.where(q_ids >= k_ids, scores, NEG_INF)
            if window is not None:
                scores = jnp.where(q_ids - k_ids <= window, scores,
                                   NEG_INF)
        if padded:
            valid = kvm_ref[0][:1, :] > 0.0  # [1, block_kv]
            scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        # A fully-masked row/block leaves m_new at NEG_INF, where
        # exp(NEG_INF - NEG_INF) = 1 would pollute l: zero those terms.
        p = jnp.where(scores > NEG_INF / 2, p, 0.0)
        correction = jnp.exp(m_prev - m_new)
        correction = jnp.where(m_prev > NEG_INF / 2, correction, 0.0)
        l_new = l_prev * correction + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * correction + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(safe_l)
        lse = jnp.where(l == 0.0, NEG_INF, lse)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


def _pack_kv_mask(kv_mask, sk):
    """[B, Sk] bool -> [B, 8, Sk] f32: keys along the LANES, the way the
    kernels' [block_q, block_kv] score tiles hold them, so the mask row
    broadcasts over a tile as it is (row 0 carries the value; 8 rows
    make one f32 sublane tile).  Keys along the sublanes had to be
    turned into a lane vector inside the kernel, which this toolchain's
    Mosaic lowers to spills: the v5e compile of a 256-key block ran out
    of VMEM and a 512-key block did not finish (tests/
    test_chip_compile.py holds the bert-base shape to this)."""
    m = kv_mask.astype(jnp.float32)[:, None, :]
    return jnp.broadcast_to(m, (kv_mask.shape[0], 8, sk))


def _flash_forward(q, k, v, kvm, causal: bool, scale: float,
                   window=None):
    """q/k/v: [B, H, S, D] -> (out, lse[B, H, Sq, 128]).

    ``kvm``: None or packed key-padding mask [B, 8, Sk] f32."""
    batch, heads, sq, d = q.shape
    sk = k.shape[2]
    if sq % 128 or sk % 128:
        raise ValueError(
            f"flash_attention needs seq lengths divisible by 128 (the "
            f"TPU lane tile); got Sq={sq}, Sk={sk}. Use "
            f"ops.dot_product_attention for ragged shapes.")
    block_q = _pick_block(sq, BLOCK_Q)
    block_kv = _pick_block(sk, BLOCK_KV)
    q_shift = sk - sq
    n_kv = sk // block_kv
    # Causal windowed: remap the kv grid axis to the O(W) tiles that
    # can intersect [q_lo - window, q_hi] — HBM traffic per q block
    # drops from O(S) to O(W) (VERDICT r2 task 4).  The env switch
    # exists for A/B benchmarking of the remap itself.
    remap = (window is not None and window > 0 and causal
             and not os.environ.get("POLYAXON_TPU_FLASH_NO_REMAP"))
    n_vis = _win_tiles(window + block_q, block_kv, n_kv) if remap \
        else n_kv
    if n_vis == n_kv:
        remap = False
    grid = (batch, heads, sq // block_q, n_vis)
    padded = kvm is not None

    def kv_block(i, j):
        if not remap:
            return j
        return _kv_base(i, block_q, block_kv, q_shift, window,
                        n_kv, n_vis) + j

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_kv=block_kv, q_shift=q_shift, padded=padded,
        window=window, n_kv_total=n_kv if remap else None)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda b, h, i, j: (b, h, kv_block(i, j), 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda b, h, i, j: (b, h, kv_block(i, j), 0)),
    ]
    inputs = [q, k, v]
    if padded:
        in_specs.append(pl.BlockSpec((1, 8, block_kv),
                                     lambda b, h, i, j: (b, 0,
                                                         kv_block(i, j))))
        inputs.append(kvm)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, i, j: (b, h, i, 0)),
            # LSE rides a 128-lane minor dim (TPU-friendly); column 0
            # is the value.
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        # CPU tests run the kernels in the pallas interpreter (same code
        # path the TPU compiles) — see tests/test_ops.py.
        interpret=_interpret(),
    )(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, causal: bool, scale: float,
                   block_q: int, block_kv: int, q_shift: int,
                   padded: bool = False, window=None, n_kv_total=None):
    if padded:
        kvm_ref, dq_ref, dq_acc = refs
    else:
        kvm_ref = None
        dq_ref, dq_acc = refs
    iq = pl.program_id(2)
    j = pl.program_id(3)
    n_kv = pl.num_programs(3)
    ikv = j
    if n_kv_total is not None:  # windowed kv-grid remap (see _fwd_kernel)
        ikv = _kv_base(iq, block_q, block_kv, q_shift, window,
                       n_kv_total, n_kv) + j

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = _block_needed(iq, ikv, block_q, block_kv, q_shift,
                           causal, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]      # [bq, 1]
        delta = delta_ref[0, 0][:, :1]  # [bq, 1]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(scores - lse)       # exp(NEG_INF-ish) -> 0
        if causal or window is not None:
            q_ids, k_ids = _block_ids(iq, ikv, block_q, block_kv, q_shift)
            if causal:
                p = jnp.where(q_ids >= k_ids, p, 0.0)
            if window is not None:
                p = jnp.where(q_ids - k_ids <= window, p, 0.0)
        if padded:
            # Select (not multiply) so a fully-masked row's inf p terms
            # (lse == NEG_INF) cannot produce NaN.
            valid = kvm_ref[0][:1, :] > 0.0
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bkv]
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, causal: bool, scale: float, block_q: int,
                    block_kv: int, q_shift: int, padded: bool = False,
                    window=None, n_q_total=None):
    if padded:
        kvm_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        kvm_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    ikv = pl.program_id(2)
    j = pl.program_id(3)
    n_q = pl.num_programs(3)
    iq = j
    if n_q_total is not None:  # windowed q-grid remap (dkv is kv-major)
        iq = _q_base(ikv, block_q, block_kv, q_shift, window,
                     n_q_total, n_q) + j

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = _block_needed(iq, ikv, block_q, block_kv, q_shift,
                           causal, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(scores - lse)
        if causal or window is not None:
            q_ids, k_ids = _block_ids(iq, ikv, block_q, block_kv, q_shift)
            if causal:
                p = jnp.where(q_ids >= k_ids, p, 0.0)
            if window is not None:
                p = jnp.where(q_ids - k_ids <= window, p, 0.0)
        if padded:
            valid = kvm_ref[0][:1, :] > 0.0  # this kv block
            p = jnp.where(valid, p, 0.0)
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, kvm, o, lse, do, causal: bool, scale: float,
                    dlse=None, window=None):
    batch, heads, sq, d = q.shape
    sk = k.shape[2]
    block_q = _pick_block(sq, BLOCK_Q_BWD or BLOCK_Q)
    block_kv = _pick_block(sk, BLOCK_KV_BWD or BLOCK_KV)
    q_shift = sk - sq
    padded = kvm is not None
    n_q, n_kv = sq // block_q, sk // block_kv
    # Windowed remap (see _flash_forward): dq visits O(W) kv tiles per
    # q block; dkv visits O(W) q tiles per kv block.
    remap = (window is not None and window > 0 and causal
             and not os.environ.get("POLYAXON_TPU_FLASH_NO_REMAP"))
    kv_vis = _win_tiles(window + block_q, block_kv, n_kv) if remap \
        else n_kv
    q_vis = _win_tiles(window + block_kv, block_q, n_q) if remap \
        else n_q

    def kv_block(i, j):
        if not remap or kv_vis == n_kv:
            return j
        return _kv_base(i, block_q, block_kv, q_shift, window,
                        n_kv, kv_vis) + j

    def q_block(i, j):
        if not remap or q_vis == n_q:
            return j
        return _q_base(i, block_q, block_kv, q_shift, window,
                       n_q, q_vis) + j

    # delta = rowsum(dO * O): one fused XLA pass, [B, H, Sq, 128].
    # With an LSE cotangent (the blockwise/ring combination
    # differentiates through lse), dS gains a +P*dlse term; since
    # dS = P * (dP - delta), folding it in is just delta -= dlse —
    # the kernels themselves are unchanged.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)[..., None]
    delta = jnp.broadcast_to(delta, (batch, heads, sq, 128))

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, i, j: (b, h, kv_block(i, j), 0))
    rowspec = pl.BlockSpec((1, 1, block_q, 128),
                           lambda b, h, i, j: (b, h, i, 0))

    dq_in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    dq_inputs = [q, k, v, do, lse, delta]
    if padded:
        dq_in_specs.append(pl.BlockSpec(
            (1, 8, block_kv),
            lambda b, h, i, j: (b, 0, kv_block(i, j))))
        dq_inputs.append(kvm)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_kv=block_kv,
                          q_shift=q_shift, padded=padded,
                          window=window,
                          n_kv_total=n_kv if remap and kv_vis < n_kv
                          else None),
        grid=(batch, heads, n_q, kv_vis),
        in_specs=dq_in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(*dq_inputs)

    # kv-major grid: same block index maps with (i=kv block, j=q block).
    qspec_t = pl.BlockSpec((1, 1, block_q, d),
                           lambda b, h, i, j: (b, h, q_block(i, j), 0))
    kspec_t = pl.BlockSpec((1, 1, block_kv, d),
                           lambda b, h, i, j: (b, h, i, 0))
    rowspec_t = pl.BlockSpec((1, 1, block_q, 128),
                             lambda b, h, i, j: (b, h, q_block(i, j), 0))

    dkv_in_specs = [qspec_t, kspec_t, kspec_t, qspec_t, rowspec_t,
                    rowspec_t]
    dkv_inputs = [q, k, v, do, lse, delta]
    if padded:
        dkv_in_specs.append(pl.BlockSpec((1, 8, block_kv),
                                         lambda b, h, i, j: (b, 0, i)))
        dkv_inputs.append(kvm)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_kv=block_kv,
                          q_shift=q_shift, padded=padded,
                          window=window,
                          n_q_total=n_q if remap and q_vis < n_q
                          else None),
        grid=(batch, heads, n_kv, q_vis),
        in_specs=dkv_in_specs,
        out_specs=[kspec_t, kspec_t],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(*dkv_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, kvm, causal, scale, window=None):
    out, _ = _flash_forward(q, k, v, kvm, causal, scale, window)
    return out


def _flash_fwd(q, k, v, kvm, causal, scale, window=None):
    out, lse = _flash_forward(q, k, v, kvm, causal, scale, window)
    return out, (q, k, v, kvm, out, lse)


def _flash_bwd(causal, scale, window, res, g):
    q, k, v, kvm, o, lse = res
    if os.environ.get("POLYAXON_TPU_FLASH_XLA_BWD"):
        # Escape hatch: XLA-recompute backward (materializes [S, S]).
        from .attention import _xla_attention

        mask = None if kvm is None else \
            (kvm[:, None, None, 0, :] > 0.0)

        def ref(q, k, v):
            out = _xla_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3), mask, causal,
                                 scale, window=window)
            return out.transpose(0, 2, 1, 3)

        dq, dk, dv = jax.vjp(ref, q, k, v)[1](g)
        return dq, dk, dv, None
    dq, dk, dv = _flash_backward(q, k, v, kvm, o, lse, g, causal, scale,
                                 window=window)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_lse(q, k, v, kvm, causal, scale, window=None):
    """Like ``_flash`` but also returns the row logsumexp [B, H, Sq] —
    what blockwise consumers (ring attention) need to combine
    per-block normalized outputs exactly.  ``window`` here is the RAW
    kernel semantics (None = off; any int masks q_pos - k_pos <=
    window, including non-positive values — ring's boundary
    rotations)."""
    out, lse = _flash_forward(q, k, v, kvm, causal, scale, window)
    return out, lse[..., 0]


def _flash_lse_fwd(q, k, v, kvm, causal, scale, window=None):
    out, lse = _flash_forward(q, k, v, kvm, causal, scale, window)
    return (out, lse[..., 0]), (q, k, v, kvm, out, lse)


def _flash_lse_bwd(causal, scale, window, res, cts):
    q, k, v, kvm, o, lse = res
    do, dlse = cts
    dq, dk, dv = _flash_backward(q, k, v, kvm, o, lse, do, causal, scale,
                                 dlse=dlse, window=window)
    return dq, dk, dv, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q, k, v, *, causal: bool = False,
                        scale: float = 1.0, kv_mask=None, window=None):
    """Flash attention over BSHD tensors returning ``(out, lse)``.

    ``out``: [B, Sq, H, D] (same as :func:`flash_attention`);
    ``lse``: [B, H, Sq] f32 row logsumexp of the scaled scores
    (NEG_INF on fully-masked rows, whose out-rows are zero).  This is
    the building block for blockwise/ring attention: normalized block
    outputs combine exactly via o = sum_r o_r * exp(lse_r - lse_total).
    Same contract as :func:`flash_attention`: Sq/Sk must be multiples
    of 128; shorter sequences use dot_product_attention.
    """
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    kvm = None if kv_mask is None else _pack_kv_mask(kv_mask, k.shape[2])
    out, lse = _flash_lse(q, k, v, kvm, causal, scale, window)
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(q, k, v, *, causal: bool = False, scale: float = 1.0,
                    kv_mask=None, window=None) -> jax.Array:
    """Flash attention over BSHD tensors (public convention).

    Transposes to head-major BHSD for the kernels so each (q-block,
    kv-block) tile is contiguous in VMEM, and back on the way out.
    ``kv_mask``: optional [B, Sk] boolean key-padding mask (True =
    attend) — the padded-batch case that used to force the O(S^2) XLA
    fallback.

    CONTRACT (tightened with the 2026-07 block-size fix): Sq and Sk
    must be multiples of 128 — the lane-width-aligned tiles the MXU
    needs; sequences shorter than 128 are rejected with a ValueError
    (they used to run via a shrunken block).  Short/ragged sequences
    belong on ``ops.attention.dot_product_attention``, which is what
    the routed ``flash_eligible`` path already falls back to.
    """
    if window is not None:
        if not causal:
            raise ValueError(
                "sliding window attention is causal: position i "
                "attends to [i-window, i]; pass causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1; got {window}")
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    kvm = None if kv_mask is None else _pack_kv_mask(kv_mask, k.shape[2])
    out = _flash(q, k, v, kvm, causal, scale,
                 None if window is None else int(window))
    return out.transpose(0, 2, 1, 3)
