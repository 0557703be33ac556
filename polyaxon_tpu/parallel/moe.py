"""Expert parallelism: switch-style MoE routing with all-to-all dispatch.

EP capability (SURVEY.md 2.12): experts are sharded over the ``ep`` mesh
axis; tokens route to their top-1 expert with a capacity limit, travel via
``all_to_all`` (ICI), run the expert MLP, and return.  Dense einsum
dispatch/combine keeps everything MXU-shaped (no dynamic gathers — XLA
and the TPU both prefer the one-hot matmul form).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import active_batch_axes


def top1_dispatch(logits: jax.Array, capacity: int):
    """Build dispatch/combine tensors for top-1 (switch) routing.

    logits: [T, E] router scores for T tokens.
    Returns (dispatch [T, E, C] bool-ish f32, combine [T, E, C] f32,
    aux_loss scalar).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # [T, E]
    # Position of each token within its expert's queue.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # [T, E], -1 elsewhere
    pos_in_expert = jnp.max(pos, axis=-1)  # [T]
    keep = pos_in_expert < capacity
    gate = gate * keep

    pos_onehot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                                dtype=jnp.float32)  # [T, C]
    dispatch = onehot[:, :, None] * pos_onehot[:, None, :] * keep[:, None, None]
    combine = dispatch * gate[:, None, None]

    # Switch load-balancing loss: E * sum_e(fraction_e * prob_e).
    fraction = onehot.mean(axis=0)
    prob_mean = probs.mean(axis=0)
    aux = e * jnp.sum(fraction * prob_mean)
    return dispatch, combine, aux


def moe_layer(
    x: jax.Array,
    router_w: jax.Array,
    expert_w1: jax.Array,
    expert_w2: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    activation: Callable = jax.nn.gelu,
    batch_axes=("dp", "fsdp"),
):
    """Expert-parallel switch MoE layer.

    x: GLOBAL [B, S, D]; experts sharded over ``ep``:
    router_w [D, E] replicated, expert_w1 [E, D, F], expert_w2 [E, F, D].
    Returns ([B, S, D], aux_loss).

    Tokens are sharded over ``ep`` along the sequence dim (each rank
    routes 1/ep of the tokens; the capacity limit applies per source
    rank), so per-rank expert FLOPs are 1/ep of dense — the point of EP.
    """
    from jax import shard_map

    b, s, d = x.shape
    e = expert_w1.shape[0]
    ep = mesh.shape.get(axis_name, 1)
    if e % ep:
        raise ValueError(
            f"num experts {e} must be divisible by ep axis size {ep}")
    if s % ep:
        raise ValueError(
            f"sequence length {s} must be divisible by ep axis size {ep}")

    batch = active_batch_axes(mesh, batch_axes)

    def body(xl, rw, w1, w2):
        tl = xl.shape[0] * xl.shape[1]
        flat = xl.reshape(tl, d)
        el = w1.shape[0]
        capacity = max(1, int(capacity_factor * tl / e))

        logits = flat.astype(jnp.float32) @ rw.astype(jnp.float32)
        dispatch, combine, aux = top1_dispatch(logits, capacity)
        # [T, E, C] x [T, D] -> [E, C, D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch,
                               flat.astype(jnp.float32))
        # Exchange: each rank keeps its own expert rows from every rank.
        expert_in = expert_in.reshape(ep, el, capacity, d)
        expert_in = jax.lax.all_to_all(expert_in, axis_name, split_axis=0,
                                       concat_axis=0, tiled=True)
        # After the tiled all_to_all the leading axis indexes the SOURCE
        # rank and the expert axis holds only OUR local experts.
        expert_in = expert_in.reshape(ep, el, capacity, d)
        xin = expert_in.transpose(1, 0, 2, 3).reshape(el, ep * capacity, d)
        h = jnp.einsum("ecd,edf->ecf", xin, w1.astype(jnp.float32))
        h = activation(h)
        h = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32))
        # Route back: inverse transpose + all_to_all.
        h = h.reshape(el, ep, capacity, d).transpose(1, 0, 2, 3)
        h = jax.lax.all_to_all(h, axis_name, split_axis=0, concat_axis=0,
                               tiled=True)
        h = h.reshape(e, capacity, d)
        out = jnp.einsum("tec,ecd->td", combine, h)
        # aux differs per token shard: average over every axis the tokens
        # are sharded on so the returned scalar really is replicated.
        aux = jax.lax.pmean(aux, (axis_name,) + (batch or ()))
        return out.reshape(xl.shape).astype(x.dtype), aux

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(batch, axis_name, None), P(), P(axis_name),
                  P(axis_name)),
        out_specs=(P(batch, axis_name, None), P()),
        check_vma=False,
    )(x, router_w, expert_w1, expert_w2)
