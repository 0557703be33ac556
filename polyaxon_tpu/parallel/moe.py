"""Expert parallelism: switch-style MoE routing with all-to-all dispatch.

EP capability (SURVEY.md 2.12): experts are sharded over the ``ep`` mesh
axis; tokens route to their top-1 expert with a capacity limit, travel via
``all_to_all`` (ICI), run the expert MLP, and return.  Dense einsum
dispatch/combine keeps everything MXU-shaped (no dynamic gathers — XLA
and the TPU both prefer the one-hot matmul form).

Beside it, the token-choice layer of the served sparse decoders
(``models/afmoe.py``, ``models/deepseek_v2.py``,
``models/nemotron_h.py``):
:func:`sigmoid_topk_route` and :func:`softmax_topk_route` score every
token over ALL experts, and :func:`held_experts_ffn` computes the part
of the routed sum that the experts HELD here give — one chip's share
of an expert-parallel deployment, told ``expert_offset`` and holding
``E_h`` experts' weights.  No token routed to a held expert is dropped;
what absent experts would add is left out, and nothing stands in for
the absent chips or their exchange.
"""

from __future__ import annotations

import functools
from typing import Callable


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.grouped_matmul import grouped_matmul
from ..spans import scope
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


# -- token-choice top-k over held experts -----------------------------------


def sigmoid_topk_route(x, router_w, select_bias, k: int, *,
                       scale: float = 1.0, normalize: bool = True):
    """Token-choice routing by sigmoid scores: ``x`` [..., d] over
    ``router_w`` [d, E] -> ``(chosen [..., k] int32, weights [..., k]
    f32)``.

    Scores are float32 at full matmul precision whatever ``x`` is kept
    in: the top-k cut is a discontinuity, and a rounded score moves
    tokens between experts.  ``select_bias`` [E] enters the CHOICE only
    (``top_k(s + b)``); the weights are the chosen scores themselves,
    normalised over the k (``+ 1e-20``) and scaled.  Traced under
    the scope ``ptpu_route`` (spans.py)."""
    with scope("ptpu_route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(
            s + select_bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * scale


def softmax_topk_route(x, router_w, k: int, *, scale: float = 1.0):
    """Token-choice routing by softmax scores, greedy: ``x`` [..., d]
    over ``router_w`` [d, E] -> ``(chosen [..., k] int32, weights
    [..., k] f32)``.  The scores are the softmax over ALL experts,
    float32 at full matmul precision as in :func:`sigmoid_topk_route`
    and for its reason; the weights are the k largest as they are
    (they do not add up to 1) times ``scale``.  Traced under the
    scope ``ptpu_route`` (spans.py)."""
    with scope("ptpu_route"):
        g = jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        w, chosen = jax.lax.top_k(g, k)
        return chosen.astype(jnp.int32), w * scale


def held_pair_counts(chosen, num_held: int, expert_offset: int = 0):
    """Token-expert pairs that fall on each HELD expert: ``chosen``
    [..., k] (ids over all experts) -> int32 [num_held].  Plain
    elementwise code, so under ``vmap`` every lane counts its own."""
    local = chosen.reshape(-1) - expert_offset
    hit = local[:, None] == jnp.arange(num_held)[None, :]
    return jnp.sum(hit, axis=0, dtype=jnp.int32)


@functools.lru_cache(maxsize=None)
def _grouped_ffn(expert_offset: int, gated: bool):
    """The grouped computation for one ``expert_offset`` and one form
    of expert — ``gated``: SwiGLU, ``(w_gate, w_up, w_down)``; else
    relu², ``(w_up, w_down)`` — flat over tokens, with a batching rule
    that FLATTENS: a vmapped decode step (serving/slots.py: one lane a
    slot, one token a lane) becomes ONE grouped matmul over every
    slot's token, not a matmul a lane."""

    @jax.custom_batching.custom_vmap
    def ffn(x, chosen, weights, *ws):
        t, k = chosen.shape
        held_n = ws[0].shape[0]
        local = chosen.reshape(-1) - expert_offset          # [T*k]
        held = (local >= 0) & (local < held_n)
        key = jnp.where(held, local, held_n)    # absent experts sort last
        order = jnp.argsort(key, stable=True)
        sizes = held_pair_counts(chosen, held_n, expert_offset)
        rows = x[order // k]                                # [T*k, d]
        dot = functools.partial(grouped_matmul, sizes=sizes)
        if gated:
            w_gate, w_up, w_down = ws
            h = jax.nn.silu(dot(rows, w_gate)) * dot(rows, w_up)
        else:
            w_up, w_down = ws
            h = jnp.square(jax.nn.relu(dot(rows, w_up)))
        out = dot(h.astype(x.dtype), w_down)                # [T*k, d]
        # Rows past the last group belong to no held expert: whatever
        # the grouped matmul left there must not reach the sum.
        out = jnp.where((key[order] < held_n)[:, None], out, 0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        out = out[back].reshape(t, k, -1)
        w = jnp.where(held.reshape(t, k), weights, 0.0)
        return jnp.einsum("tk,tkd->td", w, out.astype(jnp.float32))

    @ffn.def_vmap
    def _flatten(axis_size, in_batched, x, chosen, weights, *ws):
        if any(in_batched[3:]) or not all(in_batched[:3]):
            raise NotImplementedError(
                "held_experts_ffn under vmap: tokens batched, expert "
                "weights shared")
        t = x.shape[1]
        flat = lambda a: a.reshape((axis_size * t,) + a.shape[2:])  # noqa: E731
        y = ffn(flat(x), flat(chosen), flat(weights), *ws)
        return y.reshape(axis_size, t, -1), True

    return ffn


def held_experts_ffn(x, chosen, weights, w_gate, w_up, w_down, *,
                     expert_offset: int = 0):
    """The held experts' part of ``sum_e w_e FFN_e(x)``: ``FFN_e`` the
    SwiGLU ``W_down (silu(x W_gate) * x W_up)``, or, where ``w_gate``
    is None, the ungated relu² ``W_down relu(x W_up)^2``
    (``models/nemotron_h.py``).

    ``x`` [T, d]; ``chosen``/``weights`` [T, k] from a route above
    (ids over ALL experts); ``w_gate``,
    ``w_up`` [E_h, d, f] and ``w_down`` [E_h, f, d] the weights of
    experts ``[expert_offset, expert_offset + E_h)``.  Returns float32
    [T, d].

    One grouped computation serves a prefill chunk and a decode step:
    the token-expert pairs are sorted by held expert (absent experts'
    pairs last, outside every group), each expert's rows go through its
    weights in ``ops/grouped_matmul.grouped_matmul`` — the Pallas
    kernel on a TPU, ``jax.lax.ragged_dot`` elsewhere: a grouped matmul
    that reads an expert's weights once and touches no other token —
    and the rows
    return to their tokens under the routing weights.  The row count is
    the static ``T * k``, every pair's place should all of them fall
    here, so no held pair is ever dropped; no ``[T, d, f]`` copy of
    weights a token is made.  Traced whole under the scope
    ``ptpu_experts`` (spans.py)."""
    ws = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    with scope("ptpu_experts"):
        return _grouped_ffn(int(expert_offset), w_gate is not None)(
            x, chosen, weights, *ws)
