"""Activation sharding constraints (VERDICT r1 #2).

Parameter shardings alone let XLA pick activation layouts per-op; on
mixed dp×fsdp×tp meshes that produced "Involuntary full
rematerialization" — a per-step full-tensor copy whenever consecutive
ops disagreed on layout.  The fix is the standard GSPMD recipe: models
pin their activation layouts with ``with_sharding_constraint`` so
params and activations agree end-to-end.

Models don't know the mesh, so the train-step machinery publishes it as
an *ambient mesh* for the duration of tracing (a contextvar read at
trace time, zero runtime cost).  ``constrain`` is a no-op when no mesh
is ambient (single-device tests, plain ``model.apply``) and silently
drops axis names the mesh doesn't have — model code stays
strategy-agnostic.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Sequence, Tuple, Union

AxisName = Union[None, str, Sequence[str]]

_AMBIENT_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "ptpu_ambient_mesh", default=None)

# Serving-exact mesh (serving/meshed.py): a SECOND ambient channel
# with different semantics.  Training publishes the mesh so constrain
# SHARDS activations (the Megatron layout — fastest, but the row-
# parallel matmuls psum partial products, which reorders float
# accumulation).  The serving engine's contract is TOKEN-BITWISE
# equality to unmeshed execution, so under an exact mesh every
# constrain site that names a TENSOR axis ("tp"/"ep") instead forces
# the activation REPLICATED — an all-gather, which is pure
# concatenation — right before the row-parallel contraction that
# would otherwise psum.  The SPMD decomposition then contains no
# cross-device float reduction at all: column-parallel matmuls keep
# every output element's accumulation order, attention shards over
# heads (per-head math untouched), and gathers move bytes, never
# reassociate sums.  docs/SERVING.md "Meshed serving".
_EXACT_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "ptpu_serving_exact_mesh", default=None)

# The canonical batch-dim axes (matches mesh.active_batch_axes).
BATCH: Tuple[str, ...] = ("dp", "fsdp")

# Axes whose constrain sites sit immediately before a contraction
# over the constrained dim (o_proj/down_proj inputs, vocab logits):
# the exact mode's force-replicate points.
TENSOR_AXES: Tuple[str, ...] = ("tp", "ep")


@contextlib.contextmanager
def ambient_mesh(mesh):
    """Publish ``mesh`` to ``constrain`` calls traced inside the block."""
    token = _AMBIENT_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _AMBIENT_MESH.reset(token)


def current_mesh():
    return _AMBIENT_MESH.get()


@contextlib.contextmanager
def exact_mesh(mesh):
    """Publish ``mesh`` as the serving-exact mesh for traces inside
    the block (no-op when ``mesh`` is None).  Contextvar-scoped, so
    each caller wraps its own jit CALLS (tracing happens on first call)
    and concurrent meshed/unmeshed traces on other threads never see
    it."""
    if mesh is None:
        yield None
        return
    token = _EXACT_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _EXACT_MESH.reset(token)


def current_exact_mesh():
    return _EXACT_MESH.get()


def in_manual_context() -> bool:
    """Is this trace inside a ``shard_map`` (some mesh axis Manual)?"""
    import jax

    abstract = jax.sharding.get_abstract_mesh()
    return abstract is not None and any(
        "Manual" in str(t) for t in getattr(abstract, "axis_types", ()))


def constrain(x, *axes: AxisName):
    """``with_sharding_constraint`` against the ambient mesh.

    Each entry of ``axes`` is None, a mesh axis name, or a tuple of
    names for one dimension of ``x`` (align with ``x.ndim``; trailing
    dims may be omitted and stay unconstrained).  Names absent from the
    ambient mesh, or present with size 1, are dropped — so
    ``constrain(x, BATCH, None, "tp")`` is safe on any mesh.

    Under a serving-exact mesh (:func:`exact_mesh`) the semantics
    flip: a site naming a TENSOR axis forces the activation
    REPLICATED (the pre-contraction all-gather of the reduction-free
    serving layout), every other site is a no-op — bitwise equality
    to unmeshed execution, see the module-level note on _EXACT_MESH.
    """
    emesh = _EXACT_MESH.get()
    if emesh is not None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def _names(a):
            return (a,) if isinstance(a, str) else tuple(a or ())

        if any(n in TENSOR_AXES for a in axes for n in _names(a)):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(emesh, P()))
        return x
    mesh = _AMBIENT_MESH.get()
    if mesh is None:
        return x

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # Inside shard_map the mesh axes are Manual and per-axis constraints
    # are illegal (and meaningless — the caller already laid data out);
    # models run under both jit (constrain) and shard_map (no-op), e.g.
    # blocks executing inside the pp pipeline.
    if in_manual_context():
        return x

    spec = []
    for a in axes:
        names = (a,) if isinstance(a, str) else tuple(a or ())
        names = tuple(n for n in names if mesh.shape.get(n, 1) > 1)
        spec.append(names if len(names) > 1
                    else (names[0] if names else None))
    ndim = getattr(x, "ndim", len(spec))
    spec = spec[:ndim] + [None] * (ndim - len(spec))
    if all(s is None for s in spec):
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))
    except ValueError:
        # Manual-axes contexts that the abstract-mesh probe missed
        # (e.g. shard_map traced under jit): constraints are layout
        # hints, never correctness — drop them rather than abort.
        return x
