"""What the served weights rest in.

A zoo model computes in ``cfg.dtype`` (bfloat16) on parameters made,
trained and checkpointed in float32: every flax ``Dense`` and ``Embed``
rounds its kernel, bias or table to the compute dtype on USE.  Inside a
serving program that rounding is a pass over the whole tree in every
decode dispatch and every prefill program — the same values rounded to
the same values again.  A served tree therefore rests as the MODULES
DECLARE it: a family whose config has ``param_dtype`` (gpt2.py,
afmoe.py) is built for serving with ``param_dtype = dtype``, and every
float leaf that the abstract tree of that model holds in another dtype
than the tree at hand is rounded ONCE, here, round-to-nearest-even as
the modules did — the logits are bitwise those of the float32 tree.
Leaves the model keeps in float32 (LayerNorm scales and biases, a
router) are declared float32 and stay; a family without the field
declares nothing and is served as it was; a tree that already rests as
declared is handed on, the same arrays.

The counters (``/info``, ``/metrics``): ``weights_bytes``,
``weights_bytes_by_dtype``, ``weights_cast_bytes``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.quant import QuantizedTensor


def resting_overrides(model) -> Dict[str, Any]:
    """The config overrides under which ``model``'s family declares a
    served tree: ``{"param_dtype": cfg.dtype}`` where its config has
    both fields, nothing otherwise."""
    cfg = getattr(model, "cfg", None)
    if not dataclasses.is_dataclass(cfg):
        return {}
    fields = {f.name for f in dataclasses.fields(cfg)}
    if not {"dtype", "param_dtype"} <= fields:
        return {}
    return {"param_dtype": cfg.dtype}


def declared_tree(model, inputs):
    """The abstract variables of ``model.init`` on ``inputs``: shapes
    and dtypes alone, no memory, no compile, no key drawn from."""
    return jax.eval_shape(
        model.init, jax.ShapeDtypeStruct((2,), jnp.uint32), inputs)


def rest_as_declared(variables, declared) -> Tuple[Any, int]:
    """``variables`` with every float leaf cast to the dtype the leaf
    of the same path has in ``declared`` (an abstract tree: the
    ``jax.eval_shape`` of the serving model's ``init``), and the bytes
    that were cast, as they lay before.  Leaves ``declared`` lacks
    (quantized weights, a collection the model does not make) and
    leaves that already rest as declared are the arrays given; with
    nothing to cast the tree itself is returned."""
    keystr = jax.tree_util.keystr
    want = {keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_flatten_with_path(declared)[0]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        variables, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    leaves, cast_bytes = [], 0
    for path, leaf in flat:
        dtype = want.get(keystr(path))
        if dtype is not None and not isinstance(leaf, QuantizedTensor) \
                and leaf.dtype != dtype \
                and jnp.issubdtype(leaf.dtype, jnp.floating) \
                and jnp.issubdtype(dtype, jnp.floating):
            cast_bytes += leaf.nbytes
            leaf = jnp.asarray(leaf).astype(dtype)
        leaves.append(leaf)
    if not cast_bytes:
        return variables, 0
    return jax.tree_util.tree_unflatten(treedef, leaves), cast_bytes


def weights_report(trees, compute_dtype: Optional[Any],
                   cast_bytes: int) -> Dict[str, Any]:
    """The counters of the trees the serving programs are handed
    (target and draft): logical bytes, shapes alone."""
    by_dtype: Dict[str, int] = {}
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            name = jnp.dtype(leaf.dtype).name
            by_dtype[name] = by_dtype.get(name, 0) + int(leaf.nbytes)
    return {
        "weights_bytes": sum(by_dtype.values()),
        "weights_bytes_by_dtype": by_dtype,
        "weights_cast_bytes": int(cast_bytes),
        **({"weights_compute_dtype": jnp.dtype(compute_dtype).name}
           if compute_dtype is not None else {}),
    }
