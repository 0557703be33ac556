"""Shared scan-over-layers scaffolding for the decoder zoo.

One traced block, rolled over a leading ``[num_layers]`` param axis
(``nn.scan``): compile time stays flat in depth and the stacked params
are exactly what pipeline parallelism consumes.  Models whose blocks
take only the carry (GPT-2, Llama) reuse this; blocks with broadcast
side inputs (BERT's mask) keep their own scan body; MoE-GPT's body
carries the load-balance loss beside the activations and shares
``scan_layers``.

The config duck-type: ``remat: bool``, ``remat_policy: Optional[str]``
(a ``jax.checkpoint_policies`` member name; None = save nothing).
"""

from __future__ import annotations

from typing import Any, Optional, Type

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import kv_cache


def remat_policy(name: Optional[str]):
    return getattr(jax.checkpoint_policies, name) if name else None


class LayerScanBody(nn.Module):
    """What a scan body over the layers needs beside its
    ``__call__(carry, decode=None, layer=None)`` so that decoding
    CARRIES the stacked KV cache: ``scan_layers`` lifts ``__call__``
    with the cache collection as the scan's xs/ys (training, and the
    CREATION of a cache) and ``carried`` with the cache as the scan's
    carry; ``run`` picks between them.  Subclasses hold ``cfg`` (with
    ``num_layers``)."""

    def carried(self, carry, layer):
        """One DECODE layer over the whole stacked cache: the block is
        told which layer it is and reads its keys and values out of
        the ``[num_layers, ...]`` cache variables (kv_cache.
        append_kv_cache ``layer=``).  A call of several rows writes
        them into the stack here; a call of ONE row a sequence (a
        decode step) hands it back instead — ``(carry, rows)``, the
        scan's ys — for ``run`` to write after the loop
        (kv_cache.defers)."""
        with kv_cache.deferred_rows() as rows:
            carry, _ = self(carry, True, layer)
        return carry, rows

    def run(self, carry, decode=None):
        """``(carry, decode?) -> (carry, None)`` through every layer.

        Decoding over an EXISTING cache carries the stacked cache
        through the layer loop: no layer's keys and values are sliced
        out of the stack and none is written back whole — a layer
        reads its own plane as an operand of the attention.  With the
        stack as the scan's xs/ys (the lifted ``__call__``) every
        decode step read and wrote the entire cache once more, which
        was most of a serving step's device time (PERF.md section 6,
        PR 28).

        WHAT IS WRITTEN, WHERE AND WHEN.  A call of several rows a
        sequence (a prefill piece, a speculative verify) writes them
        inside the loop, each layer its own at ``[layer, :, index:
        index+S]``: one in-place update a layer and leaf.  A call of
        ONE row (a decode step) writes nothing inside the loop: each
        layer attends over its plane with the new row laid over its
        position, the rows leave the loop as its ys, and they are
        written HERE, once a leaf for all layers, at ``[:, :, index]``
        (kv_cache.write_deferred).  Under a slot pool's vmap the index
        differs by lane, a write at it is a scatter, and the TPU runs a
        scatter as one dependent write a lane: inside the loop that
        was layers x leaves x slots writes a step — 3 of
        gpt2-medium's 7 ms — after it leaves x slots (PERF.md
        section 6, PR 36).  The cache holds the rows when the apply
        returns, as it always did.

        Training takes the lifted ``__call__`` as before, and so does
        the first decode apply that CREATES the cache variables
        (``generate.init_cache``'s shape probe): a scan cannot carry
        variables that do not exist yet."""
        if decode and self.variables.get("cache"):
            carry, rows = self.carried(carry,
                                       jnp.arange(self.cfg.num_layers))
            kv_cache.write_deferred(self, rows)
            return carry, None
        return self(carry, decode or None)


def scan_layers(body_cls: Type[LayerScanBody], length: int, *args,
                name: str):
    """``body_cls(*args, name=name)`` rolled over a leading
    ``[length]`` axis of its params (and of the decode cache), with
    the two lifted methods :class:`LayerScanBody` describes."""
    common = dict(
        split_rngs={"params": True},
        length=length,
        metadata_params={nn.PARTITION_NAME: "layers"},
    )
    return nn.scan(
        body_cls,
        methods={
            "__call__": dict(
                variable_axes={"params": 0, "cache": 0},
                in_axes=nn.broadcast, **common),
            "carried": dict(
                variable_axes={"params": 0}, variable_carry="cache",
                in_axes=0, **common),
        },
    )(*args, name=name)


class ScanBlock(LayerScanBody):
    """scan body: (carry, decode?) -> (carry, None) around one decoder
    block.  ``decode`` rides as an nn.broadcast input (a static Python
    bool/None shared by every layer) so ONE scanned stack — one param
    tree — serves both training and KV-cache decoding."""

    block_cls: Type[nn.Module]
    cfg: Any

    @nn.compact
    def __call__(self, x, decode=None, layer=None):
        if decode:
            # No gradients in decode; remat would only re-run the
            # cache mutation.
            return self.block_cls(self.cfg, name="block")(
                x, decode=True, layer=layer), None
        cls = nn.remat(self.block_cls, prevent_cse=False,
                       policy=remat_policy(self.cfg.remat_policy)) \
            if self.cfg.remat else self.block_cls
        return cls(self.cfg, name="block")(x), None


def scan_stack(block_cls: Type[nn.Module], cfg: Any, *, name: str):
    """The scanned layer stack as a module (params live under
    ``<name>/block/...`` with a leading [num_layers] axis; the decode
    path's KV cache stacks the same way).  Call as
    ``stack.run(x, decode)`` where decode is None/False (train) or
    True (KV-cache steps and chunked prefill, for blocks whose
    ``__call__`` takes ``decode`` and ``layer``); ``stack(x, None)``
    is the training scan alone."""
    return scan_layers(ScanBlock, cfg.num_layers, block_cls, cfg,
                       name=name)
