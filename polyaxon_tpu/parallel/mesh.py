"""Mesh construction and sharding helpers.

TPU-first design (SURVEY.md §7 step 5): one ``jax.sharding.Mesh`` whose
axes encode the parallelism strategy.  Canonical axis names:

    dp   data parallel (gradient allreduce over ICI/DCN)
    fsdp fully-sharded data parallel (param shard + allgather)
    tp   tensor parallel (matmul partials, allreduce/reducescatter)
    pp   pipeline parallel (collective_permute between stages)
    sp   sequence/context parallel (ring attention / Ulysses all-to-all)
    ep   expert parallel (MoE all-to-all)

On multi-slice hardware the mesh is laid out so the *leading* axis (usually
dp) spans DCN between slices while all other axes stay inside a slice on
ICI — the hierarchical-collective recipe from the scaling playbook.
"""

from __future__ import annotations

import logging
import math
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "ep", "tp")

logger = logging.getLogger(__name__)


class MeshError(ValueError):
    pass


@dataclass
class MeshSpec:
    """Declarative mesh: axis name -> size; -1 for 'fill with the rest'."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    num_slices: int = 1

    @classmethod
    def from_dict(cls, data: Optional[Dict[str, int]]) -> "MeshSpec":
        data = dict(data or {})
        known = {k: int(v) for k, v in data.items()
                 if k in AXIS_ORDER or k == "num_slices"}
        unknown = set(data) - set(known)
        if unknown:
            raise MeshError(f"Unknown mesh axes: {sorted(unknown)}")
        return cls(**known)

    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Fill -1 axes so the product equals n_devices."""
        sizes = self.sizes()
        fill_axes = [a for a, s in sizes.items() if s == -1]
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if n_devices % fixed:
            raise MeshError(
                f"Mesh axes {sizes} do not divide device count {n_devices}"
            )
        remaining = n_devices // fixed
        if not fill_axes:
            if fixed != n_devices:
                raise MeshError(
                    f"Mesh axes product {fixed} != device count {n_devices}"
                )
        elif len(fill_axes) == 1:
            sizes[fill_axes[0]] = remaining
        else:
            sizes[fill_axes[0]] = remaining
            for a in fill_axes[1:]:
                sizes[a] = 1
        return sizes


def build_mesh(
    spec: Optional[MeshSpec] = None,
    devices: Optional[Sequence] = None,
    allow_split_physical_axes: bool = True,
):
    """Construct a Mesh from a spec over the given (default: all) devices.

    Uses ``mesh_utils.create_device_mesh`` so the logical axes map onto the
    physical ICI torus with nearest-neighbor contiguity; for multi-slice
    topologies the hybrid helper puts the leading axis across DCN.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.resolve(len(devices))
    axis_names = tuple(a for a in AXIS_ORDER)
    shape = tuple(sizes[a] for a in axis_names)

    if spec.num_slices > 1:
        per_slice = [s for s in shape]
        dcn = [1] * len(shape)
        # dp axis (index 0) spans slices over DCN.
        if shape[0] % spec.num_slices:
            raise MeshError(
                f"dp axis ({shape[0]}) must be divisible by num_slices "
                f"({spec.num_slices}) for hybrid ICI x DCN meshes"
            )
        per_slice[0] = shape[0] // spec.num_slices
        dcn[0] = spec.num_slices
        try:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per_slice, dcn, devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
            return Mesh(dev_array, axis_names)
        except (ValueError, AssertionError) as e:
            # CPU/virtual devices carry no slice index: fall through to
            # the single-slice layout, and say so.
            logger.warning(
                "mesh %s: create_hybrid_device_mesh refused (%s: %s); "
                "laying the devices out as one slice",
                dict(sizes), type(e).__name__, e)

    try:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    except (ValueError, AssertionError, NotImplementedError) as e:
        # The logical axes then follow enumeration order, not the
        # physical torus: neighbours on an axis may not be neighbours
        # on ICI.  Correct, possibly slower — never silent.
        logger.warning(
            "mesh %s: create_device_mesh refused (%s: %s); devices "
            "reshaped flat in enumeration order",
            dict(sizes), type(e).__name__, e)
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def local_mesh(**axis_sizes: int):
    """Convenience: mesh over local devices, e.g. local_mesh(dp=4, tp=2)."""
    return build_mesh(MeshSpec.from_dict(axis_sizes))


def active_batch_axes(mesh, batch_axes: Tuple[str, ...] = ("dp", "fsdp")):
    """The subset of ``batch_axes`` with size > 1 on this mesh (or None).

    Single source of truth for "which axes shard the batch dim" — used by
    data_sharding, the strategy library, and every shard_map spec in
    ring/ulysses/pipeline/moe.
    """
    return tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None


def data_sharding(mesh, *, batch_axes: Tuple[str, ...] = ("dp", "fsdp")):
    """NamedSharding for a [batch, ...] array sharded over the data axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(active_batch_axes(mesh, batch_axes)))


def replicate_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def logical_axis_rules(spec: Optional[MeshSpec] = None) -> List[Tuple[str, Optional[str]]]:
    """flax-style logical->mesh axis rules for the standard vocabulary."""
    return [
        ("batch", ("dp", "fsdp")),
        ("seq", "sp"),
        ("embed", "fsdp"),
        ("hidden", "tp"),
        ("heads", "tp"),
        ("kv", None),
        ("mlp", "tp"),
        ("vocab", "tp"),
        ("expert", "ep"),
        ("stage", "pp"),
    ]
