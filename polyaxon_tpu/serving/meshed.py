"""Mesh placement for the serving engine — the EXACT tensor-parallel
serving layout.

The decode engine is mesh-native when the server passes a
``ServingMesh``: model params go under ``NamedSharding`` over a
``jax.sharding.Mesh`` built from the seed's ``parallel.mesh.MeshSpec``
machinery, and the slot-stacked KV cache (fixed-lane pool, paged page
pool, and the draft pools) shards its HEADS axis over ``tp`` — the
memory that actually scales with slots x context, and the bandwidth
the decode step streams every token.

Layout contract — REDUCTION-FREE by construction, so meshed serving
is TOKEN-BITWISE-IDENTICAL to the unmeshed engine per seed (the
repo's determinism backbone extends to every mesh shape instead of
degrading to "numerically close"):

- COLUMN-PARALLEL params shard their OUTPUT dim over ``tp``
  (q/k/v/qkv projections, gate/up/fc1 MLP inputs): each device
  computes its own output columns over the FULL contraction dim, so
  every output element keeps the exact accumulation order of the
  unmeshed matmul.
- The KV cache shards over HEADS: per-head attention (scores,
  softmax, values) touches only that head's data — no cross-device
  math at all.
- ROW-PARALLEL weights (o_proj/down_proj/fc2), embeddings, norms and
  the lm_head stay REPLICATED, and the models' existing ``constrain``
  sites force their inputs replicated under the serving-exact mesh
  (``parallel.constraints.exact_mesh``): the all-gather that replaces
  Megatron's psum is a concatenation — bytes move, sums never
  reassociate.  (True row-parallel weight sharding for over-chip
  params needs an approximate-equality contract and is the ROADMAP
  residual, with multi-host meshes.)
- MoE expert params ([E, in, out]) shard the EXPERT dim over ``ep``:
  decode's per-token expert gather fetches the routed expert's
  weights cross-device, per-expert math untouched.
- The slot axis is replicated by default, or data-parallel over
  ``dp`` (fixed-lane pools only): each device steps its own slots
  with replicated weights.

Divisibility of what the mesh CLAIMS to shard is a STARTUP error,
not a silent replicate: a model whose KV head count doesn't divide
``tp`` (or expert count ``ep``, or slot count ``dp``) refuses to
serve meshed with a message naming the offending pair — KV/attention
sharding is the win the mesh advertises, and degrading it silently
to replication would report mesh wins that don't exist.  The one
deliberate replicate-fallback is a COLUMN-PARALLEL MLP kernel whose
output dim happens not to divide ``tp`` (e.g. an odd
``intermediate_size``): that weight stays replicated — already the
row-parallel weights' placement, bitwise-identical either way — and
the KV/attention sharding the startup checks guarantee is
unaffected.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..parallel import constraints as _constraints
from ..parallel.mesh import MeshError, MeshSpec, build_mesh

__all__ = ["ServingMesh", "parse_mesh", "MeshError"]

# Axes the serving engine speaks.  fsdp/pp/sp are training-stack
# strategies (gradient sharding, stage pipelining) with no serving
# semantics here — requesting them is a usage error, not a no-op.
SERVING_AXES = ("dp", "tp", "ep")

# Column-parallel kernels: output dim sharded, contraction dim whole
# — the reduction-free subset of parallel.strategies.TP_RULES.  Row-
# parallel names (o_proj/down_proj/fc2/wo) are deliberately ABSENT:
# sharding their input dim makes XLA psum partial products, which
# reorders float accumulation and breaks the bitwise contract.
_COL_PARALLEL = re.compile(
    r"(q_proj|k_proj|v_proj|qkv|query|key|value"
    r"|fc1|wi|up_proj|gate_proj|intermediate)[^/]*/kernel")
_EP_PARALLEL = re.compile(r"experts_w[12]$")

# Cache-collection leaves that carry a HEADS axis at ndim-2 (the
# [..., B, positions, heads, feat] layout of kv_cache.append_kv_cache
# and the int8 scale leaves; stacked/paged pools only ADD leading or
# split middle axes, so heads stays at ndim-2 in every storage
# discipline).
_KV_LEAVES = ("cached_key", "cached_value", "cached_key_scale",
              "cached_value_scale")


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None) or getattr(p, "name", None) or \
            getattr(p, "idx", None)
        parts.append(str(key))
    return "/".join(parts)


def parse_mesh(arg) -> MeshSpec:
    """``"tp=4"`` / ``"tp=2,ep=2"`` / dict / MeshSpec -> a serving
    MeshSpec (absent axes default to 1 — never -1 fill: a serving
    mesh uses exactly the devices it asks for)."""
    if isinstance(arg, MeshSpec):
        spec = arg
    else:
        if isinstance(arg, str):
            sizes: Dict[str, int] = {}
            for part in arg.split(","):
                part = part.strip()
                if not part:
                    continue
                if "=" not in part:
                    raise MeshError(
                        f"mesh axis {part!r} must be AXIS=SIZE "
                        f"(e.g. tp=4)")
                k, _, v = part.partition("=")
                try:
                    sizes[k.strip()] = int(v)
                except ValueError:
                    raise MeshError(
                        f"mesh axis size {v!r} is not an integer")
        elif isinstance(arg, dict):
            try:
                sizes = {k: int(v) for k, v in arg.items()}
            except (TypeError, ValueError):
                raise MeshError(
                    f"mesh axis sizes must be integers; got {arg!r}")
        else:
            raise MeshError(
                f"mesh must be a spec string (tp=4), a dict, or a "
                f"MeshSpec; got {type(arg).__name__}")
        unknown = set(sizes) - set(SERVING_AXES)
        if unknown:
            raise MeshError(
                f"serving mesh supports axes {SERVING_AXES}; got "
                f"{sorted(unknown)} (fsdp/pp/sp are training "
                f"strategies)")
        # Absent axes default to 1 (never MeshSpec's -1 fill: a
        # serving mesh uses exactly the devices it asks for).
        for axis in SERVING_AXES:
            sizes.setdefault(axis, 1)
        spec = MeshSpec.from_dict(sizes)
    for axis in ("fsdp", "pp", "sp"):
        if getattr(spec, axis) not in (1,):
            raise MeshError(
                f"serving mesh supports axes {SERVING_AXES}; "
                f"{axis}={getattr(spec, axis)} is a training "
                f"strategy")
    for axis in SERVING_AXES:
        size = getattr(spec, axis)
        if size == -1:
            raise MeshError(
                f"serving mesh sizes must be explicit; {axis}=-1 "
                f"(fill) is a training-spec convention")
        if size < 1:
            raise MeshError(f"mesh axis {axis} must be >= 1; got "
                            f"{size}")
    return spec


class ServingMesh:
    """One mesh + the serving placement rules over it.

    Built once at server startup over the FIRST ``dp * tp * ep``
    local devices; every placement below commits arrays to
    ``NamedSharding``s of this mesh (replication included — an
    uncommitted array fed to a mesh program forces a per-call
    transfer, the SHARD-LEAK class ``ptpu check`` flags)."""

    def __init__(self, spec, devices: Optional[Sequence] = None):
        import jax

        self.spec = parse_mesh(spec)
        self.dp = self.spec.dp
        self.tp = self.spec.tp
        self.ep = self.spec.ep
        self.n_devices = self.dp * self.tp * self.ep
        if devices is None:
            devices = jax.devices()
        if len(devices) < self.n_devices:
            raise MeshError(
                f"mesh {self.describe()['axes']} needs "
                f"{self.n_devices} devices; only {len(devices)} "
                f"available (on CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N)")
        self.mesh = build_mesh(self.spec,
                               devices=list(devices)[:self.n_devices])

    # -- introspection ---------------------------------------------------

    def describe(self) -> Dict[str, Any]:
        """The /info `mesh` block: active axes, sizes, device count."""
        return {
            "axes": {a: getattr(self, a) for a in SERVING_AXES
                     if getattr(self, a) > 1} or {"tp": 1},
            "devices": self.n_devices,
            "layout": "exact",
        }

    def axes_str(self) -> str:
        return ",".join(f"{a}={getattr(self, a)}"
                        for a in SERVING_AXES
                        if getattr(self, a) > 1) or "tp=1"

    # -- trace context ---------------------------------------------------

    def exact(self):
        """Context manager publishing the serving-exact mesh for jit
        traces inside it (parallel.constraints.exact_mesh)."""
        return _constraints.exact_mesh(self.mesh)

    # -- shardings -------------------------------------------------------

    @property
    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def _spec_sharding(self, *entries):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(*entries))

    # -- model validation ------------------------------------------------

    def validate_model(self, model, role: str = "model",
                       n_slots: Optional[int] = None) -> None:
        """Startup divisibility checks, with clean errors naming the
        offending (count, axis-size) pair."""
        cfg = getattr(model, "cfg", None)
        if self.tp > 1:
            heads = getattr(cfg, "num_kv_heads", None)
            label = "num_kv_heads"
            if heads is None:
                heads = getattr(cfg, "num_heads", None)
                label = "num_heads"
            if heads is None:
                raise MeshError(
                    f"mesh tp={self.tp}: the {role} has no head "
                    f"count (cfg.num_heads) to shard the KV cache "
                    f"over")
            if heads % self.tp:
                raise MeshError(
                    f"the {role}'s KV head count ({label}={heads}) "
                    f"is not divisible by mesh tp={self.tp}; pick a "
                    f"tp that divides it (sharding that silently "
                    f"replicates would fake the mesh win)")
        if self.ep > 1:
            experts = getattr(cfg, "num_experts", None)
            if experts is None:
                raise MeshError(
                    f"mesh ep={self.ep}: the {role} has no experts "
                    f"(cfg.num_experts) to shard")
            if experts % self.ep:
                raise MeshError(
                    f"the {role}'s expert count ({experts}) is not "
                    f"divisible by mesh ep={self.ep}")
        if self.dp > 1 and n_slots is not None and n_slots % self.dp:
            raise MeshError(
                f"n_slots ({n_slots}) is not divisible by mesh "
                f"dp={self.dp} (dp shards the slot axis)")

    # -- param placement -------------------------------------------------

    def param_shardings(self, variables) -> Any:
        """NamedSharding pytree for ``variables``: column-parallel
        kernels over tp, expert params over ep, everything else
        replicated (committed).  A column kernel whose output dim
        doesn't divide tp replicates (see the module docstring: the
        attention/KV dims are guaranteed divisible by
        validate_model; MLP widths are best-effort)."""
        import jax

        def leaf_sharding(path, leaf):
            name = _path_str(path)
            shape = getattr(leaf, "shape", ())
            nd = len(shape)
            if self.ep > 1 and _EP_PARALLEL.search(name) and nd >= 1 \
                    and shape[0] % self.ep == 0:
                return self._spec_sharding(
                    *(["ep"] + [None] * (nd - 1)))
            if self.tp > 1 and _COL_PARALLEL.search(name) \
                    and nd >= 2 and shape[-1] % self.tp == 0:
                return self._spec_sharding(
                    *([None] * (nd - 1) + ["tp"]))
            return self.replicated

        return jax.tree_util.tree_map_with_path(leaf_sharding,
                                                variables)

    def place_params(self, variables) -> Any:
        import jax

        shardings = self.param_shardings(variables)
        return jax.tree_util.tree_map(jax.device_put, variables,
                                      shardings)

    # -- KV cache placement ----------------------------------------------

    def cache_leaf_sharding(self, key: str, leaf, *,
                            slot_axis: bool = False):
        """NamedSharding for one cache-collection leaf (by tree-path
        ``key``): heads (ndim-2) over tp for the standard KV leaves,
        slot axis (0) over dp when the leaf belongs to a slot-stacked
        pool, everything else replicated."""
        shape = getattr(leaf, "shape", ())
        nd = len(shape)
        spec = [None] * nd
        named = any(key.endswith(f"{n}']") or key.endswith(n)
                    for n in _KV_LEAVES)
        if self.tp > 1 and named and nd >= 2 \
                and shape[nd - 2] % self.tp == 0:
            spec[nd - 2] = "tp"
        if self.dp > 1 and slot_axis and nd >= 1 \
                and shape[0] % self.dp == 0:
            spec[0] = "dp"
        return self._spec_sharding(*spec)

    def cache_shardings(self, tree, *, slot_axis: bool = False):
        """NamedSharding pytree for a cache pytree (a B=1 template,
        or a slot-stacked pool when ``slot_axis``)."""
        import jax

        def leaf_sharding(path, leaf):
            return self.cache_leaf_sharding(
                jax.tree_util.keystr(path), leaf,
                slot_axis=slot_axis)

        return jax.tree_util.tree_map_with_path(leaf_sharding, tree)

    # -- paged pool placement --------------------------------------------

    def pool_leaf_sharding(self, meta: Dict[str, Any], pool_leaf):
        """NamedSharding for one PAGED pool leaf.  The pool splits the
        position axis into (n_pages, page_tokens), shifting heads to
        ``pos_axis + 2`` == pool ndim-2 for the named KV layout;
        unnamed fallback leaves (unknown head position) replicate."""
        nd = getattr(pool_leaf, "ndim", 0)
        spec = [None] * nd
        if self.tp > 1 and meta.get("heads_axis") is not None:
            axis = meta["heads_axis"]
            if axis < nd and pool_leaf.shape[axis] % self.tp == 0:
                spec[axis] = "tp"
        return self._spec_sharding(*spec)

    @staticmethod
    def shardings_of(placed):
        """The shardings a placed pytree's arrays ALREADY have — what a
        program that takes the weights as an argument (generate.
        jit_over) names as that argument's ``in_shardings``."""
        import jax

        return jax.tree.map(lambda x: x.sharding, placed)

    @staticmethod
    def describe_placement(pool) -> list:
        """Where a LIVE KV pool's leaves sit, read off the arrays'
        own shardings (not off the plan above): one
        ``{"shape", "spec", "devices", "shard_shape"}`` per leaf —
        the /info proof that heads are split over tp on every device
        rather than assumed to be."""
        import jax

        return [{"shape": list(leaf.shape),
                 "spec": str(getattr(leaf.sharding, "spec", None)),
                 "devices": len(leaf.sharding.device_set),
                 "shard_shape": list(
                     leaf.sharding.shard_shape(leaf.shape))}
                for leaf in jax.tree.leaves(pool)]

    # -- host-array placement --------------------------------------------

    def put_replicated(self, x):
        """Commit a host array to the mesh, replicated — the
        sanctioned spelling for feeding host-built operands to a
        mesh-compiled program (SHARD-LEAK)."""
        import jax

        return jax.device_put(np.asarray(x), self.replicated)
