"""Gradient-allreduce schedule shape for the dp train step, pinned on
REAL v5e-compiled HLO (SURVEY.md §7 hard part 2: the ≥90 % 8→256-chip
scaling target lives or dies on the gradient all-reduce overlapping
backward compute).

Multi-chip TPU hardware cannot exist in CI, but the TPU compiler can:
``jax.experimental.topologies`` gives a deviceless v5e:2x4 topology and
``lower().compile()`` runs the full XLA TPU pipeline (SPMD partitioner,
combiner, scheduler) producing a scheduled module — without a chip.
These tests compile the framework's actual
``make_train_step`` for every model family in the zoo and assert the
overlap PRECONDITIONS in the scheduled HLO:

1. Gradient all-reduces are COMBINED into a few bucketed ops, not one
   per parameter (per-param ARs can't amortize ICI latency).
2. The first all-reduce is scheduled strictly BEFORE the last compute
   fusion: reductions start while backward/update compute still runs —
   the schedule shape that lets the hardware overlap them.
3. No all-gather appears in a pure-dp step (params are replicated; an
   all-gather would mean an accidental resharding inserted by XLA).

What this deliberately does NOT assert: ``all-reduce-start/-done``
async pairs.  Empirical finding (see docs/SCALING.md): this libtpu's
deviceless compile keeps collectives in sync form in ``as_text()``
even with ``xla_tpu_enable_async_collective_fusion`` — the async
(continuation-fusion) rewrite happens at runtime lowering on real
devices, so pair-splitting is only observable in an on-TPU profile.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from polyaxon_tpu.models.registry import get_model
from polyaxon_tpu.parallel import make_train_step
from polyaxon_tpu.parallel.mesh import MeshSpec, build_mesh
from polyaxon_tpu.parallel.strategies import make_param_shardings

# Model families (CI-sized variants, same code paths as the headline
# configs): classifier MLP, ResNet (convs+BN), GPT-2 (flash attention,
# scanned stack), BERT (MLM loss), Llama (RoPE/GQA/RMSNorm).
# Value = max all-reduce count in the scheduled module.  Transformers
# and the MLP get a handful of combined gradient buckets (≤8).  ResNet
# additionally pays 2 small ARs per BatchNorm layer: batch statistics
# reduce over the SHARDED batch axis in forward, and those ARs are
# sequentially dependent so the combiner cannot merge them — an
# inherent dp+BN cost the scaling model (docs/SCALING.md) accounts for.
ZOO = {"mlp": 8, "resnet50-tiny": 40, "gpt2-tiny": 8, "bert-tiny": 8,
       "llama-tiny": 8}


@pytest.fixture(scope="module")
def v5e_topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x4")
    except Exception as e:  # no TPU compiler support in this env
        pytest.skip(f"deviceless TPU topology unavailable: {e}")


def _compile_dp_step(topo, model_name, batch_size=16):
    """AOT-compile the framework's dp train step for v5e; no devices."""
    spec = get_model(model_name)
    model = spec.make_model()
    batch = spec.make_batch(batch_size)
    batch_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    mesh = build_mesh(MeshSpec(dp=8), devices=topo.devices)
    rng = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(
        model.init, rng,
        jnp.zeros(batch["inputs"].shape, batch["inputs"].dtype))
    step = make_train_step(spec.loss_fn(model), optax.sgd(0.01),
                           mesh=mesh, donate=True)
    from jax.sharding import NamedSharding, PartitionSpec as P

    opt_abs = jax.eval_shape(step.optimizer.init, params_abs)
    step.state_shardings = {
        "params": make_param_shardings(params_abs, mesh),
        "opt_state": make_param_shardings(opt_abs, mesh),
        "step": NamedSharding(mesh, P()),
    }
    state_abs = {"params": params_abs, "opt_state": opt_abs,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
    jitted = step._build()
    return jitted.lower(state_abs, batch_abs, rng).compile()


def _entry_op_sequence(hlo_text):
    """('AR'|'F') per all-reduce/fusion op, in ENTRY schedule order."""
    lines = hlo_text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    seq = []
    for line in lines[start:]:
        s = line.strip()
        if not s.startswith("%"):
            continue
        if "all-reduce(" in s:
            seq.append("AR")
        elif re.search(r"fusion(\.\d+)?\(", s):
            seq.append("F")
    return seq


@pytest.mark.parametrize("model_name", sorted(ZOO))
def test_dp_gradient_allreduce_schedule(v5e_topology, model_name):
    compiled = _compile_dp_step(v5e_topology, model_name)
    txt = compiled.as_text()

    assert "is_scheduled=true" in txt, "expected a scheduled module"

    n_ar = txt.count("all-reduce(")
    # ≥1: the gradient reduction exists.  The per-model cap asserts
    # gradients are combined into buckets, not one AR per parameter
    # tensor (the transformers have dozens of params -> an uncombined
    # schedule blows straight past it).
    assert 1 <= n_ar <= ZOO[model_name], \
        f"{model_name}: {n_ar} all-reduces"

    # Pure dp: params replicated, no resharding gathers.
    assert txt.count("all-gather(") == 0, \
        f"{model_name}: unexpected all-gather in dp-only step"

    seq = _entry_op_sequence(txt)
    ar_pos = [i for i, k in enumerate(seq) if k == "AR"]
    last_fusion = max(i for i, k in enumerate(seq) if k == "F")
    assert ar_pos, f"{model_name}: no all-reduce scheduled in ENTRY"
    # Overlap precondition: the first reduction launches while compute
    # is still scheduled after it (backward tail / optimizer update).
    assert ar_pos[0] < last_fusion, (
        f"{model_name}: all-reduce scheduled after all compute "
        f"(positions {ar_pos} vs last fusion {last_fusion}) — "
        f"no overlap possible")


def test_dp_allreduce_bytes_match_scaling_model(v5e_topology):
    """The bytes the schedule actually reduces = the analytic model's
    input (docs/SCALING.md): sum over AR operand shapes ≈ param bytes.
    Pinning this keeps the SCALING.md arithmetic honest against code
    drift (e.g. an fp32 gradient sneaking into a bf16 model)."""
    compiled = _compile_dp_step(v5e_topology, "gpt2-tiny")
    txt = compiled.as_text()
    # Operand dtypes/shapes of each AR op line in the ENTRY schedule.
    ar_bytes = 0
    for line in txt.splitlines():
        s = line.strip()
        if "all-reduce(" not in s or not s.startswith("%"):
            continue
        # e.g. %all-reduce.9 = (f32[768,768]{...}, ...) all-reduce(
        for dt, dims in re.findall(r"(f32|bf16|f16)\[([\d,]*)\]",
                                   s.split("all-reduce(")[0]):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            ar_bytes += n * {"f32": 4, "bf16": 2, "f16": 2}[dt]
    spec = get_model("gpt2-tiny")
    model = spec.make_model()
    batch = spec.make_batch(2)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros(batch["inputs"].shape, batch["inputs"].dtype))
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert ar_bytes > 0
    # Reduced bytes should be within 2x of param bytes (dtype casts,
    # fused loss terms allowed) — catches per-layer duplication or a
    # silently-widened gradient dtype.
    assert 0.4 * param_bytes <= ar_bytes <= 2.0 * param_bytes, (
        f"AR bytes {ar_bytes} vs param bytes {param_bytes}")
