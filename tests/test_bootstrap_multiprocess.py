"""REAL multi-process jax.distributed bootstrap (SURVEY.md §7 hard part
#1 / §4 "multi-node without a cluster").

Two actual OS processes receive the same ``PTPU_*`` env block the
converter/operator inject, call ``initialize_from_env()`` (the
TF_CONFIG/NCCL/MPI replacement), form one 2-device global CPU mesh, and
run a cross-process psum.  This is the north-star wiring executed for
real — not a golden-env assertion.
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

WORKER = textwrap.dedent("""
    import sys

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    topo = initialize_from_env(timeout_s=60)
    assert topo is not None and topo.is_distributed, topo
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 2, jax.device_count()

    # cross-process collective: sum of process ids over the global mesh
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(jax.devices(), ("dp",))
    local = jnp.full((1,), float(jax.process_index()))
    arr = jax.make_array_from_single_device_arrays(
        (2,), NamedSharding(mesh, P("dp")),
        [jax.device_put(local, jax.local_devices()[0])])
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
    # every process sees the replicated global sum 0 + 1 = 1
    assert float(total) == 1.0, float(total)
    print(f"proc {topo.process_id} psum OK", flush=True)
""")


TRAIN_WORKER = textwrap.dedent("""
    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    topo = initialize_from_env(timeout_s=60)
    assert jax.process_count() == 2 and jax.device_count() == 8

    import jax.numpy as jnp
    import optax

    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.parallel import MeshSpec, build_mesh, make_train_step

    # dp spans processes (DCN analogue), fsdp spans local devices (ICI)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
    spec = get_model("mlp")
    model, params = spec.init_params(batch_size=2)
    step = make_train_step(spec.loss_fn(model), optax.sgd(0.1), mesh,
                           donate=False)
    state = step.init_state(params)
    # identical host batch on every process -> device_put shards it over
    # the global mesh (gradient allreduce crosses the process boundary)
    batch = {k: jnp.asarray(v) for k, v in spec.make_batch(8).items()}
    batch = jax.device_put(batch, step.batch_sharding)
    losses = []
    for i in range(3):
        state, metrics = step(state, batch, jax.random.PRNGKey(0))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    print(f"proc {topo.process_id} train OK {losses}", flush=True)
""")


def _run_procs(worker, n_procs, local_devices, extra_env=None,
               timeout=420):
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    procs = []
    for pid in range(n_procs):
        env = {
            **os.environ,
            **(extra_env or {}),
            "PYTHONPATH": str(REPO),
            "PTPU_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "PTPU_NUM_PROCESSES": str(n_procs),
            "PTPU_PROCESS_ID": str(pid),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={local_devices}",
        }
        procs.append(subprocess.Popen(
            [sys.executable, "-c", worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outputs = []
    try:
        for proc in procs:
            out, _ = proc.communicate(timeout=timeout)
            outputs.append(out)
    finally:
        # A wedged gang member (the hang class this harness exists to
        # catch) must not orphan the others holding the coordinator
        # port for the rest of the pytest session.
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                try:
                    out, _ = proc.communicate(timeout=10)
                    outputs.append(f"[killed after hang]\n{out}")
                except Exception:
                    pass
    for pid, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"proc {pid} failed:\n{out}"
    return outputs


def _run_two_procs(worker, local_devices):
    return _run_procs(worker, 2, local_devices)


TRACKING_WORKER = textwrap.dedent("""
    import os, sys

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    initialize_from_env(timeout_s=60)

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from polyaxon_tpu import tracking
    from polyaxon_tpu.checkpoint import CheckpointManager

    # UNMANAGED distributed run: no env-injected run identity -> the
    # chief's auto-created uuid must be broadcast so every process
    # shares ONE run (separate checkpoint dirs deadlock orbax's
    # cross-process barriers - regression for the train.py hang).
    run = tracking.init(name="shared", collect_system_metrics=False,
                        track_env=False, track_code=False)
    print("UUID=" + run.run_uuid, flush=True)

    mesh = Mesh(jax.devices(), ("dp",))
    rep = NamedSharding(mesh, P())
    state = {"w": jax.device_put(jnp.ones((4,)), rep)}
    ckpt = CheckpointManager(run_uuid=run.run_uuid, async_save=True)
    ckpt.save(1, state, force=True)
    ckpt.wait()
    ckpt.close()
    run.end()
    print("CKPT OK", flush=True)
""")


def test_two_process_bootstrap_and_psum():
    outputs = _run_two_procs(WORKER, local_devices=1)
    for out in outputs:
        assert "psum OK" in out


def test_two_process_train_step_descends():
    """Full multi-host training path: TrainStep over a dp(2-process) x
    fsdp(4-device) global mesh, gradient allreduce over DCN-analogue."""
    outputs = _run_two_procs(TRAIN_WORKER, local_devices=4)
    for out in outputs:
        assert "train OK" in out


def test_unmanaged_distributed_run_shares_uuid_and_checkpoints(
        tmp_path, monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_HOME", str(tmp_path / "home"))
    outputs = _run_two_procs(TRACKING_WORKER, local_devices=1)
    uuids = set()
    for out in outputs:
        assert "CKPT OK" in out, out
        for line in out.splitlines():
            if line.startswith("UUID="):
                uuids.add(line.split("=", 1)[1])
    assert len(uuids) == 1, f"processes tracked separate runs: {uuids}"


SHARDED_AXES_WORKER = textwrap.dedent("""
    import os

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    # The SAME program is the n_procs=1 reference leg (the comparison
    # is only meaningful if worker and reference cannot drift apart).
    n_procs = int(os.environ["PTPU_NUM_PROCESSES"])
    topo = initialize_from_env(timeout_s=120)
    assert jax.process_count() == n_procs, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    import jax.numpy as jnp
    import optax

    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.parallel import MeshSpec, build_mesh, make_train_step
    from polyaxon_tpu.parallel.constraints import ambient_mesh

    fsdp = int(os.environ["TEST_FSDP"])
    tp = int(os.environ["TEST_TP"])
    mesh = build_mesh(MeshSpec(dp=1, fsdp=fsdp, tp=tp))

    # process-id -> mesh-coordinate must follow the injected topology:
    # jax.devices() is process-major (PTPU_PROCESS_ID order) and mesh
    # axes fill in AXIS_ORDER with tp fastest, so the owner of
    # mesh.devices[f, t] is fully determined by the env block.
    local_per = 8 // n_procs
    grid = mesh.devices.reshape(fsdp, tp)
    for f in range(fsdp):
        for t in range(tp):
            expect = (f * tp + t) // local_per
            got = grid[f, t].process_index
            assert got == expect, (f, t, got, expect)

    spec = get_model("gpt2-tiny")
    model, params = spec.init_params(batch_size=2)
    loss_fn = spec.loss_fn(model)
    step = make_train_step(loss_fn, optax.sgd(0.1), mesh, donate=False)
    state = step.init_state(params)
    batch = {k: jnp.asarray(v) for k, v in spec.make_batch(4).items()}
    batch = jax.device_put(batch, step.batch_sharding)

    def lg(p, b):
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b,
                                                                None)
        return l, optax.global_norm(g)

    with ambient_mesh(mesh):
        l, n = jax.jit(lg)(state["params"], batch)
    print(f"RESULT fsdp={fsdp} tp={tp} "
          f"LOSS={float(l):.8f} NORM={float(n):.8f}", flush=True)
""")


def _parse_result(out):
    import re

    m = re.search(r"LOSS=([\d.eE+-]+) NORM=([\d.eE+-]+)", out)
    assert m, out
    return float(m.group(1)), float(m.group(2))


def test_four_process_gang_sharded_axes_cross_processes():
    """VERDICT r2 task 6: 4 processes x 2 local devices with fsdp (and,
    in the second config, tp) axes SPANNING process boundaries — where
    process-id <-> mesh-coordinate bugs live.  Every process's
    loss/grad-norm must match a single-process 8-device run of the
    identical program, and device ownership must follow the injected
    PTPU_* topology env."""
    # (fsdp, tp): fsdp=4 puts each fsdp shard on a different process;
    # tp=4 makes every tp group straddle two processes.
    for fsdp, tp in ((4, 2), (2, 4)):
        env = {"TEST_FSDP": str(fsdp), "TEST_TP": str(tp)}
        # Reference leg: the IDENTICAL worker program, one process with
        # all 8 devices (initialize_from_env no-ops at n=1) — worker
        # and reference cannot drift apart.
        ref_out, = _run_procs(SHARDED_AXES_WORKER, n_procs=1,
                              local_devices=8, extra_env=env)
        ref_loss, ref_norm = _parse_result(ref_out)
        outputs = _run_procs(SHARDED_AXES_WORKER, n_procs=4,
                             local_devices=2, extra_env=env)
        for out in outputs:
            loss, norm = _parse_result(out)
            assert abs(loss - ref_loss) < 5e-5 * max(1, abs(ref_loss)), \
                (fsdp, tp, loss, ref_loss)
            assert abs(norm - ref_norm) < 5e-5 * max(1, abs(ref_norm)), \
                (fsdp, tp, norm, ref_norm)


SP_RING_WORKER = textwrap.dedent("""
    import os

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    n_procs = int(os.environ["PTPU_NUM_PROCESSES"])
    topo = initialize_from_env(timeout_s=120)
    assert jax.process_count() == n_procs, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    import dataclasses

    import jax.numpy as jnp
    import numpy as np
    import optax

    from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
    from polyaxon_tpu.ops.attention import sequence_parallel
    from polyaxon_tpu.parallel import MeshSpec, build_mesh

    # dp=2 x sp=4 over 8 devices in 4 processes (2 local each): every
    # sp ring spans TWO process boundaries, so the blockwise KV
    # ppermute rotation crosses real process gaps — the habitat of
    # process-id <-> mesh-coordinate bugs (VERDICT r3 missing #5).
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    cfg = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32)
    model = GPT2Model(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 64)))
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        return (model.apply(p, tokens).astype(jnp.float32) ** 2).mean()

    with sequence_parallel(mesh, "ring"), mesh:
        l, g = jax.jit(jax.value_and_grad(loss))(params)
    n = optax.global_norm(g)
    assert np.isfinite(float(l)) and np.isfinite(float(n))
    print(f"RESULT sp=4 LOSS={float(l):.8f} NORM={float(n):.8f}",
          flush=True)
""")


EP_MOE_WORKER = textwrap.dedent("""
    import os

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    n_procs = int(os.environ["PTPU_NUM_PROCESSES"])
    topo = initialize_from_env(timeout_s=120)
    assert jax.process_count() == n_procs, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.parallel import MeshSpec, build_mesh, make_train_step

    # dp=2 x ep=4 over 8 devices in 4 processes: each expert group of
    # 4 devices straddles two processes, so the MoE dispatch/combine
    # all-to-all crosses real process boundaries.
    mesh = build_mesh(MeshSpec(dp=2, ep=4))
    spec = get_model("moe-gpt-tiny")
    model, params = spec.init_params(batch_size=2)
    loss_fn = spec.loss_fn(model)
    step = make_train_step(loss_fn, optax.sgd(0.1), mesh, donate=False)
    state = step.init_state(params)
    batch = {k: jnp.asarray(v) for k, v in spec.make_batch(4).items()}
    batch = jax.device_put(batch, step.batch_sharding)

    def lg(p, b):
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b,
                                                                None)
        return l, optax.global_norm(g)

    from polyaxon_tpu.parallel.constraints import ambient_mesh

    with ambient_mesh(mesh):
        l, n = jax.jit(lg)(state["params"], batch)
    assert np.isfinite(float(l)) and np.isfinite(float(n))
    print(f"RESULT ep=4 LOSS={float(l):.8f} NORM={float(n):.8f}",
          flush=True)
""")


def test_four_process_gang_ring_attention_crosses_processes():
    """Ring attention's ppermute KV rotation over an sp axis that spans
    process boundaries: 4 processes x 2 devices, sp=4 — outputs/grads
    must match the identical 1-process 8-device program."""
    ref_out, = _run_procs(SP_RING_WORKER, n_procs=1, local_devices=8)
    ref_loss, ref_norm = _parse_result(ref_out)
    outputs = _run_procs(SP_RING_WORKER, n_procs=4, local_devices=2)
    for out in outputs:
        loss, norm = _parse_result(out)
        assert abs(loss - ref_loss) < 5e-5 * max(1, abs(ref_loss)), \
            (loss, ref_loss)
        assert abs(norm - ref_norm) < 5e-5 * max(1, abs(ref_norm)), \
            (norm, ref_norm)


MULTISLICE_WORKER = textwrap.dedent("""
    import os

    import jax
    jax.config.update("jax_platforms", "cpu")

    from polyaxon_tpu.parallel.bootstrap import initialize_from_env

    n_procs = int(os.environ["PTPU_NUM_PROCESSES"])
    topo = initialize_from_env(timeout_s=120)
    assert jax.process_count() == n_procs, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.parallel import MeshSpec, build_mesh, make_train_step
    from polyaxon_tpu.parallel.constraints import ambient_mesh

    # The dryrun's 2-slice hybrid topology (__graft_entry__), now over
    # a REAL 8-process gang with one device per process: dp=2 over
    # num_slices=2 puts EVERY dp pair across the DCN (slice) boundary,
    # and fsdp=4 spans four distinct processes inside each slice —
    # the gradient allreduce is hierarchical (ICI reduce-scatter,
    # DCN all-reduce, ICI all-gather) when slices are physical, and on
    # this CPU gang it must still be NUMERICALLY identical to the
    # 1-process run of the same program.
    mesh = build_mesh(MeshSpec(dp=2, fsdp=4, num_slices=2))
    spec = get_model("gpt2-tiny")
    model, params = spec.init_params(batch_size=2)
    loss_fn = spec.loss_fn(model)
    step = make_train_step(loss_fn, optax.sgd(0.1), mesh, donate=False)
    state = step.init_state(params)
    # batch divisible by dp x fsdp = 8
    batch = {k: jnp.asarray(v) for k, v in spec.make_batch(8).items()}
    batch = jax.device_put(batch, step.batch_sharding)

    def lg(p, b):
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b,
                                                                None)
        return l, optax.global_norm(g)

    with ambient_mesh(mesh):
        l, n = jax.jit(lg)(state["params"], batch)
    assert np.isfinite(float(l)) and np.isfinite(float(n))
    # ...and one real optimizer step must execute across the gang.
    state, metrics = step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))
    print(f"RESULT slices=2 LOSS={float(l):.8f} NORM={float(n):.8f}",
          flush=True)
""")


def test_eight_process_two_slice_gang_dp_over_dcn():
    """VERDICT r4 next-6: 8 REAL processes forming the dryrun's 2-slice
    hybrid mesh (dp=2 x fsdp=4, num_slices=2), one device each — the
    dp axis crosses the slice/DCN boundary and fsdp crosses process
    boundaries within each slice.  Loss/grad-norm parity vs the
    identical 1-process 8-device program."""
    ref_out, = _run_procs(MULTISLICE_WORKER, n_procs=1, local_devices=8)
    ref_loss, ref_norm = _parse_result(ref_out)
    # 8 jax processes on a 1-CPU CI host: give the gang headroom (the
    # uncontended run takes ~3 min; 420s flaked under suite load).
    outputs = _run_procs(MULTISLICE_WORKER, n_procs=8, local_devices=1,
                         timeout=720)
    for out in outputs:
        loss, norm = _parse_result(out)
        assert abs(loss - ref_loss) < 5e-5 * max(1, abs(ref_loss)), \
            (loss, ref_loss)
        assert abs(norm - ref_norm) < 5e-5 * max(1, abs(ref_norm)), \
            (norm, ref_norm)


def test_four_process_gang_moe_all_to_all_crosses_processes():
    """MoE expert-parallel dispatch over an ep axis spanning process
    boundaries: 4 processes x 2 devices, ep=4 — loss/grads must match
    the identical 1-process 8-device program."""
    ref_out, = _run_procs(EP_MOE_WORKER, n_procs=1, local_devices=8)
    ref_loss, ref_norm = _parse_result(ref_out)
    outputs = _run_procs(EP_MOE_WORKER, n_procs=4, local_devices=2)
    for out in outputs:
        loss, norm = _parse_result(out)
        assert abs(loss - ref_loss) < 5e-5 * max(1, abs(ref_loss)), \
            (loss, ref_loss)
        assert abs(norm - ref_norm) < 5e-5 * max(1, abs(ref_norm)), \
            (norm, ref_norm)
