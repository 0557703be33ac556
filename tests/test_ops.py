"""Op library tests: flash kernel (pallas interpreter) vs XLA reference."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.ops.attention import _xla_attention, dot_product_attention


def _qkv(b=1, s=256, h=2, d=128, dtype=jnp.float32, seed=0):
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_xla_attention_matches_naive_softmax():
    q, k, v = _qkv(s=32, d=16)
    out = dot_product_attention(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (16 ** -0.5)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_causal_masks_future():
    q, k, v = _qkv(s=8, d=16)
    out = dot_product_attention(q, k, v, causal=True)
    # Row 0 can only attend to position 0 -> equals v[0].
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(v[:, 0]), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_xla(causal, monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, k, v = _qkv(s=256, d=128)
    out = flash_attention(q, k, v, causal=causal, scale=128 ** -0.5)
    ref = _xla_attention(q, k, v, None, causal, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_flash_gradients_match_xla(monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, k, v = _qkv(s=128, d=128)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               scale=128 ** -0.5).sum()

    def loss_ref(q, k, v):
        return _xla_attention(q, k, v, None, True, 128 ** -0.5).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_flash_cross_length_causal_matches_xla(monkeypatch):
    """Sq != Sk causal (decode-suffix shape): bottom-right alignment."""
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, _, _ = _qkv(s=128, d=128, seed=1)
    _, k, v = _qkv(s=256, d=128, seed=2)
    out = flash_attention(q, k, v, causal=True, scale=128 ** -0.5)
    ref = _xla_attention(q, k, v, None, True, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_flash_rejects_ragged_seq(monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, k, v = _qkv(s=200, d=128)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kv_mask_matches_xla(causal, monkeypatch):
    """Key-padding masks run in the pallas kernels (VERDICT r1 #8)."""
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, k, v = _qkv(b=2, s=256, d=128)
    lengths = np.array([200, 131])
    kv_mask = jnp.asarray(np.arange(256)[None, :] < lengths[:, None])
    out = flash_attention(q, k, v, causal=causal, scale=128 ** -0.5,
                          kv_mask=kv_mask)
    mask4 = kv_mask[:, None, None, :]
    ref = _xla_attention(q, k, v, mask4, causal, 128 ** -0.5)
    valid_q = np.asarray(kv_mask)  # padded query rows are don't-care
    np.testing.assert_allclose(
        np.asarray(out)[valid_q], np.asarray(ref)[valid_q],
        atol=2e-3, rtol=2e-3)


def test_flash_kv_mask_gradients_match_xla(monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, k, v = _qkv(b=2, s=128, d=128)
    lengths = np.array([100, 77])
    kv_mask = jnp.asarray(np.arange(128)[None, :] < lengths[:, None])
    mask4 = kv_mask[:, None, None, :]
    # Only read valid query rows: padded rows' outputs are don't-care
    # and would otherwise feed garbage cotangents into the comparison.
    w = kv_mask[:, :, None, None].astype(q.dtype)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, scale=128 ** -0.5,
                                kv_mask=kv_mask) * w).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, mask4, True, 128 ** -0.5)
                * w).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_flash_fully_masked_row_is_finite(monkeypatch):
    """A batch element whose keys are ALL padded must yield zeros/finite
    grads, not NaN."""
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.flash import flash_attention
    q, k, v = _qkv(b=2, s=128, d=128)
    kv_mask = jnp.asarray(
        np.stack([np.ones(128, bool), np.zeros(128, bool)]))
    out = flash_attention(q, k, v, scale=128 ** -0.5, kv_mask=kv_mask)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out[1]), 0.0, atol=1e-6)
    g = jax.grad(lambda q: flash_attention(
        q, k, v, scale=128 ** -0.5, kv_mask=kv_mask).sum())(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_pick_block_divides_seq():
    """Blocks must DIVIDE the sequence (seq=1280 with a 1024 cap must
    fall back to 640, not truncate the grid)."""
    from polyaxon_tpu.ops.flash import _pick_block
    assert _pick_block(1280, 1024) == 640
    assert _pick_block(1024, 1024) == 1024
    assert _pick_block(4096, 1024) == 1024
    assert _pick_block(128, 1024) == 128
    assert _pick_block(384, 256) == 128  # 256 does not divide 384


def test_flash_nondividing_cap_matches_xla(monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    import polyaxon_tpu.ops.flash as fl
    monkeypatch.setattr(fl, "BLOCK_Q", 1024)
    monkeypatch.setattr(fl, "BLOCK_KV", 1024)
    q, k, v = _qkv(s=1280, h=1, d=128)
    out = fl.flash_attention(q, k, v, causal=True, scale=128 ** -0.5)
    ref = _xla_attention(q, k, v, None, True, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_registry_analytic_train_flops():
    """Headline models carry analytic MFU numerators (VERDICT r1 #1:
    MFU = analytic FLOPs / step time / peak; XLA cost analysis cannot
    see pallas kernel FLOPs)."""
    from polyaxon_tpu.models.registry import get_model
    # gpt2-medium at batch 8, seq 1024: ~18.6 TFLOPs/step (6*N*T-scale).
    f = get_model("gpt2-medium").train_flops(8)
    assert 15e12 < f < 25e12
    # resnet50 at batch 128: ~3.1 TFLOPs/step.
    f = get_model("resnet50").train_flops(128)
    assert 2.5e12 < f < 4e12
    for name in ("bert-base", "vit-base", "moe-gpt-small"):
        assert get_model(name).train_flops is not None


@pytest.mark.parametrize("window", [64, 128, 200])
def test_flash_sliding_window_matches_xla(window, monkeypatch):
    """Windowed kernels (block-skip + in-block mask) match the XLA
    reference, including windows that don't align to blocks."""
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    import polyaxon_tpu.ops.flash as fl
    monkeypatch.setattr(fl, "BLOCK_Q", 128)
    monkeypatch.setattr(fl, "BLOCK_KV", 128)
    q, k, v = _qkv(b=2, s=512, d=128)
    out = fl.flash_attention(q, k, v, causal=True, scale=128 ** -0.5,
                             window=window)
    ref = _xla_attention(q, k, v, None, True, 128 ** -0.5,
                         window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_flash_sliding_window_gradients(monkeypatch):
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    import polyaxon_tpu.ops.flash as fl
    monkeypatch.setattr(fl, "BLOCK_Q", 128)
    monkeypatch.setattr(fl, "BLOCK_KV", 128)
    q, k, v = _qkv(b=1, s=384, d=128)

    def f_flash(q, k, v):
        o = fl.flash_attention(q, k, v, causal=True, scale=128 ** -0.5,
                               window=100)
        return (o.astype(jnp.float32) ** 2).sum()

    def f_ref(q, k, v):
        o = _xla_attention(q, k, v, None, True, 128 ** -0.5, window=100)
        return (o.astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)


def test_flash_window_requires_causal():
    from polyaxon_tpu.ops.flash import flash_attention
    q = jnp.zeros((1, 128, 1, 64))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=16)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, q, q, window=16)


def test_window_block_skip_logic():
    """Blocks entirely outside [i-window, i] are skipped."""
    from polyaxon_tpu.ops.flash import _block_needed
    # q block 3 (rows 384-511), window 64: kv block 0 (cols 0-127) has
    # max col 127 < 384-64 -> skipped; kv block 2 (cols 256-383) needed.
    assert not _block_needed(3, 0, 128, 128, 0, True, 64)
    assert _block_needed(3, 2, 128, 128, 0, True, 64)
    assert _block_needed(3, 3, 128, 128, 0, True, 64)
    assert not _block_needed(0, 1, 128, 128, 0, True, 64)  # future


def test_window_zero_rejected():
    """window=0 must error, not silently disable windowing."""
    from polyaxon_tpu.ops.flash import flash_attention
    q = jnp.zeros((1, 128, 1, 64))
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, q, q, causal=True, window=0)
    with pytest.raises(ValueError, match=">= 1"):
        dot_product_attention(q, q, q, causal=True, window=0)
    from polyaxon_tpu.models.llama import LlamaConfig
    with pytest.raises(ValueError, match="sliding_window"):
        LlamaConfig(sliding_window=0)


def test_window_routes_through_sp(monkeypatch):
    """window + active sequence parallelism routes through the windowed
    ring/Ulysses paths and matches local windowed attention."""
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.attention import sequence_parallel
    from polyaxon_tpu.parallel import MeshSpec, build_mesh
    mesh = build_mesh(MeshSpec(dp=-1, sp=2))
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(kk, (4, 256, 2, 64)) for kk in ks)
    ref = _xla_attention(q, k, v, None, True, 64 ** -0.5, window=100)
    for mode in ("ring", "ulysses"):
        with sequence_parallel(mesh, mode):
            out = dot_product_attention(q, k, v, causal=True, window=100)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"mode={mode}")


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "tp": 2},
                                  {"fsdp": 2, "tp": 2}],
                         ids=["dp4", "dp2xtp2", "fsdp2xtp2"])
@pytest.mark.parametrize("padded", [False, True],
                         ids=["nomask", "kvmask"])
def test_flash_under_a_train_mesh_matches_one_device(axes, padded,
                                                     monkeypatch):
    """A step jitted over several devices cannot leave a Mosaic kernel
    to GSPMD (the TPU compiler refuses to partition it), so the routed
    call hands each device its shard of batch and heads.  Values and
    gradients equal the one-device call's: rows and heads never mix."""
    monkeypatch.setenv("POLYAXON_TPU_FLASH_INTERPRET", "1")
    from polyaxon_tpu.ops.attention import route_counts
    from polyaxon_tpu.parallel import MeshSpec, build_mesh
    from polyaxon_tpu.parallel.constraints import ambient_mesh

    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshSpec.from_dict({"dp": 1, **axes}),
                      devices=jax.devices()[:n])
    q, k, v = _qkv(b=4, s=128, h=2, d=64)
    mask = None
    if padded:
        lengths = np.array([128, 100, 77, 128])
        mask = jnp.asarray(
            np.arange(128)[None, :] < lengths[:, None])[:, None, None, :]

    def grad():
        # A fresh function each time: the route is chosen while
        # TRACING, and jit would hand the second call the first trace.
        def loss(q, k, v):
            out = dot_product_attention(q, k, v, causal=True, mask=mask)
            return (out ** 2).sum(), out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, ref), ref_g = grad()(q, k, v)
    before = route_counts()["flash"]
    with ambient_mesh(mesh):
        (_, out), g = grad()(q, k, v)
        assert "shard_map" in str(jax.make_jaxpr(
            lambda q, k, v: dot_product_attention(
                q, k, v, causal=True, mask=mask))(q, k, v))
    assert route_counts()["flash"] > before   # the kernel, not XLA
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
