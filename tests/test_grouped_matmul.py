"""The expert layers' grouped matmul (ops/grouped_matmul.py): the
Pallas route, run through the interpreter on the CPU, against
``jax.lax.ragged_dot`` — groups of every size, empty ones, rows past
the last group — and the rule that picks the route and the tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.ops import grouped_matmul as GM
from polyaxon_tpu.parallel.moe import held_experts_ffn


def _case(m, k, n, groups, dtype, unused=7):
    keys = jax.random.split(jax.random.PRNGKey(m + k), 2)
    x = jax.random.normal(keys[0], (m, k), dtype)
    w = jax.random.normal(keys[1], (groups, k, n), dtype)
    sizes = np.random.RandomState(m).multinomial(
        m - unused, [1.0 / groups] * groups).astype(np.int32)
    sizes[2] += sizes[1]
    sizes[1] = 0                                # an empty group
    return x, w, jnp.asarray(sizes), m - unused


@pytest.mark.parametrize("m,k,n,groups,dtype", [
    (256, 32, 24, 8, jnp.float32),      # a prefill piece's pairs
    (48, 32, 24, 8, jnp.float32),       # a decode step's: one row tile
    (512, 256, 384, 4, jnp.bfloat16),   # two row tiles, bfloat16
], ids=["piece", "step", "bf16"])
def test_kernel_route_equals_ragged_dot(m, k, n, groups, dtype):
    x, w, sizes, used = _case(m, k, n, groups, dtype)
    before = GM.route_counts()
    got = GM.grouped_matmul(x, w, sizes, interpret=True)
    want = jax.lax.ragged_dot(x, w, group_sizes=sizes)
    assert got.dtype == want.dtype == dtype and got.shape == (m, n)
    # rows past the last group belong to none: undefined, and masked
    # by whoever calls (parallel/moe.held_experts_ffn)
    np.testing.assert_allclose(
        np.asarray(got[:used], np.float32),
        np.asarray(want[:used], np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-4)
    assert GM.route_counts()["pallas"] == before["pallas"] + 1


def test_cpu_takes_the_xla_route():
    x, w, sizes, _ = _case(256, 32, 24, 8, jnp.float32)
    before = GM.route_counts()
    assert not GM.kernel_eligible(256, 32, 24)
    GM.grouped_matmul(x, w, sizes)
    assert GM.route_counts()["xla"] == before["xla"] + 1


def test_tiles_at_the_served_shapes(monkeypatch):
    """deepseek-v2-lite: 3 072 pairs a piece, 96 a step, (2048, 1408)
    up and (1408, 2048) down; trinity: 2 048 and 128, (3072, 3072)."""
    assert GM.tiling(3072, 2048, 1408) == (256, 512, 1408)
    assert GM.tiling(3072, 1408, 2048) == (128, 1408, 1024)
    assert GM.tiling(96, 2048, 1408) == (96, 512, 1408)
    assert GM.tiling(2048, 3072, 3072) == (128, 512, 1024)
    assert GM.tiling(128, 3072, 3072) == (128, 512, 1024)
    assert GM.tiling(100, 2048, 1408) is None       # no whole row tile
    assert GM.tiling(300, 2048, 1408) is None
    monkeypatch.setenv("POLYAXON_TPU_ASSUME_TPU", "1")
    assert GM.kernel_eligible(3072, 2048, 1408)
    assert not GM.kernel_eligible(100, 2048, 1408)


def test_expert_ffn_through_the_kernel_route(monkeypatch):
    """The held experts' sum with every grouped matmul on the kernel's
    route (interpreted), absent experts' pairs outside every group."""
    real = GM.grouped_matmul
    monkeypatch.setattr(
        "polyaxon_tpu.parallel.moe.grouped_matmul",
        lambda rows, w, sizes: real(rows, w, sizes, interpret=True))
    t, d, f, held, offset = 64, 32, 24, 4, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (t, d))
    chosen = jax.random.randint(keys[1], (t, 2), 0, 8)
    w = jax.random.uniform(keys[2], (t, 2))
    wg, wu = (jax.random.normal(k, (held, d, f)) for k in keys[3:5])
    wd = jax.random.normal(keys[5], (held, f, d))
    want = jnp.zeros((t, d))
    for e in range(held):
        w_e = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1,
                      keepdims=True)
        want += w_e * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    # a fresh trace: the cached one may have taken the other route
    from polyaxon_tpu.parallel import moe
    moe._grouped_ffn.cache_clear()
    got = held_experts_ffn(x, chosen, w, wg, wu, wd,
                           expert_offset=offset)
    moe._grouped_ffn.cache_clear()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
