"""ops/selective_scan.py: the Pallas kernel (under the interpreter)
held to the ``lax.scan`` form, and both to a loop written out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.ops import selective_scan as S

# float32 on both sides; the kernel updates a state at a time where the
# scan multiplies whole arrays, and sums the N products in another
# order: 4e-6 is the largest difference seen, on outputs of order 1-10.
TOL = dict(rtol=2e-5, atol=2e-5)


def operands(b, length, d, n, seed=0, zero_state=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(k[0], (b, length, d))
    delta = jax.nn.softplus(jax.random.normal(k[1], (b, length, d)) - 3)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                          (n, d)) * jnp.exp(
        0.1 * jax.random.normal(k[2], (n, d)))
    bm = jax.random.normal(k[3], (b, length, n))
    cm = jax.random.normal(k[4], (b, length, n))
    skip = 1.0 + 0.1 * jax.random.normal(k[5], (d,))
    h0 = jnp.zeros((b, n, d)) if zero_state \
        else jax.random.normal(k[6], (b, n, d))
    z = jax.random.normal(jax.random.fold_in(k[6], 1), (b, length, d))
    return u, delta, a, bm, cm, skip, h0, z


def written_out(u, delta, a, bm, cm, skip, h0, z=None):
    """The recurrence as numpy loops over batch and position."""
    u, delta, a, bm, cm, skip, h = (np.asarray(x, np.float64) for x in
                                    (u, delta, a, bm, cm, skip, h0))
    y = np.zeros(u.shape)
    h = h.copy()
    for i in range(u.shape[0]):
        for t in range(u.shape[1]):
            h[i] = np.exp(delta[i, t][None, :] * a) * h[i] \
                + (delta[i, t] * u[i, t])[None, :] * bm[i, t][:, None]
            y[i, t] = (h[i] * cm[i, t][:, None]).sum(0) + skip * u[i, t]
    if z is not None:
        z = np.asarray(z, np.float64)
        y = y * z / (1.0 + np.exp(-z))
    return y, h


def test_scan_form_is_the_recurrence_written_out():
    args = operands(2, 11, 24, 4)
    y, h = S.selective_scan_xla(*args)
    want_y, want_h = written_out(*args)
    np.testing.assert_allclose(y, want_y, **TOL)
    np.testing.assert_allclose(h, want_h, **TOL)
    y, _ = S.selective_scan_xla(*args[:-1])      # ungated
    np.testing.assert_allclose(y, written_out(*args[:-1])[0], **TOL)


def test_one_step_is_a_scan_of_one():
    u, delta, a, bm, cm, skip, h0, _ = operands(2, 1, 24, 4)
    y, h = S.selective_step(u[:, 0], delta[:, 0], a, bm[:, 0], cm[:, 0],
                            skip, h0)
    want_y, want_h = S.selective_scan_xla(u, delta, a, bm, cm, skip, h0)
    np.testing.assert_allclose(y, want_y[:, 0], **TOL)
    np.testing.assert_allclose(h, want_h, **TOL)


@pytest.mark.parametrize("length,block", [(16, 8), (20, 8), (5, 8),
                                          (37, 16)],
                         ids=["whole-blocks", "ragged", "one-short-block",
                              "ragged-16"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
def test_kernel_matches_the_scan_form(length, block, gated):
    """A non-zero ``h0``; lengths the block divides and lengths it does
    not (the padding is ``delta = 0``: the state it leaves is exact)."""
    args = operands(2, length, 2048, 16, seed=length)
    if not gated:
        args = args[:-1]
    y, h = S.selective_scan_pallas(*args, block_l=block, interpret=True)
    want_y, want_h = S.selective_scan_xla(*args)
    assert y.shape == want_y.shape and h.shape == want_h.shape
    np.testing.assert_allclose(y, want_y, **TOL)
    np.testing.assert_allclose(h, want_h, **TOL)


def test_state_of_one_call_feeds_the_next():
    """Two pieces through the kernel, the first's ``h_L`` the second's
    ``h0``, equal one scan over both (and the kernel over both)."""
    u, delta, a, bm, cm, skip, h0, z = operands(1, 24, 1024, 16, seed=5)
    cut = 10
    first = [x[:, :cut] for x in (u, delta)] + [a] \
        + [x[:, :cut] for x in (bm, cm)] + [skip, h0, z[:, :cut]]
    y1, h1 = S.selective_scan_pallas(*first, block_l=8, interpret=True)
    second = [x[:, cut:] for x in (u, delta)] + [a] \
        + [x[:, cut:] for x in (bm, cm)] + [skip, h1, z[:, cut:]]
    y2, h2 = S.selective_scan_pallas(*second, block_l=8, interpret=True)
    want_y, want_h = S.selective_scan_xla(u, delta, a, bm, cm, skip, h0, z)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), want_y, **TOL)
    np.testing.assert_allclose(h2, want_h, **TOL)


def test_routes_are_counted_and_chosen_by_width(monkeypatch):
    """The one entry point: the kernel for whole channel blocks where
    there is a TPU (here: a deviceless compile's switch standing in,
    the call traced and not run), the scan form otherwise; each traced
    call counted."""
    args = operands(1, 6, 1024, 16)
    before = S.route_counts()
    want = S.selective_scan(*args)                  # a CPU: the scan
    assert S.route_counts()["xla"] == before["xla"] + 1
    monkeypatch.setenv("POLYAXON_TPU_ASSUME_TPU", "1")
    assert S.scan_eligible(1024) and not S.scan_eligible(1000)
    got = jax.eval_shape(S.selective_scan, *args)
    assert S.route_counts()["pallas"] == before["pallas"] + 1
    assert [x.shape for x in got] == [x.shape for x in want]
    S.selective_scan(*operands(1, 6, 64, 4))        # odd width: the scan
    assert S.route_counts()["xla"] == before["xla"] + 2
