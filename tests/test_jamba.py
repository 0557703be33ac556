"""The ``jamba`` decoder (models/jamba.py) held to its plain reference
(reference/jamba.py) at ``jamba-tiny``: three Mamba-1 layers and one
multi-query attention layer.

Every parameter is perturbed away from its init (norm scales are 1, the
skip ``D`` is 1, the convolution's bias is small: each would hide a
missing term), compute is float32.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models.jamba import JambaConfig, JambaModel
from polyaxon_tpu.models.kv_cache import cache_kinds, leaf_kinds
from polyaxon_tpu.reference import jamba as R

TINY = dataclasses.replace(JambaConfig.tiny(), dtype=jnp.float32)

# float32 on both sides, the same mathematics in another order (the
# zoo model batches, fuses the gate into the scan and carries the
# state; the reference loops over heads and positions): the largest
# difference seen over the cases below is 3e-6 on logits of order 1.
ATOL = 3e-5


def ref_cfg(cfg: JambaConfig) -> dict:
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        attn_layer_period=cfg.attn_layer_period,
        attn_layer_offset=cfg.attn_layer_offset,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
        mamba_dt_rank=cfg.mamba_dt_rank, rms_norm_eps=cfg.rms_norm_eps)


def perturbed(tree, seed: int = 2):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.1 * (1.0 if leaf.ndim == 1 else 0.3)
        * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    model = JambaModel(TINY)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                             TINY.vocab_size)
    params = perturbed(model.init(jax.random.PRNGKey(0),
                                  ids[:, :8])["params"])
    ref = R.forward(params, ids[0], ref_cfg(TINY))
    return model, {"params": params}, ids, np.asarray(ref)


def test_config_places_attention_by_period_and_offset():
    full = JambaConfig.jamba2_3b()
    assert [i for i in range(full.num_layers) if full.is_attention(i)] \
        == [7, 21]
    assert full.d_inner == 5120 and full.param_dtype == jnp.bfloat16
    assert [i for i in range(TINY.num_layers) if TINY.is_attention(i)] \
        == [1]


def test_tree_keeps_float32_where_the_recurrence_needs_it():
    """``A_log``, ``D``, ``dt_bias`` and every norm scale are declared
    float32 whatever the matrices rest in (serving/weights.py rounds a
    served tree to what the model declares)."""
    model = JambaModel(dataclasses.replace(
        JambaConfig.tiny(), param_dtype=jnp.bfloat16))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    flat = {jax.tree_util.keystr(p): l.dtype
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}
    for path, dtype in flat.items():
        f32 = any(s in path for s in ("A_log", "'D'", "dt_bias", "scale"))
        assert dtype == (jnp.float32 if f32 else jnp.bfloat16), path


def test_one_shot_forward_matches_the_reference(tiny):
    model, variables, ids, ref = tiny
    got = model.apply(variables, ids)
    np.testing.assert_allclose(got[0], ref, atol=ATOL)


def test_prefill_then_decode_matches_the_reference_forward(tiny):
    """Prefill 17 tokens, then 23 single steps through the cache: every
    step's logits against the reference's ONE forward over all 40."""
    model, variables, ids, ref = tiny
    logits, cache = G.prefill(model, variables, ids[:, :17])
    np.testing.assert_allclose(logits[0], ref[16], atol=ATOL)
    for t in range(17, 40):
        logits, cache = G.prefill(model, variables, ids[:, t:t + 1],
                                  cache=cache, position=t)
        np.testing.assert_allclose(logits[0], ref[t], atol=ATOL,
                                   err_msg=f"position {t}")


def test_generate_is_the_reference_argmax(tiny):
    model, variables, ids, _ = tiny
    out = np.asarray(G.generate(model, variables, ids[:, :9],
                                max_new_tokens=6))[0]
    for t in range(9, 15):
        ref = R.forward(variables["params"], out[:t], ref_cfg(TINY),
                        rows=[t - 1])
        assert int(np.argmax(ref[0])) == out[t]


@pytest.mark.parametrize("piece", [1, 3, 8])
def test_a_prompt_in_pieces_equals_one_piece(tiny, piece):
    """The state and the convolution's tail carried from piece to
    piece: 29 tokens in pieces of 1, 3 or 8 (and a remainder) leave the
    cache and the logits one piece of 29 leaves.  A piece shorter than
    the convolution's reach (1 < 3 taps behind) is the case that reads
    a tail made of MORE than one earlier piece."""
    model, variables, ids, ref = tiny
    whole_logits, whole = G.prefill(model, variables, ids[:, :29])
    cache, at = None, 0
    while at < 29:
        n = min(piece, 29 - at)
        logits, cache = G.prefill(model, variables, ids[:, at:at + n],
                                  cache=cache, position=at)
        at += n
    np.testing.assert_allclose(logits, whole_logits, atol=ATOL)
    np.testing.assert_allclose(logits[0], ref[28], atol=ATOL)
    for (path, a, kind), (_, b, _) in zip(leaf_kinds(cache),
                                          leaf_kinds(whole)):
        np.testing.assert_allclose(a, b, atol=ATOL,
                                   err_msg=jax.tree_util.keystr(path))
        if kind == "state":
            assert "cache_index" not in jax.tree_util.keystr(path)


def test_cache_holds_state_leaves_without_a_position_axis(tiny):
    model = tiny[0]
    assert cache_kinds(model) == ("full", "state")
    cache = G.init_cache(model, 1)
    kinds = {jax.tree_util.keystr(p): (leaf.shape, kind)
             for p, leaf, kind in leaf_kinds(cache)}
    assert kinds["['h_0']['mamba']['ssm_state']"] == ((1, 4, 64), "state")
    assert kinds["['h_0']['mamba']['conv_tail']"] == ((1, 3, 64), "state")
    assert kinds["['h_1']['attn']['cached_key']"] == ((1, 64, 1, 8), "full")
    assert "['h_0']['mamba']['cache_index']" not in kinds
    # A longer context lengthens the planes and no state leaf.
    longer = G.init_cache(JambaModel(dataclasses.replace(
        TINY, max_position=128)), 1)
    for (path, a, kind), (_, b, _) in zip(leaf_kinds(cache),
                                          leaf_kinds(longer)):
        assert (a.shape == b.shape) == (
            kind == "state" or "cache_index" in
            jax.tree_util.keystr(path))


def test_a_state_cannot_be_rewound(tiny):
    model = tiny[0]
    with pytest.raises(ValueError, match="cannot be rewound"):
        G._rollback_cache(G.init_cache(model, 1), 3)


def test_zoo_entry_has_a_finite_loss_and_gradients():
    """``jamba-tiny`` through the registry's loss: the ``lax.scan`` form
    of the recurrence differentiates (the kernel is forward only)."""
    from polyaxon_tpu.models.registry import get_model

    spec = get_model("jamba-tiny")
    model, variables = spec.init_params(batch_size=2)
    (loss, _), grads = jax.value_and_grad(
        spec.loss_fn(model), has_aux=True)(
        variables, spec.make_batch(2), jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves and all(np.isfinite(np.asarray(g)).all() and
                          float(jnp.abs(g).max()) > 0 for g in leaves)
