"""The ``deepseek_v2`` decoder (models/deepseek_v2.py) held to its plain
reference (reference/deepseek_v2.py) at ``deepseek-v2-tiny``: one dense
and two expert layers, latent attention in every one.

The reference always expands every latent to a key and a value a head;
the model does that for a call of more than one position and attends
over the latents as they lie for a call of one, so the decode cases
here hold the ABSORBED path to an independent formulation.  Every
parameter is perturbed away from its init (norm scales are 1: each
would hide a missing term), compute is float32.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import deepseek_v2 as D
from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                             DeepseekV2Model)
from polyaxon_tpu.models.kv_cache import (cache_kinds, full_planes,
                                          leaf_kinds)
from polyaxon_tpu.ops.attention import route_counts
from polyaxon_tpu.ops.rotary import (yarn_correction_range, yarn_inv_freq,
                                     yarn_mscale)
from polyaxon_tpu.parallel.moe import softmax_topk_route
from polyaxon_tpu.reference import deepseek_v2 as R

TINY = dataclasses.replace(DeepseekV2Config.tiny(), dtype=jnp.float32)

# float32 on both sides, the same mathematics in another order: the
# largest difference seen over the cases below is 4e-6 on logits of
# order 4.
ATOL = 4e-5


def ref_cfg(cfg: DeepseekV2Config) -> dict:
    return dict(
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.first_k_dense,
        num_attention_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=False,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta,
        rope_scaling=dict(
            factor=cfg.rope_factor,
            original_max_position_embeddings=cfg
            .rope_original_max_position,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale,
            mscale_all_dim=cfg.rope_mscale_all_dim),
        rms_norm_eps=cfg.rms_norm_eps)


def perturbed(tree, seed: int = 2):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.1 * (1.0 if leaf.ndim == 1 else 0.3)
        * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def built(cfg):
    model = DeepseekV2Model(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                             cfg.vocab_size)
    params = perturbed(model.init(jax.random.PRNGKey(0),
                                  ids[:, :8])["params"])
    ref = R.forward(params, ids[0], ref_cfg(cfg),
                    experts_held=cfg.experts_held,
                    expert_offset=cfg.expert_offset)
    return model, {"params": params}, ids, np.asarray(ref)


@pytest.fixture(scope="module")
def tiny():
    return built(TINY)


def test_served_cut_keeps_the_published_widths():
    full = DeepseekV2Config.v2_lite_stage0()
    assert (full.hidden_size, full.num_heads, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim,
            full.v_head_dim) == (2048, 16, 512, 128, 64, 128)
    assert (full.num_experts, full.experts_held,
            full.num_experts_per_tok, full.n_shared_experts,
            full.moe_intermediate_size, full.intermediate_size) \
        == (64, 64, 6, 2, 1408, 10944)
    assert (full.num_layers, full.first_k_dense, full.vocab_size) \
        == (7, 1, 102400)
    assert full.latent_width == 576
    assert full.param_dtype == jnp.bfloat16
    # s = 192^-1/2 m^2, m = 0.1 * 0.707 * ln 40 + 1 = 1.2608
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    assert abs(full.softmax_scale - m * m / math.sqrt(192)) < 1e-9
    assert full.rope_table_scale == 1.0


def test_tree_keeps_float32_for_norms_and_router():
    model = DeepseekV2Model(dataclasses.replace(
        DeepseekV2Config.tiny(), param_dtype=jnp.bfloat16))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    flat = {jax.tree_util.keystr(p): l.dtype
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert any("kv_b_proj" in p for p in flat)
    for path, dtype in flat.items():
        f32 = any(s in path for s in ("router_kernel", "scale"))
        assert dtype == (jnp.float32 if f32 else jnp.bfloat16), path


# -- YaRN -------------------------------------------------------------------


def test_yarn_frequencies_are_the_closed_form_at_the_published_values():
    """64 rope dims, theta 10 000, factor 40 over an original 4 096,
    beta 32 and 1: the ramp runs from pair 10 to pair 23."""
    low, high = yarn_correction_range(64, 10000.0, 4096, 32, 1)
    assert (low, high) == (10, 23)
    got = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    assert got.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)     # kept
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13.0
    np.testing.assert_allclose(
        got[16], f[16] * (1 - ramp) + f[16] / 40 * ramp, rtol=1e-6)
    assert np.all(np.diff(got) < 0)
    # ...and the reference's own writing of it agrees.
    np.testing.assert_allclose(got, R.yarn_frequencies(
        64, 10000.0, dict(factor=40, original_max_position_embeddings=4096,
                          beta_fast=32, beta_slow=1)), rtol=1e-6)
    assert yarn_mscale(1.0, 0.707) == 1.0


def test_yarn_at_the_tiny_preset_blends_between_its_ends():
    """Original 8, factor 4, 2 pairs: cd(32) < 0 clips low to 0, the
    ramp ends at pair ``high``; pair 0 keeps its frequency."""
    low, high = yarn_correction_range(4, 10000.0, 8, 32, 1)
    assert low == 0 and high >= 1
    got = yarn_inv_freq(4, 10000.0, 4.0, 8, 32, 1)
    assert got[0] == 1.0
    assert 0.01 / 4 <= got[1] <= 0.01


# -- the route --------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3])
def test_route_is_a_plain_softmax_top_k(k):
    x = jax.random.normal(jax.random.PRNGKey(3), (13, 32))
    w = jax.random.normal(jax.random.PRNGKey(4), (32, 8))
    chosen, weights = softmax_topk_route(x, w, k, scale=1.5)
    g = np.asarray(jax.nn.softmax(
        np.asarray(x, np.float64) @ np.asarray(w, np.float64), axis=-1))
    order = np.argsort(-g, axis=-1)[:, :k]
    assert np.array_equal(np.asarray(chosen), order)
    want = np.take_along_axis(g, order, axis=-1)
    assert np.all(want.sum(-1) < 1.0)        # NOT renormalised
    np.testing.assert_allclose(weights, 1.5 * want, rtol=2e-5)


# -- model against reference ------------------------------------------------


def test_one_shot_forward_matches_the_reference(tiny):
    model, variables, ids, ref = tiny
    got = model.apply(variables, ids)
    np.testing.assert_allclose(got[0], ref, atol=ATOL)


def test_a_share_of_the_experts_matches_the_reference():
    """Experts 4-7 of 8 held: what the absent ones would add is left
    out on both sides."""
    cfg = dataclasses.replace(TINY, experts_held=4, expert_offset=4)
    model, variables, ids, ref = built(cfg)
    np.testing.assert_allclose(model.apply(variables, ids)[0], ref,
                               atol=ATOL)
    whole = built(TINY)[3]
    assert np.abs(whole - ref).max() > 1e-3


@pytest.mark.parametrize("chunk", [None, 8, 5])
def test_prefill_then_decode_matches_the_reference_forward(tiny, chunk):
    """Prefill 17 tokens (one piece, or pieces of 8 or 5: the expanded
    path over rows an earlier piece wrote), then 23 single steps
    through the cache (the absorbed path): every step's logits against
    the reference's ONE materialised forward over all 40."""
    model, variables, ids, ref = tiny
    before = route_counts()
    logits, cache = G.prefill(model, variables, ids[:, :17], chunk=chunk)
    np.testing.assert_allclose(logits[0], ref[16], atol=ATOL)
    for t in range(17, 40):
        logits, cache = G.prefill(model, variables, ids[:, t:t + 1],
                                  cache=cache, position=t)
        np.testing.assert_allclose(logits[0], ref[t], atol=ATOL,
                                   err_msg=f"position {t}")
    after = route_counts()
    assert after["latent_expanded"] > before["latent_expanded"]
    assert after["latent_absorbed"] > before["latent_absorbed"]


def test_generate_is_the_reference_argmax(tiny):
    model, variables, ids, _ = tiny
    out = np.asarray(G.generate(model, variables, ids[:, :9],
                                max_new_tokens=3))[0]
    for t in range(9, 12):
        ref = R.forward(variables["params"], out[:t], ref_cfg(TINY),
                        experts_held=8, rows=[t - 1])
        assert int(np.argmax(ref[0])) == out[t]


@pytest.mark.parametrize("piece", [1, 3, 8])
def test_a_prompt_in_pieces_equals_one_piece(tiny, piece):
    """29 tokens in pieces of 1 (every one absorbed), 3 or 8 (and a
    remainder) leave the plane and the logits one piece of 29 leaves."""
    model, variables, ids, ref = tiny
    whole_logits, whole = G.prefill(model, variables, ids[:, :29])
    cache, at = None, 0
    while at < 29:
        n = min(piece, 29 - at)
        logits, cache = G.prefill(model, variables, ids[:, at:at + n],
                                  cache=cache, position=at)
        at += n
    np.testing.assert_allclose(logits, whole_logits, atol=ATOL)
    for (path, a, kind), (_, b, _) in zip(leaf_kinds(cache),
                                          leaf_kinds(whole)):
        assert kind == "latent"
        np.testing.assert_allclose(a, b, atol=ATOL, err_msg=str(path))


# -- the two paths over one plane -------------------------------------------


def test_absorbed_equals_expanded_on_the_same_plane():
    """The two attention functions over the SAME rows, queries and
    expansion: one query a lane and several, causal masks included."""
    cfg = TINY
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    b, s, t = 2, 3, 11
    q_nope = jax.random.normal(k[0], (b, s, cfg.num_heads,
                                      cfg.qk_nope_head_dim))
    q_pe = jax.random.normal(k[1], (b, s, cfg.num_heads,
                                    cfg.qk_rope_head_dim))
    rows = jax.random.normal(k[2], (b, t, cfg.latent_width))
    w_kvb = jax.random.normal(k[3], (
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim))
    pos = (t - s) + jnp.arange(s)
    allowed = (jnp.arange(t)[None, :] <= pos[:, None])[None, None]
    args = (q_nope, q_pe, rows, w_kvb, allowed, cfg)
    with jax.default_matmul_precision("highest"):
        a, e = D.absorbed_attention(*args), D.expanded_attention(*args)
    assert a.shape == (b, s, cfg.num_heads * cfg.v_head_dim)
    np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-5)
    assert D.takes_absorbed(1) and not D.takes_absorbed(2)


def test_a_step_forced_through_the_expanded_path_gives_the_same_logits(
        tiny, monkeypatch):
    """One decode step over a prefilled plane by the absorbed path (the
    model's rule) and, the rule turned off, by the expanded one."""
    model, variables, ids, _ = tiny
    _, cache = G.prefill(model, variables, ids[:, :20])
    step = lambda: G.prefill(  # noqa: E731
        model, variables, ids[:, 20:21], cache=cache, position=20)[0]
    absorbed = step()
    before = route_counts()["latent_expanded"]
    monkeypatch.setattr(D, "takes_absorbed", lambda positions: False)
    expanded = step()
    assert route_counts()["latent_expanded"] == before + TINY.num_layers
    np.testing.assert_allclose(absorbed, expanded, atol=ATOL)


# -- the cache tree ---------------------------------------------------------


def test_cache_holds_one_latent_plane_a_layer_and_nothing_else(tiny):
    model, variables, ids, _ = tiny
    assert cache_kinds(model) == ("latent",)
    _, cache = G.prefill(model, variables, ids[:, :9])
    found = {jax.tree_util.keystr(p): (leaf.shape, kind)
             for p, leaf, kind in leaf_kinds(cache)}
    assert found == {
        f"['h_{i}']['attn']['{name}']": (shape, "latent")
        for i in range(TINY.num_layers)
        for name, shape in (("cache_index", ()),
                            ("cached_latent", (1, 64, 20)))}
    # rank 16 + rope 4 = 20 numbers a position a layer; K and V a head
    # would be 4 x (12 + 8) = 80.
    plane_bytes = sum(leaf.nbytes for _, leaf, _ in leaf_kinds(cache)
                      if leaf.ndim)
    assert plane_bytes == TINY.num_layers * 64 * 20 * 4
    assert full_planes(cache) == full_planes(cache, "latent") \
        == {(64, False): TINY.num_layers}
    assert full_planes(cache, "full") == {}


def test_the_cached_rope_key_is_stored_rotated(tiny):
    """Position p of the plane holds ``[RMSNorm(c) | rot_p(k_pe)]``:
    the rope part of a row differs with its position, the latent part
    does not."""
    model, variables, ids, _ = tiny
    same = jnp.full((1, 6), 7, jnp.int32)
    _, cache = G.prefill(model, variables, same)
    plane = np.asarray(cache["h_0"]["attn"]["cached_latent"])[0]
    r = TINY.kv_lora_rank
    np.testing.assert_allclose(plane[0, :r], plane[5, :r], atol=1e-6)
    assert np.abs(plane[0, r:] - plane[5, r:]).max() > 1e-3
    assert not plane[6:].any()


def test_rollback_rewinds_a_latent_plane_by_its_index(tiny):
    """A speculative slot's rewind: rows past the index are stale and
    masked, the next append overwrites them."""
    model, variables, ids, ref = tiny
    _, cache = G.prefill(model, variables, ids[:, :20])
    cache = G._rollback_cache(cache, 12)
    logits, _ = G.prefill(model, variables, ids[:, 12:15], cache=cache,
                          position=12)
    np.testing.assert_allclose(logits[0], ref[14], atol=ATOL)
