"""The ``afmoe`` decoder (models/afmoe.py) held to its plain reference
(reference/afmoe.py) at ``afmoe-tiny``: window 8, 8 experts top 2, the
second of 2 shares of 4, four window layers and a full one.

Every parameter is perturbed away from its init (norm scales are 1 and
would hide a missing norm), compute is float32, and contexts pass the
window and the ring so that ring and full layers differ.
"""

import base64
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models.afmoe import AfmoeConfig, AfmoeModel, AfmoeMoE
from polyaxon_tpu.parallel.moe import (held_experts_ffn, held_pair_counts,
                                       sigmoid_topk_route)
from polyaxon_tpu.reference import afmoe as R

TINY = dataclasses.replace(AfmoeConfig.tiny(), dtype=jnp.float32)


def ref_cfg(cfg: AfmoeConfig) -> dict:
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window,
        layer_types=list(cfg.layer_types),
        num_dense_layers=cfg.num_dense_layers,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        route_scale=cfg.route_scale, route_norm=cfg.route_norm,
        mup_enabled=cfg.mup_enabled)


def perturbed(tree, seed: int = 2):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.1 * (1.0 if leaf.ndim == 1 else 0.3)
        * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    model = AfmoeModel(TINY)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                             TINY.vocab_size)
    params = perturbed(model.init(jax.random.PRNGKey(0),
                                  ids[:, :8])["params"])
    ref = R.forward(params, ids[0], ref_cfg(TINY),
                    experts_held=TINY.experts_held,
                    expert_offset=TINY.expert_offset)
    return model, {"params": params}, ids, np.asarray(ref)


@pytest.mark.parametrize("length", [5, 9, 40])
def test_forward_matches_the_reference(tiny, length):
    """No cache: within the window, one past it, far past it."""
    model, variables, ids, ref = tiny
    out = model.apply(variables, ids[:, :length])
    np.testing.assert_allclose(out[0], ref[:length], atol=2e-5)


@pytest.mark.parametrize("prompt,chunk", [(12, 4), (12, None), (5, None),
                                          (21, 5), (20, 16)])
def test_prefill_then_decode_matches_the_reference(tiny, prompt, chunk):
    """Prefill (chunks of 4 and 5 are written into the ring before
    they are read; 12, 16 and 20 at once take the copying path) then
    one-token steps to position 39: ring capacity 12, window 8."""
    model, variables, ids, ref = tiny
    logits, cache = G.prefill(model, variables, ids[:, :prompt],
                              chunk=chunk)
    np.testing.assert_allclose(logits[0], ref[prompt - 1], atol=2e-5)
    for t in range(prompt, 40):
        out, mut = model.apply({**variables, "cache": cache},
                               ids[:, t:t + 1], decode=True,
                               mutable=["cache"])
        cache = mut["cache"]
        np.testing.assert_allclose(out[0, 0], ref[t], atol=2e-5,
                                   err_msg=f"position {t}")


# Chunks of 4 at cache index 0, in the middle and as the last before
# ``max_position`` (64: the full layer's plane is read to 8, 16, 32 or
# 64 rows, so 4 and 24 rows end inside a width and 40 crosses into the
# last).
@pytest.mark.parametrize("filled", [0, 20, 36, 60])
def test_a_chunk_read_to_its_extent_equals_the_chunk_read_whole(filled):
    """The engine's prefill programs read the full layer's plane only
    as far as the chunk has written it: the same logits and the same
    cache, rings and plane, as the chunk that reads the plane whole."""
    model = AfmoeModel(TINY)
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 64), 0,
                             TINY.vocab_size)
    variables = {"params": perturbed(model.init(
        jax.random.PRNGKey(0), ids[:, :8])["params"])}
    ptpu_prefill, ptpu_extend = G.prefill_programs(model)
    chunk = ids[:, filled:filled + 4]
    if not filled:
        # from position 0 the extent is the prompt's length, known
        # while tracing: one static width, nothing to branch on
        assert "cond" not in str(jax.make_jaxpr(ptpu_prefill)(
            variables, chunk))
        got = ptpu_prefill(variables, chunk)
        want = G.prefill(model, variables, chunk, with_stats=True)
    else:
        _, cache = G.prefill(model, variables, ids[:, :filled], chunk=4)
        assert "cond" in str(jax.make_jaxpr(ptpu_extend)(
            variables, cache, chunk, filled))
        got = ptpu_extend(variables, cache, chunk, filled)
        want = G.prefill(model, variables, chunk, cache=cache,
                         position=filled, with_stats=True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert np.array_equal(got[2], want[2])      # the pairs sown


def test_a_rollback_inside_the_prefix_stays_masked(tiny):
    """24 tokens cached, the index rewound to 20 (a speculative
    round's rejection; the rings' slack of 4 allows no more), then a
    chunk of 2: rows 22 and 23 stay stale and lie INSIDE the width the
    chunk reads (24 of 64).  Its logits are those of a cache that never
    held the rejected tokens, bounded or whole."""
    model, variables, ids, _ = tiny
    _, ptpu_extend = G.prefill_programs(model)
    _, cache = G.prefill(model, variables, ids[:, :24], chunk=4)
    other = (ids[:, 30:32] + 1) % TINY.vocab_size
    rewound = G._rollback_cache(cache, 20)
    got, _, _ = ptpu_extend(variables, rewound, other, 20)
    whole, _ = G.prefill(model, variables, other, cache=rewound,
                         position=20)
    _, clean = G.prefill(model, variables, ids[:, :20], chunk=4)
    want, _ = G.prefill(model, variables, other, cache=clean,
                        position=20)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got, whole, rtol=1e-6, atol=1e-6)


def test_the_cache_holds_rings_and_a_plane(tiny):
    model = tiny[0]
    cache = G.init_cache(model, 1)
    ring = TINY.sliding_window + TINY.kv_ring_chunk
    for i, kind in enumerate(TINY.layer_types):
        leaves = cache[f"h_{i}"]["attn"]
        if kind == "sliding_attention":
            assert leaves["cached_key"].shape[1] == ring
            assert leaves["cached_pos"].shape == (ring,)
        else:
            assert leaves["cached_key"].shape[1] == TINY.max_position
            assert "cached_pos" not in leaves


# -- the expert layer ---------------------------------------------------------


def _moe_params(cfg, seed=3):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 24,
                                                     cfg.hidden_size))
    layer = AfmoeMoE(cfg)
    return layer, perturbed(layer.init(jax.random.PRNGKey(0),
                                       x)["params"]), x


def test_shares_add_up_to_the_uncut_layer():
    """What both shares give, the shared expert counted once, is what
    the uncut reference gives for the whole layer."""
    whole = dataclasses.replace(TINY, experts_held=8, expert_offset=0)
    _, params, x = _moe_params(whole)
    uncut = R.moe_ffn(params, x[0], ref_cfg(whole), 8, 0)
    shared = R.swiglu(x[0], params["shared"]["gate_proj"]["kernel"],
                      params["shared"]["up_proj"]["kernel"],
                      params["shared"]["down_proj"]["kernel"])
    total = shared
    for offset in (0, 4):
        cfg = dataclasses.replace(TINY, experts_held=4,
                                  expert_offset=offset)
        part = {**params, **{
            k: params[k][offset:offset + 4]
            for k in ("experts_gate", "experts_up", "experts_down")}}
        y = AfmoeMoE(cfg).apply({"params": part}, x)[0]
        np.testing.assert_allclose(
            y, R.moe_ffn(part, x[0], ref_cfg(cfg), 4, offset),
            atol=2e-5)
        total = total + (y - shared)
    np.testing.assert_allclose(total, uncut, atol=5e-5)


@pytest.mark.parametrize("normalize", [True, False])
def test_choice_by_score_plus_bias_weights_by_score(normalize):
    """A bias large enough to change the choice: the chosen set is the
    top-k of s + b, the weights are functions of s alone."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k1, (16, 32))
    router = jax.random.normal(k2, (32, 8))
    bias = 0.5 * jax.random.normal(k3, (8,))
    chosen, w = sigmoid_topk_route(x, router, bias, 2, scale=2.448,
                                   normalize=normalize)
    s = np.asarray(jax.nn.sigmoid(x @ router))
    by_sum = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    by_score = np.argsort(-s, axis=-1)[:, :2]
    assert (np.sort(chosen, -1) == np.sort(by_sum, -1)).all()
    assert (np.sort(by_sum, -1) != np.sort(by_score, -1)).any()
    picked = np.take_along_axis(s, np.asarray(chosen), axis=-1)
    want = picked / picked.sum(-1, keepdims=True) if normalize \
        else picked
    np.testing.assert_allclose(w, 2.448 * want, rtol=1e-5)


@pytest.mark.parametrize("vmapped", [False, True],
                         ids=["flat", "vmapped-lanes"])
def test_grouped_ffn_drops_no_held_pair(vmapped):
    """Every pair on a held expert is computed (all four choices may
    fall here), under vmap as one grouped matmul over the lanes."""
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    t, d, f, held, offset = 12, 16, 24, 4, 4
    x = jax.random.normal(keys[0], (t, d))
    chosen = jax.random.randint(keys[1], (t, 2), 0, 8)
    chosen = chosen.at[0].set(jnp.array([4, 5]))    # both held
    w = jax.random.uniform(keys[2], (t, 2))
    wg, wu = (jax.random.normal(k, (held, d, f)) for k in keys[3:5])
    wd = jax.random.normal(keys[5], (held, f, d))
    want = jnp.zeros((t, d))
    for e in range(held):
        w_e = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1,
                      keepdims=True)
        want += w_e * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    if vmapped:
        got = jax.jit(jax.vmap(lambda a, b, c: held_experts_ffn(
            a[None], b[None], c[None], wg, wu, wd,
            expert_offset=offset)[0]))(x, chosen, w)
    else:
        got = held_experts_ffn(x, chosen, w, wg, wu, wd,
                               expert_offset=offset)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    counts = held_pair_counts(chosen, held, offset)
    assert int(counts.sum()) == int(((chosen >= 4) & (chosen < 8)).sum())


# -- through the server -------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """``afmoe-tiny`` behind a ModelServer as ``ptpu serve`` builds it
    (float32 compute so that the comparison is tight): one greedy and
    one sampled request with their logits."""
    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.serving import ModelServer

    model, variables = get_model("afmoe-tiny").init_params(
        batch_size=1, dtype=jnp.float32)
    variables = {"params": perturbed(variables["params"])}
    ms = ModelServer(model, variables, model_name="afmoe-tiny",
                     n_slots=4, prefill_chunk=4, decode_window=8)
    prompt = np.random.RandomState(0).randint(0, 256, (1, 20)).tolist()
    replies = [ms.generate({"prompt": prompt, "max_new_tokens": 12,
                            "logits": True, **extra})
               for extra in ({}, {"temperature": 0.8, "seed": 3})]
    info = ms.info()
    metrics = ms.metrics_text()
    ms.close()
    return model, variables, prompt[0], replies, info, metrics


@pytest.mark.parametrize("which", [0, 1], ids=["greedy", "sampled"])
def test_slot_pool_logits_match_the_reference(served, which):
    """Chunked prefill, insertion into the pool, decode through the
    pool's programs to position 31 (ring 12, window 8): every token's
    logits against the reference's full forward."""
    model, variables, prompt, replies, _, _ = served
    reply = replies[which]
    field = reply["logits"]
    got = np.frombuffer(base64.b64decode(field["b64"]),
                        "<f4").reshape(field["shape"])
    new = reply["new_tokens"][0]
    assert got.shape == (len(new), model.cfg.vocab_size)
    ref = R.forward(variables["params"],
                    np.asarray(prompt + new[:-1]), ref_cfg(model.cfg),
                    experts_held=4, expert_offset=4)
    np.testing.assert_allclose(got, np.asarray(ref)[len(prompt) - 1:],
                               atol=5e-5)
    if not which:
        assert new == [int(t) for t in got.argmax(-1)]


def test_pool_with_two_leaf_kinds_stays_in_place(served):
    info = served[4]
    assert info["kv_pool_dispatches_total"] > 0
    assert info["kv_pool_in_place_total"] == \
        info["kv_pool_dispatches_total"]
    kinds = info["kv_pool_bytes_by_kind"]
    assert kinds["window"] > 0 and kinds["full"] > 0
    assert kinds["window"] + kinds["full"] == info["kv_pool_bytes"]
    assert info["routing"]["greedy"] == info["routing"]["sampled"] \
        == "engine" and info["solo_fallbacks"] == {}


def test_pair_counters_count_prefill_and_decode(served):
    """2 requests x 20 prompt tokens, 11 decode steps each over 4
    lanes (idle lanes step too), 2 choices a token, 4 expert layers."""
    info, metrics = served[4], served[5]
    assert info["prefill_tokens_total"] == 40
    tokens = 40 + info["decode_steps_total"] * info["slots"]
    assert info["moe_pairs_routed_total"] == tokens * 2 * 4
    assert sum(info["moe_expert_pairs"]) == info["moe_pairs_held_total"]
    assert 0 < info["moe_pairs_held_total"] < info["moe_pairs_routed_total"]
    # 4 held experts x 4 expert layers at most, a piece or a step.
    runs = info["prefill_chunks_total"] + info["decode_steps_total"]
    assert 0 < info["moe_experts_touched_total"] <= min(
        info["moe_pairs_held_total"], 4 * 4 * runs)
    assert "ptpu_serving_moe_experts_touched_total" in metrics
    assert "ptpu_serving_moe_pairs_held_total" in metrics
    assert 'ptpu_serving_kv_pool_bytes_by_kind{kind="window"}' in metrics


@pytest.mark.parametrize("lanes", [1, 3], ids=["a_piece", "pool_lanes"])
def test_experts_touched_are_counted_a_layer_over_all_lanes(tiny, lanes):
    """The last entry of the sown total: (layer, held expert) with at
    least one pair in ONE program run — a prefill piece, or a decode
    step whose lanes' pairs are summed BEFORE they are counted (a
    layer's grouped matmul runs once for the pool) — against the
    layers' own vectors."""
    model, variables, ids, _ = tiny
    held = model.cfg.experts_held

    def sown(tokens):
        _, mut = model.apply(variables, tokens, mutable=[G.STATS])
        return mut[G.STATS]

    if lanes == 1:
        layers = [np.asarray(v).reshape(-1, held + 1).sum(0)
                  for v in jax.tree.leaves(sown(ids[:, :6]))]
        got = G.prefill(model, variables, ids[:, :6], with_stats=True)[2]
    else:
        per_lane = [jax.tree.leaves(sown(ids[:, t:t + 1]))
                    for t in range(lanes)]
        layers = [sum(np.asarray(v).reshape(-1, held + 1).sum(0)
                      for v in layer) for layer in zip(*per_lane)]
        rows = jax.vmap(lambda t: G.stats_rows(sown(t[None, None])))(
            ids[0, :lanes])
        got = G.stats_total(rows.sum(axis=0))
    want = sum(int(np.count_nonzero(v[:-1])) for v in layers)
    assert len(layers) == 4 and 0 < want <= 4 * held
    assert int(got[-1]) == want
    assert np.array_equal(got[:-1], sum(layers))


def test_plane_rows_count_chunks_to_their_extent_and_steps_whole(served):
    """2 requests x 5 chunks of 4: the full layer's plane (64 rows, read
    to 8, 16, 32 or 64) handed to the extents 4..20 as 8, 8, 16, 16, 32
    rows.
    The pool's step reads it whole: it is its layer's own variable
    (kv_cache.narrows)."""
    info, metrics = served[4], served[5]
    steps = info["decode_steps_total"] * info["slots"] * 64
    assert info["kv_plane_rows_read_total"] == 2 * 80 + steps
    assert info["kv_plane_rows_held_total"] == 2 * 5 * 64 + steps
    assert "ptpu_serving_kv_plane_rows_read_total" in metrics
    assert "ptpu_serving_kv_plane_rows_held_total" in metrics


@pytest.mark.parametrize("option", ["paged", "mesh"])
def test_paged_and_mesh_refuse_a_mixed_cache(option):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    args = ["serve", "--model", "afmoe-tiny", "--cpu"] + (
        ["--kv-paged"] if option == "paged" else ["--mesh", "tp=1"])
    result = CliRunner().invoke(cli, args)
    assert result.exit_code != 0
    assert "two kinds of KV cache" in result.output
    assert result.output.count("\n") <= 3


def test_a_failed_group_drops_its_prefilled_cache(tiny):
    """engine._fail_group: the failed stream's lanes go at once."""
    from polyaxon_tpu.serving.engine import DecodeEngine

    model, variables, ids, _ = tiny
    engine = DecodeEngine(model, variables, autostart=False)
    group = engine.submit(np.asarray(ids[:, :8]), 4, None, None)
    stream = group.streams[0]
    engine.tick()                   # prefills; no slot taken yet or one
    if stream.cache is None:        # admitted already: give it lanes
        stream.cache = G.init_cache(model, 1)
    stream.logits = jnp.zeros((1, 4))
    engine._fail_group(group, RuntimeError("boom"))
    assert stream.cache is None and stream.logits is None \
        and stream.d_cache is None
    assert group.event.is_set()
    engine.close()
