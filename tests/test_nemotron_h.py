"""The ``nemotron_h`` decoder (models/nemotron_h.py) held to its plain
reference (reference/nemotron_h.py) at ``nemotron-h-tiny``: Mamba-2
layers (8 heads of 4 in 2 groups, a state of 8, blocks of 4), relu²
experts (8, top 2, the second of 2 shares of 4 held) with a shared
one, and a grouped-query attention layer without positions.

The plain forward and the served pool (prefill in pieces, then decode
through the slot pool's programs) against the reference's full
forward, the counters, the refusals.  Every parameter is perturbed
away from its init (norm scales are 1 and would hide a missing norm);
compute is float32.  The chunked form, the experts and the benchmark
configuration: tests/test_ssd.py.
"""

import base64
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
from polyaxon_tpu.reference import nemotron_h as R

TINY = dataclasses.replace(NemotronHConfig.tiny(), dtype=jnp.float32)


def ref_cfg(cfg: NemotronHConfig) -> dict:
    return dict(
        hidden_size=cfg.hidden_size, hybrid_override_pattern=cfg.pattern,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        mamba_num_heads=cfg.mamba_num_heads,
        mamba_head_dim=cfg.mamba_head_dim,
        ssm_state_size=cfg.ssm_state_size, n_groups=cfg.n_groups,
        conv_kernel=cfg.conv_kernel, layer_norm_epsilon=cfg.norm_eps,
        n_routed_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob)


def perturbed(tree, seed: int = 2):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.1 * (1.0 if leaf.ndim == 1 else 0.3)
        * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


# -- the model, and through the server ---------------------------------------


@jax.jit
def reference(params, ids):
    """The reference's logits of one sequence at ``nemotron-h-tiny``
    (one compiled program for every test's 24 positions)."""
    with jax.default_matmul_precision("highest"):
        return R.forward(params, ids, ref_cfg(TINY), experts_held=4,
                         expert_offset=4)


def test_forward_matches_the_reference():
    model = NemotronHModel(TINY)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                             TINY.vocab_size)
    params = perturbed(jax.jit(model.init)(jax.random.PRNGKey(0),
                                           ids)["params"])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)({"params": params}, ids)[0]
    np.testing.assert_allclose(got, reference(params, ids[0]),
                               rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def served():
    """``nemotron-h-tiny`` behind a ModelServer as ``ptpu serve`` builds
    it (float32 compute so that the comparison is tight): a prompt of
    13 in pieces of 4, 4, 4 and 1 (the state and the tail carried
    across them, the last piece the one-position step), then 11 decode
    steps over 3 lanes; one greedy and one sampled request with their
    logits."""
    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.serving import ModelServer

    model, variables = get_model("nemotron-h-tiny").init_params(
        batch_size=1, dtype=jnp.float32)
    variables = {"params": perturbed(variables["params"])}
    ms = ModelServer(model, variables, model_name="nemotron-h-tiny",
                     n_slots=3, prefill_chunk=4, decode_window=4)
    prompt = np.random.RandomState(0).randint(0, 256, (13,)).tolist()
    try:
        replies = [ms.generate({"prompt": prompt, "max_new_tokens": 12,
                                "logits": True, **extra})
                   for extra in ({}, {"temperature": 0.8, "seed": 3})]
        info, metrics = ms.info(), ms.metrics_text()
    finally:
        ms.close()
    return model, variables, prompt, replies, info, metrics


@pytest.mark.parametrize("which", [0, 1], ids=["greedy", "sampled"])
def test_slot_pool_logits_match_the_reference(served, which):
    model, variables, prompt, replies, _, _ = served
    reply = replies[which]
    field = reply["logits"]
    got = np.frombuffer(base64.b64decode(field["b64"]),
                        "<f4").reshape(field["shape"])
    new = reply["new_tokens"][0]
    assert got.shape == (len(new), model.cfg.vocab_size)
    ref = reference(variables["params"], np.asarray(prompt + new[:-1]))
    np.testing.assert_allclose(got, np.asarray(ref)[len(prompt) - 1:],
                               rtol=1e-4, atol=1e-4)
    if not which:
        assert new == [int(t) for t in got.argmax(-1)]


def test_counters_count_the_chunked_form_and_the_lanes_stepped(served):
    """2 requests x pieces of 4, 4, 4 (through the chunked form) x 2
    Mamba-2 layers, and 1 (the step); every decode step rewrites every
    lane's state, Mamba-1's counters as they stand; the ungated expert
    layers count their pairs, 2 choices a token in 2 expert layers."""
    info, metrics = served[4], served[5]
    assert info["prefill_tokens_total"] == 26
    assert info["ssm_scan_tokens_total"] == 2 * 12 * 2
    lanes = info["decode_steps_total"] * info["slots"]
    assert info["ssm_state_steps_total"] == lanes + 2
    assert info["moe_pairs_routed_total"] == (26 + lanes) * 2 * 2
    assert 0 < info["moe_pairs_held_total"] < info["moe_pairs_routed_total"]
    assert info["moe_experts_touched_total"] > 0
    kinds = info["kv_pool_bytes_by_kind"]
    assert kinds["state"] > 0 and kinds["full"] > 0
    assert info["kv_pool_in_place_total"] == \
        info["kv_pool_dispatches_total"] > 0
    for name in ("ssm_scan_tokens_total", "ssm_state_steps_total",
                 "moe_experts_touched_total"):
        assert f"ptpu_serving_{name} {info[name]}" in metrics


@pytest.mark.parametrize("option", ["paged", "mesh", "spec-k"])
def test_options_that_rewind_or_cut_by_position_refuse(option):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    extra = {"paged": ["--kv-paged"], "mesh": ["--mesh", "tp=1"],
             "spec-k": ["--spec-k", "2"]}[option]
    result = CliRunner().invoke(
        cli, ["serve", "--model", "nemotron-h-tiny", "--cpu"] + extra)
    assert result.exit_code != 0
    assert "two kinds of KV cache" in result.output
    assert "recurrent state without a position axis" in result.output
    assert result.output.count("\n") <= 3


@pytest.mark.parametrize("tail_rests_pinned", [True, False])
def test_a_pool_leaf_resting_otherwise_keeps_the_pool_programs_fresh(
        tail_rests_pinned, monkeypatch):
    """The pool's programs are read from the persistent cache only
    where every pool leaf's device default IS its pinned row-major
    layout: a TPU rests a ``[slots, 1, 3, width]`` tail otherwise,
    though its lane ``[1, 3, width]`` rests row-major."""
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.serving import slots

    model = NemotronHModel(NemotronHConfig.tiny())
    prompt = np.asarray([[3, 1, 4, 1, 5]], np.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
    tail = jax.ShapeDtypeStruct((2, 1, 3, 64), jnp.bfloat16)
    fmt = slots.SlotKVManager(model, variables, 2)._pool_formats(tail)
    assert slots._rests_as_pinned(tail, fmt)    # a CPU: row-major
    monkeypatch.setattr(
        slots, "_rests_as_pinned",
        lambda shape, fmt: tail_rests_pinned or len(shape.shape) != 4)
    mgr = slots.SlotKVManager(model, variables, 2)
    _, cache = G.prefill(model, variables, prompt)
    mgr.insert(mgr.acquire(), cache, 1, 5)
    assert mgr._pin_is_default is tail_rests_pinned
