"""Latent planes through the serving engine (serving/engine.py,
serving/slots.py) at ``deepseek-v2-tiny``: one plane a layer, no heads
axis, in the one donated slot pool, read by two attention paths.

What a latent plane can do — be prefilled in pieces, inserted, stepped
in place under dispatch-ahead, rewound by a speculative slot — is held
to the solo runs token for token and to the plain reference logit for
logit; what the options that know K and V a head cannot do with it —
cut it into pages, shard its heads — is refused with one line.
"""

import base64
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import deepseek_v2 as D
from polyaxon_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                             DeepseekV2Model)
from polyaxon_tpu.models.generate import generate, generate_positional
from polyaxon_tpu.models.kv_cache import PlaneReads, causal_pairs
from polyaxon_tpu.reference import deepseek_v2 as R
from polyaxon_tpu.serving import (DecodeEngine, ModelServer,
                                  SchedulerPolicy)
from polyaxon_tpu.serving.scheduler import SamplingSpec
from polyaxon_tpu.serving.slots import pool_refusal

from test_deepseek_v2 import perturbed, ref_cfg

LAYERS = 3          # deepseek-v2-tiny: every layer keeps a latent plane
WIDTH = 20          # rank 16 + rope 4


@pytest.fixture(scope="module")
def tiny():
    """f32 ``deepseek-v2-tiny`` over a vocabulary of 48: margins
    dominate cross-program rounding, so token equality is exact."""
    cfg = dataclasses.replace(DeepseekV2Config.tiny(), vocab_size=48,
                              dtype=jnp.float32)
    model = DeepseekV2Model(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, {"params": perturbed(variables["params"])}


def _engine(model, variables, **policy):
    kw = dict(n_slots=3, decode_window=4, prefill_chunk=8)
    kw.update(policy)
    return DecodeEngine(model, variables, autostart=False,
                        policy=SchedulerPolicy(**kw))


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 48, (1, n)).astype(
        np.int32)


SAMPLED = dict(seed=11, temperature=0.9, top_k=16)


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_continuous_batching_of_mixed_lengths_equals_each_alone(tiny,
                                                                mode):
    """Five requests of prompt lengths 3..21 (1 to 3 pieces of 8) and
    budgets 4..12 on three slots, dispatch-ahead on: each commits
    exactly the tokens of its own solo run, the pool is updated in
    place, and every pool program was compiled for ONE signature."""
    model, variables = tiny
    eng = _engine(model, variables)
    cases = [(3, 9, 1), (21, 4, 2), (8, 12, 3), (17, 7, 4), (13, 5, 5)]
    groups = []
    for n, new, seed in cases:
        sampling = SamplingSpec(**SAMPLED) if mode == "sampled" else None
        groups.append(eng.submit(_prompt(n, seed), new, None, None,
                                 sampling=sampling))
    eng.run_until_idle()
    for (n, new, seed), g in zip(cases, groups):
        if mode == "sampled":
            want = generate_positional(model, variables, _prompt(n, seed),
                                       max_new_tokens=new, **SAMPLED)
        else:
            want = generate(model, variables, _prompt(n, seed),
                            max_new_tokens=new)
        assert g.result().tolist() == np.asarray(want).tolist(), \
            f"prompt {n}, budget {new}"
    stats = eng.stats()
    assert stats["kv_pool_in_place_total"] == \
        stats["kv_pool_dispatches_total"] > 0
    assert stats["decode_dispatches_ahead_total"] > 0
    programs = {**eng.slots._step_fns, **eng.slots._insert_fns}
    assert {key: getattr(fn, "func", fn)._cache_size()
            for key, fn in programs.items()} \
        == dict.fromkeys(programs, 1)
    assert stats["moe_pairs_routed_total"] > 0
    assert 0 < stats["moe_experts_touched_total"] \
        <= stats["moe_pairs_held_total"]


def test_speculative_slots_rewind_a_latent_plane(tiny):
    """A draft beside the target, both with latent planes: the verify
    chunk takes the expanded path, rejection rewinds each plane by its
    index, and the tokens are plain greedy's."""
    model, variables = tiny
    draft = model.init(jax.random.PRNGKey(99),
                       jnp.zeros((1, 4), jnp.int32))
    assert pool_refusal((model, model), speculative=True) is None
    eng = DecodeEngine(model, variables, autostart=False,
                       policy=SchedulerPolicy(n_slots=2, decode_window=4),
                       draft_model=model, draft_variables=draft)
    prompt = _prompt(6, 3)
    g = eng.submit(prompt, 10, None, None,
                   sampling=SamplingSpec(spec_k=3))
    eng.run_until_idle()
    assert g.result().tolist() == np.asarray(generate(
        model, variables, prompt, max_new_tokens=10)).tolist()
    assert eng.admitted_spec_total == 1


@pytest.fixture(scope="module")
def served(tiny):
    """One request behind a ModelServer on ONE slot (no idle lane steps
    beside it): a prompt of 21 in pieces of 8, 8 and 5, then 9 decode
    steps, with the logits every token was chosen from — and what the
    two attention paths were TRACED with meanwhile."""
    model, variables = tiny
    traced = {"expanded": [], "absorbed": []}
    real = {name: getattr(D, name + "_attention") for name in traced}

    def spy(name):
        def attention(q_nope, q_pe, rows, *rest):
            traced[name].append((q_nope.shape[-3], rows.shape[-2]))
            return real[name](q_nope, q_pe, rows, *rest)
        return attention

    patch = pytest.MonkeyPatch()
    for name in traced:
        patch.setattr(D, name + "_attention", spy(name))
    ms = ModelServer(model, variables, model_name="deepseek-v2-tiny",
                     n_slots=1, prefill_chunk=8, decode_window=4)
    try:
        prompt = _prompt(21, 4)[0].tolist()
        reply = ms.generate({"prompt": prompt, "max_new_tokens": 10,
                             "logits": True})
        info, metrics = ms.info(), ms.metrics_text()
    finally:
        ms.close()
        patch.undo()
    return model, variables, prompt, reply, info, metrics, traced


def test_slot_pool_logits_match_the_reference(served):
    """Chunked prefill (expanded), insertion into the pool, decode
    through the pool's program (absorbed): every token's logits against
    the reference's materialised forward."""
    model, variables, prompt, reply, _, _, _ = served
    field = reply["logits"]
    got = np.frombuffer(base64.b64decode(field["b64"]),
                        "<f4").reshape(field["shape"])
    new = reply["new_tokens"][0]
    assert got.shape == (10, 48)
    ref = R.forward(variables["params"], np.asarray(prompt + new[:-1]),
                    ref_cfg(model.cfg), experts_held=8)
    np.testing.assert_allclose(got, np.asarray(ref)[len(prompt) - 1:],
                               atol=5e-5)
    assert new == [int(t) for t in got.argmax(-1)]


def test_path_counters_are_what_the_programs_were_traced_with(served):
    """The host's arithmetic against the traced programs: pieces of 8,
    8 and 5 expand the 8, 16 and 32 rows they read (the plane of 64 is
    read to 8, 16, 32 or 64); a decode step attends over the whole
    plane as it lies, one query a lane."""
    info, metrics, traced = served[4:]
    # fresh prefill: one static width; extend pieces: every width.
    assert set(traced["expanded"]) == {(8, 8)} | {
        (s, n) for s in (8, 5) for n in (8, 16, 32, 64)}
    # (the server's own one-token probe reads a fresh plane to 8)
    assert set(traced["absorbed"]) - {(1, 8)} == {(1, 64)}
    assert info["prefill_tokens_total"] == 21
    assert info["decode_steps_total"] == 9
    pieces = [(0, 8), (8, 8), (16, 5)]
    assert info["latent_pairs_expanded_total"] == LAYERS * sum(
        int(causal_pairs(start, n)) for start, n in pieces) \
        == LAYERS * (36 + 100 + 95)
    assert info["latent_rows_expanded_total"] == LAYERS * (8 + 16 + 32)
    # positions 21..29, each over the keys up to its own
    assert info["latent_pairs_absorbed_total"] \
        == LAYERS * sum(range(22, 31))
    assert info["kv_plane_rows_read_total"] \
        == LAYERS * (8 + 16 + 32 + 9 * 64)
    assert info["kv_plane_rows_held_total"] == LAYERS * (3 + 9) * 64
    routes = info["attention_routes"]
    # a call a layer and program (a call's branches are one call)
    assert routes["latent_expanded"] >= 3 * LAYERS
    assert routes["latent_absorbed"] >= LAYERS
    for name in ("latent_pairs_expanded_total",
                 "latent_pairs_absorbed_total",
                 "latent_rows_expanded_total"):
        assert f"ptpu_serving_{name} {info[name]}" in metrics


def test_pool_holds_latent_planes_and_no_key_or_value(served):
    info, metrics = served[4], served[5]
    kinds = info["kv_pool_bytes_by_kind"]
    # A slot: 3 layers x (64 positions x 20 f32 + an index).
    assert kinds == {"window": 0, "full": 0, "state": 0,
                     "latent": LAYERS * (64 * WIDTH * 4 + 4)}
    assert kinds["latent"] == info["kv_pool_bytes"]
    assert info["kv_pool_in_place_total"] == \
        info["kv_pool_dispatches_total"] > 0
    assert 'ptpu_serving_kv_pool_bytes_by_kind{kind="latent"}' in metrics
    # the expert layers' grouped matmuls: XLA's ragged_dot on a CPU
    routes = info["grouped_matmul_routes"]
    assert routes["xla"] > 0 and set(routes) == {"pallas", "xla"}


def test_idle_lanes_count_their_pairs_too():
    """The host counts every lane of a window, as the program steps
    every lane: lanes parked at 0 beside one at position 21."""
    reads = PlaneReads()
    reads.latent_planes, reads.latent_cap = LAYERS, 64
    reads.count_steps(4, np.array([21, 0, 0]))
    assert reads.pairs_absorbed == LAYERS * (
        (22 + 23 + 24 + 25) + 2 * (1 + 2 + 3 + 4))
    reads.count_piece(1, 6)     # a resumed stream's last piece
    assert reads.pairs_absorbed == LAYERS * (94 + 20 + 6)
    assert reads.pairs_expanded == reads.rows_expanded == 0


@pytest.mark.parametrize("option", ["paged", "mesh"])
def test_options_that_know_k_and_v_a_head_refuse(option):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    extra = {"paged": ["--kv-paged"], "mesh": ["--mesh", "tp=1"]}[option]
    result = CliRunner().invoke(
        cli, ["serve", "--model", "deepseek-v2-tiny", "--cpu"] + extra)
    assert result.exit_code != 0
    assert "one kind of KV cache" in result.output
    assert "latent planes without a heads axis" in result.output
    assert result.output.count("\n") <= 3


def test_the_engine_refuses_by_the_same_line(tiny):
    model, variables = tiny
    line = pool_refusal((model,), paged=True)
    assert line == pool_refusal((None, model), meshed=True)
    assert pool_refusal((model,)) is None
    with pytest.raises(ValueError) as paged:
        DecodeEngine(model, variables, autostart=False,
                     policy=SchedulerPolicy(n_slots=2, kv_paged=True))
    assert str(paged.value) == line
