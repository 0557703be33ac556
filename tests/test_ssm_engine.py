"""State without positions through the serving engine
(serving/engine.py, serving/slots.py) at ``jamba-tiny``: recurrent
state and a convolution's tail as a third kind of leaf in the one
donated slot pool, beside an attention layer's planes.

What a state leaf can do — be inserted, carried from one prefill piece
to the next, stepped in place, stored whole and re-prefilled — is held
to the solo runs token for token; what it cannot — be rewound, be cut
into pages, be sharded by heads — is refused with one line.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import jamba
from polyaxon_tpu.models.generate import generate, generate_positional
from polyaxon_tpu.models.jamba import JambaConfig, JambaModel
from polyaxon_tpu.serving import (DecodeEngine, ModelServer,
                                  SchedulerPolicy)
from polyaxon_tpu.serving.scheduler import SamplingSpec
from polyaxon_tpu.serving.slots import pool_refusal

STATE_LAYERS = 3        # jamba-tiny: layers 0, 2, 3 (1 is attention)


@pytest.fixture(scope="module")
def tiny():
    """f32 ``jamba-tiny`` over a vocabulary of 48: margins dominate
    cross-program rounding, so token equality is exact."""
    cfg = dataclasses.replace(JambaConfig.tiny(), vocab_size=48,
                              dtype=jnp.float32)
    model = JambaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


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
    budgets 4..12 on three slots: admitted as slots free up, stepped
    together, each commits exactly the tokens of its own solo run."""
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


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_preempt_and_resume_is_token_identical(tiny, mode):
    """A batch request evicted mid-decode re-prefills ``prompt ++
    committed[:-1]`` into a FRESH state (pieces of powers of two): no
    rewind is asked of the state, and the tokens are those of the
    uninterrupted run."""
    model, variables = tiny
    prompt, other = _prompt(11, 7), _prompt(5, 8)
    if mode == "sampled":
        sampling = SamplingSpec(**SAMPLED)
        want = generate_positional(model, variables, prompt,
                                   max_new_tokens=14, **SAMPLED)
    else:
        sampling = None
        want = generate(model, variables, prompt, max_new_tokens=14)
    eng = _engine(model, variables, n_slots=1, decode_window=1,
                  slo_ttft_s=0.0001)
    victim = eng.submit(prompt, 14, None, None, sampling=sampling,
                        priority="batch")
    for _ in range(5):
        eng.tick()
    assert 2 <= len(victim.streams[0].out) < 14
    inter = eng.submit(other, 3, None, None, priority="interactive")
    eng.run_until_idle()
    assert eng.preempted_total == 1 and eng.resumed_total == 1
    assert victim.result().tolist() == np.asarray(want).tolist()
    assert inter.result().tolist() == np.asarray(generate(
        model, variables, other, max_new_tokens=3)).tolist()


def test_crash_recovery_re_prefills_a_fresh_state(tiny):
    """The pool rebuilt under residents (a lost donated pool): they
    are requeued, re-prefilled from their tokens, and finish with the
    solo run's tokens."""
    model, variables = tiny
    eng = _engine(model, variables, n_slots=2, decode_window=1)
    prompt = _prompt(12, 9)
    g = eng.submit(prompt, 10, None, None)
    for _ in range(4):
        eng.tick()
    assert 1 <= len(g.streams[0].out) < 10
    assert eng.recover_from_crash() == 1
    eng.run_until_idle()
    assert g.result().tolist() == np.asarray(generate(
        model, variables, prompt, max_new_tokens=10)).tolist()


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_whole_entry_prefix_hit_equals_a_cold_request(tiny, sampled):
    """``POST /prefill`` stores a SNAPSHOT after exactly the entry's
    tokens; a prompt that starts with the whole entry extends it (the
    state carried on, the tail included) and answers as a cold one."""
    model, variables = tiny
    ms = ModelServer(model, variables, model_name="jamba-tiny",
                     n_slots=2, prefill_chunk=8)
    try:
        system = _prompt(13, 3)[0].tolist()
        req = {"prompt": system + [4, 8, 15], "max_new_tokens": 6,
               **({"temperature": 0.8, "seed": 9} if sampled else {})}
        cold = ms.generate(dict(req))
        assert "prefix_hit_len" not in cold
        ms.prefill_prompt({"prompt": system})
        warm = ms.generate(dict(req))
        assert warm["prefix_hit_len"] == len(system)
        assert warm["new_tokens"] == cold["new_tokens"]
        # A prompt that shares only PART of the entry finds no entry:
        # the store matches whole entries (radix lookup), so a state
        # is never asked for an earlier position.
        part = ms.generate({"prompt": system[:9] + [1, 2],
                            "max_new_tokens": 3})
        assert "prefix_hit_len" not in part
    finally:
        ms.close()


def test_counters_against_hand_counts(tiny, monkeypatch):
    """One request: a prompt of 21 in pieces of 8, 8 and 5 — exact
    lengths, so the scans the programs were TRACED with are 8 and 5
    positions long and no padded position enters one — then 9 decode
    steps over 3 lanes (idle lanes step too)."""
    model, variables = tiny
    traced = []
    real = jamba.selective_scan
    monkeypatch.setattr(
        jamba, "selective_scan",
        lambda u, *a: traced.append(u.shape[1]) or real(u, *a))
    ms = ModelServer(model, variables, model_name="jamba-tiny",
                     n_slots=3, prefill_chunk=8, decode_window=4)
    try:
        ms.generate({"prompt": _prompt(21, 4)[0].tolist(),
                     "max_new_tokens": 10})
        info, metrics = ms.info(), ms.metrics_text()
    finally:
        ms.close()
    assert sorted(set(traced)) == [5, 8]
    assert traced.count(8) == 2 * STATE_LAYERS      # prefill + extend
    assert traced.count(5) == STATE_LAYERS
    assert info["prefill_tokens_total"] == 21
    assert info["ssm_scan_tokens_total"] == 21 * STATE_LAYERS
    assert info["decode_steps_total"] == 9
    assert info["ssm_state_steps_total"] == 9 * 3
    kinds = info["kv_pool_bytes_by_kind"]
    # A slot: 3 layers x (h 4 x 64 f32 + tail 3 x 64 f32) of state,
    # K and V planes of 64 x 8 f32 and an index.
    assert kinds == {"window": 0, "latent": 0,
                     "state": 3 * 3 * (4 + 3) * 64 * 4,
                     "full": 3 * (2 * 64 * 8 * 4 + 4)}
    assert sum(kinds.values()) == info["kv_pool_bytes"]
    assert set(info["scan_routes"]) == {"pallas", "xla"}
    assert info["scan_routes"]["xla"] >= 3 * STATE_LAYERS
    assert "ptpu_serving_ssm_scan_tokens_total 63" in metrics
    assert "ptpu_serving_ssm_state_steps_total 27" in metrics
    assert 'ptpu_serving_kv_pool_bytes_by_kind{kind="state"}' in metrics
    assert 'ptpu_serving_scan_routes{route="xla"}' in metrics


def test_a_piece_of_one_position_takes_the_step_not_the_scan(tiny):
    """Pieces of powers of two (a resumed stream's): 7 = 4 + 2 + 1;
    the last is the one-position update."""
    model, variables = tiny
    eng = _engine(model, variables, prefill_chunk=None)
    reads = eng.slots.plane_reads
    g = eng.submit(_prompt(7, 2), 2, None, None)
    g.streams[0].pieces = SchedulerPolicy.pow2_pieces(7)
    eng.run_until_idle()
    assert reads.scan_tokens == 6 * STATE_LAYERS
    assert reads.state_steps == 1 + eng.decode_steps_total * 3
    assert g.result().tolist() == np.asarray(generate(
        model, variables, _prompt(7, 2), max_new_tokens=2)).tolist()


@pytest.mark.parametrize("option", ["paged", "mesh", "draft", "spec-k"])
def test_options_that_rewind_or_cut_by_position_refuse(option):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    extra = {"paged": ["--kv-paged"], "mesh": ["--mesh", "tp=1"],
             "draft": ["--draft-model", "jamba-tiny"],
             "spec-k": ["--spec-k", "2"]}[option]
    result = CliRunner().invoke(
        cli, ["serve", "--model", "jamba-tiny", "--cpu"] + extra)
    assert result.exit_code != 0
    assert "two kinds of KV cache" in result.output
    assert "recurrent state without a position axis" in result.output
    assert result.output.count("\n") <= 3


def test_the_engine_refuses_by_the_same_line(tiny):
    model, variables = tiny
    line = pool_refusal((model,), paged=True)
    assert line == pool_refusal((model,), speculative=True) \
        == pool_refusal((None, model), meshed=True)
    assert pool_refusal((model,)) is None
    with pytest.raises(ValueError) as paged:
        DecodeEngine(model, variables, autostart=False,
                     policy=SchedulerPolicy(n_slots=2, kv_paged=True))
    with pytest.raises(ValueError) as spec:
        DecodeEngine(model, variables, autostart=False,
                     draft_model=model, draft_variables=variables,
                     policy=SchedulerPolicy(n_slots=2))
    assert str(paged.value) == str(spec.value) == line
    # A model of one position-keyed kind is refused nothing.
    from polyaxon_tpu.models.registry import get_model

    gpt2 = get_model("gpt2-tiny").make_model()
    assert pool_refusal((gpt2, gpt2), paged=True, meshed=True,
                        speculative=True) is None
