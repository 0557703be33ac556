"""Mamba-2's chunked form (ops/ssd.py) against the plain reference's
sequential recurrence (reference/nemotron_h.py), the ungated relu²
form of ``parallel/moe.held_experts_ffn``, an expert layer's eight
shares against the uncut layer, and the benchmark configuration of
NVIDIA-Nemotron-3-Nano-30B-A3B against the published widths.  The
model and the served pool: tests/test_nemotron_h.py."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models.nemotron_h import NemotronHConfig, NemotronHMoE
from polyaxon_tpu.ops.ssd import ssd_scan, ssd_step
from polyaxon_tpu.parallel.moe import held_experts_ffn
from polyaxon_tpu.reference import nemotron_h as R
from test_nemotron_h import TINY, perturbed, ref_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the Mamba-2 recurrence ---------------------------------------------------


@pytest.fixture(scope="module")
def recurrence():
    """Inputs of one sequence of 23 positions, 8 heads of 4 in 2
    groups, a state of 6, and the reference's sequential outputs."""
    rng = np.random.RandomState(0)
    t, heads, p, groups, n = 23, 8, 4, 2, 6
    x = rng.randn(1, t, heads, p).astype(np.float32)
    dt = np.log1p(np.exp(rng.randn(1, t, heads))).astype(np.float32)
    a = -np.exp(rng.randn(heads)).astype(np.float32)
    b, c = (rng.randn(1, t, groups, n).astype(np.float32)
            for _ in range(2))
    d = rng.randn(heads).astype(np.float32)
    per_head = lambda m: np.repeat(m[0], heads // groups, axis=1)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = R.recurrence(x[0], dt[0], a, per_head(b), per_head(c), d)
    return (x, dt, a, b, c, d), np.asarray(want)


@pytest.mark.parametrize("pieces", [(23,), (8, 8, 7), (3, 13, 1, 6)],
                         ids=["whole", "blocks-and-a-rest", "ragged"])
def test_chunked_pieces_with_the_state_carried_equal_the_recurrence(
        recurrence, pieces):
    """Pieces of any length in blocks of 4 (a piece the block does not
    divide is padded with dt = 0), the state carried from piece to
    piece; a piece of one position takes the decode step."""
    (x, dt, a, b, c, d), want = recurrence
    h = jnp.zeros((1, 8, 4, 6), jnp.float32)
    ys, at = [], 0
    with jax.default_matmul_precision("highest"):
        for n in pieces:
            cut = slice(at, at + n)
            if n == 1:
                y, h = ssd_step(x[:, at], dt[:, at], a, b[:, at],
                                c[:, at], d, h)
                y = y[:, None]
            else:
                y, h = ssd_scan(x[:, cut], dt[:, cut], a, b[:, cut],
                                c[:, cut], d, h, chunk=4)
            ys.append(np.asarray(y))
            at += n
    np.testing.assert_allclose(np.concatenate(ys, axis=1)[0], want,
                               rtol=2e-5, atol=2e-5)


def test_padding_leaves_the_state_as_the_last_position_left_it(
        recurrence):
    """5 positions in one padded block of 8 hand on the same state as
    5 steps."""
    (x, dt, a, b, c, d), _ = recurrence
    h0 = jnp.asarray(np.random.RandomState(1).randn(1, 8, 4, 6),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, got = ssd_scan(x[:, 3:8], dt[:, 3:8], a, b[:, 3:8],
                          c[:, 3:8], d, h0, chunk=8)
        h = h0
        for i in range(3, 8):
            _, h = ssd_step(x[:, i], dt[:, i], a, b[:, i], c[:, i], d, h)
    np.testing.assert_allclose(got, h, rtol=2e-5, atol=2e-5)


# -- the expert layer ---------------------------------------------------------


@pytest.mark.parametrize("vmapped", [False, True],
                         ids=["flat", "vmapped-lanes"])
def test_ungated_experts_equal_a_loop_over_experts(vmapped):
    """``w_gate`` None: ``W_down relu(x W_up)^2`` for every held pair
    (both choices of token 0 fall here), under vmap as one grouped
    matmul over the lanes."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    t, d, f, held, offset = 12, 16, 24, 4, 4
    x = jax.random.normal(keys[0], (t, d))
    chosen = jax.random.randint(keys[1], (t, 2), 0, 8)
    chosen = chosen.at[0].set(jnp.array([4, 5]))
    w = jax.random.uniform(keys[2], (t, 2))
    wu = jax.random.normal(keys[3], (held, d, f))
    wd = jax.random.normal(keys[4], (held, f, d))
    want = jnp.zeros((t, d))
    for e in range(held):
        w_e = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1,
                      keepdims=True)
        want += w_e * (jnp.square(jax.nn.relu(x @ wu[e])) @ wd[e])
    if vmapped:
        got = jax.jit(jax.vmap(lambda a, b, c: held_experts_ffn(
            a[None], b[None], c[None], None, wu, wd,
            expert_offset=offset)[0]))(x, chosen, w)
    else:
        got = held_experts_ffn(x, chosen, w, None, wu, wd,
                               expert_offset=offset)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_eight_shares_add_up_to_the_uncut_layer():
    """16 experts top 2 over 8 shares of 2 (EP 8): what every share
    gives, the shared expert counted once, is what the uncut reference
    gives for the whole layer; each share equals the reference told
    the same share."""
    whole = dataclasses.replace(TINY, num_experts=16, experts_held=16,
                                expert_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (1, 24, whole.hidden_size))
    layer = NemotronHMoE(whole)
    params = perturbed(jax.jit(layer.init)(jax.random.PRNGKey(0),
                                           x)["params"])
    ref = jax.jit(lambda p, x, offset: R.moe(p, x, ref_cfg(whole), 2,
                                             offset))
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(lambda p, x: R.moe(p, x, ref_cfg(whole), 16, 0))(
            params, x[0])
        shared = R.relu2_mlp(x[0], params["shared"]["up_proj"]["kernel"],
                             params["shared"]["down_proj"]["kernel"])
        total = shared
        for offset in range(0, 16, 2):
            cfg = dataclasses.replace(whole, experts_held=2,
                                      expert_offset=offset)
            part = {**params, **{k: params[k][offset:offset + 2]
                                 for k in ("experts_up", "experts_down")}}
            y = jax.jit(NemotronHMoE(cfg).apply)({"params": part}, x)[0]
            np.testing.assert_allclose(y, ref(part, x[0], offset),
                                       rtol=1e-4, atol=1e-4)
            total = total + (y - shared)
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-4)


# -- the benchmark's configuration -------------------------------------------

# The published config.json of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, its
# numbers (the configuration file's ``source``).
PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 64, "max_position_embeddings": 262144,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "norm_topk_prob": True, "num_key_value_heads": 2,
    "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "vocab_size": 131072,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
}


def test_the_configuration_keeps_every_published_width():
    """The file's numbers are the published ones but for the three cuts
    it lists (layers, experts held, vocabulary), and the zoo's served
    model is the file."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    cuts = {"num_hidden_layers": 13, "n_routed_experts": 16,
            "vocab_size": 16384}
    assert sorted(config["reduced"]) == sorted(cuts)
    for key, value in PUBLISHED.items():
        assert config[key] == cuts.get(key, value), key
        if key in config["published"]:
            assert config["published"][key] == value, key
    held = config["held"]
    assert held["hybrid_override_pattern"] == \
        PUBLISHED["hybrid_override_pattern"][:13] == "MEMEM*EMEMEM*"
    cfg = NemotronHConfig.nano_stage0_ep8()
    assert (cfg.pattern, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.mamba_num_heads,
            cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups,
            cfg.conv_kernel, cfg.chunk_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.shared_intermediate_size, cfg.routed_scaling_factor,
            cfg.norm_eps, cfg.experts_held, cfg.expert_offset,
            cfg.vocab_size) == (
        held["hybrid_override_pattern"], 2688, 32, 2, 128, 64, 64, 128,
        8, 4, 128, 128, 6, 1856, 3712, 2.5, 1e-05,
        held["n_routed_experts"], held["expert_offset"], 16384)
    assert cfg.d_inner == 64 * 64 and cfg.conv_dim == 4096 + 2 * 8 * 128
    ref = config["reference"]["cfg"]
    for key, value in ref.items():
        assert value == (held["hybrid_override_pattern"]
                         if key == "hybrid_override_pattern"
                         else PUBLISHED[key]), key
