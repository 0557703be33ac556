"""Served weights rest in the dtype the programs compute in
(serving/weights.py, cli/main.py ``_build_serving_model``).

The contract: the tree is made (or restored, or quantized) in float32
as ever and THEN each leaf is rounded once to what the serving model's
modules declare for it — the rounding the modules did on every use, so
logits and tokens are BITWISE the float32 tree's; the LayerNorm leaves,
which compute in float32, stay float32; a family that declares nothing
is served as it was, a tree that rests as declared is handed on, the
same arrays; and everything ``train.py`` builds stays float32.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.models.registry import get_model
from polyaxon_tpu.ops.quant import QuantizedTensor, quantize_params
from polyaxon_tpu.serving import DecodeEngine, SchedulerPolicy
from polyaxon_tpu.serving.scheduler import SamplingSpec
from polyaxon_tpu.serving.slots import SlotKVManager
from polyaxon_tpu.serving.weights import (declared_tree,
                                          rest_as_declared,
                                          resting_overrides,
                                          weights_report)

SLOTS = 4
PROMPTS = ([3, 1, 4], [1, 5, 9, 2, 6], [5, 3, 5, 8, 9, 7, 9],
           [2, 7, 1, 8, 2, 8, 1, 8, 2])
SAMP = dict(temperature=0.9, top_k=16, top_p=0.95)
TOKS = jnp.zeros((1, 4), jnp.int32)


def _perturbed(model, seed):
    """``model.init`` with every leaf moved off its initial value (the
    LayerNorm scales away from 1, every bias away from 0) by float32
    noise: nearly no element is a bfloat16 number."""
    variables = model.init(jax.random.PRNGKey(seed), TOKS)
    leaves, tree = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
              for l, k in zip(leaves, keys)]
    for l in leaves:
        back = l.astype(jnp.bfloat16).astype(jnp.float32)
        assert l.dtype == jnp.float32 and float((back != l).mean()) > 0.9
    return jax.tree.unflatten(tree, leaves)


def _serve(model, variables):
    """What ``_build_serving_model`` does to a tree: the serving model
    and the tree at rest as it declares."""
    cfg = dataclasses.replace(model.cfg, **resting_overrides(model))
    served = type(model)(cfg)
    tree, cast = rest_as_declared(variables,
                                  declared_tree(served, TOKS))
    return served, tree, cast


@pytest.fixture(scope="module")
def tiny():
    """gpt2-tiny (bfloat16 compute), a perturbed float32 tree, and the
    served pair made from it."""
    model = GPT2Model(GPT2Config.tiny())
    variables = _perturbed(model, 0)
    return (model, variables) + _serve(model, variables)


def _is_norm(path):
    return "ln" in jax.tree_util.keystr(path)


# -- (a) the rule, and bitwise the float32 tree's results -------------------


def test_dense_and_embed_leaves_rest_in_bfloat16_layernorms_in_float32(
        tiny):
    model, variables, served, tree, cast = tiny
    assert model.cfg.param_dtype == jnp.float32
    assert served.cfg.param_dtype == served.cfg.dtype == jnp.bfloat16
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = [l for p, l in flat if _is_norm(p)]
    others = [l for p, l in flat if not _is_norm(p)]
    # ln1 and ln2 of the stack, ln_f: a scale and a bias each
    assert len(norms) == 6 and all(l.dtype == jnp.float32
                                   for l in norms)
    # wte, wpe, and a kernel and a bias of qkv, o_proj, fc1, fc2
    assert len(others) == 10 and all(l.dtype == jnp.bfloat16
                                     for l in others)
    assert cast == sum(l.nbytes for p, l in
                       jax.tree_util.tree_flatten_with_path(
                           variables)[0] if not _is_norm(p))
    # the float32 tree was not touched, and a second pass finds nothing
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(variables))
    again, none = rest_as_declared(tree, declared_tree(served, TOKS))
    assert again is tree and none == 0


def test_prefill_logits_are_bitwise_the_float32_trees_and_casting_all_is_not(
        tiny):
    model, variables, served, tree, _ = tiny
    prompt = np.asarray([PROMPTS[3]], np.int32)
    want, _ = G.prefill(model, variables, prompt)
    got, _ = G.prefill(served, tree, prompt)
    assert want.dtype == got.dtype == jnp.float32
    assert np.array_equal(np.asarray(want), np.asarray(got))
    # the rule is not "everything": the LayerNorms compute in float32
    # on float32 leaves, and rounding those too is another model
    everything = jax.tree.map(lambda l: l.astype(jnp.bfloat16), tree)
    other, _ = G.prefill(served, everything, prompt)
    assert not np.array_equal(np.asarray(want), np.asarray(other))


def _key(row):
    return np.asarray(jax.random.key_data(
        jax.random.fold_in(jax.random.PRNGKey(11), row)), np.uint32)


def _pool_tokens(model, variables, sampled):
    """Four slots at four positions: 16 decode steps in windows of 8,
    the tokens [16, S] and the last step's logits."""
    mgr = SlotKVManager(model, variables, SLOTS)
    for slot, prompt in enumerate(PROMPTS):
        assert mgr.acquire() == slot
        _, cache = G.prefill(model, variables,
                             np.asarray([prompt], np.int32))
        extra = dict(base_key=_key(slot), **SAMP) if sampled else {}
        mgr.insert(slot, cache, 1, len(prompt), **extra)
    toks = np.concatenate([mgr.step(8, sampled, 8) for _ in range(2)])
    return toks, np.asarray(mgr.last_logits)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_pool_decode_is_bitwise_the_float32_trees(tiny, sampled):
    model, variables, served, tree, _ = tiny
    want, want_logits = _pool_tokens(model, variables, sampled)
    got, got_logits = _pool_tokens(served, tree, sampled)
    assert want.shape == (16, SLOTS)
    assert np.array_equal(want, got)
    assert want_logits.dtype == np.float32
    assert np.array_equal(want_logits, got_logits)


# -- (b) nothing to cast: the same arrays -----------------------------------


def test_a_tree_that_rests_as_declared_is_handed_on_untouched():
    """afmoe-tiny made as ``trinity-large-ep8`` rests, bfloat16
    matrices beside a float32 router and float32 norms: the helper
    returns the very tree it was given."""
    spec = get_model("afmoe-tiny")
    model, variables = spec.init_params(batch_size=1,
                                        param_dtype=jnp.bfloat16)
    assert resting_overrides(model) == {"param_dtype": jnp.bfloat16}
    inputs = spec.make_batch(1)["inputs"]
    dtypes = {jnp.dtype(l.dtype).name
              for l in jax.tree.leaves(variables["params"])}
    assert dtypes == {"bfloat16", "float32"}
    tree, cast = rest_as_declared(variables,
                                  declared_tree(model, inputs))
    assert tree is variables and cast == 0


def test_a_family_without_the_field_declares_nothing():
    from polyaxon_tpu.cli.main import _build_serving_model

    model, _ = get_model("llama-tiny").init_params(batch_size=1)
    assert resting_overrides(model) == {}
    assert resting_overrides(object()) == {}
    served, tree, cast = _build_serving_model("llama-tiny", 1, None,
                                              False, False)
    assert cast == 0 and served.cfg == model.cfg
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(tree))


@pytest.mark.parametrize("name", ["gpt2-tiny", "afmoe-tiny"])
def test_the_serving_build_draws_in_float32_then_rounds(name):
    """``_build_serving_model``: the values are the registry's float32
    draw, rounded — not another stream drawn in bfloat16 — and the
    leaves the model computes on in float32 are the draw itself."""
    from polyaxon_tpu.cli.main import _build_serving_model

    _, drawn = get_model(name).init_params(batch_size=1)
    served, tree, cast = _build_serving_model(name, 1, None, False,
                                              False)
    assert served.cfg.param_dtype == served.cfg.dtype == jnp.bfloat16
    n = 0
    for a, b in zip(jax.tree.leaves(drawn["params"]),
                    jax.tree.leaves(tree["params"])):
        assert np.array_equal(
            np.asarray(a.astype(b.dtype), np.float32),
            np.asarray(b, np.float32))
        n += a.nbytes if a.dtype != b.dtype else 0
    assert cast == n > 0


# -- (c) the other serving paths: tokens equal the float32 tree's -----------

PROMPT = np.asarray([[3, 1, 4, 1]], np.int32)
REQUESTS = (
    (PROMPT, 12, None),
    (np.asarray([[2, 7, 1, 8, 2]], np.int32), 10,
     SamplingSpec(seed=7, temperature=1.0, top_k=8)),
    (np.asarray([[5, 6, 7]], np.int32), 9, None),
)
SPEC = SamplingSpec(seed=7, temperature=0.9, top_k=16, spec_k=3)


@pytest.fixture(scope="module")
def four_heads():
    """bfloat16 compute, 4 heads (tp=2 divides), a target and a draft
    tree, each as float32 and as served."""
    cfg = dataclasses.replace(
        GPT2Config.tiny(), vocab_size=64, hidden_size=32,
        num_layers=2, num_heads=4, max_position=64)
    model = GPT2Model(cfg)
    target, draft = _perturbed(model, 3), _perturbed(model, 99)
    served, s_target, _ = _serve(model, target)
    _, s_draft, _ = _serve(model, draft)
    return model, target, draft, served, s_target, s_draft


def _engine_tokens(model, variables, dvars, *, mesh=None, paged=False,
                   spec=False):
    kw = dict(n_slots=4, decode_window=8)
    if paged:
        kw.update(kv_paged=True, kv_page_tokens=8)
    extra = dict(draft_model=model, draft_variables=dvars) \
        if spec else {}
    eng = DecodeEngine(model, variables, autostart=False,
                       policy=SchedulerPolicy(**kw), mesh=mesh,
                       **extra)
    try:
        groups = [eng.submit(p, new, None, None, sampling=s)
                  for p, new, s in REQUESTS]
        if spec:
            groups.append(eng.submit(PROMPT, 12, None, None,
                                     sampling=SPEC))
        eng.run_until_idle()
        return [g.result().tolist() for g in groups]
    finally:
        eng.close()


@pytest.mark.parametrize("path", ["int8-weights", "kv-paged",
                                  "mesh-tp2", "speculative"])
def test_engine_tokens_equal_the_float32_trees(four_heads, path):
    model, target, draft, served, s_target, s_draft = four_heads
    kw = {}
    if path == "int8-weights":
        # quantized FROM the float32 values, then the rest rounded:
        # the order _build_serving_model keeps
        target = {"params": quantize_params(target["params"],
                                            min_size=1024)}
        served, s_target, cast = _serve(model, target)
        leaves = jax.tree.leaves(
            s_target, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        assert any(isinstance(l, QuantizedTensor) for l in leaves)
        assert cast > 0
    elif path == "kv-paged":
        kw = dict(paged=True)
    elif path == "mesh-tp2":
        kw = dict(mesh="tp=2")
    else:
        kw = dict(spec=True)
    want = _engine_tokens(model, target, draft, **kw)
    got = _engine_tokens(served, s_target, s_draft, **kw)
    assert want == got
    assert len({tuple(w[0]) for w in want}) == len(want)


# -- (d) training stays float32 ---------------------------------------------


def test_what_train_builds_is_float32_in_every_leaf():
    """The model, the parameters and the optimizer's state as
    ``train.py`` makes them for ``--model gpt2-tiny``, before and
    after a step."""
    from polyaxon_tpu.parallel import MeshSpec, build_mesh, \
        make_train_step
    from polyaxon_tpu.train import make_optimizer

    spec = get_model("gpt2-tiny")
    model = spec.make_model()
    assert model.cfg.param_dtype == jnp.float32
    batch = spec.make_batch(8)
    params = model.init(jax.random.PRNGKey(0), batch["inputs"])
    step = make_train_step(spec.loss_fn(model),
                           make_optimizer("adamw", 1e-3),
                           build_mesh(MeshSpec(dp=-1)), grad_accum=1,
                           donate=True)
    state = step.init_state(params)

    def all_float32(state):
        floats = [l for l in jax.tree.leaves(
            (state["params"], state["opt_state"]))
            if jnp.issubdtype(l.dtype, jnp.floating)]
        return len(floats) > 32 and all(l.dtype == jnp.float32
                                        for l in floats)

    assert all_float32(state)
    state, _ = step(state, batch, jax.random.PRNGKey(1))
    assert all_float32(state)


# -- (f) the counters -------------------------------------------------------


def test_info_and_metrics_report_the_weights_counters():
    from polyaxon_tpu.cli.main import _build_serving_model
    from polyaxon_tpu.serving import ModelServer

    model, variables, cast = _build_serving_model("gpt2-tiny", 1, None,
                                                  False, False)
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     n_slots=2, weights_cast_bytes=cast)
    try:
        info, metrics = ms.info(), ms.metrics_text()
    finally:
        ms.close()
    leaves = jax.tree.leaves(variables)
    by_dtype = info["weights_bytes_by_dtype"]
    assert info["weights_bytes"] == sum(l.nbytes for l in leaves) \
        == sum(by_dtype.values())
    assert set(by_dtype) == {"bfloat16", "float32"}
    assert by_dtype["float32"] == sum(
        l.nbytes for l in leaves if l.dtype == jnp.float32)
    # float32 bytes became half as many bfloat16 bytes
    assert info["weights_cast_bytes"] == cast == 2 * by_dtype["bfloat16"]
    assert info["weights_compute_dtype"] == "bfloat16"
    assert f"ptpu_serving_weights_bytes {info['weights_bytes']}" \
        in metrics
    assert f"ptpu_serving_weights_cast_bytes {cast}" in metrics
    assert 'ptpu_serving_weights_bytes_by_dtype{dtype="bfloat16"} ' \
        f'{by_dtype["bfloat16"]}' in metrics


def test_a_server_handed_a_float32_tree_reports_nothing_cast(tiny):
    """A library caller who builds the server itself: the tree as
    given, both trees counted."""
    from polyaxon_tpu.serving import ModelServer

    model, variables = tiny[:2]
    ms = ModelServer(model, variables, n_slots=2, draft_model=model,
                     draft_variables=variables)
    try:
        info = ms.info()
    finally:
        ms.close()
    n = sum(l.nbytes for l in jax.tree.leaves(variables))
    assert info["weights_bytes"] == 2 * n
    assert info["weights_bytes_by_dtype"] == {"float32": 2 * n}
    assert info["weights_cast_bytes"] == 0


def _reader():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "perfbench",
                        "layer_metrics", "weights_compute_dtype_pct.py")
    spec = importlib.util.spec_from_file_location("wcd_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_benchmarks_reader_reads_the_counters(tiny):
    import types

    read = _reader()
    served, tree = tiny[2], tiny[3]
    info = weights_report([tree, None], served.cfg.dtype, 1)
    ctx = types.SimpleNamespace(collected={"info_close": info})
    n = sum(l.nbytes for l in jax.tree.leaves(tree))
    low = sum(l.nbytes for l in jax.tree.leaves(tree)
              if l.dtype == jnp.bfloat16)
    assert read(ctx) == pytest.approx(100.0 * low / n)
    assert 90 < read(ctx) < 100
    # a program without the counters reports nothing, and does not raise
    for lacking in ({}, {"weights_bytes": 0},
                    {"weights_bytes": 8,
                     "weights_bytes_by_dtype": {"float32": 8}}):
        ctx = types.SimpleNamespace(collected={"info_close": lacking})
        assert read(ctx) is None
    assert read(types.SimpleNamespace(collected={})) is None
