"""Deviceless compiles for the chip: the main path's programs, at real
widths, through the real TPU compiler — no chip, no chip time.

The ONLY tier-1 file that describes the chip.  The topology is described
inside a module-scoped fixture (never at import, never in conftest.py,
not autouse): only the xdist worker that is handed this file loads the
TPU compiler's library, and every worker collects the same tests.  The
compiles run in this process (a child could not load the library a
second time) with the persistent compilation cache off — a deviceless
executable can be written to it but not read back without a chip.

What passes here compiled; nothing ran.  A time, a rate or a result
comes only from ``chip_smoke.py`` on the chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

SLOTS = 8           # `ptpu serve --slots` default
DECODE_WINDOW = 8   # `ptpu serve --decode-window` default


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Persistent cache off around the deviceless compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch, no_cache):
    """``flash_eligible`` asks ``jax.default_backend()``, which is the
    CPU here; steer it with the switch it already reads."""
    monkeypatch.setenv("POLYAXON_TPU_ASSUME_TPU", "1")
    monkeypatch.delenv("POLYAXON_TPU_FLASH_INTERPRET", raising=False)
    monkeypatch.delenv("POLYAXON_TPU_NO_FLASH", raising=False)


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


# (batch, seq, heads, head_dim), causal, key-padding mask, window
FLASH_CASES = {
    "gpt2-medium": ((4, 1024, 16, 64), True, False, None),
    "bert-base-kvmask": ((16, 512, 12, 64), False, True, None),
    "tinyllama": ((2, 2048, 32, 64), True, False, None),
    "sliding-window": ((1, 4096, 8, 128), True, False, 1023),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_fwd_bwd_compiles(one_chip, on_tpu, case):
    """The forward and both backward kernels, at the attention shapes
    of the models the repo trains at published widths."""
    from polyaxon_tpu.ops.flash import flash_attention

    shape, causal, padded, window = FLASH_CASES[case]
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv_mask = jax.ShapeDtypeStruct(shape[:2], jnp.bool_,
                                   sharding=one_chip) if padded else None

    def loss(q, k, v, kv_mask):
        out = flash_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                              window=window, scale=shape[-1] ** -0.5)
        return (out.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, kv_mask).compile()
    # fwd + dq + dkv
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("n_chips", [1, 4])
def test_gpt2_medium_train_step_compiles(topo, on_tpu, n_chips):
    """The whole b4 step as ``train.py`` builds it: its optimizer
    factory (``--optimizer sgd``, the spec of examples/gpt2/
    onechip.yaml), its donation, its batch sharding — and it fits the
    chip with the Pallas kernels inside.  On four chips (``--strategy
    dp:4``) GSPMD cannot partition a Mosaic kernel: the step compiles
    only because ops/attention.py hands the kernel its shard."""
    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.parallel import MeshSpec, build_mesh, \
        make_train_step
    from polyaxon_tpu.parallel.strategies import make_param_shardings
    from polyaxon_tpu.train import make_optimizer

    spec = get_model("gpt2-medium")
    model = spec.make_model()
    mesh = build_mesh(MeshSpec(dp=-1),
                      devices=list(topo.devices)[:n_chips])
    step = make_train_step(spec.loss_fn(model),
                           make_optimizer("sgd", 2.5e-4), mesh,
                           grad_accum=1, donate=True)
    tokens = jax.ShapeDtypeStruct((4, 1024), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    opt = jax.eval_shape(step.optimizer.init, params)
    step.state_shardings = {
        "params": make_param_shardings(params, mesh),
        "opt_state": make_param_shardings(opt, mesh),
        "step": NamedSharding(mesh, P()),
    }
    state = {"params": params, "opt_state": opt,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    compiled, _ = step.precompile(state, {"inputs": tokens},
                                  jax.random.PRNGKey(0))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert ("all-reduce(" in text) == (n_chips > 1)
    mem = compiled.memory_analysis()   # per device
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 15.75 * 2 ** 30


def _serving_shapes(one_chip, served=True, slots=SLOTS):
    """gpt2-medium as ``ptpu serve`` holds it: the variables of
    ``spec.init_params(batch_size=1)`` at rest as the serving model
    declares them (serving/weights.py; ``served=False``: as they are
    drawn, float32) and the stacked cache of a pool of ``slots`` (the
    default's), as shapes on the described chip."""
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.serving.weights import (declared_tree,
                                              resting_overrides)

    spec = get_model("gpt2-medium")
    model = spec.make_model()
    if served:
        model = spec.make_model(**resting_overrides(model))
    variables = _abstract(declared_tree(
        model, jax.ShapeDtypeStruct((1, 1024), jnp.int32)), one_chip)
    one = jax.eval_shape(lambda: G.init_cache(model, 1))
    pool = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((slots,) + l.shape, l.dtype,
                                       sharding=one_chip), one)
    return model, variables, pool


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_engine_decode_window_compiles(one_chip, on_tpu, sampled):
    """The engine's fused decode-window body (serving/slots.py) over
    the default pool.  The engine closes over its variables; passing
    them as an argument is what lets the same body lower from shapes."""
    from polyaxon_tpu.serving.slots import build_step_body

    model, variables, pool = _serving_shapes(one_chip)

    def program(variables, *operands):
        return build_step_body(model, variables, DECODE_WINDOW,
                               sampled)(*operands)

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((SLOTS,) + tail, dtype,
                                    sharding=one_chip)

    steps = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    operands = [pool, steps, vec(jnp.int32), vec(jnp.int32),
                vec(jnp.bool_), vec(jnp.int32)]
    if sampled:
        operands += [vec(jnp.uint32, 2), vec(jnp.int32),
                     vec(jnp.float32), vec(jnp.int32), vec(jnp.float32)]
    compiled = jax.jit(program).lower(variables, *operands).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 30


def _manager_window(one_chip, monkeypatch, slots, sampled):
    """``(compiled, pool)``: the decode window as a slot manager of
    ``slots`` builds it over gpt2-medium — the pool donated, pinned
    row-major in and out — compiled for the described chip."""
    from polyaxon_tpu.serving.slots import SlotKVManager

    model, variables, pool = _serving_shapes(one_chip, slots=slots)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: list(one_chip.device_set))
    mgr = SlotKVManager(model, variables, slots)
    mgr._cache_sh = mgr._pool_formats(pool)
    fn = mgr._build_step(DECODE_WINDOW, sampled)

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((slots,) + tail, dtype,
                                    sharding=one_chip)

    operands = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32)]
    if sampled:
        operands += [vec(jnp.uint32, 2), vec(jnp.int32),
                     vec(jnp.float32), vec(jnp.int32), vec(jnp.float32)]
    return fn.func.lower(*fn.args, pool, *operands).compile(), pool


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_decode_window_updates_the_pool_in_place(one_chip, on_tpu,
                                                 monkeypatch, sampled):
    """The window program as the slot manager builds it — pool
    donated, pinned row-major in and out: the v5e compiler aliases
    every pool leaf to an output, keeps the pool in the one layout
    from parameter to result, and copies nothing of its size (left to
    its default layout the pool rests position-minor, and the program
    converts all of it on entry and again on exit)."""
    import re

    compiled, pool = _manager_window(one_chip, monkeypatch, SLOTS, sampled)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes
    kv = "bf16[%s]" % ",".join(
        str(d) for d in jax.tree.leaves(pool)[-1].shape)
    row_major = kv + "{5,4,3,2,1,0"
    params = [l for l in text.splitlines()
              if re.search(r"= %s\S* parameter\(\d+\), sharding"
                           % re.escape(kv), l)]
    assert len(params) == 2 and all(row_major in l for l in params)
    assert not re.search(r"= %s\S* copy\(" % re.escape(kv), text)
    # one padded pool in the arguments, no second one among the
    # temporaries (with ONE predicate under the key's and the value's
    # overlay the compiler copied the whole value stack before the
    # layer loop in every step: kv_cache._append)
    assert mem.temp_size_in_bytes < pool_bytes
    # a step's rows are written after the layer loop, none inside it
    # (kv_cache.defers): the loop that holds the attention's
    # conditional makes nothing of a pool leaf's shape but what
    # carries it
    made = re.findall(r"= %s\S* ([a-z\-]+)\(" % re.escape(kv),
                      _layer_loop(text))
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast"}, made


def test_decode_window_scores_in_whole_tiles(one_chip, on_tpu,
                                             monkeypatch):
    """`gpt2m-serve-closed`'s pool (24 slots): each fusion that scores
    a step's queries against the first ``n`` rows of the key plane
    walks those rows in tiles that divide ``n``, for every width short
    of the whole plane.  Laid over the rows under a ``[slots, n]``
    predicate the new row cost the 512-row branch a ``[2, 171]`` tile
    and 232 us a layer where ``[3, 128]`` took 70 (kv_cache._append;
    PERF.md section 6, PR 36)."""
    import re

    from polyaxon_tpu.models import kv_cache

    compiled, pool = _manager_window(one_chip, monkeypatch, 24, True)
    cap = jax.tree.leaves(pool)[-1].shape[-3]
    tiles = {int(n): int(tile) for n, tile in re.findall(
        r"= f32\[24,(\d+),16\]\S* fusion\(.*?bqhd,bkhd->bhqk/dot_general"
        r".*?output_window_bounds\":\[\"\d+\",\"(\d+)\"",
        compiled.as_text())}
    narrow = kv_cache.prefix_widths(cap)[:-1]
    assert set(narrow) <= set(tiles), tiles
    assert all(n % tiles[n] == 0 for n in narrow), tiles


def _computations(text):
    """``{name: body}`` of an HLO module's computations."""
    import re

    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \(.*?^\}", text, re.M | re.S)}


def _reach(comps, name, seen=None):
    """``name`` and every computation it calls."""
    import re

    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return seen
    seen.add(name)
    for callee in re.findall(r"(?:calls|to_apply|body|condition|"
                             r"true_computation|false_computation)="
                             r"%?([\w.\-]+)", comps[name]):
        _reach(comps, callee, seen)
    for group in re.findall(r"branch_computations=\{([^}]*)\}",
                            comps[name]):
        for callee in group.split(","):
            _reach(comps, callee.strip().lstrip("%"), seen)
    return seen


def _layer_loop(text):
    """The text of the ``while`` that walks the layers in a compiled
    decode window — its body and everything the body calls.  Of the
    loops that hold the attention's conditional over the prefix
    widths it is the innermost."""
    import re

    comps = _computations(text)
    holds = {b: _reach(comps, b) for b in re.findall(
        r" while\(.*?body=%?([\w.\-]+)", text)}
    holds = {b: names for b, names in holds.items()
             if any("conditional(" in comps[c] for c in names)}
    layer, = [b for b, names in holds.items()
              if not any(o != b and o in names for o in holds)]
    return "\n".join(comps[c] for c in sorted(holds[layer]))


def _whole_plane_scores(text, dims):
    """How many of the computations that the program's conditional
    branches to make a float32 result whose shape holds ``dims`` (a
    regex: the whole plane's keys on an axis — scores or weights over
    all of it); 1 is the widest branch alone.  Fails where such a
    result is made anywhere else."""
    import re

    comps = _computations(text)
    branches = {b.strip().lstrip("%") for m in re.finditer(
        r"branch_computations=\{([^}]*)\}", text)
        for b in m.group(1).split(",")}
    assert branches
    wide = re.compile(r"= f32\[(?:\d+,)*%s(?:,\d+)*\]\S* " % dims)

    inside = {b: _reach(comps, b) for b in branches}
    owned = set().union(*inside.values())
    outside = [n for n, body in comps.items()
               if n not in owned and wide.search(body)]
    assert not outside, outside
    return sum(any(wide.search(comps[n]) for n in names)
               for names in inside.values())


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_decode_window_reads_no_whole_plane_outside_the_widest_branch(
        one_chip, on_tpu, monkeypatch, sampled):
    """gpt2-medium's decode program reads each layer's K and V plane
    through ONE conditional over the prefix widths
    (kv_cache.attend_kv_cache): outside its widest branch no operation
    has a whole plane's shape — none is sliced out of the pool — and
    none makes scores over all 1 024 keys."""
    import re

    from polyaxon_tpu.models.kv_cache import prefix_widths
    from polyaxon_tpu.serving.slots import SlotKVManager

    model, variables, pool = _serving_shapes(one_chip)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: list(one_chip.device_set))
    mgr = SlotKVManager(model, variables, SLOTS)
    mgr._cache_sh = mgr._pool_formats(pool)
    fn = mgr._build_step(DECODE_WINDOW, sampled)

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((SLOTS,) + tail, dtype,
                                    sharding=one_chip)

    operands = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32)]
    if sampled:
        operands += [vec(jnp.uint32, 2), vec(jnp.int32),
                     vec(jnp.float32), vec(jnp.int32), vec(jnp.float32)]
    text = fn.func.lower(*fn.args, pool, *operands).compile().as_text()
    conds = re.findall(r"branch_computations=\{([^}]*)\}", text)
    assert len(conds) == 1      # one layer body, one conditional
    assert len(conds[0].split(",")) == len(prefix_widths(1024)) == 4
    # a plane of the pool: [slots, 1, 1024, heads, 64] of any dtype
    assert not re.search(r"= \w+\[%d,1,1024,16,64\]\S* " % SLOTS, text)
    # (hidden is 1 024 too: the scores are [.., keys, heads] or
    # [.., heads, keys])
    assert _whole_plane_scores(text, "(?:1024,16|16,1024)") == 1


# fc1, fc2, qkv, o_proj (a stack of 24), wte
WEIGHT_SHAPES = ("24,1024,4096", "24,4096,1024", "24,1024,3072",
                 "24,1024,1024", "50257,1024")


def _weight_converts(text):
    """The weights' shapes that ``text`` converts from a float32 array
    it was handed: a ``convert`` to bfloat16, of a weight's shape,
    whose operand is a float32 parameter of its computation (the
    program's own, or a fusion's).  A float32 product inside a fusion
    (the head's multiply-and-reduce over the table on a chip without
    bfloat16 vector units) is no such pass over memory."""
    import re

    found = set()
    for body in _computations(text).values():
        f32_params = set(re.findall(
            r"%([\w.\-]+) = f32\[[\d,]*\]\S* parameter\(", body))
        for shape, operand in re.findall(
                r"= bf16\[([\d,]*)\]\S* convert\(%([\w.\-]+)\)", body):
            if shape in WEIGHT_SHAPES and operand in f32_params:
                found.add(shape)
    return sorted(found)


@pytest.mark.parametrize("program", ["decode-window", "prefill"])
def test_served_programs_convert_no_weight(one_chip, on_tpu,
                                           monkeypatch, program):
    """gpt2-medium's decode window and a prefill program, lowered from
    the served tree's avals: the compiled program converts no weight
    from float32, where the same program over the float32 tree
    converts all five in every dispatch (which also shows that the
    check can fail)."""
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.serving.slots import SlotKVManager

    monkeypatch.setattr(jax, "devices",
                        lambda *a: list(one_chip.device_set))

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((SLOTS,) + tail, dtype,
                                    sharding=one_chip)

    def compiled_text(served):
        model, variables, pool = _serving_shapes(one_chip, served)
        if program == "prefill":
            toks = jax.ShapeDtypeStruct((1, 24), jnp.int32,
                                        sharding=one_chip)
            return variables, jax.jit(G.prefill_programs(model)[0]) \
                .lower(variables, toks).compile().as_text()
        mgr = SlotKVManager(model, variables, SLOTS)
        mgr._cache_sh = mgr._pool_formats(pool)
        fn = mgr._build_step(DECODE_WINDOW, True)
        operands = [
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
            vec(jnp.int32), vec(jnp.uint32, 2),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32)]
        return variables, fn.func.lower(
            *fn.args, pool, *operands).compile().as_text()

    variables, text = compiled_text(served=True)
    by_dtype = {}
    for leaf in jax.tree.leaves(variables):
        by_dtype[leaf.dtype.name] = by_dtype.get(leaf.dtype.name, 0) \
            + leaf.size * leaf.dtype.itemsize
    # 354.8 M parameters in bfloat16; the LayerNorms' 0.1 M in float32
    assert 0.70e9 < by_dtype["bfloat16"] < 0.72e9
    assert 0.3e6 < by_dtype["float32"] < 0.5e6
    assert _weight_converts(text) == []
    assert "bf16[24,1024,4096]" in text     # the weights ARE there
    _, text = compiled_text(served=False)
    assert _weight_converts(text) == sorted(WEIGHT_SHAPES)


def test_meshed_decode_window_compiles(topo, on_tpu):
    """``ptpu serve --mesh tp=4``: the same body under the serving
    mesh's own shardings and its exact layout — heads of the KV pool
    cut in four, gathers but no cross-device sum in the program (the
    reason meshed tokens can equal unmeshed ones bitwise)."""
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.serving.meshed import ServingMesh
    from polyaxon_tpu.serving.slots import build_step_body

    mesh = ServingMesh("tp=4", devices=list(topo.devices))
    model = get_model("gpt2-medium").make_model()
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1024), jnp.int32))
    pool = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((SLOTS,) + l.shape, l.dtype),
        jax.eval_shape(lambda: G.init_cache(model, 1)))
    pool_sh = mesh.cache_shardings(pool, slot_axis=True)
    rep = mesh.replicated
    slots = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)

    def program(variables, *operands):
        return build_step_body(model, variables, DECODE_WINDOW,
                               False)(*operands)

    with mesh.exact():
        compiled = jax.jit(
            program,
            in_shardings=(mesh.param_shardings(variables), pool_sh)
            + (rep,) * 5,
            out_shardings=(rep, rep, pool_sh),
        ).lower(variables, pool,
                jax.ShapeDtypeStruct((), jnp.int32), slots, slots,
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
                slots).compile()
    text = compiled.as_text()
    assert "all-gather(" in text and "all-reduce(" not in text
    kv = [(l.shape, sh.shard_shape(l.shape)) for l, sh in zip(
        jax.tree.leaves(pool), jax.tree.leaves(pool_sh))
        if len(l.shape) >= 4]
    assert kv and all(shard[-2] * 4 == full[-2] for full, shard in kv)


@pytest.mark.parametrize("prompt_len", [24, 77, 512])
def test_engine_prefill_compiles(one_chip, on_tpu, prompt_len):
    """The engine's prefill program (``G.prefill_programs``,
    engine.py) at the prompt lengths the smoke sends and at a long
    one."""
    from polyaxon_tpu.models import generate as G

    model, variables, _ = _serving_shapes(one_chip)
    toks = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32,
                                sharding=one_chip)
    compiled = jax.jit(G.prefill_programs(model)[0]).lower(
        variables, toks).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 30
    # the planes read as far as the prompt's length, chosen while
    # tracing: nothing to branch on
    assert " conditional(" not in compiled.as_text()


# -- trinity-large-ep8: two kinds of cache in one pool, grouped experts -----

TRINITY_SLOTS = 32      # perfbench/configs/trinity-large-preview.json


def _trinity_shapes(one_chip):
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.models.registry import get_model

    model = get_model("trinity-large-ep8").make_model()
    variables = _abstract(jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)), one_chip)
    one = jax.eval_shape(lambda: G.init_cache(model, 1))
    return model, variables, one


def test_trinity_decode_window_keeps_both_cache_kinds_in_place(
        one_chip, on_tpu, monkeypatch):
    """The served cut's decode program as the slot manager builds it,
    32 slots: bfloat16 weights at rest (8.65 GB) beside the pool (3.49
    GB), every pool leaf — rings and the full plane — aliased to an
    output, nothing of a leaf's size copied or concatenated, the
    grouped expert matmul there as the Pallas kernel (ops/grouped_matmul.py)."""
    import re

    from polyaxon_tpu.serving.slots import SlotKVManager

    model, variables, one = _trinity_shapes(one_chip)
    pool = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((TRINITY_SLOTS,) + l.shape,
                                       l.dtype, sharding=one_chip), one)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: list(one_chip.device_set))
    mgr = SlotKVManager(model, variables, TRINITY_SLOTS)
    mgr._cache_sh = mgr._pool_formats(pool)
    fn = mgr._build_step(DECODE_WINDOW, True)

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((TRINITY_SLOTS,) + tail, dtype,
                                    sharding=one_chip)

    operands = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32), vec(jnp.uint32, 2),
                vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
                vec(jnp.float32)]
    compiled = fn.func.lower(*fn.args, pool, *operands).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    weights = sum(l.size * l.dtype.itemsize
                  for l in jax.tree.leaves(variables))
    pool_bytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(pool))
    assert 8.5e9 < weights < 8.8e9 and 3.4e9 < pool_bytes < 3.6e9
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1 * 2 ** 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    lane = r"bf16\[%d,1,(4608|8192),8,128\]" % TRINITY_SLOTS
    assert not re.search(r"= %s\S* (copy|concatenate)\(" % lane, text)
    # The grouped matmuls took the Pallas kernel: XLA's own ragged-dot
    # is a tpu_custom_call too, so the count alone would not tell.
    assert text.count("tpu_custom_call") >= 3
    assert "ragged-dot" not in text
    # The full layer's plane is its layer's own variable, read whole
    # in the pool's step (kv_cache.narrows): sliced under a conditional
    # the compiler converted the layout of all of it in every branch,
    # 2 x 537 MB a step among the temporaries.
    assert " conditional(" not in text


@pytest.mark.parametrize("first", [True, False],
                         ids=["prefill", "extend"])
def test_trinity_prefill_chunk_compiles(one_chip, on_tpu, first):
    """A 512-token chunk of the served cut (``--prefill-chunk 512``):
    the ring written before it is read (no cache-sized concatenate),
    temporaries that leave room beside 12.1 GB of weights and pool."""
    import re

    from polyaxon_tpu.models import generate as G

    model, variables, one = _trinity_shapes(one_chip)
    toks = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    ptpu_prefill, ptpu_extend = G.prefill_programs(model)
    if first:
        compiled = jax.jit(ptpu_prefill).lower(variables, toks).compile()
    else:
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = jax.jit(ptpu_extend).lower(
            variables, _abstract(one, one_chip), toks, pos).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30
    assert not re.search(
        r"= bf16\[1,(4608|5120),8,128\]\S* concatenate\(", text)
    # The grouped matmuls took the Pallas kernel: XLA's own ragged-dot
    # is a tpu_custom_call too, so the count alone would not tell.
    assert text.count("tpu_custom_call") >= 3
    assert "ragged-dot" not in text
    # The full layer's plane is read as far as the chunk has written
    # it.  From position 0 that is known while tracing: the first of
    # the four widths, no conditional, no scores over 8 192 keys
    # anywhere.  An extended cache: one conditional over the widths,
    # and outside its widest branch no scores over all 8 192 keys.
    if first:
        assert " conditional(" not in text
        assert not re.search(r"= f32\[(?:\d+,)*8192(?:,\d+)*\]", text)
        assert re.search(r"= f32\[(?:\d+,)*1024(?:,\d+)*\]", text)
    else:
        assert len(re.findall(r" conditional\(", text)) == 1
        assert _whole_plane_scores(text, "8192") == 1


# -- jamba2-3b: state without a position axis beside two MQA planes ---------

JAMBA_SLOTS = 128       # perfbench/configs/ai21-jamba2-3b.json


def _jamba_shapes(one_chip):
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.models.registry import get_model

    model = get_model("jamba2-3b").make_model()
    variables = _abstract(jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)), one_chip)
    one = jax.eval_shape(lambda: G.init_cache(model, 1))
    return model, variables, one


def test_jamba_scan_kernel_compiles_at_the_served_piece(one_chip, on_tpu):
    """One layer's selective scan over a 128-position piece at the
    published widths (d_inner 5 120, d_state 16), gated, a state
    carried in: the Mosaic compiler takes it."""
    from polyaxon_tpu.ops import selective_scan as S

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(S.selective_scan).lower(
        arr(1, 128, 5120), arr(1, 128, 5120), arr(16, 5120),
        arr(1, 128, 16), arr(1, 128, 16), arr(5120,), arr(1, 16, 5120),
        arr(1, 128, 5120)).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


def test_jamba_prefill_piece_takes_the_kernel(one_chip, on_tpu):
    """A 128-token piece of the whole model (``--prefill-chunk 128``):
    every one of the 26 Mamba layers through the Pallas scan, 6.06 GB
    of weights, one slot's cache out."""
    from polyaxon_tpu.models import generate as G

    model, variables, one = _jamba_shapes(one_chip)
    toks = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one_chip)
    compiled = jax.jit(G.prefill_programs(model)[0]).lower(
        variables, toks).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 26
    weights = sum(l.size * l.dtype.itemsize
                  for l in jax.tree.leaves(variables))
    assert 6.0e9 < weights < 6.1e9
    # The single KV head is not padded to a tile: a slot's cache and
    # the logits come out in 10.9 MB (10.37 MB logical + 0.26).
    assert mem.output_size_in_bytes < 11.5e6
    assert mem.temp_size_in_bytes < 0.5 * 2 ** 30


def test_jamba_decode_window_keeps_state_and_planes_in_place(
        one_chip, on_tpu, monkeypatch):
    """The decode program as the slot manager builds it, 128 slots:
    every pool leaf — state, tails, planes — aliased to an output, the
    one-position update plain XLA (no kernel), everything within the
    chip beside the weights."""
    from polyaxon_tpu.models.kv_cache import leaf_kinds
    from polyaxon_tpu.serving.slots import SlotKVManager

    model, variables, one = _jamba_shapes(one_chip)
    pool = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((JAMBA_SLOTS,) + l.shape,
                                       l.dtype, sharding=one_chip), one)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: list(one_chip.device_set))
    mgr = SlotKVManager(model, variables, JAMBA_SLOTS)
    mgr._cache_sh = mgr._pool_formats(pool)
    fn = mgr._build_step(DECODE_WINDOW, True)

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((JAMBA_SLOTS,) + tail, dtype,
                                    sharding=one_chip)

    operands = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32), vec(jnp.uint32, 2),
                vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
                vec(jnp.float32)]
    compiled = fn.func.lower(*fn.args, pool, *operands).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    by_kind = {}
    for _, leaf, kind in leaf_kinds(pool):
        by_kind[kind] = by_kind.get(kind, 0) \
            + leaf.size * leaf.dtype.itemsize
    assert 1.18e9 < by_kind["state"] < 1.20e9       # 9.32 MB a slot
    assert 0.13e9 < by_kind["full"] < 0.14e9        # 1.05 MB a slot
    assert mem.alias_size_in_bytes >= sum(by_kind.values())
    # ...and 1.495 GB as it rests: the tail's 3 rows in a tile of 8.
    assert mem.alias_size_in_bytes < 1.6e9
    assert mem.temp_size_in_bytes < 0.5 * 2 ** 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert "tpu_custom_call" not in text


# -- deepseek-v2-lite, stage 0: one latent plane a layer --------------------

DSV2_SLOTS = 16         # perfbench/configs/deepseek-v2-lite.json
DSV2_POSITIONS = 16896


def _dsv2_shapes(one_chip):
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.models.registry import get_model

    model = get_model("deepseek-v2-lite-stage0").make_model()
    variables = _abstract(jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)), one_chip)
    one = jax.eval_shape(lambda: G.init_cache(model, 1))
    return model, variables, one


def test_dsv2_decode_window_keeps_the_latent_planes_in_place(
        one_chip, on_tpu, monkeypatch):
    """The decode program as the slot manager builds it, 16 slots of
    16 896 positions at the published widths: 8.02 GB of weights, a
    pool of latent planes and nothing else — 576 numbers a position a
    layer, resting in 640 lanes (five tiles of 128), no sublane padding
    of a heads axis there is not — every leaf aliased to an output, the
    absorbed path's temporaries small, the plane never expanded."""
    import re

    from polyaxon_tpu.models.kv_cache import leaf_kinds
    from polyaxon_tpu.serving.slots import SlotKVManager

    model, variables, one = _dsv2_shapes(one_chip)
    pool = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((DSV2_SLOTS,) + l.shape,
                                       l.dtype, sharding=one_chip), one)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: list(one_chip.device_set))
    mgr = SlotKVManager(model, variables, DSV2_SLOTS)
    mgr._cache_sh = mgr._pool_formats(pool)
    fn = mgr._build_step(DECODE_WINDOW, True)

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((DSV2_SLOTS,) + tail, dtype,
                                    sharding=one_chip)

    operands = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32), vec(jnp.uint32, 2),
                vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
                vec(jnp.float32)]
    compiled = fn.func.lower(*fn.args, pool, *operands).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    weights = sum(l.size * l.dtype.itemsize
                  for l in jax.tree.leaves(variables))
    assert 8.01e9 < weights < 8.03e9
    kinds = {kind for _, _, kind in leaf_kinds(pool)}
    assert kinds == {"latent"}
    logical = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(pool))
    a_position = 7 * 576 * 2                    # bytes, logical
    assert logical == DSV2_SLOTS * (DSV2_POSITIONS * a_position + 7 * 4)
    # ...and as it rests: 640 lanes a row, 8 960 B a position.
    resting = DSV2_SLOTS * DSV2_POSITIONS * 7 * 640 * 2
    assert resting <= mem.alias_size_in_bytes < resting + 1e6
    assert mem.temp_size_in_bytes < 0.3 * 2 ** 30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    # The absorbed path: no key or value a head over the plane's rows.
    assert not re.search(r"= bf16\[(?:\d+,)*16896,16,(?:256|128)\]", text)
    # The grouped matmuls took the Pallas kernel: XLA's own ragged-dot
    # is a tpu_custom_call too, so the count alone would not tell.
    assert text.count("tpu_custom_call") >= 3
    assert "ragged-dot" not in text


def test_dsv2_extend_piece_compiles(one_chip, on_tpu):
    """A 512-token piece onto an existing cache (``--prefill-chunk
    512``): ONE conditional a layer over the four widths a plane is
    read to, the rows read expanded to keys and values (the widest
    branch 16 896 x 16 x 256), scores over 16 896 keys outside that
    branch nowhere, temporaries that leave room beside 8.02 GB of
    weights and 2.42 GB of pool."""
    import re

    from polyaxon_tpu.models import generate as G

    model, variables, one = _dsv2_shapes(one_chip)
    toks = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(G.prefill_programs(model)[1]).lower(
        variables, _abstract(one, one_chip), toks, pos).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(re.findall(r" conditional\(", text)) == 7
    assert re.search(r"bf16\[(?:1,)?2112,16,256\]", text)
    assert re.search(r"bf16\[(?:1,)?16896,16,256\]", text)
    assert mem.temp_size_in_bytes < 1.0 * 2 ** 30
    # One lane's cache out, in the device's default layout: 7 planes
    # of 16 896 x 576, NOT padded to 640 lanes as the pinned row-major
    # pool is; and a row of float32 logits.
    lane = 7 * DSV2_POSITIONS * 576 * 2
    assert lane <= mem.output_size_in_bytes < lane + 1e6
    # The grouped matmuls took the Pallas kernel: XLA's own ragged-dot
    # is a tpu_custom_call too, so the count alone would not tell.
    assert text.count("tpu_custom_call") >= 3
    assert "ragged-dot" not in text
