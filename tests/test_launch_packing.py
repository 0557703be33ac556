"""A decode launch hands the device its host operands as ONE packed
array (serving/slots.py ``LaunchPacking``, ``SlotManager._launch``).

What is held here: every layout a launch packs comes back from the
program's unpacking bit for bit, with its dtype and shape (NaN
payloads, -0.0, all-ones keys, negative positions); a launch of every
kind, under both KV managers, makes exactly ONE host-to-device
transfer, hands its program nothing that lives on the host, and counts
that one array; a packed program computes what its body computes over
the operands handed to it one by one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import generate as G
from polyaxon_tpu.models.gpt2 import GPT2Config, GPT2Model
from polyaxon_tpu.serving.paged import PagedSlotKVManager
from polyaxon_tpu.serving.slots import (LaunchPacking, SlotKVManager,
                                        build_step_body)

S = 3
FED = "fed"         # stands where the device's feedback token goes


def _bits(*ints):
    return np.asarray(ints, np.uint32).view(np.float32)


def _slots_arrays():
    """The slots' arrays at their edges: NaNs with payloads and both
    signs, -0.0, infinities, all-ones key words, negative positions."""
    return dict(
        toks=np.asarray([0, 50256, -1], np.int32),
        fresh=np.asarray([True, False, True]),
        positions=np.asarray([-7, 0, 2 ** 31 - 1], np.int32),
        keys=np.asarray([[0xFFFFFFFF, 0], [1, 0x80000000],
                         [0xDEADBEEF, 0xFFFFFFFF]], np.uint32),
        idxs=np.asarray([-2 ** 31, 1, 9], np.int32),
        temps=_bits(0x7FC00001, 0x80000000, 0xFFBADA55),   # NaN, -0, NaN
        tks=np.asarray([0, -1, 16], np.int32),
        tps=np.concatenate([_bits(0x7F800000, 0xFF800001),  # inf, NaN
                            np.float32([0.95])]),
        sks=np.asarray([0, 2, -3], np.int32))


def _layout(kind):
    a = _slots_arrays()
    if kind in ("plain", "sampled"):
        ops = (np.int32(8), a["toks"], FED, a["fresh"], a["positions"])
        if kind == "sampled":
            ops += (a["keys"], a["idxs"], a["temps"], a["tks"], a["tps"])
        return ops
    tables = np.asarray([[5, -1], [0, 7], [2 ** 31 - 1, 3]], np.int32)
    d0 = np.asarray([0, -4, 1], np.int32)
    spec = (a["toks"], a["positions"], a["idxs"], a["keys"], a["temps"],
            a["tks"], a["tps"], a["sks"])
    if kind == "spec":
        return spec
    if kind == "paged-spec":
        return (tables, d0) + spec
    return (tables, d0, a["toks"], FED, a["fresh"], a["positions"],
            a["keys"], a["idxs"], a["temps"], a["tks"], a["tps"])


@pytest.mark.parametrize("kind", ["plain", "sampled", "spec",
                                  "paged-sampled", "paged-spec"])
def test_pack_then_unpack_returns_every_operand_bit_for_bit(kind):
    fed = jnp.asarray([4, 5, 6], jnp.int32)
    ops = tuple(fed if a is FED else a for a in _layout(kind))
    packing = LaunchPacking(ops)
    words, device = LaunchPacking.pack(ops)
    assert words.dtype == np.int32 and words.ndim == 1
    assert words.size == packing.size == sum(
        np.size(a) for a in ops if a is not fed)
    assert [d is fed for d in device] == (
        [True] if any(a is fed for a in ops) else [])
    got = jax.jit(lambda w, *d: packing.unpack(w, d))(words, *device)
    assert len(got) == len(ops)
    for want, out in zip(ops, got):
        want, out = np.asarray(want), np.asarray(out)
        assert (out.dtype, out.shape) == (want.dtype, want.shape)
        assert out.tobytes() == want.tobytes()


def test_the_buffer_is_fresh_and_refuses_what_it_cannot_carry():
    toks = np.arange(S, dtype=np.int32)
    words, _ = LaunchPacking.pack((np.int32(2), toks))
    toks[:] = 99            # the slots' arrays are written at once
    assert words.tolist() == [2, 0, 1, 2]
    with pytest.raises(TypeError, match="float64"):
        LaunchPacking.pack((np.zeros(S),))


# -- one transfer a launch ----------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(
        GPT2Config.tiny(), vocab_size=32, hidden_size=32, num_layers=1,
        num_heads=2, max_position=64, dtype=jnp.float32)
    model = GPT2Model(cfg=cfg)
    init = lambda seed: model.init(jax.random.PRNGKey(seed),   # noqa
                                   jnp.zeros((1, 4), jnp.int32))
    return model, init(0), init(99)


def _manager(tiny, paged):
    model, variables, draft = tiny
    kw = dict(draft_model=model, draft_variables=draft)
    if paged:
        mgr = PagedSlotKVManager(model, variables, S, page_tokens=8,
                                 max_position=64, decode_window=4, **kw)
    else:
        mgr = SlotKVManager(model, variables, S, **kw)
    prompt = np.asarray([[3, 1, 4, 1, 5]], np.int32)
    _, cache = G.prefill(model, variables, prompt)
    _, d_cache = G.prefill(model, draft, prompt)
    key = np.asarray(jax.random.key_data(jax.random.PRNGKey(7)),
                     np.uint32)
    for slot, spec_k in ((mgr.acquire(), 0), (mgr.acquire(), 2)):
        mgr.insert(slot, cache, 1, 5, base_key=key, temperature=0.8,
                   top_k=8, draft_cache=d_cache, spec_k=spec_k,
                   **({"total_tokens": 32} if paged else {}))
    return mgr


@pytest.mark.parametrize("paged", [False, True], ids=["lanes", "paged"])
def test_a_launch_of_every_kind_makes_one_transfer(tiny, paged,
                                                   monkeypatch):
    """Counted at the one place a host array can reach the device
    from ``_launch``, ``jax.device_put``, and held at the program's
    call: every argument it is handed already lives on the device."""
    mgr = _manager(tiny, paged)
    transfers, calls = [], []
    real_put = jax.device_put

    def put(x, *a, **kw):
        if any(isinstance(leaf, (np.ndarray, np.generic))
               for leaf in jax.tree.leaves(x)):
            transfers.append(x)
        return real_put(x, *a, **kw)

    real_program = mgr._program

    def program(*a):
        key, build, *rest = real_program(*a)

        def watched():
            fn = build()

            def call(*args):
                calls.append(all(isinstance(leaf, jax.Array)
                                 for leaf in jax.tree.leaves(args)))
                return fn(*args)
            return call
        return (key, watched, *rest)

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(mgr, "_program", program)
    launches = 0
    for step in (lambda: mgr.step(2, False), lambda: mgr.step(2, True),
                 lambda: mgr.step(2, True), lambda: mgr.step_spec(1, 2)):
        before = len(transfers)
        step()
        launches += 1
        assert len(transfers) - before == 1
        (words,) = transfers[-1:]
        assert words.dtype == np.int32 and words.ndim == 1
    assert calls == [True] * launches
    assert mgr.upload_arrays_total == launches


def test_a_packed_window_computes_what_its_body_computes(tiny):
    """The fixed-lane sampled window against ``build_step_body`` over
    the same operands one by one, the pool copied first: the same
    tokens, last tokens and logits, bit for bit."""
    model, variables, _ = tiny
    mgr = _manager(tiny, paged=False)
    mgr.launch(2, sampled=True, cap=4)  # ``fed`` is a program's now
    ops = (np.int32(3), *(a.copy() if isinstance(a, np.ndarray) else a
                          for a in mgr.state.operands("sampled")))
    pool = jax.tree.map(jnp.copy, mgr.kv_pool())
    body = jax.jit(lambda v, p, *o: build_step_body(
        model, v, 4, True)(p, *o)[:2])
    want = jax.device_get(body(variables, pool, *ops))
    got_outs = mgr.launch(3, sampled=True, cap=4).host[0]
    got = jax.device_get((mgr.last_logits, mgr.state.fed))
    assert got_outs.tobytes() == want[0][:3].tobytes()
    assert got[0].tobytes() == want[1]["logits"].tobytes()
    assert got[1].tobytes() == want[1]["tok"].tobytes()
