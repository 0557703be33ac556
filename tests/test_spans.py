"""Tier-1 coverage for the host sections on the profiler's clock
(polyaxon_tpu/spans.py): the closed list of names, the counters, what
a CPU trace taken through ``start_trace`` holds after ``train.py``'s
loop and after an engine tick, the Python tracer's switch, and the
wait for chips a dying predecessor still holds (chips.py).  And for
the device half, the scopes: the closed list, where each stands in the
lowered programs of the served architectures and of a training step,
and that a scope is metadata alone (the program's text without debug
info is the same without it)."""

import ast
import contextlib
import errno
import glob
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from polyaxon_tpu import chips, spans
from polyaxon_tpu.models.registry import get_model
from polyaxon_tpu.serving import ModelServer, make_server
from polyaxon_tpu.serving.engine import DecodeEngine
from polyaxon_tpu.serving.scheduler import SchedulerPolicy
from polyaxon_tpu.serving.telemetry import ProfileSession, Telemetry

PACKAGE = os.path.dirname(os.path.abspath(spans.__file__))
HOST_PLANE = "/host:CPU"
STEP_FIELDS = ("upload_s", "enqueue_s", "sync_s", "commit_s",
               "lock_wait_s", "admit_s", "prefill_s")


@pytest.fixture(scope="module", autouse=True)
def _no_engine_left_running():
    """A trace taken here holds every thread of the process.  Test
    files that ran earlier on this worker build servers they never
    close (``test_serving.py`` does), and such an engine's loop goes on
    entering ``ptpu/idle_wait``, ``ptpu/sweep`` and ``ptpu/board``
    beside the spans these tests count: stop those loops first."""
    import gc

    for obj in gc.get_objects():
        if isinstance(obj, DecodeEngine):
            obj.close()


# ---------------------------------------------------------------------------
# the list and the helper
# ---------------------------------------------------------------------------


def _call_literals(func: str):
    """``(file, [names])`` of every ``<func>(...)`` call in the package:
    the string constants of its first argument (one, or the arms of a
    conditional)."""
    out = []
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and getattr(node.func, "id", None) == func \
                    and node.args:
                out.append((os.path.relpath(path, PACKAGE), [
                    n.value for n in ast.walk(node.args[0])
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, str)]))
    return out


def _span_literals():
    """``(file, name)`` of every ``span("...")`` call in the package."""
    return [(f, names[0]) for f, names in _call_literals("span") if names]


def test_span_names_closed_and_every_literal_listed():
    names = spans.SPAN_NAMES
    assert len(set(names)) == len(names)
    assert all(n == "ptpu_step" or n.startswith("ptpu/") for n in names)
    assert spans.STEP_MARKER in names and spans.TRAIN_STEP in names
    used = _span_literals()
    assert len(used) >= 17      # train.py 6, engine 8, the dispatch 3
    assert [u for u in used if u[1] not in names] == []
    # every listed name is used: a literal, or one of the two markers
    # entered by name (spans.step_span, slots.SlotManager._dispatch)
    assert set(names) - {n for _, n in used} \
        == {spans.STEP_MARKER, spans.TRAIN_STEP}


@pytest.mark.parametrize("name", ["ptpu/upload", "ptpu/enqueue",
                                  "ptpu/sync"])
def test_a_dispatch_section_has_one_call_site_in_serving(name):
    """The host half of a decode dispatch is written once
    (slots.SlotManager._dispatch), whatever the KV manager and the
    kind of step."""
    sites = [f for f, n in _span_literals()
             if n == name and f.startswith("serving" + os.sep)]
    assert sites == [os.path.join("serving", "slots.py")]


def test_one_place_starts_a_trace_and_none_uses_the_private_session():
    starts, private = [], []
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "start_trace" \
                    and getattr(node.value, "attr", None) == "profiler":
                starts.append(os.path.relpath(path, PACKAGE))
            if isinstance(node, (ast.Attribute, ast.Name)) \
                    and getattr(node, "attr", getattr(node, "id", None)) \
                    in ("xla_client", "ProfilerSession"):
                private.append(os.path.relpath(path, PACKAGE))
    assert starts == ["spans.py"]
    assert private == []


def test_acc_sums_and_take_resets():
    acc = {}
    for _ in range(3):
        with spans.span("ptpu/data_wait", acc):
            pass
    with spans.span("ptpu/enqueue", acc, window=8):
        pass
    with spans.span("ptpu/board"):      # no acc: nothing recorded
        pass
    assert set(acc) == {"ptpu/data_wait", "ptpu/enqueue"}
    total = acc["ptpu/data_wait"]
    assert 0 < total < 0.1
    assert spans.take(acc, "ptpu/data_wait") == round(total, 6)
    assert spans.take(acc, "ptpu/data_wait") == 0.0
    assert spans.take(acc, "ptpu/log_write") == 0.0     # never entered


def test_span_passes_an_exception_through_and_still_counts():
    acc = {}
    with pytest.raises(KeyError):
        with spans.span("ptpu/commit", acc):
            raise KeyError("x")
    assert acc["ptpu/commit"] > 0


# ---------------------------------------------------------------------------
# the scopes: the list, and where each stands in the lowered programs
# ---------------------------------------------------------------------------


def test_scope_names_closed_and_every_literal_listed():
    names = spans.SCOPE_NAMES
    assert len(set(names)) == len(names)
    assert all(n.startswith("ptpu_") and "/" not in n for n in names)
    assert not set(names) & set(spans.SPAN_NAMES)
    used = [(f, n) for f, ns in _call_literals("scope") for n in ns]
    assert len(used) >= 20
    assert [u for u in used if u[1] not in names] == []
    assert {n for _, n in used} == set(names)       # every one is used
    # every site goes through spans.scope
    raw = []
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            if "named_scope(" in f.read():
                raw.append(os.path.relpath(path, PACKAGE))
    assert raw == ["spans.py"]


def test_scope_refuses_a_name_not_listed():
    with spans.scope("ptpu_attend"):
        pass
    for name in ("ptpu_attention", "ptpu/decode", "attend"):
        with pytest.raises(ValueError):
            spans.scope(name)


def _manager(model, variables):
    """``(manager, cache)``: a slot manager of two slots over ``model``
    with one prefilled, sampled stream (``cache``) inserted."""
    import jax

    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.serving.slots import SlotKVManager

    mgr = SlotKVManager(model, variables, 2)
    _, cache = G.prefill(model, variables,
                         np.asarray([[3, 1, 4, 1, 5]], np.int32))
    mgr.insert(mgr.acquire(), cache, 1, 5, temperature=0.9, top_k=16,
               base_key=np.asarray(jax.random.key_data(
                   jax.random.PRNGKey(11)), np.uint32))
    return mgr, cache


def _decode_window(model, variables):
    """The manager's sampled decode window, ready to lower."""
    import jax.numpy as jnp

    from polyaxon_tpu.serving.slots import LaunchPacking

    mgr, _ = _manager(model, variables)
    mgr.step(2, True)               # a launch leaves the feedback token
    words, device = LaunchPacking.pack(
        (np.int32(2), *mgr.state.operands("sampled")))
    operands = [jnp.asarray(words), *device]

    def lower():
        fn = mgr._build_step(2, True)       # traced anew each time
        return fn.func.lower(*fn.args, mgr.kv_pool(), *operands)
    return lower


def _extend_piece(model, variables):
    """``jit_ptpu_extend``: a piece of four onto a prefilled cache."""
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import generate as G

    _, cache = G.prefill(model, variables,
                         np.asarray([[3, 1, 4, 1]], np.int32))
    toks = jnp.asarray([[5, 9, 2, 6]], jnp.int32)
    return lambda: jax.jit(G.prefill_programs(model, chunk=4)[1]).lower(
        variables, cache, toks, 4)


def _prefill_piece(model, variables):
    """``jit_ptpu_prefill``: a prompt of four into a fresh cache."""
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import generate as G

    toks = jnp.asarray([[3, 1, 4, 1]], jnp.int32)
    return lambda: jax.jit(G.prefill_programs(model, chunk=4)[0]).lower(
        variables, toks)


def _insertion(model, variables):
    import jax.numpy as jnp

    mgr, cache = _manager(model, variables)
    return lambda: mgr._build_insert(False).lower(
        mgr.kv_pool(), cache, jnp.asarray(1, jnp.int32))


def _train_step(name):
    import jax
    import optax

    from polyaxon_tpu.parallel.mesh import MeshSpec, build_mesh
    from polyaxon_tpu.parallel.strategies import make_train_step

    spec = get_model(name)
    model = spec.make_model()
    batch = spec.make_batch(2)
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    rng = jax.random.PRNGKey(0)

    def lower():
        step = make_train_step(spec.loss_fn(model), optax.sgd(0.01),
                               mesh=mesh, donate=False)
        state = step.init_state(model.init(rng, batch["inputs"]))
        return step._build().lower(state, batch, rng)
    return lower


WRITES, ATTEND, SAMPLE = "ptpu_kv_write", "ptpu_attend", "ptpu_sample"
EXPERTS = ("ptpu_route", "ptpu_experts")
# (model, program) -> the scopes the lowered program must hold.
SCOPED_PROGRAMS = {
    ("gpt2-tiny", "decode"): (ATTEND, WRITES, SAMPLE),
    ("gpt2-tiny", "prefill"): (ATTEND, WRITES),
    ("gpt2-tiny", "extend"): (ATTEND, WRITES),
    ("gpt2-tiny", "insert"): (WRITES,),
    ("afmoe-tiny", "decode"): (ATTEND, WRITES, SAMPLE) + EXPERTS,
    ("afmoe-tiny", "extend"): (ATTEND, WRITES) + EXPERTS,
    ("jamba-tiny", "decode"): (ATTEND, WRITES, SAMPLE,
                               "ptpu_state_step"),
    ("jamba-tiny", "extend"): (ATTEND, WRITES, "ptpu_scan"),
    ("nemotron-h-tiny", "extend"): (ATTEND, WRITES, "ptpu_scan")
    + EXPERTS,
    ("deepseek-v2-tiny", "decode"): (ATTEND, WRITES, SAMPLE) + EXPERTS,
    ("deepseek-v2-tiny", "extend"): (ATTEND, WRITES,
                                     "ptpu_latent_expand") + EXPERTS,
    ("gpt2-tiny", "train"): (ATTEND, "ptpu_optimizer"),
    ("bert-tiny", "train"): (ATTEND, "ptpu_optimizer"),
}


@pytest.mark.parametrize("name, program", list(SCOPED_PROGRAMS),
                         ids=["-".join(k) for k in SCOPED_PROGRAMS])
def test_lowered_program_holds_its_scopes_and_only_as_metadata(
        name, program, monkeypatch):
    """With debug info the lowered program names each scope the model
    has on this path (and no other of the list's); without debug info
    its text is the same, byte for byte, as with every scope a no-op:
    a scope adds no operation, operand, shape or layout."""
    if program == "train":
        lower = _train_step(name)
    else:
        model, variables = get_model(name).init_params(batch_size=1)
        lower = {"decode": _decode_window, "prefill": _prefill_piece,
                 "extend": _extend_piece,
                 "insert": _insertion}[program](model, variables)
    lowered = lower()
    named, plain = lowered.as_text(debug_info=True), lowered.as_text()
    want = set(SCOPED_PROGRAMS[name, program])
    held = {n for n in spans.SCOPE_NAMES if f"/{n}/" in named
            or f"({n})/" in named}
    assert held == want, (held ^ want)
    assert not any(n in plain for n in spans.SCOPE_NAMES)
    # the stack reads as the issue's table has it: the width a
    # conditional took under the attention's scope, forward and
    # backward around it in a training step
    if (name, program) == ("gpt2-tiny", "decode"):
        assert "ptpu_attend/cond/branch_1_fun/" in named
    if program == "train":
        assert "transpose(jvp(" in named and "/ptpu_attend/" in named
    real = spans.scope
    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("polyaxon_tpu") \
                and getattr(mod, "scope", None) is real:
            monkeypatch.setattr(
                mod, "scope", lambda name: contextlib.nullcontext())
    bare = lower()
    assert not any(n in bare.as_text(debug_info=True)
                   for n in spans.SCOPE_NAMES)
    assert bare.as_text() == plain


# ---------------------------------------------------------------------------
# what a trace holds
# ---------------------------------------------------------------------------


def _host_events(trace_dir):
    """``[(line, name, start_ns, end_ns)]`` of the dump's host plane."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns))
    return out


def _ptpu(events):
    return sorted((e for e in events if e[1].startswith("ptpu")),
                  key=lambda e: (e[2], -e[3]))


def _parents(events):
    """``{(child name, parent name or None)}``: the innermost span that
    encloses each span."""
    out = set()
    stack = []
    for e in _ptpu(events):
        while stack and stack[-1][3] <= e[2]:
            stack.pop()
        out.add((e[1], stack[-1][1] if stack else None))
        stack.append(e)
    return out


def _python_tracer_events(events):
    # the Python tracer names a call "$<file>:<line> <function>" (and
    # "$<module> <builtin>")
    return [e for e in events if e[1].startswith("$")]


@pytest.fixture()
def train_run(tmp_path, monkeypatch):
    """Four steps of ``train.py``'s loop, steps 1-3 under its
    ``--profile-at`` trace; ``(store, uuid, trace dir)``."""
    from polyaxon_tpu.client import FileRunStore
    from polyaxon_tpu.train import main

    import jax

    home = str(tmp_path / "home")
    monkeypatch.setenv("POLYAXON_TPU_HOME", home)
    monkeypatch.setenv("POLYAXON_TPU_NO_TPU", "1")
    names = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, names)
    assert main(["--model", "mlp", "--cpu", "--steps", "4",
                 "--batch-size", "8", "--log-every", "2",
                 "--profile-at", "1", "--profile-steps", "3",
                 "--eval-every", "2", "--checkpoint-every", "2",
                 "--no-resume"]) == 0
    # A process that takes a trace keys its compilation cache by the
    # programs' names too (config.enable_compilation_cache): the
    # switch is the process's, and this one runs other tests.
    assert getattr(jax.config, names) is True
    jax.config.update(names, was)
    store = FileRunStore(home)
    uuid = store.list_runs()[0]["uuid"]
    return store, uuid, os.path.join(store.artifacts_path(uuid), "traces")


def test_train_loop_spans_nest_and_no_python_tracer(train_run):
    _, _, trace_dir = train_run
    events = _host_events(trace_dir)
    steps = [e for e in events if e[1] == spans.TRAIN_STEP]
    assert len(steps) == 3              # the last closes before the stop
    assert len({e[0] for e in _ptpu(events)}) == 1      # one thread
    parents = _parents(events)
    assert (spans.TRAIN_STEP, None) in parents
    # (no ptpu/eval: the synthetic data has no evaluation set)
    for name in ("ptpu/data_wait", "ptpu/enqueue", "ptpu/checkpoint",
                 "ptpu/log_sync", "ptpu/log_write"):
        assert (name, spans.TRAIN_STEP) in parents, (name, parents)
    assert all(p in (None, spans.TRAIN_STEP) for _, p in parents)
    assert _python_tracer_events(events) == []


def test_logged_block_carries_the_host_counters(train_run):
    store, uuid, _ = train_run

    def series(name):
        return {e["step"]: e["value"]
                for e in store.read_events(uuid, "metric", name)}

    loss = series("loss")
    assert sorted(loss) == [2, 4]
    for name in ("host_data_wait_s", "host_enqueue_s", "host_log_s"):
        values = series(name)
        assert sorted(values) == [2, 4], name
        assert all(0 <= v < 60 for v in values.values()), (name, values)
    # two enqueues a block; the first block has no earlier log_write
    assert all(v > 0 for v in series("host_enqueue_s").values())
    assert series("host_log_s")[2] == 0.0
    assert series("host_log_s")[4] > 0


@pytest.fixture(scope="module")
def tiny():
    return get_model("gpt2-tiny").init_params(batch_size=1)


@pytest.mark.parametrize("kind", ["plain", "spec"])
@pytest.mark.parametrize("kv_paged", [False, True],
                         ids=["lanes", "paged"])
def test_engine_tick_spans_nest_and_step_record_fields(
        tiny, tmp_path, kv_paged, kind):
    """Both KV managers and both kinds of step run the ONE dispatch
    (slots.SlotManager._dispatch) under the one tick: the same
    sections, nested the same way, and the same record fields."""
    from polyaxon_tpu.serving.scheduler import SamplingSpec

    model, variables = tiny
    tel = Telemetry(buffer=256)
    spec = kind == "spec"
    eng = DecodeEngine(
        model, variables, autostart=False, telemetry=tel,
        policy=SchedulerPolicy(n_slots=2, queue_depth=8, decode_window=1,
                               **(dict(kv_paged=True, kv_page_tokens=8)
                                  if kv_paged else {})),
        **(dict(draft_model=model, draft_variables=variables)
           if spec else {}))
    sampling = SamplingSpec(spec_k=2) if spec else None
    try:
        warm = eng.submit(np.asarray([[5, 6, 7]], np.int32), 12, None,
                          None, sampling=sampling)
        eng.run_until_idle()            # compiles outside the trace
        assert warm.event.is_set()
        before = sum(1 for e in tel.events() if e["name"] == "step")
        group = eng.submit(np.asarray([[1, 2, 3]], np.int32), 12, None,
                           None, sampling=sampling)
        spans.start_trace(str(tmp_path))
        try:
            # sweep, prefill + admit, dispatch 1: launched and (plain:
            # nothing was in flight to run ahead of) left in flight,
            # or (speculative: serial) collected and committed
            eng.tick()
            # dispatch 2, launched BEFORE dispatch 1 is collected and
            # committed (plain), or after (speculative); the record
            # written after this one holds this commit
            eng.tick()
        finally:
            import jax

            jax.profiler.stop_trace()
        eng.run_until_idle()
        assert group.event.is_set()
    finally:
        eng.close()
    events = _host_events(str(tmp_path))
    parents = _parents(events)
    want = {
        ("ptpu/sweep", None), ("ptpu/prefill", None),
        ("ptpu/admit", "ptpu/prefill"), ("ptpu/decode", None),
        ("ptpu/lock_wait", "ptpu/decode"), ("ptpu_step", "ptpu/decode"),
        ("ptpu/upload", "ptpu_step"), ("ptpu/enqueue", "ptpu_step"),
        ("ptpu/sync", "ptpu_step"), ("ptpu/commit", "ptpu/decode"),
    }
    assert want <= parents, want - parents
    assert parents - want <= {("ptpu/board", None)}
    assert _python_tracer_events(events) == []
    records = [e["args"] for e in tel.events() if e["name"] == "step"]
    assert len(records) >= before + 2
    for rec in records:
        assert set(STEP_FIELDS) <= set(rec), rec
        assert all(0 <= rec[f] < 60 for f in STEP_FIELDS)
        assert "device_s" in rec and rec["device_s"] >= rec["sync_s"]
        assert rec["sync_s"] > 0
        assert rec["kind"] == kind
        assert ("k" in rec, "accepted" in rec) == (spec, spec)
        assert ("pages_free" in rec) == kv_paged
    # A record holds the wait for its own dispatch and the upload and
    # enqueue of the one launched in the same marker: the next one
    # where the engine runs ahead (plain), its own in a serial
    # dispatch (speculative), none where a run's last dispatch is
    # drained (the warm run's, and this one's).
    assert sum(rec["upload_s"] == 0 for rec in records) \
        == (0 if spec else 2)
    first, second = records[before:before + 2]
    assert first["admit_s"] > 0 and first["enqueue_s"] > 0
    assert second["commit_s"] > 0 and second["admit_s"] == 0.0
    # the step counters are reported unmeshed too
    stats = eng.stats()
    assert stats["step_device_seconds_total"] > 0
    assert stats["step_wall_seconds_total"] \
        >= stats["step_device_seconds_total"]
    assert 0 < stats["step_device_share"] <= 1
    assert "mesh" not in stats


# ---------------------------------------------------------------------------
# the Python tracer's switch
# ---------------------------------------------------------------------------


def _traced_call(session, **kw):
    import jax.numpy as jnp

    session.start(**kw)
    try:
        with spans.span("ptpu/board"):
            json.dumps({"x": float(jnp.ones((4,)).sum())})
    finally:
        d = session.stop()
    return _host_events(d)


def test_python_tracer_off_by_default_on_when_asked(tmp_path):
    session = ProfileSession(str(tmp_path))
    off = _traced_call(session)
    assert [e[1] for e in _ptpu(off)] == ["ptpu/board"]
    assert _python_tracer_events(off) == []
    on = _traced_call(session, python_tracer=True)
    assert [e[1] for e in _ptpu(on)] == ["ptpu/board"]
    assert _python_tracer_events(on) != []


def test_profile_start_body_switches_the_tracer(tiny, tmp_path):
    import threading

    model, variables = tiny
    ms = ModelServer(model, variables, model_name="gpt2-tiny",
                     max_batch=4, batching="off",
                     profile_dir=str(tmp_path))
    seen = []
    real = ms.profiler.start
    ms.profiler.start = lambda **kw: (seen.append(kw), real(**kw))[1]
    srv = make_server("127.0.0.1", 0, ms)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    def post(path, data):
        req = urllib.request.Request(base + path, data=data, method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read())

    try:
        for body, want in ((None, False), (b"{}", False),
                           (b'{"python_tracer": true}', True)):
            assert post("/profile/start", body)["profiling"] is True
            assert post("/profile/stop", None)["profiling"] is False
            assert seen[-1] == {"python_tracer": want}
        with pytest.raises(urllib.error.HTTPError) as err:
            post("/profile/start", b"[1")
        assert err.value.code == 400
        assert not ms.profiler.active
    finally:
        srv.shutdown()
        srv.server_close()
        ms.close()


# ---------------------------------------------------------------------------
# chips a dying predecessor still holds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("busy_polls, timeout_s, freed", [
    (0, 5.0, True),         # free at once: no wait
    (3, 5.0, True),         # released after three polls
    (10 ** 6, 0.05, False),  # never released: gives up at the timeout
])
def test_wait_for_chips(monkeypatch, busy_polls, timeout_s, freed):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    opened = []
    real_open = os.open

    def fake_open(path, flags, *a):
        if path.startswith("/dev/vfio/"):
            opened.append(path)
            if path == "/dev/vfio/1" and opened.count(path) <= busy_polls:
                raise OSError(errno.EBUSY, "Device or resource busy")
            if path == "/dev/vfio/2":
                raise OSError(errno.EACCES, "not ours to judge")
            return real_open(os.devnull, flags)
        return real_open(path, flags, *a)

    monkeypatch.setattr(os, "open", fake_open)
    waited = chips.wait_for_chips(
        timeout_s=timeout_s, poll_s=0.005,
        nodes=["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2"])
    assert opened.count("/dev/vfio/0") == 1     # free ones asked once
    assert opened.count("/dev/vfio/2") == 1
    if freed:
        assert opened.count("/dev/vfio/1") == busy_polls + 1
        assert waited < timeout_s
    else:
        assert waited >= timeout_s


def test_wait_for_chips_leaves_hand_bound_chips_alone(monkeypatch):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    monkeypatch.setattr(os, "open", lambda *a: pytest.fail("probed"))
    assert chips.wait_for_chips(nodes=["/dev/vfio/0"]) == 0.0
