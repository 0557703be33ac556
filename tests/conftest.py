"""Test harness config: force JAX onto 8 virtual CPU devices.

Multi-chip hardware is not available in CI; sharding/collective tests run on
a virtual CPU mesh (SURVEY.md section 4: "multi-node without a cluster").
Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # hard override: tests never touch an accelerator
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("POLYAXON_TPU_NO_TPU", "1")
# ONE persistent compilation cache for the whole suite, placed the way
# a deployment places it (config.enable_compilation_cache): through the
# environment, so in-process `train.main()` calls and the children the
# executor tests spawn all leave it where it is.  (Don't run two pytest
# processes in one workspace: concurrent writers racing on one cache
# entry have aborted natively in put_executable_and_time.)
_JAX_CACHE_DIR = os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(__file__), ".jax_cache"))

# Plugins (jaxtyping) import jax BEFORE this conftest runs, so jax.config
# already captured the env; override the live config too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", _JAX_CACHE_DIR)

import pytest  # noqa: E402

_CLEAR_EVERY = 60
_test_counter = {"n": 0}


def pytest_runtest_teardown(item, nextitem):
    """Release compiled executables periodically — the load-bearing
    fix for the round-4 full-suite crash.

    With the suite at 607 tests, single-process runs segfaulted
    natively inside XLA:CPU's LLVM JIT mid-compile once enough
    programs had accumulated — reproduced with the compilation cache
    on AND off, with heavy test files reordered first (the victim just
    moved to a different big compile).  The 534-test suite never crashed;
    every victim passes standalone.  jax.clear_caches() drops live
    executables so the JIT's code arena never reaches the cliff; the
    cost is recompiles across the boundary (cross-FILE reuse is
    minimal — the mitigated run was FASTER than the crashing ones).
    """
    _test_counter["n"] += 1
    if _test_counter["n"] % _CLEAR_EVERY == 0:
        jax.clear_caches()


# -- fast tier (VERDICT r4 next-5) ------------------------------------
#
# The full suite takes ~25-35 min on a 1-CPU host; "suite green" must
# stay cheap to falsify.  Modules dominated by JAX numerics (big
# compiles, multi-process gangs, sanitizer builds) carry the `slow`
# marker, auto-applied here so the tier lives in ONE place:
#
#   pytest -m "not slow" -q     # fast tier, < 5 min on 1 CPU
#   pytest -q                   # full suite (CI parity)
#
# The fast tier keeps the orchestration surface — schemas, compiler,
# scheduler/agent, kube transport, CLI, tracking, tuner, serving — so
# a regression in the framework's control plane is caught in minutes;
# the slow tier carries the numeric/parallel evidence.
SLOW_MODULES = {
    "test_bootstrap_multiprocess.py",  # real process gangs (~8 min)
    "test_operator_chaos.py",          # ASan/TSan builds + chaos
    "test_models.py",                  # big-compile numerics
    "test_ring_flash.py",
    "test_ring_kv_cache.py",
    "test_pp_tp.py",
    "test_parallel.py",
    "test_spmd_layout.py",
    "test_sp_integration.py",
    "test_collective_overlap.py",
    "test_moe_model.py",
    "test_speculative.py",
    "test_ops.py",
    "test_chunked_prefill.py",
    "test_sharded_decode.py",
    "test_import_hf.py",
    "test_mnist_example.py",
    "test_preemption_resume.py",
    "test_multislice.py",
    "test_t5.py",
    "test_llama.py",
    "test_kv_int8.py",
    "test_data.py",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: JAX-numeric / multi-process / sanitizer "
        "tests excluded from the fast tier (pytest -m 'not slow')")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(item.fspath.strpath) in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def tmp_home(tmp_path, monkeypatch):
    """Isolate user home/config so tests never touch ~/.polyaxon_tpu."""
    home = tmp_path / "home"
    home.mkdir()
    monkeypatch.setenv("POLYAXON_TPU_HOME", str(home))
    monkeypatch.delenv("POLYAXON_TPU_RUN_UUID", raising=False)
    monkeypatch.delenv("POLYAXON_TPU_PROJECT", raising=False)
    return home
