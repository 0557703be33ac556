"""What PR 22 changed so that the main path can be trusted on the chip:
where the compile cache goes, the one peak table, who may touch JAX,
and ``chip_smoke.py``'s contract where no chip is."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, env=None, **kw):
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, **(env or {})}, **kw)


class TestCompilationCachePlacement:
    """config.enable_compilation_cache: the directory comes from
    outside, or is the checkout's — never from the run store."""

    PROBE = ("import json, jax;"
             "from polyaxon_tpu.config import enable_compilation_cache;"
             "print(json.dumps({'ret': enable_compilation_cache(),"
             " 'cfg': jax.config.jax_compilation_cache_dir,"
             " 'min_s': jax.config"
             ".jax_persistent_cache_min_compile_time_secs}))")

    def _probe(self, **env):
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        proc = subprocess.run([sys.executable, "-c", self.PROBE],
                              cwd=REPO, capture_output=True, text=True,
                              timeout=600, env={**base, **env})
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_env_set_is_left_untouched(self, tmp_path):
        got = self._probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        # JAX read the variable itself; the helper set nothing.
        assert got["ret"] == got["cfg"] == str(tmp_path)
        assert got["min_s"] == 1.0      # JAX's default, not ours

    @pytest.mark.parametrize("home", [None, "a", "b"])
    def test_unset_is_the_checkout_whatever_the_home(self, tmp_path,
                                                     home):
        env = {} if home is None else {
            "POLYAXON_TPU_HOME": str(tmp_path / home)}
        got = self._probe(**env)
        assert got["ret"] == got["cfg"] == os.path.join(REPO,
                                                        ".jax_cache")
        assert got["min_s"] == 0.0

    @pytest.mark.parametrize("placed", ["checkout", "env"])
    def test_a_traced_process_keys_the_cache_by_names_too(self, tmp_path,
                                                          placed):
        """An executable read from the cache carries the names of the
        tree that compiled it, and a device trace reads them
        (spans.scope): a process that will take a trace keys the cache
        by the metadata too, wherever the directory comes from; every
        other process keeps the key it had."""
        code = ("import json, jax;"
                "from polyaxon_tpu.config import enable_compilation_cache"
                " as on; name = "
                "'jax_compilation_cache_include_metadata_in_key';"
                "a = on(); b = getattr(jax.config, name);"
                "c = on(names_in_key=True);"
                "print(json.dumps([a, b, c, getattr(jax.config, name)]))")
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        if placed == "env":
            base["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=600,
                              env=base)
        assert proc.returncode == 0, proc.stderr[-2000:]
        plain, before, traced, after = json.loads(
            proc.stdout.strip().splitlines()[-1])
        assert plain == traced      # the directory does not move
        assert before is False and after is True

    def test_in_process_train_leaves_the_suite_cache_alone(self):
        """conftest places the suite's cache through the environment,
        so the helper — which every in-process ``train.main()`` calls —
        is a no-op here: nothing to leak, nothing to reassert."""
        import jax

        from polyaxon_tpu.config import enable_compilation_cache

        before = jax.config.jax_compilation_cache_dir
        assert enable_compilation_cache() == before
        assert jax.config.jax_compilation_cache_dir == before

    def test_one_place_sets_the_directory(self):
        hits = []
        roots = [os.path.join(REPO, "polyaxon_tpu"),
                 os.path.join(REPO, "bench.py"),
                 os.path.join(REPO, "chip_smoke.py")]
        for root in roots:
            files = [root] if os.path.isfile(root) else [
                os.path.join(d, f) for d, _, fs in os.walk(root)
                for f in fs if f.endswith(".py")]
            for path in files:
                with open(path) as f:
                    if "jax_compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(path, REPO))
        assert hits == [os.path.join("polyaxon_tpu", "config.py")]


class TestPeakTable:
    @pytest.mark.parametrize("kind,peak", [
        ("TPU v5 lite", 197e12),    # what a v5e reports
        ("TPU v5e", 197e12),
        ("TPU v5p", 459e12),
        ("TPU v4", 275e12),
        ("TPU v3", 123e12),
        ("TPU v2", 45e12),
        ("TPU v6 lite", 918e12),
    ])
    def test_known_kinds(self, kind, peak):
        from polyaxon_tpu.chips import peak_bf16_flops

        assert peak_bf16_flops(kind) == peak

    @pytest.mark.parametrize("kind", ["cpu", "", None, "NVIDIA H100"])
    def test_not_a_tpu_is_none(self, kind):
        from polyaxon_tpu.chips import peak_bf16_flops

        assert peak_bf16_flops(kind) is None

    @pytest.mark.parametrize("kind", ["TPU v9", "TPU7x", "TPU"])
    def test_unknown_tpu_raises(self, kind):
        from polyaxon_tpu.chips import peak_bf16_flops

        with pytest.raises(ValueError, match="no published bf16 peak"):
            peak_bf16_flops(kind)

    def test_serving_recorder_reads_the_same_table(self, monkeypatch):
        """One table: the flight recorder's peak is chips.py's, an
        unknown TPU is an error there too, and the CPU's stand-in stays
        labelled nominal."""
        import jax

        from polyaxon_tpu.serving import profiling

        class Dev:
            def __init__(self, kind):
                self.device_kind = kind

        monkeypatch.setattr(jax, "devices",
                            lambda *a: [Dev("TPU v5 lite")])
        assert profiling.detect_peak_flops() == {
            "peak_flops": 197e12, "peak_flops_source": "device"}
        monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v9")])
        with pytest.raises(ValueError):
            profiling.detect_peak_flops()
        monkeypatch.setattr(jax, "devices", lambda *a: [Dev("cpu")])
        assert profiling.detect_peak_flops() == {
            "peak_flops": profiling.NOMINAL_PEAK_FLOPS,
            "peak_flops_source": "nominal"}


class TestWhoTouchesJax:
    """A parent that has touched JAX holds the chip; these parents
    start children that need it, so they stay off JAX."""

    @pytest.mark.parametrize("modules", [
        "polyaxon_tpu.cli.main, chip_smoke",
        "polyaxon_tpu.runner.local, polyaxon_tpu.tune.controller",
        "polyaxon_tpu.client, polyaxon_tpu.config, polyaxon_tpu.chips",
    ])
    def test_import_leaves_jax_out(self, modules):
        proc = _python(f"import sys; import {modules}; "
                       f"print('jax' in sys.modules)")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip() == "False"

    def test_bench_py_import_leaves_jax_out(self):
        proc = _python("import sys; import bench; "
                       "print('jax' in sys.modules)")
        assert proc.stdout.strip() == "False", proc.stderr[-2000:]


class TestBenchWithoutTpu:
    @pytest.mark.parametrize("argv", [[], ["--all"],
                                      ["--decode", "gpt2-tiny"]],
                             ids=["default", "all", "decode"])
    def test_exits_nonzero_and_prints_no_metric(self, argv):
        """The real script, as the driver would start it, where JAX
        finds no TPU: no metric line, no row, a non-zero exit."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), *argv],
            cwd=REPO, capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "no TPU" in proc.stderr


class TestChipSmokeWithoutChip:
    def _run(self, *argv, cwd=REPO, script=None):
        proc = subprocess.run(
            [sys.executable,
             script or os.path.join(REPO, "chip_smoke.py"), *argv],
            cwd=cwd, capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        lines = proc.stdout.strip().splitlines()
        return proc, lines, json.loads(lines[-1])

    @pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                             ids=["one-chip", "four-chips"])
    def test_cpu_is_not_ok(self, tmp_path, argv):
        """No accelerator: non-zero exit, a well-formed last line that
        says so, and no phase was started (no gpt2-medium on the CPU)."""
        proc, lines, last = self._run(*argv, "--out",
                                      str(tmp_path / "out"))
        assert proc.returncode != 0
        assert last == {"ok": False, "device": {
            "platform": "cpu", "kind": "cpu",
            "count": last["device"]["count"]}}
        assert not any(l.startswith("---") for l in lines)

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        """The script without the program: nothing to drive, so no
        result — whatever device there is."""
        import shutil

        alone = tmp_path / "alone"
        alone.mkdir()
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
        proc, _, last = self._run("--rehearse",
                                  cwd=str(alone),
                                  script=str(alone / "chip_smoke.py"))
        assert proc.returncode != 0 and last["ok"] is False

    def test_a_failed_phase_fails_the_run_even_on_a_tpu(self, tmp_path,
                                                        monkeypatch,
                                                        capsys):
        """The exit code follows the phases, not only the device: with
        a (faked) TPU and one phase failing, the run ends non-zero and
        the last line says ``"ok": false``; with none failing, 0."""
        monkeypatch.syspath_prepend(REPO)
        import chip_smoke

        tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        monkeypatch.setattr(chip_smoke, "probe_device", lambda s: tpu)
        monkeypatch.setattr(chip_smoke, "train_phase", lambda s: None)
        monkeypatch.setattr(
            chip_smoke, "serve_phase",
            lambda s: s.check(False, "serve: closed port"))
        rc = chip_smoke.main(["--out", str(tmp_path / "a")])
        last = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert rc == 1 and last == {"ok": False, "device": tpu}

        def boom(s):
            raise RuntimeError("phase blew up")
        monkeypatch.setattr(chip_smoke, "serve_phase", boom)
        rc = chip_smoke.main(["--out", str(tmp_path / "b")])
        last = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert rc == 1 and last["ok"] is False

        monkeypatch.setattr(chip_smoke, "serve_phase", lambda s: None)
        rc = chip_smoke.main(["--out", str(tmp_path / "c")])
        last = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert rc == 0 and last == {"ok": True, "device": tpu}

    def test_four_chip_option_runs_only_the_four_chip_phases(
            self, tmp_path, monkeypatch, capsys):
        monkeypatch.syspath_prepend(REPO)
        import chip_smoke

        ran = []
        four = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
        monkeypatch.setattr(chip_smoke, "probe_device", lambda s: four)
        for name in ("train_phase", "serve_phase", "dp4_phase",
                     "meshed_phase"):
            monkeypatch.setattr(chip_smoke, name,
                                lambda s, name=name: ran.append(name))
        rc = chip_smoke.main(["--chips", "4", "--out",
                              str(tmp_path / "o")])
        last = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert ran == ["dp4_phase", "meshed_phase"]
        assert rc == 0 and last["device"]["count"] == 4
        # One chip asked for, four found (or the reverse): not ok.
        ran.clear()
        rc = chip_smoke.main(["--out", str(tmp_path / "p")])
        assert rc == 1 and ran == []


class TestGangStopsTogether:
    @staticmethod
    def _gang_file(tmp_home, script):
        import yaml

        spec = {"version": 1.1, "kind": "operation", "name": "gang",
                "component": {"kind": "component", "name": "g", "run": {
                    "kind": "tpujob",
                    "slice": {"type": "v5litepod-1", "chipsPerHost": 1},
                    "worker": {"replicas": 2, "container": {
                        "image": "x",
                        "command": [sys.executable, "-c", script]}}}}}
        path = os.path.join(str(tmp_home), "gang.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(spec, f)
        return path

    def test_gang_on_a_host_with_chips_is_refused_at_once(
            self, tmp_home, monkeypatch):
        """Two replicas, one host's chips, nothing binding replica to
        chip: FAILED before any process starts, with the reason — and
        decided from device nodes, not by asking JAX."""
        from polyaxon_tpu.client import FileRunStore
        from polyaxon_tpu.polyaxonfile import check_polyaxonfile
        from polyaxon_tpu.runner import LocalExecutor, local

        marker = os.path.join(str(tmp_home), "started")
        path = self._gang_file(
            tmp_home, f"open({marker!r}, 'w').close()")
        monkeypatch.setattr(local, "_host_tpu_chips", lambda: 1)
        monkeypatch.delenv("JAX_PLATFORMS")
        store = FileRunStore(str(tmp_home))
        record = LocalExecutor(store=store).run_operation(
            check_polyaxonfile(path))
        assert record["status"] == "failed"
        message = store.get_statuses(record["uuid"])[-1].message
        assert "one process at a time" in message
        assert "worker-0, worker-1" in message
        assert not os.path.exists(marker)
        # Held to the CPU, the same gang is the virtual-device harness.
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        record = LocalExecutor(store=store).run_operation(
            check_polyaxonfile(path))
        assert record["status"] == "succeeded"

    def test_one_dead_replica_stops_the_gang(self, tmp_home):
        """A replica that cannot start (on a host with chips: the one
        that finds its chip held) must end the run FAILED at once, not
        leave its peers waiting at the coordinator."""
        import time

        from polyaxon_tpu.client import FileRunStore
        from polyaxon_tpu.polyaxonfile import check_polyaxonfile
        from polyaxon_tpu.runner import LocalExecutor

        path = self._gang_file(
            tmp_home,
            "import os, sys, time\n"
            "if os.environ['PTPU_PROCESS_ID'] == '1':\n"
            "    print('TPU is already in use'); sys.exit(3)\n"
            "time.sleep(600)\n")
        store = FileRunStore(str(tmp_home))
        t0 = time.time()
        record = LocalExecutor(store=store).run_operation(
            check_polyaxonfile(path))
        assert time.time() - t0 < 60
        assert record["status"] == "failed"
        message = store.get_statuses(record["uuid"])[-1].message
        assert "worker-1 exited 3" in message
        assert "stopped the gang" in message
        assert "TPU is already in use" in message


class TestWeightsAreProgramArguments:
    """A jitted program that closes over the model's variables carries a
    copy of the model as constants.  At gpt2-medium width that ran the
    chip's host out of memory on the server's first request; at test
    widths nobody notices — so it is held here, on the lowered text."""

    @staticmethod
    def _weights_inside(partial_fn, *operands) -> bool:
        import re

        lowered = partial_fn.func.lower(*partial_fn.args, *operands)
        # A baked-in array is a dense hex literal; the largest honest
        # constant of these programs is a few bytes.
        return bool(re.search(r'dense<"0x[0-9A-Fa-f]{4096,}',
                              lowered.as_text()))

    @pytest.fixture(scope="class")
    def tiny(self):
        from polyaxon_tpu.models.registry import get_model

        return get_model("gpt2-tiny").init_params(batch_size=1)

    def test_a_closure_would_be_caught(self, tiny):
        """The detector detects: the old spelling embeds the weights."""
        import functools

        import jax
        import numpy as np

        from polyaxon_tpu.models import generate as G

        model, variables = tiny
        closed = functools.partial(jax.jit(
            lambda toks: G.prefill(model, variables, toks)))
        assert self._weights_inside(closed, np.zeros((1, 8), np.int32))

    @pytest.mark.parametrize("paged", [False, True],
                             ids=["fixed-lane", "paged"])
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_decode_window_program(self, tiny, paged, sampled):
        import numpy as np

        from polyaxon_tpu.models import generate as G
        from polyaxon_tpu.serving.paged import PagedSlotKVManager
        from polyaxon_tpu.serving.slots import (LaunchPacking,
                                                SlotKVManager)

        model, variables = tiny
        _, cache = G.prefill(model, variables,
                             np.zeros((1, 8), np.int32))
        n = 4
        if paged:
            mgr = PagedSlotKVManager(
                model, variables, n, page_tokens=16,
                max_position=model.cfg.max_position)
            mgr.ensure_shaped(cache)
            P = 2
            fn = mgr._build_step(2, sampled, P)
            leading = mgr._leading(P, 1)
        else:
            mgr = SlotKVManager(model, variables, n)
            mgr._ensure_stacked(cache)
            fn = mgr._build_step(2, sampled)
            leading = (np.int32(2),)
        # the launch's packed host words, and the feedback token
        words, _ = LaunchPacking.pack((*leading, *mgr.state.operands(
            "sampled" if sampled else "plain")))
        operands = (mgr.kv_pool(), words, np.zeros((n,), np.int32))
        assert not self._weights_inside(fn, *operands)

    @pytest.mark.parametrize("kind", ["engine-prefill", "engine-extend",
                                      "server-greedy", "server-sampled",
                                      "server-pfill"])
    def test_prefill_and_solo_programs(self, tiny, kind):
        import jax
        import numpy as np

        from polyaxon_tpu.models import generate as G
        from polyaxon_tpu.serving import ModelServer

        model, variables = tiny
        toks = np.zeros((1, 8), np.int32)
        ms = ModelServer(model, variables, model_name="tiny", n_slots=2)
        try:
            if kind == "engine-prefill":
                fn, operands = ms.engine._pf_fn(8, True), (toks,)
            elif kind == "engine-extend":
                _, cache = G.prefill(model, variables, toks)
                fn = ms.engine._pf_fn(8, False)
                operands = (cache, toks, np.int32(8))
            elif kind == "server-greedy":
                fn = ms._fn(("greedy", 1, 8, 4, 0.0, None, None, None,
                             1, None))
                operands = (toks, jax.random.PRNGKey(0))
            elif kind == "server-sampled":
                fn = ms._fn(("sample_pos", 1, 8, 4, None, None, None,
                             None, 1, None))
                operands = (toks, G.sample_stream_keys(0, 1),
                            np.float32(0.8), np.int32(0),
                            np.float32(0.0))
            else:
                fn = ms._split_fns(1, 8, "pfill", None)
                operands = (toks,)
            assert not self._weights_inside(fn, *operands)
        finally:
            ms.close()
