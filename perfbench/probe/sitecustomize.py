"""The benchmark's probe inside a child process.

``perfbench/procs.py`` puts this directory on a child's ``PYTHONPATH``,
so Python imports this file at start-up.  Only the process that holds
the chip can ask it anything, and the harness's parent must stay off
JAX, so the child answers for itself.  Once the PROGRAM has initialised
a JAX backend (this never does), a daemon thread writes to
``$PERFBENCH_PROBE_PREFIX.<pid>.json`` what that JAX reports: platform,
device kind, device count, and the allocator's ``bytes_in_use``,
``bytes_reserved`` and ``peak_bytes_in_use`` of the fullest local
device.  It reads the allocator then (eight readings in a quarter of a
second, the fullest kept), and afterwards only when the parent asks by
creating ``$PERFBENCH_PROBE_PREFIX.<pid>.ask``: twice a second all
through a run cost 12 ms a time with the record's write, so there is no
reading inside a measured window; the parent asks when the window has
closed and the load is still on.  The record says how many readings
were taken and what the allocator calls cost.  Nothing of the
program is touched; without the variable the thread is not started.  A
``sitecustomize`` further along the path still runs.
"""

import importlib.machinery
import importlib.util
import json
import os
import sys
import threading
import time


def _full(stats: dict) -> int:
    # The runtime sets a loaded program's temporaries aside as
    # "reserved" and leaves them out of (peak_)bytes_in_use: a v5e
    # training step read 2.9 GB in use beside 12.6 GB reserved.  So the
    # peak is in use + reserved as sampled, and never under the
    # allocator's own high-water mark.
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0))


def _watch(prefix: str) -> None:
    while True:
        bridge = sys.modules.get("jax._src.xla_bridge")
        if bridge is not None and getattr(bridge, "_backends", None):
            break
        time.sleep(0.2)
    import jax

    devices = jax.local_devices()
    path = f"{prefix}.{os.getpid()}.json"
    record = {"pid": os.getpid(), "platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": 0, "samples": 0, "cost_s": 0.0}
    ask = f"{prefix}.{os.getpid()}.ask"
    while True:
        _read(devices, record)
        with open(path + ".tmp", "w") as f:
            json.dump(record, f)
        os.replace(path + ".tmp", path)
        if os.path.exists(ask):
            os.remove(ask)      # answered
        while not os.path.exists(ask):
            time.sleep(0.25)


def _read(devices, record: dict, times: int = 8, gap: float = 0.03) -> None:
    """The fullest of ``times`` readings ``gap`` seconds apart goes into
    ``record``.  One reading is an instant: between two dispatches the
    server read 3.96 GB in use, with a copy of its pool in flight 6.37
    GB; eight readings span three of its steps, two of a training job."""
    for i in range(times):
        t = time.perf_counter()
        for device in devices:
            stats = device.memory_stats() or {}
            if _full(stats) > record["memory_peak_bytes"]:
                record.update(
                    memory_peak_bytes=int(_full(stats)),
                    bytes_in_use=stats.get("bytes_in_use"),
                    bytes_reserved=stats.get("bytes_reserved"),
                    peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                    bytes_limit=stats.get("bytes_limit"))
        record["samples"] += 1
        record["cost_s"] += time.perf_counter() - t
        if i + 1 < times:
            time.sleep(gap)


def _next_sitecustomize() -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    rest = [p for p in sys.path if os.path.abspath(p or ".") != here]
    spec = importlib.machinery.PathFinder.find_spec("sitecustomize", rest)
    if spec is not None and spec.loader is not None:
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


_prefix = os.environ.get("PERFBENCH_PROBE_PREFIX")
if _prefix:
    threading.Thread(target=_watch, args=(_prefix,), daemon=True,
                     name="perfbench-probe").start()
_next_sitecustomize()
