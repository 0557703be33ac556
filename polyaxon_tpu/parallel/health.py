"""Slice health checks (SURVEY.md 5.3: failure detection).

The operator supervises pods and the control plane sweeps zombie
heartbeats; this module covers the third failure mode — the process is
alive but the ACCELERATOR fabric under it is not (wedged TPU runtime,
a chip dropped off the ICI torus after preemption, a runtime that
hangs instead of raising).  ``check_slice_health`` runs a tiny all-device
collective with a deadline in a worker thread: a healthy slice answers
in milliseconds; a wedged one hangs, the deadline fires, and the caller
can checkpoint-and-exit so the operator reschedules the gang
(TPU slices cannot resize elastically — restart is the recovery).

``train.py`` runs it right after distributed bootstrap, before touching
the checkpoint.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass
from typing import Optional

logger = logging.getLogger(__name__)


@dataclass
class SliceHealth:
    ok: bool
    detail: str
    latency_s: Optional[float] = None
    n_devices: int = 0


def check_slice_health(mesh=None, timeout_s: float = 60.0) -> SliceHealth:
    """Prove every device in the mesh (default: all devices) can compute
    and communicate: an all-device psum of ones must return n_devices.

    Never raises; never hangs past ``timeout_s`` (the probe runs in a
    daemon thread — a wedged runtime strands that thread, not the
    caller, mirroring bench.py's never-kill-mid-init lesson).
    """
    import jax

    devices = list(mesh.devices.flat) if mesh is not None \
        else jax.devices()
    n = len(devices)
    result: dict = {}

    def probe():
        try:
            import numpy as np

            import jax.numpy as jnp
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec as P)

            probe_mesh = Mesh(np.asarray(devices), ("all",))
            ones = jnp.ones((n,), jnp.float32)
            arr = jax.device_put(
                ones, NamedSharding(probe_mesh, P("all")))
            total = jax.jit(
                jnp.sum,
                out_shardings=NamedSharding(probe_mesh, P()))(arr)
            result["value"] = float(jax.device_get(total))
        except Exception as e:  # noqa: BLE001 - report, don't raise
            result["error"] = f"{type(e).__name__}: {e}"

    start = time.monotonic()
    thread = threading.Thread(target=probe, daemon=True,
                              name="ptpu-slice-health")
    thread.start()
    thread.join(timeout=timeout_s)
    latency = time.monotonic() - start

    if thread.is_alive():
        return SliceHealth(
            ok=False, latency_s=None, n_devices=n,
            detail=f"collective probe hung past {timeout_s:.0f}s "
                   f"(runtime wedged?); probe thread left to finish")
    if "error" in result:
        return SliceHealth(ok=False, latency_s=latency, n_devices=n,
                           detail=result["error"])
    value = result.get("value")
    if value != float(n):
        return SliceHealth(
            ok=False, latency_s=latency, n_devices=n,
            detail=f"psum over {n} devices returned {value}")
    return SliceHealth(ok=True, latency_s=latency, n_devices=n,
                       detail=f"{n} devices healthy")
