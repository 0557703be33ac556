"""Published per-chip peaks, keyed by ``device_kind``, and the host's
chip device nodes.

The ONE peak table of the repo: every MFU number (``bench.py``, the
serving flight recorder) divides by a value from here.  Source: Google
Cloud TPU documentation, the "System architecture" page of each
generation (peak compute per chip, bf16).  Keys are substrings of
``jax.devices()[0].device_kind`` as the runtime reports it (a v5e
reports ``"TPU v5 lite"``); first match wins, so the more specific
spellings come first.
"""

from __future__ import annotations

import errno
import glob
import os
import time
from typing import List, Optional, Sequence

PEAK_BF16_FLOPS = (
    ("v6 lite", 918e12),   # Trillium / v6e
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),   # v5e
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_bf16_flops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s of one chip of this ``device_kind``.

    ``None`` for a device that is not a TPU (the caller decides what a
    CPU run may report).  A TPU that is not in the table is an error,
    never a default: a utilization over a guessed peak is a wrong
    number under a device metric's name."""
    kind = (device_kind or "").lower()
    if "tpu" not in kind:
        return None
    for key, peak in PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no published bf16 peak for TPU device_kind {device_kind!r}; "
        f"add it to polyaxon_tpu/chips.py with its source")


def chip_nodes() -> List[str]:
    """The device nodes of this host's TPU chips (``/dev/accel*`` on
    older generations, one ``/dev/vfio/<n>`` group per chip on v5e and
    later).  Counted without JAX: a process that has touched JAX holds
    the chips its children need."""
    return sorted(glob.glob("/dev/accel[0-9]*")
                  + glob.glob("/dev/vfio/[0-9]*"))


def wait_for_chips(timeout_s: float = 90.0, poll_s: float = 0.25,
                   nodes: Optional[Sequence[str]] = None) -> float:
    """Before the first JAX call of a process that takes the host's
    chips: wait while another process still holds one, at most
    ``timeout_s``; returns the seconds waited.

    A vfio group can be opened by one process at a time, and libtpu
    fails at once on a held one (``open(/dev/vfio/2): Device or
    resource busy``): JAX then has no backend and the job dies.  A
    holder that was just killed keeps its groups until its last thread
    has released them, which for a four-chip job killed with SIGKILL
    took some 25 s on a v5e host (PERF.md section 6, PR 27): a job
    restarted right after its predecessor was stopped would die of
    that.  So each group
    is opened and closed again here, which holds nothing; ``EBUSY``
    means wait, anything else is libtpu's to report.  Only the vfio
    groups are probed (opening an ``/dev/accel*`` node is not known to
    be free of effects), and nothing where the chips were bound by hand
    (``TPU_VISIBLE_CHIPS``, ``TPU_VISIBLE_DEVICES``)."""
    if os.environ.get("TPU_VISIBLE_CHIPS") \
            or os.environ.get("TPU_VISIBLE_DEVICES"):
        return 0.0
    if nodes is None:
        nodes = [n for n in chip_nodes() if n.startswith("/dev/vfio/")]
    t0 = time.monotonic()
    busy = list(nodes)
    while busy:
        still = []
        for node in busy:
            try:
                os.close(os.open(node, os.O_RDWR))
            except OSError as e:
                if e.errno == errno.EBUSY:
                    still.append(node)
        busy = still
        if not busy or time.monotonic() - t0 >= timeout_s:
            break
        time.sleep(poll_s)
    return time.monotonic() - t0
