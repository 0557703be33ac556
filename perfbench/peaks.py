"""Published per-chip peaks, keyed by ``device_kind``.

The benchmark's own copy of ``polyaxon_tpu/chips.py`` (so that no PR
that claims a gain can move the yardstick), with memory bandwidth added.
Source: Google Cloud TPU documentation, "TPU v5e" / "System
architecture" of each generation: peak dense bf16 FLOP/s and HBM
bandwidth of one chip.  Keys are substrings of
``jax.devices()[0].device_kind`` as the runtime reports it (a v5e says
``"TPU v5 lite"``); the first match wins.
"""

from __future__ import annotations

# (substring of device_kind, bf16 FLOP/s, HBM bytes/s)
PEAKS = (
    ("v5 lite", 197e12, 819e9),     # v5e
    ("v5litepod", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
)


def peaks(device_kind: str) -> dict:
    """``{"flops": ..., "bytes_per_s": ...}`` of one chip.  A device
    that is not in the table is an error, never a default: a share of a
    guessed peak is a wrong number under a device metric's name."""
    kind = (device_kind or "").lower()
    for key, flops, bw in PEAKS:
        if key in kind:
            return {"flops": flops, "bytes_per_s": bw}
    raise ValueError(
        f"no published peak for device_kind {device_kind!r}; add it to "
        f"perfbench/peaks.py with its source")
