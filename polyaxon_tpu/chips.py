"""Published per-chip peaks, keyed by ``device_kind``.

The ONE peak table of the repo: every MFU number (``bench.py``, the
serving flight recorder) divides by a value from here.  Source: Google
Cloud TPU documentation, the "System architecture" page of each
generation (peak compute per chip, bf16).  Keys are substrings of
``jax.devices()[0].device_kind`` as the runtime reports it (a v5e
reports ``"TPU v5 lite"``); first match wins, so the more specific
spellings come first.
"""

from __future__ import annotations

from typing import Optional

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
