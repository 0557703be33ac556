"""Named host sections on the profiler's clock.

One list of names, one helper.  ``span(name)`` enters a
``jax.profiler.TraceAnnotation``: while a trace is being taken the
section lies on the ``/host:CPU`` plane, on its thread's line, on the
clock the device planes use, so an idle gap of the device can be given
to what the host was doing (``perfbench/host_spans.py``).  With an
``acc`` it also adds its seconds on the host's ``perf_counter`` to
``acc[name]``: counters that are always on, for the runs nobody traces
(``train.py``'s logged blocks, the engine's step records).  With no
trace running a span costs two clock reads and a no-op annotation.

``start_trace`` is the one place the program starts a profiler trace.
The Python tracer is off unless asked for: it instruments every call
on every thread, so a trace taken with it measures a slower host than
the untraced run (the serving cell's device idled 7.4-11.1 % of a
trace with it and 4.6-7.2 % without, PERF.md section 6).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from .analysis.xprof import STEP_MARKER

TRAIN_STEP = "ptpu/train_step"

# Closed: a name used anywhere in the package is in here
# (tests/test_spans.py), and the benchmark's readers match "ptpu".
SPAN_NAMES = (
    # train.py's loop: the step, and inside it
    TRAIN_STEP,
    "ptpu/data_wait",       # next(batches) and its device_put
    "ptpu/enqueue",         # the step program's call (also the engine's)
    "ptpu/checkpoint",      # ckpt.save
    "ptpu/log_sync",        # the float()s that wait for the device
    "ptpu/eval",
    "ptpu/log_write",       # run.log_metrics + print
    # the engine's tick, on the engine's thread, nested as the code nests
    "ptpu/idle_wait",       # the loop's wait when there is no work
    "ptpu/sweep",           # _sweep_lifecycle, _maybe_preempt
    "ptpu/prefill",         # one _advance_prefill
    "ptpu/admit",           # first token, insertion into the pool
    "ptpu/decode",          # one _decode_step, holding:
    "ptpu/lock_wait",       # taking device_lock
    STEP_MARKER,            # dispatch + sync (analysis/xprof.py's anchor)
    "ptpu/upload",          # host operands to the device
    "ptpu/sync",            # device_get
    "ptpu/commit",          # tokens out, eviction, _complete, tel.step
    "ptpu/board",           # the debug snapshot
)


class span:
    """``with span(name, acc, **stats): ...`` — see the module."""

    __slots__ = ("_name", "_acc", "_annotation", "_t0")

    def __init__(self, name: str, acc: Optional[Dict[str, float]] = None,
                 **stats):
        import jax

        self._name = name
        self._acc = acc
        self._annotation = jax.profiler.TraceAnnotation(name, **stats)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        elapsed = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._acc is not None:
            self._acc[self._name] = self._acc.get(self._name, 0.0) + elapsed
        return False


def step_span(step: int):
    """One iteration of ``train.py``'s loop, as the profiler's own
    step marker (``StepTraceAnnotation``)."""
    import jax

    return jax.profiler.StepTraceAnnotation(TRAIN_STEP, step_num=step)


def take(acc: Dict[str, float], name: str) -> float:
    """``acc[name]`` in seconds, and reset: the sum since the last
    take."""
    return round(acc.pop(name, 0.0), 6)


def start_trace(log_dir: str, python_tracer: bool = False,
                hlo_proto: bool = True) -> None:
    """``jax.profiler.start_trace`` with the Python tracer off (on for
    debugging: every Python call of every thread) and, by default, the
    HLO protos in the dump; ``jax.profiler.stop_trace`` ends it."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python_tracer else 0
    options.enable_hlo_proto = hlo_proto
    jax.profiler.start_trace(log_dir, profiler_options=options)
