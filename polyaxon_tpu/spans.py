"""Named host sections on the profiler's clock, and named parts of the
device's programs.

The host half: one list of names, one helper.  ``span(name)`` enters a
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

The device half of the same idea: a span names a host section, a SCOPE
names a part of a device program.  ``scope(name)`` is
``jax.named_scope`` for a name of ``SCOPE_NAMES``: it writes the name
into the JAX name stack of every operation traced inside it, beside
what Flax writes there for a module and JAX for ``jvp`` and
``transpose``.  A device trace carries that stack with every
operation (the ``tf_op`` of the event's metadata), and
``perfbench/device_scopes.py`` splits a program's device time by it.
A scope stands ONLY where the stack is silent otherwise: around the
attention over a cache, the writes into a pool, the sampler, the
expert layers, a recurrent state's step, the optimizer; never around
a Flax module, which names itself.  It is metadata of the lowered
program: no operation, no operand, nothing at run time, and the
program's text without debug info is the same with and without it
(tests/test_spans.py).
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


# Closed, as SPAN_NAMES is: a scope used anywhere in the package is in
# here (tests/test_spans.py), and perfbench/device_scopes.py maps each
# to the part of a program it names.  No "/" in a name: it separates
# the segments of the name stack.
SCOPE_NAMES = (
    "ptpu_attend",          # scores, softmax, values over the keys read
    "ptpu_kv_write",        # a write into a leaf of a cache or a pool
    "ptpu_latent_expand",   # latent rows through W_kvb to K, V a head
    "ptpu_route",           # token-choice routing: scores and top-k
    "ptpu_experts",         # the held experts: sort, grouped matmuls, sum
    "ptpu_scan",            # the selective scan over a piece
    "ptpu_state_step",      # a recurrent state's one-position update
    "ptpu_sample",          # logits shaped and a token drawn, or arg-max
    "ptpu_optimizer",       # optimizer.update and the parameters' update
)


def scope(name: str):
    """``with scope(name): ...`` while TRACING: the operations traced
    inside carry ``name`` in their name stack — see the module."""
    import jax

    if name not in SCOPE_NAMES:
        raise ValueError(f"{name!r} is not in spans.SCOPE_NAMES")
    return jax.named_scope(name)


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
