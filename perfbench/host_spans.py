"""What the host was doing while the device waited.

The program puts its host sections on the profiler's clock as
``ptpu*`` spans (``polyaxon_tpu/spans.py``) and keeps counters of them
that are on in every run.  This file reads both for the per-layer
metrics; where the program has neither (a parent commit), every
function returns None and raises nothing.

From the traced run's xplane: the idle gaps of each device (the
complement of ``trace_reduce``'s busy union inside its window), each
given to the innermost ``ptpu*`` span that covers it on the thread
that holds most of the spans, the rest ``unattributed``; before that
the clock check: the program a ``ptpu_step`` section dispatched begins
after the section's enqueue began and ends before its sync ended, once
the device planes are shifted by one offset.  The shift, the violations
left and the five longest gaps with their owners go to earlier lines of
the run (``run.py`` writes ``breakdown.idle_gaps`` itself and is not
this file's to edit: ``top_gaps`` has the form that list takes).

From the run store: the ``host_*_s`` counters of a job's logged blocks
inside the window.  From the engine's step records: their host
sections.

``python perfbench/host_spans.py <dir or file>`` prints the report of a
trace by hand.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce
from procs import say
from trace_reduce import Event, Interval

HOST_PLANE = "/host:CPU"
SPAN = re.compile(r"^ptpu[_/]")
STEP_MARKER = "ptpu_step"
ENQUEUE, SYNC = "ptpu/enqueue", "ptpu/sync"
TRAIN_STEP = "ptpu/train_step"
UNATTRIBUTED = "unattributed"
JOB_COUNTERS = ("host_data_wait_s", "host_enqueue_s", "host_log_s")


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------


def span_thread(planes) -> Tuple[Optional[str], List[Event]]:
    """The host line that holds the most ``ptpu*`` spans (the engine's
    thread, ``train.py``'s main thread) and those spans in start
    order, outer before inner."""
    best: Tuple[Optional[str], List[Event]] = (None, [])
    for line, events in planes.get(HOST_PLANE, {}).items():
        spans = [e for e in events if SPAN.match(e.name)]
        if len(spans) > len(best[1]):
            best = (line, spans)
    return best[0], sorted(best[1], key=lambda e: (e.start, -e.end))


def innermost(spans: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """The spans of one thread, which nest like a call stack, as
    disjoint pieces ``(start, end, name)`` in time order, each named by
    the innermost span that covers it."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    at = 0.0

    def emit(until: float) -> None:
        nonlocal at
        if until > at:
            pieces.append((at, until, stack[-1].name))
            at = until

    for ev in spans:
        while stack and stack[-1].end <= ev.start:
            emit(stack[-1].end)
            stack.pop()
        if stack:
            emit(ev.start)
        else:
            at = ev.start
        stack.append(ev)
    while stack:
        emit(stack[-1].end)
        stack.pop()
    return pieces


def gaps_of(busy: Sequence[Interval]) -> List[Interval]:
    """The complement of a merged busy union inside its own window."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def clock_check(planes, spans: Sequence[Event]) -> Optional[dict]:
    """The device clock against the host's, over the ``ptpu_step``
    sections.  A section's program is the last to begin of the events
    of device 0's ``XLA Modules`` line that lie mostly inside it: the
    one its own enqueue dispatched (what runs before it there was
    enqueued earlier, by an admission, and the device came to it
    late).  With the device shifted back by ``d``, the program must
    begin after its section's ``ptpu/enqueue`` began and end before
    its ``ptpu/sync`` ended, so ``d`` lies between the latest end's
    overhang and the earliest start's lead.  ``d`` is 0 where that
    already holds, else the middle of what the sections allow.  None
    where the trace has no such section."""
    devices = sorted(n for n in planes if trace_reduce.DEVICE_PLANE.match(n))
    modules = planes[devices[0]].get(trace_reduce.MODULES_LINE, []) \
        if devices else []
    lead, overhang = [], []
    for step in (e for e in spans if e.name == STEP_MARKER):
        inside = [e for e in spans if step.start <= e.start
                  and e.end <= step.end and e.name in (ENQUEUE, SYNC)]
        enq = min((e.start for e in inside if e.name == ENQUEUE),
                  default=step.start)
        sync = max((e.end for e in inside if e.name == SYNC),
                   default=step.end)
        programs = [m for m in modules
                    if min(m.end, step.end) - max(m.start, step.start)
                    > 0.5 * (m.end - m.start)]
        if programs:
            program = max(programs, key=lambda m: m.start)
            lead.append(program.start - enq)
            overhang.append(program.end - sync)
    if not lead:
        return None
    low, high = max(overhang), min(lead)
    shift = 0.0 if low <= 0.0 <= high else (low + high) / 2.0
    return {"sections": len(lead), "shift_ns": shift,
            "violations": sum(1 for a, b in zip(lead, overhang)
                              if a - shift < 0 or b - shift > 0),
            "violations_unshifted": sum(1 for a, b in zip(lead, overhang)
                                        if a < 0 or b > 0),
            "overhang_ms": [min(overhang) / 1e6, max(overhang) / 1e6],
            "lead_ms": [min(lead) / 1e6, max(lead) / 1e6]}


def attribute(planes, rehearse: bool = False) -> Optional[dict]:
    """The report of one trace; None where it holds no ``ptpu*`` span
    or no device operation.  Seconds are averaged over the devices as
    ``trace_reduce.reduce`` averages them."""
    thread, spans = span_thread(planes)
    found = trace_reduce.device_ops(
        planes, trace_reduce.REHEARSAL_PLANE, trace_reduce.REHEARSAL_LINE) \
        if rehearse else trace_reduce.device_ops(planes)
    if not spans or not found:
        return None
    clock = None if rehearse else clock_check(planes, spans)
    shift = clock["shift_ns"] if clock else 0.0
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    by_owner: Dict[str, float] = {}
    gaps_out = []
    idle = window = 0.0
    devices = 0
    for device, ops in found.items():
        busy = trace_reduce.merge((e.start - shift, e.end - shift)
                                  for e in ops)
        if not busy:
            continue
        devices += 1
        window += busy[-1][1] - busy[0][0]
        for a, b in gaps_of(busy):
            idle += b - a
            owners: Dict[str, float] = {}
            at = a
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(pieces) and pieces[i][0] < b:
                s, e, name = pieces[i]
                lo, hi = max(s, at), min(e, b)
                if hi > lo:
                    if lo > at:
                        owners[UNATTRIBUTED] = \
                            owners.get(UNATTRIBUTED, 0.0) + lo - at
                    owners[name] = owners.get(name, 0.0) + hi - lo
                    at = hi
                i += 1
            if b > at:
                owners[UNATTRIBUTED] = owners.get(UNATTRIBUTED, 0.0) + b - at
            for name, t in owners.items():
                by_owner[name] = by_owner.get(name, 0.0) + t
            gaps_out.append((b - a, a - busy[0][0], device,
                             max(owners, key=owners.get)))
    if not devices:
        return None
    gaps_out.sort(reverse=True)
    return {
        "thread": thread, "devices": devices, "clock": clock,
        "window_s": window / devices / 1e9, "idle_s": idle / devices / 1e9,
        "by_owner": {k: v / devices / 1e9 for k, v in
                     sorted(by_owner.items(), key=lambda kv: -kv[1])},
        "train_steps": sum(1 for e in spans if e.name == TRAIN_STEP),
        # the form of `breakdown.idle_gaps`: [owner, seconds], longest
        # first, as `device_ops` is [name, seconds]
        "top_gaps": [[owner, t / 1e9] for t, _, _, owner in gaps_out[:5]],
        "top_gaps_where": [f"{owner} {t / 1e6:.3f} ms at "
                           f"{at / 1e9:.4f} s on {device}"
                           for t, at, device, owner in gaps_out[:5]],
    }


def report(ctx) -> Optional[dict]:
    """``attribute`` of this run's trace, once a run, with its earlier
    lines."""
    if hasattr(ctx, "host_spans_report"):
        return ctx.host_spans_report
    ctx.host_spans_report = None
    file = trace_reduce.find_xplane(ctx.out)
    if file is None:
        return None
    rep = attribute(trace_reduce.load(file), rehearse=ctx.rehearse)
    if rep is None:
        say("host_spans: the trace holds no ptpu* span on "
            f"{HOST_PLANE}: nothing to attribute")
        return None
    ctx.host_spans_report = rep
    for line in lines(rep):
        say("host_spans: " + line)
    return rep


def lines(rep: dict) -> List[str]:
    idle = rep["idle_s"]
    named = idle - rep["by_owner"].get(UNATTRIBUTED, 0.0)
    out = [f"spans of thread {rep['thread']!r}; device idle "
           f"{idle:.6f} s of {rep['window_s']:.6f} s a device over "
           f"{rep['devices']} device(s), {named:.6f} s of it under a "
           f"named span; {rep['train_steps']} {TRAIN_STEP} steps"]
    out.append("idle by innermost span (s): " + json.dumps(
        {k: round(v, 6) for k, v in rep["by_owner"].items()}))
    clock = rep["clock"]
    if clock is None:
        out.append(f"clock: no {STEP_MARKER} section with a device "
                   f"program in it: not checked, no shift applied")
    else:
        out.append(
            f"clock: {clock['sections']} {STEP_MARKER} sections; their "
            f"programs end {clock['overhang_ms'][0]:.3f} to "
            f"{clock['overhang_ms'][1]:.3f} ms after their sync ended "
            f"and begin {clock['lead_ms'][0]:.3f} to "
            f"{clock['lead_ms'][1]:.3f} ms after their enqueue began; "
            f"{clock['violations_unshifted']} violations unshifted; shift "
            f"applied {clock['shift_ns'] / 1e6:.3f} ms, violations left "
            f"{clock['violations']}")
    out.append("idle_gaps: " + json.dumps(rep["top_gaps"]))
    out.append("the longest gaps: " + "; ".join(rep["top_gaps_where"]))
    return out


# ---------------------------------------------------------------------------
# the run store
# ---------------------------------------------------------------------------


def job_blocks(ctx) -> Optional[dict]:
    """The ``host_*_s`` counters of the window's logged blocks (all but
    the one that opens it: a block's counters cover the steps before
    it): ``{"steps", "span_s", <counter>: seconds}``.  None where the
    job logged no such counter."""
    if hasattr(ctx, "host_spans_blocks"):
        return ctx.host_spans_blocks
    ctx.host_spans_blocks = None
    from polyaxon_tpu.client import FileRunStore
    from run import load_module

    store = FileRunStore(os.path.join(ctx.out, "home"))
    runs = store.list_runs()
    if not runs:
        return None
    uuid = runs[0]["uuid"]
    events = [(e["step"], e["timestamp"], e["value"])
              for e in store.read_events(uuid, "metric", "loss")]
    chips = ctx.cell["chips"]
    window = load_module("drivers", "job").window_rate(
        events, ctx.mix["setup_blocks"], ctx.seconds, 1, chips)
    if window is None or len(window["blocks"]) < 2:
        return None
    first, last = window["blocks"][0][0], window["blocks"][-1][0]
    out = {"steps": last - first, "span_s": window["span_s"]}
    for name in JOB_COUNTERS:
        values = [e["value"] for e in store.read_events(uuid, "metric", name)
                  if first < e["step"] <= last]
        if not values:
            return None
        out[name] = sum(values)
    ctx.host_spans_blocks = out
    say("host_spans: the window's blocks, steps "
        f"{first}-{last}: " + json.dumps(out))
    return out


def engine_field_ms(ctx, field: str) -> Optional[float]:
    """One host section of the engine's tick per decode step, in ms:
    the sum of ``field`` over the engine's step records (``GET
    /trace``) inside the window over the sum of their ``window``s.
    None where the records have no such field."""
    steps = [e["args"] for e in ctx.collected.get("engine_steps") or []]
    n = sum(a["window"] for a in steps)
    if not n or any(field not in a for a in steps):
        return None
    return 1e3 * sum(a[field] for a in steps) / n


if __name__ == "__main__":
    _file = trace_reduce.find_xplane(sys.argv[1])
    _rep = attribute(trace_reduce.load(_file)) if _file else None
    print("\n".join(lines(_rep)) if _rep
          else f"nothing to attribute under {sys.argv[1]}")
