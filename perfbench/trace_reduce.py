"""From a profiler trace to numbers: the benchmark's own reduction.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/`` with ``jax.profiler.ProfileData``
(nothing but JAX; imported here with ``JAX_PLATFORMS=cpu`` and only
after the child that held the chip has gone).  The union of intervals
is a copy of ``polyaxon_tpu/analysis/xprof.py``'s; what is a device, an
operation, a kernel and a collective is written down here from a real
v5e trace (PERF.md, "What a v5e trace holds").

``python perfbench/trace_reduce.py <dir or file>`` prints a summary of
every plane and line: how to look at a trace by hand.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, \
    Tuple

Interval = Tuple[float, float]

# A v5e trace has one plane per chip, "/device:TPU:<n>"; its line
# "XLA Ops" holds one event per executed HLO operation (nested: a
# `while` spans its body's operations), "XLA Modules" one per executed
# program, "Steps" the step markers.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = re.compile(r"^XLA Ops$")
MODULES_LINE = "XLA Modules"
# The CPU stand-in for rehearsals and the harness's own tests only: the
# XLA runtime's worker threads are the nearest thing a CPU has to a
# device line.  Never used for a result.
REHEARSAL_PLANE = re.compile(r"^/host:CPU$")
REHEARSAL_LINE = re.compile(r"^tf_XLA")
# An operation's event is named by its whole HLO text.  A Pallas kernel
# is a custom call with the target "tpu_custom_call" (the flash kernels
# are the instructions %block.N of the step program: forward, dq, dkv);
# the program holds no other (PERF.md, "What a v5e trace holds").
PALLAS_KERNEL = re.compile(r"tpu_custom_call")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO text; keep the
    instruction's name and its opcode (``%fusion.248 fusion``), and for
    a custom call its target (``%block.26 tpu_custom_call``)."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    opcode = _OPCODE.search(" " + rest)
    kind = target.group(1) if target else \
        opcode.group(1) if opcode else ""
    return f"{head} {kind}".strip()


class Event(NamedTuple):
    name: str
    start: float        # ns
    end: float          # ns
    stats: Optional[dict]


def find_xplane(root: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under ``root`` (or ``root`` itself)."""
    if os.path.isfile(root):
        return root
    hits = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load(path: str, with_stats: bool = False) -> Dict[str, Dict[str, list]]:
    """``{plane name: {line name: [Event]}}``, events in start order."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                stats = dict(e.stats) if with_stats else None
                events.append(Event(short_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns, stats))
            events.sort(key=lambda ev: (ev.start, -ev.end))
    return planes


# ---------------------------------------------------------------------------
# interval arithmetic (from analysis/xprof.py)
# ---------------------------------------------------------------------------


def merge(iv: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, sorted and coalesced."""
    out: List[Interval] = []
    for a, b in sorted((a, b) for a, b in iv if b > a):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def span(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event's own time: its duration minus its children's (events
    of one line nest like a call stack)."""
    out: List[List] = []
    stack: List[int] = []
    for ev in events:
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(ev.end, out[stack[-1]][0].end) \
                - ev.start
        out.append([ev, ev.end - ev.start])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, own)) for ev, own in out]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def device_ops(planes, plane: re.Pattern = DEVICE_PLANE,
               line: re.Pattern = OPS_LINE) -> Dict[str, List[Event]]:
    """``{device plane: its operation events in start order}``."""
    out = {}
    for name, lines in sorted(planes.items()):
        if not plane.match(name):
            continue
        ops = sorted((e for lname, events in lines.items()
                      if line.match(lname) for e in events),
                     key=lambda ev: (ev.start, -ev.end))
        if ops:
            out[name] = ops
    return out


def reduce(planes, kernel: Optional[re.Pattern] = PALLAS_KERNEL,
           rehearse: bool = False) -> Optional[dict]:
    """The trace's numbers, times in seconds, averaged over the devices
    that ran anything; None if no operation ran on a device.

    - ``window_s``: first operation's start to last operation's end;
    - ``busy_s``: the union of the operations' intervals;
    - ``kernel_s``: own time of operations whose name matches
      ``kernel``; ``kernel_calls`` their number (per device);
    - ``collective_exposed_s``: own time of collective operations on
      the operations' line.  That line is the core's one sequence of
      execution, so whatever a collective (or the ``-done`` half of an
      asynchronous one) takes there, no compute runs beside it; the
      overlapped part of an asynchronous collective lies on the line
      "Async XLA Ops" and is not counted;
    - ``modules``: ``{program name: executions}`` of device 0;
    - ``top_ops``: ``[(name, seconds)]`` by own time, summed over
      devices and divided by their number.
    """
    per_device = []
    by_name: Dict[str, float] = {}
    found = device_ops(planes, REHEARSAL_PLANE, REHEARSAL_LINE) \
        if rehearse else device_ops(planes)
    for ops in found.values():
        busy = merge((e.start, e.end) for e in ops)
        if not busy:            # only zero-length markers
            continue
        owned = self_times(ops)
        kernel_s, kernel_calls, exposed = 0.0, 0, 0.0
        for e, own in owned:
            by_name[e.name] = by_name.get(e.name, 0.0) + own
            if COLLECTIVE.search(e.name):
                exposed += own
            if kernel is not None and kernel.search(e.name):
                kernel_s += own
                kernel_calls += 1
        per_device.append({
            "window_s": busy[-1][1] - busy[0][0],
            "busy_s": span(busy), "kernel_s": kernel_s,
            "kernel_calls": kernel_calls,
            "collective_exposed_s": exposed,
        })
    if not per_device:
        return None
    n = len(per_device)
    out = {key: sum(d[key] for d in per_device) / n / 1e9
           for key in ("window_s", "busy_s", "kernel_s",
                       "collective_exposed_s")}
    out["kernel_calls"] = per_device[0]["kernel_calls"]
    out["devices"] = n
    modules: Dict[str, int] = {}
    for e in planes[min(found)].get(MODULES_LINE, []):
        modules[e.name] = modules.get(e.name, 0) + 1
    out["modules"] = modules
    out["top_ops"] = sorted(((name, t / n / 1e9)
                             for name, t in by_name.items()),
                            key=lambda kv: -kv[1])
    return out


def summary(path: str, top: int = 25) -> str:
    """A trace by hand: every plane and line, and each device line's
    heaviest names by own time with one event's stats."""
    file = find_xplane(path)
    if file is None:
        return f"no *.xplane.pb under {path}"
    planes = load(file, with_stats=True)
    out = [f"trace {file} ({os.path.getsize(file)} bytes)"]
    for pname, lines in planes.items():
        out.append(f"PLANE {pname!r}: {len(lines)} lines")
        for lname, events in lines.items():
            if not events:
                continue
            busy = merge((e.start, e.end) for e in events)
            out.append(
                f"  LINE {lname!r}: {len(events)} events, span "
                f"{(max(e.end for e in events) - events[0].start) / 1e6:.3f}"
                f" ms, union {span(busy) / 1e6:.3f} ms")
            if not pname.startswith("/device:"):
                continue
            agg: Dict[str, List] = {}
            for e, own in self_times(events):
                rec = agg.setdefault(e.name, [0.0, 0, e])
                rec[0] += own
                rec[1] += 1
            for name, (own, count, e) in sorted(
                    agg.items(), key=lambda kv: -kv[1][0])[:top]:
                stats = {k: str(v)[:120] for k, v in (e.stats or {}).items()}
                out.append(f"    {own / 1e6:10.3f} ms x{count:<6} {name}  "
                           f"{stats}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2
                  else 25))
