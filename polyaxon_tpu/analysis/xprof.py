"""Chrome-trace attribution for ``jax.profiler`` dumps.

``jax.profiler.start_trace(dir)`` writes an xprof session under
``dir/plugins/profile/<ts>/`` whose ``*.trace.json[.gz]`` file is a
Chrome trace-event document: per-device tracks on real hardware
(process names like ``/device:TPU:0``, HLO op events), and — on the
host platform — XLA runtime worker threads (``tf_XLAEigen*`` /
``tf_XLATfrtCpuClient*``) under one ``/host:CPU`` process.  This
module reduces such a document into the attribution record the
serving flight recorder (serving/profiling.py) publishes:

- every selected device/runtime event is CLASSIFIED as ``collective``
  (all-reduce / all-gather / reduce-scatter / all-to-all /
  collective-permute / psum), ``transfer`` (copy / memcpy / infeed /
  outfeed / send / recv), or ``compute`` (everything else — fusions,
  dots, scans);
- per-category busy time is the UNION of event intervals (parallel
  tracks never double-count), with overlaps resolved by priority
  collective > transfer > compute, so the categories PARTITION the
  busy timeline and their shares can never sum past 1.0 of wall;
- ``host_gap`` is the remainder: wall time in the attribution window
  during which NO selected track ran anything — dispatch bubbles,
  host scheduling, admission bookkeeping (arXiv:2011.03641's
  "host-bound" signature).

The attribution window defaults to the span of the serving step
markers (``ptpu_step`` TraceAnnotations, emitted by the slot
managers around every decode dispatch) when present, so the record
measures exactly the profiled step boundaries and not profiler
startup/teardown noise.

Pure stdlib — importable outside serving (offline analysis of a
saved dump: ``python -c "from polyaxon_tpu.analysis.xprof import
attribute_dump; print(attribute_dump('/tmp/prof'))"``) and the unit
layer the synthetic-fixture tests pin (tests/test_profiling.py).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, \
    Tuple

__all__ = ["CATEGORIES", "classify_name", "find_trace_file",
           "load_profile_events", "merge_intervals",
           "subtract_intervals", "attribute_events",
           "attribute_dump", "STEP_MARKER"]

# The TraceAnnotation name the slot managers wrap every decode
# dispatch in (serving/slots.py SlotManager._dispatch) — the parser's
# window anchor.
STEP_MARKER = "ptpu_step"

# Priority order: an event matching an earlier category never counts
# toward a later one, and overlap between categories resolves the
# same way (see attribute_events).
CATEGORIES = ("collective", "transfer", "compute")

_COLLECTIVE = re.compile(
    r"all[-_ ]?reduce|all[-_ ]?gather|reduce[-_ ]?scatter"
    r"|all[-_ ]?to[-_ ]?all|collective|psum|ppermute"
    r"|(^|[-_ .])permute", re.IGNORECASE)
_TRANSFER = re.compile(
    r"copy|memcpy|infeed|outfeed|(^|[-_ .])(send|recv)($|[-_ .0-9])"
    r"|transfer|h2d|d2h|host[-_ ]?to[-_ ]?device"
    r"|device[-_ ]?to[-_ ]?host", re.IGNORECASE)

# Host-platform fallback: XLA runtime worker threads whose events are
# the closest thing a CPU "device" has to a device track.
_RUNTIME_THREAD = re.compile(r"^tf_")
# ... minus pure bookkeeping noise on those threads: thread-pool
# region markers and waits are idle/overhead, not executed work —
# counting them as compute would report a busy device that is
# actually blocked.
_RUNTIME_NOISE = re.compile(
    r"ThreadpoolListener|TaskDispatcher|dispatch|wait", re.IGNORECASE)


def classify_name(name: str) -> str:
    """collective / transfer / compute for one event name (priority
    order — ``collective-permute-send`` is a collective, not a
    transfer)."""
    if _COLLECTIVE.search(name):
        return "collective"
    if _TRANSFER.search(name):
        return "transfer"
    return "compute"


def find_trace_file(root: str) -> Optional[str]:
    """Newest ``*.trace.json[.gz]`` under ``root`` (an xprof session
    dir, its parent ``--profile-dir``, or any ancestor) — the file
    ``load_profile_events`` wants."""
    pats = ("*.trace.json.gz", "*.trace.json")
    hits: List[str] = []
    for pat in pats:
        hits += glob.glob(os.path.join(root, "**", pat),
                          recursive=True)
    if not hits:
        return None
    return max(hits, key=os.path.getmtime)


def load_profile_events(path: str) -> List[Dict[str, Any]]:
    """Trace events from a profiler dump: ``path`` may be the trace
    file itself (.json / .json.gz) or a directory to search with
    :func:`find_trace_file`."""
    if os.path.isdir(path):
        f = find_trace_file(path)
        if f is None:
            raise FileNotFoundError(
                f"no *.trace.json[.gz] under {path!r} — did the "
                f"profiler write this dump?")
        path = f
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        doc = json.load(fh)
    evs = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(evs, list):
        raise ValueError(f"{path}: not a Chrome trace document")
    return evs


def merge_intervals(iv: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Union of half-open intervals, sorted and coalesced."""
    iv = sorted((a, b) for a, b in iv if b > a)
    out: List[Tuple[float, float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def subtract_intervals(iv: Sequence[Tuple[float, float]],
                       sub: Sequence[Tuple[float, float]]
                       ) -> List[Tuple[float, float]]:
    """``iv`` minus ``sub`` (both merged/sorted)."""
    out: List[Tuple[float, float]] = []
    j = 0
    for a, b in iv:
        cur = a
        while j < len(sub) and sub[j][1] <= cur:
            j += 1
        k = j
        while k < len(sub) and sub[k][0] < b:
            s, e = sub[k]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            if cur >= b:
                break
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _span(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in iv)


def _clip(iv: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv
            if min(b, hi) > max(a, lo)]


def _meta_maps(events: Sequence[Dict[str, Any]]):
    procs: Dict[Any, str] = {}
    threads: Dict[Tuple[Any, Any], str] = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        args = ev.get("args") or {}
        if ev.get("name") == "process_name":
            procs[ev.get("pid")] = str(args.get("name", ""))
        elif ev.get("name") == "thread_name":
            threads[(ev.get("pid"), ev.get("tid"))] = str(
                args.get("name", ""))
    return procs, threads


def attribute_events(events: Sequence[Dict[str, Any]], *,
                     window: Optional[Tuple[float, float]] = None,
                     step_marker: str = STEP_MARKER,
                     max_steps: Optional[int] = None
                     ) -> Dict[str, Any]:
    """Reduce one trace-event list to the per-window attribution
    record (all times in SECONDS):

    - device tracks = processes named ``/device:*`` when any exist
      (real accelerators), else the XLA runtime worker threads
      (``host_fallback: true`` — the honest label for a CPU smoke);
    - window = explicit ``window`` (ts microseconds), else the span
      of ``step_marker`` events — the FIRST ``max_steps`` of them
      when given, so a straggler dispatch that lands its marker
      between a logical window close and the async profiler stop
      cannot stretch the wall — else the span of the selected
      device events;
    - category seconds partition the busy union (priority
      collective > transfer > compute), ``host_gap_s`` is the
      unattributed remainder, so shares sum to exactly 1.0 of wall
      (and each is <= 1.0).
    """
    procs, threads = _meta_maps(events)
    device_pid_set = {pid for pid, name in procs.items()
                      if "/device:" in name}
    device_pids = sorted(str(p) for p in device_pid_set)
    host_fallback = not device_pid_set
    runtime_tids = {key for key, name in threads.items()
                    if _RUNTIME_THREAD.search(name)}

    # One pass, cheap-test-first: a profiled window holds tens of
    # thousands of events (the analyzer competes with the decode
    # loop for the GIL, so this loop's constant factor is the flight
    # recorder's background tax).  ThreadpoolListener bookkeeping is
    # ~95% of a host-platform dump — string-prefix reject it before
    # any regex runs.
    dev: List[Dict[str, Any]] = []
    steps: List[Dict[str, Any]] = []
    for ev in events:
        if ev.get("ph") != "X" or "ts" not in ev:
            continue
        name = ev.get("name", "")
        if name == step_marker:
            steps.append(ev)
            continue
        if name.startswith(("ThreadpoolListener", "$")):
            continue        # pool bookkeeping / python host tracer
        if host_fallback:
            if (ev.get("pid"), ev.get("tid")) not in runtime_tids \
                    or _RUNTIME_NOISE.search(name):
                continue
        elif ev.get("pid") not in device_pid_set:
            continue
        dev.append(ev)
    if window is None:
        if steps and max_steps is not None:
            anchor = sorted(steps,
                            key=lambda ev: ev["ts"])[:max_steps]
        else:
            anchor = steps or dev
        if not anchor:
            return {"wall_s": 0.0, "events": 0,
                    "step_markers": 0,
                    "host_fallback": host_fallback,
                    "device_pids": device_pids,
                    "category_s": {c: 0.0 for c in CATEGORIES},
                    "host_gap_s": 0.0,
                    "shares": {c: 0.0 for c in CATEGORIES},
                    "host_gap_share": 0.0,
                    "device_busy_share": 0.0}
        lo = min(ev["ts"] for ev in anchor)
        hi = max(ev["ts"] + ev.get("dur", 0) for ev in anchor)
    else:
        lo, hi = window
    wall_us = max(hi - lo, 1e-9)

    by_cat: Dict[str, List[Tuple[float, float]]] = {
        c: [] for c in CATEGORIES}
    for ev in dev:
        a = ev["ts"]
        b = a + ev.get("dur", 0)
        by_cat[classify_name(ev.get("name", ""))].append((a, b))

    merged = {c: merge_intervals(_clip(by_cat[c], lo, hi))
              for c in CATEGORIES}
    taken: List[Tuple[float, float]] = []
    cat_us: Dict[str, float] = {}
    for c in CATEGORIES:            # priority order
        own = subtract_intervals(merged[c], taken)
        cat_us[c] = _span(own)
        taken = merge_intervals(taken + own)
    busy_us = _span(taken)
    gap_us = max(0.0, wall_us - busy_us)

    wall_s = wall_us / 1e6
    shares = {c: round(cat_us[c] / wall_us, 6) for c in CATEGORIES}
    return {
        "wall_s": round(wall_s, 6),
        "events": len(dev),
        "step_markers": len([ev for ev in steps
                             if lo <= ev["ts"] <= hi]),
        "host_fallback": host_fallback,
        "device_pids": device_pids,
        "category_s": {c: round(cat_us[c] / 1e6, 6)
                       for c in CATEGORIES},
        "host_gap_s": round(gap_us / 1e6, 6),
        "shares": shares,
        "host_gap_share": round(gap_us / wall_us, 6),
        "device_busy_share": round(busy_us / wall_us, 6),
    }


def attribute_dump(path: str, **kw) -> Dict[str, Any]:
    """:func:`attribute_events` over a dump file/dir on disk."""
    return attribute_events(load_profile_events(path), **kw)
