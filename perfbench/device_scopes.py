"""Device time by the program's own names: (program, part).

Every event of a device plane's ``XLA Ops`` line points at an event
METADATA record, and that record's stats hold ``tf_op``: the JAX name
stack the operation was traced under
(``jit(program)/while/body/vmap(GPT2Model)/.../ptpu_attend/cond/
branch_1_fun/bqhd,bkhd->bhqk/dot_general:``).  Flax writes a module's
path into it, JAX ``jvp(`` and ``transpose(``, and the program its
scopes (``polyaxon_tpu/spans.py`` ``SCOPE_NAMES``).
``jax.profiler.ProfileData`` yields an event's OWN stats only, so this
file reads the ``.xplane.pb`` itself: a wire-format reader of the six
``XSpace`` messages it needs, standard library only.

The reduction (``reduce``): own time on the ``XLA Ops`` line of the
first device plane (``trace_reduce.self_times``' rule), keyed by the
PROGRAM whose event on the ``XLA Modules`` line holds the operation and
by the PART its stack names (``part_of``).  The first ``jit(<name>)``
of a stack is checked against that program; own time that disagrees is
reported (``mismatch_s``), never moved.

``python perfbench/device_scopes.py <dir or file> [top]`` prints each
program's parts with their heaviest stacks: a trace by hand.
"""

from __future__ import annotations

import gzip
import os
import re
import sys
import time
from typing import Dict, Iterator, List, Optional, Tuple

import trace_reduce

# Scope -> part.  The one table: what the program names
# (``spans.SCOPE_NAMES``) and what the metrics call it.
SCOPE_PARTS = {
    "ptpu_attend": "attend",
    "ptpu_kv_write": "kv_write",
    "ptpu_latent_expand": "expand",
    "ptpu_route": "experts",
    "ptpu_experts": "experts",
    "ptpu_scan": "scan",
    "ptpu_state_step": "state",
    "ptpu_sample": "sample",
    "ptpu_optimizer": "optimizer",
}
_SCOPED = frozenset(SCOPE_PARTS.values())
_SCOPE = re.compile(r"ptpu_[a-z_]+")
_PROGRAM = re.compile(r"jit\(([^()]*)\)")
# Segments of a stack that JAX writes by itself (an einsum's is its
# spec); what is left of a stack without them, without the transforms
# around a segment and without the last segment, the primitive, is
# somebody's name: a Flax module's path.
_STRUCTURAL = re.compile(
    r"^(|jit\(.*\)|pjit|while|body|cond|body_fun|cond_fun|scan|"
    r"branch_\d+_fun|closed_call|core_call|checkpoint|remat\d*|"
    r"rematted_computation|custom_jvp_call|custom_vjp_call\w*|"
    r"custom_vmap_call|custom_lin|shard_map|xla_call|.*->.*)$")
_TRANSFORM = re.compile(r"^(?:vmap|jvp|transpose|pmap)\((.*)\)$")


def _named(segment: str) -> bool:
    while True:
        inner = _TRANSFORM.match(segment)
        if inner is None:
            return not _STRUCTURAL.match(segment)
        segment = inner.group(1)


def part_of(stack: str) -> Tuple[str, str]:
    """``(part, pass)`` of a name stack.  The part: the LAST scope of
    ``SCOPE_PARTS`` in it; with none, ``backward`` under a
    ``transpose(``, ``forward`` under a ``jvp(``, ``dense`` where a
    module's path is left (projections, MLP, norms, embeddings, the
    head), else ``unnamed``.  The pass, whatever the part:
    ``backward``, ``forward`` or ``""`` by the same two markers — a
    training step's attention is a part of its own AND lies in one
    pass or the other."""
    way = "backward" if "transpose(" in stack \
        else "forward" if "jvp(" in stack else ""
    scopes = [s for s in _SCOPE.findall(stack) if s in SCOPE_PARTS]
    if scopes:
        return SCOPE_PARTS[scopes[-1]], way
    if way:
        return way, way
    named = any(_named(s) for s in stack.split("/")[:-1])
    return ("dense" if named else "unnamed"), way


# ---------------------------------------------------------------------------
# the wire format (protobuf encoding; xplane.proto's field numbers)
# ---------------------------------------------------------------------------


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _fields(buf: bytes, pos: int, end: int) -> Iterator[tuple]:
    """``(field number, value)`` of one message: an int for a varint,
    ``(start, end)`` for a length-delimited field; fixed-width fields
    (a stat's double) are passed over."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield key >> 3, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield key >> 3, (pos, pos + size)
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf: bytes, at: Tuple[int, int]) -> str:
    return buf[at[0]:at[1]].decode("utf-8", "replace")


def _map_entry(buf, at) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for number, v in _fields(buf, *at):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


def _stat_names(buf, entries) -> Dict[int, str]:
    """XPlane.stat_metadata: id -> name."""
    out = {}
    for at in entries:
        key, value = _map_entry(buf, at)
        if value is None:
            continue
        for number, v in _fields(buf, *value):
            if number == 2:
                out[key] = _text(buf, v)
    return out


def _event_metadata(buf, entries, stat_names) -> Dict[int, Tuple[str, str]]:
    """XPlane.event_metadata: id -> ``(name, tf_op)``; ``tf_op`` is ""
    where the record has none.  A stat's value is a string
    (``str_value``, field 5) or a reference to a stat metadata's name
    (``ref_value``, field 7)."""
    tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
    out = {}
    for at in entries:
        key, value = _map_entry(buf, at)
        if value is None:
            continue
        name, stack = "", ""
        for number, v in _fields(buf, *value):
            if number == 2:
                name = _text(buf, v)
            elif number == 5:               # XStat
                stat, text = None, None
                for n, sv in _fields(buf, *v):
                    if n == 1:
                        stat = _signed(sv)
                    elif n == 5:
                        text = _text(buf, sv)
                    elif n == 7:
                        text = stat_names.get(sv, "")
                if stat in tf_op and text is not None:
                    stack = text
        out[key] = (name, stack)
    return out


def _events(buf, at) -> List[Tuple[int, int, int]]:
    """XLine: its events as ``[(start ps, end ps, metadata id)]``."""
    stamp, events = 0, []
    for number, v in _fields(buf, *at):
        if number == 3:
            stamp = v
        elif number == 4:
            events.append(v)
    base, out = stamp * 1000, []
    for pos, end in events:
        # XEvent, decoded in line: a trace holds millions.  1
        # metadata_id, 2 offset_ps, 3 duration_ps; 4 (its own stats,
        # length-delimited) is passed over.
        got = [0, 0, 0, 0]
        while pos < end:
            key = buf[pos]
            pos += 1
            value = shift = 0
            while True:
                byte = buf[pos]
                pos += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            if key & 7 == 2:
                pos += value
            elif key & 7 == 0 and key < 32:
                got[key >> 3] = value
            else:
                raise ValueError(f"XEvent key {key} at byte {pos}")
        start = base + got[2]
        out.append((start, start + got[3], _signed(got[1])))
    return out


def read_plane(path: str):
    """The first device plane of a trace file (``.xplane.pb``, or that
    gzipped): ``(plane name, {line name: [(start ps, end ps, metadata
    id)]}, {metadata id: (name, tf_op)})`` for its ``XLA Ops`` and
    ``XLA Modules`` lines, or None where the file holds no device
    plane."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    planes = {}
    for number, at in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        for n, v in _fields(buf, *at):
            if n == 2:
                planes[_text(buf, v)] = at
                break
    found = sorted(p for p in planes if trace_reduce.DEVICE_PLANE.match(p))
    if not found:
        return None
    lines, events, stats = [], [], []
    for number, v in _fields(buf, *planes[found[0]]):
        {3: lines, 4: events, 5: stats}.get(number, []).append(v)
    metadata = _event_metadata(buf, events, _stat_names(buf, stats))
    wanted = {}
    for at in lines:
        for number, v in _fields(buf, *at):
            if number == 2:
                name = _text(buf, v)
                if name == trace_reduce.MODULES_LINE \
                        or trace_reduce.OPS_LINE.match(name):
                    wanted[name] = _events(buf, at)
                break
    return found[0], wanted, metadata


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _leaf(stack: str) -> str:
    """The end of a stack that tells operations of one part apart:
    from its first scope on (the width a conditional took stays in
    it), else its last three segments."""
    for found in _SCOPE.finditer(stack):
        if found.group() in SCOPE_PARTS:
            return stack[found.start():]
    return "/".join(stack.split("/")[-3:])


def reduce_plane(plane) -> dict:
    """``{"programs": {program: {"executions", "module_s", "ops_s",
    "parts": {part: s}, "passes": {pass: s}, "leaves": {part: {leaf:
    s}}}}, "busy_s", "unnamed_s", "scoped_s", "mismatch_s"}`` — seconds
    of own time on the ``XLA Ops`` line (``scoped_s``: under a scope
    of the program's).  ``module_s`` is the program's time
    on the ``XLA Modules`` line; ``ops_s`` what its operations account
    for (the parts' sum): the rest of ``module_s`` no operation ran
    in."""
    _, lines, metadata = plane
    modules = sorted(lines.get(trace_reduce.MODULES_LINE, []))
    programs: Dict[str, dict] = {}

    def program(name: str) -> dict:
        return programs.setdefault(name, {
            "executions": 0, "module_s": 0.0, "ops_s": 0.0,
            "parts": {}, "passes": {}, "leaves": {}})

    names = []
    for start, end, meta in modules:
        name = re.sub(r"\(\d+\)$", "", metadata.get(meta, ("", ""))[0])
        names.append(name)
        rec = program(name)
        rec["executions"] += 1
        rec["module_s"] += (end - start) / 1e12
    ops = [trace_reduce.Event(meta, start, end, None)
           for line, events in lines.items()
           if line != trace_reduce.MODULES_LINE
           for start, end, meta in events]
    ops.sort(key=lambda ev: (ev.start, -ev.end))
    # Own time by (program, metadata id) first: a trace holds millions
    # of events and a few thousand records.
    # (An operation outside every program's interval: program "".)
    own_of: Dict[Tuple[str, int], float] = {}
    at, last = -1, len(modules) - 1
    for ev, own in trace_reduce.self_times(ops):
        while at < last and modules[at + 1][0] <= ev.start:
            at += 1
        held = at >= 0 and ev.start < modules[at][1]
        key = (names[at] if held else "", ev.name)
        own_of[key] = own_of.get(key, 0.0) + own
    busy = unnamed = scoped = mismatch = 0.0
    for (name, meta), own in own_of.items():
        own /= 1e12
        stack = metadata.get(meta, ("", ""))[1]
        part, way = part_of(stack)
        rec = program(name)
        rec["ops_s"] += own
        rec["parts"][part] = rec["parts"].get(part, 0.0) + own
        if way:
            rec["passes"][way] = rec["passes"].get(way, 0.0) + own
        leaves = rec["leaves"].setdefault(part, {})
        leaf = _leaf(stack) if stack else "(no tf_op)"
        leaves[leaf] = leaves.get(leaf, 0.0) + own
        busy += own
        if part == "unnamed":
            unnamed += own
        elif part in _SCOPED:
            scoped += own
        said = _PROGRAM.search(stack)
        if said and name and re.sub(
                r"\W", "_", "jit_" + said.group(1)).rstrip("_") != name:
            mismatch += own
    return {"programs": programs, "busy_s": busy, "unnamed_s": unnamed,
            "scoped_s": scoped, "mismatch_s": mismatch}


def reduce_file(path: str) -> Optional[dict]:
    """``reduce_plane`` of a trace file, with what reading it cost
    (``file_bytes``, ``seconds``); None where it holds no device
    plane."""
    t = time.time()
    plane = read_plane(path)
    if plane is None:
        return None
    out = reduce_plane(plane)
    out["file_bytes"] = os.path.getsize(path)
    out["seconds"] = time.time() - t
    return out


def of(ctx) -> Optional[dict]:
    """This run's reduction, read ONCE and kept on ``ctx.collected``
    (None: no trace, a rehearsal's CPU trace, or no device plane)."""
    if "_device_scopes" not in ctx.collected:
        out = None
        # A run's directory is made anew: the one trace under it is
        # this run's (a server's under profile/, a job's in its store).
        file = None if ctx.rehearse else trace_reduce.find_xplane(ctx.out)
        if file is not None:
            out = reduce_file(file)
        if out is not None:
            from procs import say

            say(f"device scopes: {file} ({out['file_bytes']} bytes) "
                f"read in {out['seconds']:.1f}s; busy "
                f"{out['busy_s']:.4f}s, under a scope "
                f"{out['scoped_s']:.4f}s, unnamed {out['unnamed_s']:.4f}s, "
                f"stack and program disagree {out['mismatch_s']:.4f}s")
            say(table(out, top=3))
        ctx.collected["_device_scopes"] = out
    return ctx.collected["_device_scopes"]


# What the per-layer readers share (perfbench/layer_metrics/): the
# programs by the names the device trace gives them.
DECODE = ("jit_program",)
PREFILL = ("jit_ptpu_prefill", "jit_ptpu_extend")
TRAIN = ("jit_step",)


def split(ctx, programs, key: str = "parts"):
    """``({part: seconds}, executions)`` (``key``: ``parts`` or
    ``passes``) summed over ``programs`` in this run's trace; None
    where none of them ran, or where the trace holds no scope of the
    program's at all: a tree from before the scopes has nothing to
    split."""
    reduced = of(ctx)
    if reduced is None or not reduced["scoped_s"]:
        return None
    out: Dict[str, float] = {}
    runs = 0
    for name in programs:
        rec = reduced["programs"].get(name)
        if rec is None:
            continue
        runs += rec["executions"]
        for part, s in rec[key].items():
            out[part] = out.get(part, 0.0) + s
    return (out, runs) if runs else None


def decode_ms(ctx, part: str) -> Optional[float]:
    """``part``'s own time inside the decode program per decode step:
    over the growth of ``decode_steps_total`` between the ``/info``
    reads that bracket the trace (``decode_program_ms``' divisor)."""
    found = split(ctx, DECODE)
    a, b = (ctx.collected.get(k) for k in ("trace_open", "trace_close"))
    if not found or not a or not b:
        return None
    steps = b["decode_steps_total"] - a["decode_steps_total"]
    return 1e3 * found[0].get(part, 0.0) / steps if steps > 0 else None


def prefill_ms(ctx, part: str) -> Optional[float]:
    """``part``'s own time inside the prefill programs per execution
    (a piece)."""
    found = split(ctx, PREFILL)
    return 1e3 * found[0].get(part, 0.0) / found[1] if found else None


def step_ms(ctx, name: str, key: str) -> Optional[float]:
    """A part's (``key`` ``parts``) or a pass's (``passes``) own time
    inside the training step per traced step, on the first chip."""
    from run import load_module

    found = split(ctx, TRAIN, key)
    if not found:
        return None
    steps = load_module("layer_metrics", "step_device_ms").traced_steps(ctx)
    return 1e3 * found[0].get(name, 0.0) / steps


def named_pct(ctx) -> Optional[float]:
    """100 - the share of the device's busy time whose stack names
    nothing (``unnamed``)."""
    reduced = of(ctx)
    if reduced is None or not reduced["scoped_s"]:
        return None
    return 100.0 - 100.0 * reduced["unnamed_s"] / reduced["busy_s"]


def table(reduced: dict, top: int = 5) -> str:
    out = []
    for name, rec in sorted(reduced["programs"].items(),
                            key=lambda kv: -kv[1]["ops_s"]):
        if not rec["ops_s"]:
            continue
        runs = max(rec["executions"], 1)
        out.append(
            f"{name or '(outside every program)'}: x{rec['executions']} "
            f"{1e3 * rec['module_s'] / runs:.3f} ms a run, operations "
            f"{1e3 * rec['ops_s'] / runs:.3f} ms "
            f"({100 * rec['ops_s'] / max(rec['module_s'], 1e-12):.1f} %)"
            + "".join(f"; {way} {1e3 * s / runs:.3f}"
                      for way, s in sorted(rec["passes"].items())))
        for part, s in sorted(rec["parts"].items(), key=lambda kv: -kv[1]):
            out.append(f"  {part:<10} {1e3 * s / runs:9.3f} ms a run "
                       f"{100 * s / rec['ops_s']:5.1f} %")
            for leaf, ls in sorted(rec["leaves"][part].items(),
                                   key=lambda kv: -kv[1])[:top]:
                out.append(f"      {1e3 * ls / runs:9.3f}  {leaf[-110:]}")
    return "\n".join(out)


if __name__ == "__main__":
    found = trace_reduce.find_xplane(sys.argv[1])
    result = reduce_file(found) if found else None
    if result is None:
        sys.exit(f"no device plane under {sys.argv[1]}")
    print(f"{found}: {result['file_bytes']} bytes read in "
          f"{result['seconds']:.1f}s; busy {result['busy_s']:.4f}s, "
          f"unnamed {result['unnamed_s']:.4f}s = "
          f"{100 * result['unnamed_s'] / result['busy_s']:.2f} %, stack "
          f"and program disagree {result['mismatch_s']:.4f}s")
    print(table(result, int(sys.argv[2]) if len(sys.argv) > 2 else 5))
