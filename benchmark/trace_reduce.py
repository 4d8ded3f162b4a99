"""From a profiler trace to device busy and idle time, step time, device time
by named scope, and gaps.

Two halves. ``load_xplane`` reads JAX's ``.xplane.pb`` (it needs jax, so the
process that holds the chip calls it) into a plain dict of planes, lines and
``[name, start_ns, duration_ns]`` events, with, for each device, the
``op_names`` of its operations (the ``tf_op`` stat: the ``jax.named_scope``
path the program gave the operation) and the ``operands`` of those that have
none. ``reduce`` is arithmetic on that dict alone, checked by the tests on
small recorded traces.

What the planes look like on a TPU v5e (jax 0.9): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation and whose line ``XLA Modules`` has one event per executed program,
named ``<jit name>(<fingerprint>)``; the host's threads are lines of the plane
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans and the runtime's
own spans appear by name. Both sit on one clock. An operation's ``tf_op``
(``jit(step)/attn_qkv/dot_general:``) is a stat of the event's *metadata*,
which ``ProfileData`` does not hand out, so ``op_stats`` reads that one map
from the file's bytes. The compiler's own asynchronous slices and copies
(operands staged through fast memory) carry none.

One limit (PERF.md section 6, PR 25): JAX leaves metadata out of the
persistent compile cache's key, so a warm cache serves an older tree's
executables with *that* tree's scope names. ``scopes`` speaks of the measured
tree only where the cache was first filled from it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAMED_GAPS = 200  # gaps named one by one; the shorter rest are lumped
NO_OPERATION = "(no operation)"  # inside the step program, between operations
_SHAPE = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\]")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one protobuf message: an int for
    a varint, the bytes for a length-delimited or fixed field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire in (1, 2, 5):
            size = {1: 8, 5: 4}.get(wire)
            if size is None:
                size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def op_stats(path: str, stat: str = "tf_op") -> Dict[str, Dict[str, Optional[str]]]:
    """``{plane: {event name: value}}`` of one string stat of the events'
    metadata (``None`` for an event without it), from the XSpace's own bytes (tsl's ``xplane.proto``: XSpace
    planes 1; XPlane name 2, event_metadata 4, stat_metadata 5; a map entry's
    value 2; XEventMetadata name 2, stats 5; XStatMetadata id 1, name 2;
    XStat metadata_id 1, str_value 5, ref_value 7). The lines, which are
    nearly all of the file, are stepped over."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    text = lambda b: bytes(b).decode("utf-8", "replace")
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, entries, stat_names = "", [], {}
        for n, value in _fields(plane):
            if n == 2:
                name = text(value)
            elif n == 4:
                entries.append(value)
            elif n == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[meta.get(1, 0)] = text(meta.get(2, b""))
        found: Dict[str, Optional[str]] = {}
        for entry in entries:
            event, stats = "", []
            for n, value in _fields(dict(_fields(entry)).get(2, b"")):
                if n == 2:
                    event = text(value)
                elif n == 5:
                    stats.append(dict(_fields(value)))
            found[event] = None
            for s in stats:
                if stat_names.get(s.get(1)) == stat:
                    found[event] = (text(s[5]) if 5 in s
                                    else stat_names.get(s.get(7), ""))
        if found:
            out[name] = found
    return out


def operand_of(hlo: str) -> Optional[str]:
    """The largest array an HLO line names, as ``bf16[20,1024,64]``: for an
    asynchronous slice its source, for a copy what it copies."""
    best, most = None, -1
    for dtype, dims in _SHAPE.findall(hlo):
        size = 1
        for d in dims.split(","):
            size *= int(d) if d else 1
        if size > most:
            best, most = f"{dtype}[{dims}]", size
    return best


def load_xplane(path: str, min_host_ns: int = 20_000) -> Dict[str, Any]:
    """Device planes whole; of the host only events of ``min_host_ns`` or
    longer, which is what could cover a gap worth naming. ``span_ns`` is the
    traced span: from the first event to the last, of host or device. A
    device plane also carries ``op_names`` (operation -> its ``tf_op``) and,
    for the operations that have none, ``operands`` (operation -> the array
    it moves)."""
    from jax.profiler import ProfileData

    planes = []
    first, last = None, None
    tf_ops = op_stats(path)
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            for e in line.events:
                start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
                first = start if first is None else min(first, start)
                last = end if last is None else max(last, end)
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.duration_ns >= min_host_ns]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
        if device:
            named = tf_ops.get(plane.name, {})
            planes[-1]["op_names"] = {
                short_name(hlo): tf_op for hlo, tf_op in named.items() if tf_op}
            planes[-1]["operands"] = {
                short_name(hlo): operand_of(hlo) for hlo, tf_op in named.items()
                if not tf_op and operand_of(hlo)}
    return {"planes": planes, "span_ns": [first, last]}


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO line: keep its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``broadcast_select_fusion.3`` and ``.4`` are one kind of operation."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def scope_of(op_name: str) -> Optional[str]:
    """``jit(step)/attn_qkv/jit(_var)/reduce_sum:`` -> ``attn_qkv``: the
    outermost ``jax.named_scope`` round the operation, which is the first
    part of the path that is neither a ``jit(...)`` (or another transform's
    ``name(...)``) nor the primitive at its end."""
    for part in op_name.split("/")[:-1]:
        if part and not part.endswith(")"):
            return part
    return None


def _line(plane: Dict[str, Any], name: str) -> List[List[Any]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


class _HostSpans:
    """The host's spans, to ask which was the innermost at a given time."""

    def __init__(self, events: List[Tuple[int, int, str]]):
        self._start = np.array([e[0] for e in events], np.int64)
        self._end = np.array([e[1] for e in events], np.int64)
        self._names = [e[2] for e in events]

    def covering(self, at: int) -> str:
        """The shortest span that covers time ``at``."""
        over = np.flatnonzero((self._start <= at) & (self._end >= at))
        if not over.size:
            return "no_host_span"
        return self._names[over[np.argmin(self._end[over] - self._start[over])]]


def traced_span(trace: Dict[str, Any]) -> Tuple[int, int]:
    """``span_ns`` as recorded, or the first event to the last of those kept."""
    if trace.get("span_ns"):
        return tuple(trace["span_ns"])
    events = [(s, s + d) for p in trace["planes"] for line in p["lines"]
              for _, s, d in line["events"]]
    return min(s for s, _ in events), max(e for _, e in events)


def _scope_name(plane: Dict[str, Any], op: str,
                operands: Dict[str, str]) -> str:
    """The scope an operation's device time goes to: the program's named
    scope; for the compiler's unnamed slices and copies, asynchronous or
    not, their kind and the operand they move, ``(slice of weights)`` where
    ``operands`` knows the array and ``(slice of bf16[20,1024,64])`` where it
    does not; else the kind of operation in brackets."""
    scope = scope_of(plane.get("op_names", {}).get(op, ""))
    if scope:
        return scope
    kind = op_kind(op)
    for suffix in ("-start", "-done"):
        if kind.endswith(suffix):
            kind = kind[:-len(suffix)]
    moved = plane.get("operands", {}).get(op)
    if moved is None or kind not in ("slice", "copy"):
        return f"({kind})"
    return f"({kind} of {operands.get(moved, moved)})"


def _self_ns(ops: List[List[Any]]) -> List[int]:
    """Each operation's own time: a ``while`` is an event that lasts as long
    as the operations of its body, which are events too, so what lies inside
    an operation is taken off it."""
    own = [d for _, _, d in ops]
    open_ops: List[Tuple[int, int]] = []  # (end, index), innermost last
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while open_ops and open_ops[-1][0] <= start:
            open_ops.pop()
        if open_ops:
            own[open_ops[-1][1]] -= min(end, open_ops[-1][0]) - start
        open_ops.append((end, i))
    return own


def reduce(trace: Dict[str, Any], step_program: str,
           operands: Optional[Dict[str, str]] = None) -> Optional[Dict[str, Any]]:
    """Busy seconds averaged over the chips that ran anything, the seconds
    of the traced span (a chip that sat idle at either edge was idle), the
    mean device time of one execution of ``step_program``, the operations
    that took most device time, idle time by what the host was doing, and
    ``scopes``: inside the executions of ``step_program``, ``[name, device
    seconds, operations, mean microseconds]`` by named scope, every row, the
    largest first; an operation that holds others (a ``while``) counts for
    its own time only. The scopes and ``(no operation)``, the program's time
    between its operations, add up to the program's device time.
    ``operands`` names arrays by shape (``{"bf16[1280,3840]": "weights"}``).
    ``None`` where no operation ran on a device."""
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PLANE) and _line(p, OPS_LINE)]
    if not devices:
        return None
    span_start, span_end = traced_span(trace)
    host = _HostSpans([(s, s + d, n) for p in trace["planes"]
                       if p["name"] == HOST_PLANE
                       for line in p["lines"] for n, s, d in line["events"]])

    busy_ns = 0
    op_ns: Dict[str, int] = {}
    step_ns: List[int] = []
    scope_ns: Dict[str, List[int]] = {}  # name -> [ns, operations]
    gaps: List[Tuple[int, int]] = []
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        steps = sorted((s, s + d) for name, s, d in _line(plane, MODULES_LINE)
                       if name.split("(")[0] == step_program)
        starts = [s for s, _ in steps]
        names: Dict[str, str] = {}
        inside_ns = 0
        for (op, s, _), d in zip(ops, _self_ns(ops)):
            at = bisect.bisect_right(starts, s) - 1
            if at < 0 or s >= steps[at][1]:
                continue
            if op not in names:
                names[op] = _scope_name(plane, op, operands or {})
            row = scope_ns.setdefault(names[op], [0, 0])
            row[0] += d
            row[1] += 1
            inside_ns += d
        if steps:
            row = scope_ns.setdefault(NO_OPERATION, [0, 0])
            row[0] += sum(e - s for s, e in steps) - inside_ns
            row[1] += len(steps)
        busy = merge([(s, s + d) for _, s, d in ops])
        busy_ns += sum(end - start for start, end in busy)
        edges = [(span_start, span_start)] + busy + [(span_end, span_end)]
        gaps.extend((b[0] - a[1], a[1]) for a, b in zip(edges, edges[1:])
                    if b[0] > a[1])
        for name, _, d in ops:
            op_ns[op_kind(name)] = op_ns.get(op_kind(name), 0) + d
        step_ns.extend(d for name, _, d in _line(plane, MODULES_LINE)
                       if name.split("(")[0] == step_program)

    gaps.sort(reverse=True)
    idle: Dict[str, int] = {}
    for length, start in gaps[:NAMED_GAPS]:
        name = host.covering(start + length // 2)
        idle[name] = idle.get(name, 0) + length
    rest = sum(length for length, _ in gaps[NAMED_GAPS:])
    if rest:
        idle["shorter_gaps"] = rest

    n = len(devices)
    top = lambda d: [[k, v / 1e9 / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "chips": n,
        "busy_s": busy_ns / 1e9 / n,
        "window_s": (span_end - span_start) / 1e9,
        "step_count": len(step_ns),
        "step_device_ms": (sum(step_ns) / len(step_ns) / 1e6) if step_ns else None,
        "longest_gap_ms": gaps[0][0] / 1e6 if gaps else 0.0,
        "device_ops": top(op_ns),
        "idle_gaps": top(idle),
        "scopes": [[k, ns / 1e9 / n, count, ns / 1e3 / count]
                   for k, (ns, count) in
                   sorted(scope_ns.items(), key=lambda kv: -kv[1][0])],
    }
