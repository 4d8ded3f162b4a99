"""From a profiler trace to device busy and idle time, step time and gaps.

Two halves. ``load_xplane`` reads JAX's ``.xplane.pb`` (it needs jax, so the
process that holds the chip calls it) into a plain dict of planes, lines and
``[name, start_ns, duration_ns]`` events. ``reduce`` is arithmetic on that
dict alone, checked by the tests on a small recorded trace.

What the planes look like on a TPU v5e (jax 0.9): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation and whose line ``XLA Modules`` has one event per executed program,
named ``<jit name>(<fingerprint>)``; the host's threads are lines of the plane
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans and the runtime's
own spans appear by name. Both sit on one clock.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAMED_GAPS = 200  # gaps named one by one; the shorter rest are lumped


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, min_host_ns: int = 20_000) -> Dict[str, Any]:
    """Device planes whole; of the host only events of ``min_host_ns`` or
    longer, which is what could cover a gap worth naming. ``span_ns`` is the
    traced span: from the first event to the last, of host or device."""
    from jax.profiler import ProfileData

    planes = []
    first, last = None, None
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
    return {"planes": planes, "span_ns": [first, last]}


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO line: keep its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``broadcast_select_fusion.3`` and ``.4`` are one kind of operation."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


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


def reduce(trace: Dict[str, Any], step_program: str) -> Optional[Dict[str, Any]]:
    """Busy seconds averaged over the chips that ran anything, the seconds
    of the traced span (a chip that sat idle at either edge was idle), the
    mean device time of one execution of ``step_program``, the operations
    that took most device time, and idle time by what the host was doing.
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
    gaps: List[Tuple[int, int]] = []
    for plane in devices:
        ops = _line(plane, OPS_LINE)
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
    }
