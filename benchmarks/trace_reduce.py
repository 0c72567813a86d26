"""Reduction from a profiler trace to the numbers the per-layer metrics read.

Input: the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData` (nothing but JAX), or the same shape as JSON
(`fixtures/*.json`: {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}) so the arithmetic can be checked by
hand without a chip (`tests/test_trace_reduce.py`).

All arithmetic is on closed-open intervals [start, end) in nanoseconds:

  busy / idle      union of the device's op intervals over the window; on a
                   mesh every device is reduced alone and the fullest is
                   reported beside the mean
  program time     events of the device's module line whose name contains one
                   of the names the configuration's file lists
  collective time  op events whose name matches a collective, and the part of
                   them in which no other op runs on that device (exposed)
  gap attribution  each idle gap of the device, cut against the job thread's
                   host events; at every instant the innermost (latest
                   started) event takes the time, and time under no event is
                   `program_host_code`
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-to-all|all_to_all|all-reduce|all_reduce|all-gather|all_gather|"
    r"collective-permute|collective_permute|reduce-scatter|reduce_scatter",
    re.I)
#: host events in which the job's thread waits for the link or the device
TRANSFER = re.compile(
    r"np\.asarray|DevicePut|device_put|copy_to_host|CopyToHost|ToLiteral|"
    r"TransferTo|TransferFrom|BlockHostUntilReady|block_until_ready|Await|"
    r"H2D|D2H|BufferFromHost", re.I)
#: device ops that only contain other ops (a scan's while loop): they are busy
#: time, but not "another op running" beside a collective
CONTAINER = re.compile(r"^(while|conditional|call)[._\d]*", re.I)
UNATTRIBUTED = "program_host_code"

Event = Tuple[str, int, int]          # name, start_ns, end_ns


@dataclasses.dataclass
class Trace:
    planes: Dict[str, Dict[str, List[Event]]]     # plane -> line -> events

    def device_planes(self) -> List[str]:
        return sorted(p for p in self.planes if DEVICE_PLANE.match(p))


def _norm(name: str, start: float, dur: float) -> Event:
    return (str(name), int(start), int(start) + int(dur))


def load_xplane(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend(_norm(e.name, e.start_ns, e.duration_ns)
                       for e in line.events)
    return Trace(planes)


def load_json(path: str) -> Trace:
    with open(path) as f:
        raw = json.load(f)
    return Trace({
        p["name"]: {ln["name"]: [_norm(*e) for e in ln["events"]]
                    for ln in p["lines"]}
        for p in raw["planes"]})


# -- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]):
    """Parts of the (disjoint, sorted) intervals `a` not covered by the
    (disjoint, sorted) intervals `b`."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int):
    return subtract([(lo, hi)], busy)


# -- the reductions ---------------------------------------------------------

def device_ops(trace: Trace, plane: str) -> List[Event]:
    lines = trace.planes[plane]
    return lines.get(OPS_LINE) or [e for evs in lines.values() for e in evs]


WINDOW_MARKER = "benchmark.traced_window"


def window_of(trace: Trace, marker: str = WINDOW_MARKER):
    """[lo, hi) of the traced window: the host annotation the harness wraps
    around it, else the span of all device events."""
    for evs in trace.planes.get(HOST_PLANE, {}).values():
        for name, a, b in evs:
            if name == marker:
                return a, b
    evs = [e for p in trace.device_planes() for e in device_ops(trace, p)]
    if not evs:
        raise ValueError("the trace holds no device event")
    return min(e[1] for e in evs), max(e[2] for e in evs)


def busy_by_device(trace: Trace, lo: int, hi: int) -> Dict[str, int]:
    """ns in which an op ran, per device plane, inside [lo, hi)."""
    return {p: total(clip(union((a, b) for _n, a, b in device_ops(trace, p)),
                          lo, hi))
            for p in trace.device_planes()}


def program_times(trace: Trace, names: Sequence[str], lo: int, hi: int):
    """Per device plane: (executions, ns) of module events whose name
    contains one of `names`, started inside [lo, hi)."""
    out = {}
    for p in trace.device_planes():
        evs = [(a, b) for n, a, b in trace.planes[p].get(MODULES_LINE, [])
               if lo <= a < hi and any(s in n for s in names)]
        out[p] = (len(evs), total(evs))
    return out


def collective_times(trace: Trace, lo: int, hi: int):
    """Per device plane: (ns in collective ops, ns of that with no other op
    running on the device)."""
    out = {}
    for p in trace.device_planes():
        ops = device_ops(trace, p)
        coll = clip(union((a, b) for n, a, b in ops if COLLECTIVE.search(n)),
                    lo, hi)
        other = clip(union((a, b) for n, a, b in ops
                           if not COLLECTIVE.search(n)
                           and not CONTAINER.match(n)), lo, hi)
        out[p] = (total(coll), total(subtract(coll, other)))
    return out


def job_thread(trace: Trace, marker: str = "benchmark.poll_batch") -> List[Event]:
    """Host events of the thread that runs the job: the one the reader's
    annotation is on."""
    best: List[Event] = []
    for evs in trace.planes.get(HOST_PLANE, {}).values():
        if any(n == marker for n, _a, _b in evs):
            if len(evs) > len(best):
                best = evs
    # the harness's own marker of the traced window is no work of the job
    return [e for e in best if e[0] != WINDOW_MARKER]


def innermost_segments(events: Sequence[Event]) -> List[Event]:
    """Cut one thread's nested events into disjoint segments, each named by
    the innermost event active in it."""
    pts = []
    for i, (n, a, b) in enumerate(events):
        if b > a:
            pts.append((a, 1, i))
            pts.append((b, 0, i))
    pts.sort(key=lambda t: (t[0], t[1]))
    out: List[Event] = []
    active: List[int] = []          # stack by start order
    prev = None
    for t, kind, i in pts:
        if active and prev is not None and t > prev:
            out.append((events[active[-1]][0], prev, t))
        if kind == 1:
            active.append(i)
        else:
            active.remove(i)
        prev = t
    return out


def attribute_gaps(trace: Trace, plane: str, lo: int, hi: int,
                   host_events: Optional[Sequence[Event]] = None):
    """Idle time of `plane` inside [lo, hi) by what the job's thread was
    doing: {host event name: ns}, time under no event as UNATTRIBUTED."""
    busy = clip(union((a, b) for _n, a, b in device_ops(trace, plane)), lo, hi)
    idle = gaps(busy, lo, hi)
    segs = innermost_segments(
        job_thread(trace) if host_events is None else host_events)
    by_name: Dict[str, int] = {}
    covered = 0
    j = 0
    for name, a, b in segs:          # both lists are sorted and disjoint
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            part = min(b, idle[k][1]) - max(a, idle[k][0])
            if part > 0:
                by_name[name] = by_name.get(name, 0) + part
                covered += part
            k += 1
    rest = total(idle) - covered
    if rest:
        by_name[UNATTRIBUTED] = by_name.get(UNATTRIBUTED, 0) + rest
    return by_name, total(idle)


def thread_time_in(trace: Trace, pattern: re.Pattern, lo: int, hi: int) -> int:
    """ns of [lo, hi) in which the job's thread is inside an event whose
    name matches `pattern` (nested matches counted once)."""
    evs = [(a, b) for n, a, b in job_thread(trace) if pattern.search(n)]
    return total(clip(union(evs), lo, hi))


def top_device_ops(trace: Trace, lo: int, hi: int, n: int = 10):
    """[name, seconds] of the device ops that took most time, summed over
    devices and divided by their number."""
    planes = trace.device_planes()
    agg: Dict[str, int] = {}
    for p in planes:
        for name, a, b in device_ops(trace, p):
            if lo <= a < hi:
                # an op's name is its HLO text: keep what is left of " = "
                short = name.split(" = ")[0]
                agg[short] = agg.get(short, 0) + (b - a)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(len(planes), 1)] for k, v in top]
