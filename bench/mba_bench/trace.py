"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it into planes, lines and events (start
and duration in nanoseconds).  A TPU shows up as a plane named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per operation
that ran on the chip; host threads are lines of ``/host:CPU``.

The reduction keeps plain records (:class:`Event`) so that it can be
checked on a synthetic trace without a profiler:

* busy seconds: the union of the device-op intervals of each device plane,
  averaged over the devices;
* a kernel's seconds: the summed durations of the device ops whose name
  starts with the kernel's stable name (the Pallas call takes the name of
  the jitted function around it, e.g. ``support_count_fused_pallas``);
* idle gaps: the spaces between busy intervals, each named after the
  shortest host event that covers the gap's middle (what the host was
  doing while the chip waited).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Device ops per device plane, and host events, of one traced window."""

    device_ops: Dict[str, List[Event]]
    host_events: List[Event]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(hlo_text: str) -> str:
    """``%support_count_fused_pallas.1 = s32[...] custom-call(...)`` ->
    ``support_count_fused_pallas``: the op's own name, without its
    operands (which name other ops) or its instance number."""
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_planes(planes) -> Trace:
    """Device ops and host events from profiler planes (objects with
    ``name`` and ``lines``; lines with ``name`` and ``events``; events with
    ``name``, ``start_ns`` and ``duration_ns``)."""
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        # a chip's plane has an ops line; other "/device:" planes (such as
        # "/device:CUSTOM:Megascale Trace") are no chip and do not count
        if plane.name.startswith(DEVICE_PREFIX) and OPS_LINE in lines:
            device_ops[plane.name] = [
                Event(op_name(ev.name), float(ev.start_ns),
                      float(ev.duration_ns))
                for ev in lines[OPS_LINE].events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append(Event(ev.name, float(ev.start_ns),
                                          float(ev.duration_ns)))
    return Trace(device_ops=device_ops, host_events=host)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace) -> float:
    """Union of device-op intervals, averaged over the device planes."""
    if not trace.device_ops:
        return 0.0
    per_device = []
    for ops in trace.device_ops.values():
        merged = union((e.start_ns, e.end_ns) for e in ops)
        per_device.append(sum(e - s for s, e in merged) / 1e9)
    return sum(per_device) / len(per_device)


def idle_share(busy_s: float, window_s: float) -> Optional[float]:
    """1 - busy / window, in percent; None for an empty window."""
    if window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)


def kernel_seconds(trace: Trace, prefixes: Sequence[str]) -> float:
    """Summed device durations of the ops whose name starts with one of
    ``prefixes``, averaged over the device planes."""
    if not trace.device_ops:
        return 0.0
    total = sum(ev.dur_ns for ops in trace.device_ops.values()
                for ev in ops if ev.name.startswith(tuple(prefixes)))
    return total / 1e9 / len(trace.device_ops)


def top_device_ops(trace: Trace, n: int = 10) -> List[List]:
    """The n device ops (by name) that took the most seconds."""
    acc: Dict[str, float] = defaultdict(float)
    for ops in trace.device_ops.values():
        for ev in ops:
            acc[ev.name] += ev.dur_ns / 1e9 / len(trace.device_ops)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def op_counts(trace: Trace) -> Dict[str, int]:
    """How many times each device op (by name) ran, over all planes."""
    acc: Dict[str, int] = defaultdict(int)
    for ops in trace.device_ops.values():
        for ev in ops:
            acc[ev.name] += 1
    return dict(acc)


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The n longest device idle gaps, named by the host's work in them.

    Gaps are taken between busy intervals of the first device plane.  Each
    is named after the shortest host event that spans the gap's middle, or,
    where none does, after the device op that ran last before it.
    """
    if not trace.device_ops:
        return []
    plane = sorted(trace.device_ops)[0]
    ops = sorted(trace.device_ops[plane], key=lambda e: e.end_ns)
    merged = union((e.start_ns, e.end_ns) for e in ops)
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    ends = [e.end_ns for e in ops]
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        covering = [h for h in trace.host_events
                    if h.start_ns <= mid <= h.end_ns]
        if covering:
            label = "host: " + min(covering, key=lambda h: h.dur_ns).name
        else:
            before = ops[max(bisect.bisect_right(ends, s) - 1, 0)].name
            label = "after " + before
        out.append([label, (e - s) / 1e9])
    return out
