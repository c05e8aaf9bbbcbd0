"""From a jax profiler trace (.xplane.pb) to the benchmark's device
numbers: seconds in which an operation ran on the device (the union of
device-op intervals, averaged over the chips), the operations that took
most time, and the longest idle gaps. Read with nothing but jax
(`jax.profiler.ProfileData`). tests/benchmark/test_trace_reduce.py holds
it to a small recorded trace.

A TPU device plane (`/device:TPU:<n>`) carries several lines over the
same time: "XLA Ops" (one event an executed HLO op), "XLA Modules" (one
event a program run), "Steps", and others. Busy time is the union over
the "XLA Ops" line; where a plane has no such line, over all its lines.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Event = Tuple[str, int, int]        # name, start ns, end ns


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def device_events(path: str) -> Dict[str, List[Event]]:
    """plane name -> its device-op events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
        events = []
        for ln in ops:
            for ev in ln.events:
                start = int(ev.start_ns)
                events.append((ev.name, start,
                               start + int(ev.duration_ns)))
        out[plane.name] = events
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")


def op_name(name: str) -> str:
    """An op's name as the breakdown prints it. The TPU trace names an
    event by its whole HLO instruction (`fusion.2 = f32[16777216]{...}
    fusion(...), kind=kCustom, ...`): keep the instruction's name (XLA's
    instance counter tells two fusions of one program apart) and its
    (first) result's type and shape, `fusion.2_f32_16777216`; the shape
    tells the same fusion of two shape buckets apart."""
    m = HLO.match(name.strip())
    if m is None:
        return name.strip().lstrip("%")[:64]
    return f"{m.group(1)}_{m.group(2)}_{m.group(3).replace(',', 'x')}"


class Reduction:
    def __init__(self, planes: Dict[str, List[Event]], window_s: float):
        self.window_s = float(window_s)
        self.planes = planes
        busy, ops, gaps = [], {}, []
        for plane, events in planes.items():
            cover = union([(lo, hi) for _, lo, hi in events])
            busy.append(sum(hi - lo for lo, hi in cover) / 1e9)
            for name, lo, hi in events:
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + (hi - lo) / 1e9
            # what ran last before each gap names it, until host spans
            # are written into the trace
            ends = sorted((hi, name) for name, _, hi in events)
            e_i = 0
            last = "start"
            for (_, hi0), (lo1, _) in zip(cover, cover[1:]):
                while e_i < len(ends) and ends[e_i][0] <= hi0:
                    last = ends[e_i][1]
                    e_i += 1
                gaps.append((f"unattributed_after_{op_name(last)}",
                             (lo1 - hi0) / 1e9))
        self.busy_s = sum(busy) / len(busy) if busy else 0.0
        n = max(len(planes), 1)
        self.device_ops = sorted(((k, v / n) for k, v in ops.items()),
                                 key=lambda r: -r[1])
        by_name: Dict[str, float] = {}
        for name, secs in gaps:
            by_name[name] = by_name.get(name, 0.0) + secs / n
        self.idle_gaps = sorted(by_name.items(), key=lambda r: -r[1])
        self.longest_gap_s = max((g for _, g in gaps), default=0.0)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.device_ops[:10]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:10]]}


def reduce_file(path: str, window_s: float) -> Reduction:
    return Reduction(device_events(path), window_s)
