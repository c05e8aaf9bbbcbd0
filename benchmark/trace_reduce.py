"""From a jax profiler trace (.xplane.pb) to the benchmark's device
numbers: for every chip's plane the seconds in which an operation ran
(the union of its device-op intervals) and the interval it recorded
(first op to last), their means over the chips, the operations that took
most time, and the longest idle gaps. Read with nothing but jax
(`jax.profiler.ProfileData`). tests/benchmark/test_trace_reduce.py holds
it to two small recorded traces (tests/benchmark/recorded).

A TPU device plane (`/device:TPU:<n>`) carries several lines over the
same time: "XLA Ops" (one event an executed HLO op), "XLA Modules" (one
event a program run, named for the jitted function), "Steps", and
others. Busy time is the union over the "XLA Ops" line; where a plane
has no such line, over all its lines. The "XLA Modules" events are kept
beside the ops: benchmark/spans.py cuts a plane's ops into program runs
by them.

**The window is what the device recorded.** A plane begins some
milliseconds after `start_trace` returns and ends before `stop_trace`
is called (4-134 ms and 16-153 ms, eight traced runs, PR 25), on every
plane separately, so the host's clock around the two calls is not the
interval the ops come from. A plane's window is its first recorded op's
start to its last op's end; `window_s` is the mean over the planes. The
host's own reading (`host_window_s`) stands in only where no plane was
recorded, or where the run could not lay the plane on the host's clock
(`keep_host_window`): the requests are then counted over the host's
slice, and the window has to be the same interval.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Event = Tuple[str, int, int]        # name, start ns, end ns


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(lines) -> List[Event]:
    out = []
    for ln in lines:
        for ev in ln.events:
            start = int(ev.start_ns)
            out.append((ev.name, start, start + int(ev.duration_ns)))
    return out


def device_events(path: str) -> Tuple[Dict[str, List[Event]],
                                      Dict[str, List[Event]]]:
    """(plane name -> its device-op events, plane name -> its program
    runs: the "XLA Modules" events, [] where the plane has no such
    line)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules = {}, {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        modules[plane.name] = _events(
            ln for ln in lines if ln.name == MODULES_LINE)
        ops[plane.name] = _events(
            [ln for ln in lines if ln.name == OPS_LINE]
            or [ln for ln in lines if ln.name != MODULES_LINE])
    return ops, modules


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
    """`planes`: plane name -> device-op events; `window_s`: the host's
    clock around the profiler's start and stop; `modules`: plane name ->
    program-run events, where the trace has them."""

    def __init__(self, planes: Dict[str, List[Event]], window_s: float,
                 modules: Optional[Dict[str, List[Event]]] = None):
        self.host_window_s = float(window_s)
        self.planes = planes
        self.modules = {name: (modules or {}).get(name, [])
                        for name in planes}
        self.plane_busy_s: Dict[str, float] = {}
        # plane name -> (first op's start, last op's end), the plane's ns
        self.plane_recorded_ns: Dict[str, Tuple[int, int]] = {}
        ops, gaps = {}, []
        for plane, events in planes.items():
            cover = union([(lo, hi) for _, lo, hi in events])
            self.plane_busy_s[plane] = sum(hi - lo for lo, hi in cover) / 1e9
            if cover and cover[-1][1] > cover[0][0]:
                self.plane_recorded_ns[plane] = (cover[0][0], cover[-1][1])
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
        self.busy_s = sum(self.plane_busy_s.values()) / len(planes) \
            if planes else 0.0
        self.host_window = not self.plane_recorded_ns
        n = max(len(planes), 1)
        self.device_ops = sorted(((k, v / n) for k, v in ops.items()),
                                 key=lambda r: -r[1])
        by_name: Dict[str, float] = {}
        for name, secs in gaps:
            by_name[name] = by_name.get(name, 0.0) + secs / n
        self.idle_gaps = sorted(by_name.items(), key=lambda r: -r[1])
        self.longest_gap_s = max((g for _, g in gaps), default=0.0)

    def keep_host_window(self) -> None:
        """The run counts its requests over the host's slice (no join
        put the planes on the host's clock): the window is that slice
        too."""
        self.host_window = True

    def plane_window_s(self, plane: str) -> float:
        if self.host_window or plane not in self.plane_recorded_ns:
            return self.host_window_s
        lo, hi = self.plane_recorded_ns[plane]
        return (hi - lo) / 1e9

    @property
    def window_s(self) -> float:
        """Mean of the planes' windows."""
        if not self.planes:
            return self.host_window_s
        return sum(self.plane_window_s(p) for p in self.planes) \
            / len(self.planes)

    @property
    def idle_share(self) -> float:
        """1 - the mean over the planes of busy / window."""
        if not self.planes:
            return 1.0
        return 1.0 - sum(self.plane_busy_s[p] / self.plane_window_s(p)
                         for p in self.planes) / len(self.planes)

    @property
    def busy_skew(self) -> Optional[float]:
        """(max - min) / mean of the planes' busy time; None with fewer
        than two planes, or where nothing ran."""
        busy = list(self.plane_busy_s.values())
        if len(busy) < 2 or self.busy_s <= 0:
            return None
        return (max(busy) - min(busy)) / self.busy_s

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.device_ops[:10]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:10]]}


def reduce_file(path: str, window_s: float) -> Reduction:
    ops, modules = device_events(path)
    return Reduction(ops, window_s, modules)
