"""What several metric readers share: deltas of the node's always-on
histograms and counters over the window (`_nodes/stats` before and after;
only sum and count are read, never the bucket-interpolated percentiles),
and which queries the traced window covers."""

from __future__ import annotations

from typing import Optional

from benchmark import spans


def _metrics(run, when: str) -> dict:
    return run.stats[when]["telemetry"]["metrics"]


def hist_delta(run, name: str):
    """(delta count, delta sum in ms) of one histogram over the window."""
    h0 = _metrics(run, "before")["histograms"].get(name, {})
    h1 = _metrics(run, "after")["histograms"].get(name)
    if h1 is None:
        return 0, 0.0
    return (h1["count"] - h0.get("count", 0),
            h1["sum_ms"] - h0.get("sum_ms", 0.0))


def hist_delta_mean(run, name: str) -> Optional[float]:
    count, total = hist_delta(run, name)
    return total / count if count > 0 else None


def counter_delta(run, name: str) -> int:
    c0 = _metrics(run, "before")["counters"].get(name, 0)
    c1 = _metrics(run, "after")["counters"].get(name, 0)
    return c1 - c0


def slice_shares(run):
    """[(sample, share)] for every request that overlaps the traced
    window: the share of its service interval inside the window. Summed,
    the shares count the requests the traced device time belongs to
    without an edge error of a whole request at either end. The window
    is the interval the device recorded, put on the host's clock by the
    join's offset (benchmark/spans.py `recorded_intervals`; a request's
    share is its mean over the planes), the same interval `window_s`
    and idle are read over; without a join, the host's slice around the
    profiler's start and stop, for both."""
    if run.trace_slice is None:
        return []
    intervals = spans.recorded_intervals(run) or [run.trace_slice]
    out = []
    for s in run.all_samples:
        if s.done <= s.sent:
            continue
        inside = sum(max(min(s.done, b) - max(s.sent, a), 0.0)
                     for a, b in intervals) / len(intervals)
        if inside > 0:
            out.append((s, inside / (s.done - s.sent)))
    return out


def queries_in_slice(run) -> float:
    return sum(share * len(run.requests[s.index])
               for s, share in slice_shares(run))
