"""Device idle time of the traced slice a query during which a span of
one `name` was open on the host, ms: of a request's spans (`track`
`spans`) or of the process track (`process`: `gc.collect`). The device's
ops are put on the host's clock by the join's offset
(`spans.device_join`), idle is what lies between them inside each
plane's recorded interval, and the share of it under the named spans is
averaged over the planes and divided by the queries of the slice, as the
four `idle_*_ms` are: a part of them, not a fifth beside them. It is as
good as the offset: read it beside `span_clock_bracket_us`. None without
a device plane, a span ring (for `process`: a process track), a
feasible join, or a single request span of the name in the ring (a
program older than the span); 0.0 where the process track is there and
held no such span near the slice."""

from benchmark import process_track, readings, spans
from benchmark.trace_reduce import union


def idle_gaps(jn):
    """One plane's idle intervals inside its recorded interval, on the
    host's clock."""
    a, b = jn.recorded_ns
    off = jn.offset
    busy = union([(max(ev[1] - off, a), min(ev[2] - off, b))
                  for r in jn.runs for ev in r.events
                  if ev[2] - off > a and ev[1] - off < b])
    gaps, t = [], a
    for lo, hi in busy:
        if lo > t:
            gaps.append((t, lo))
        t = max(t, hi)
    if t < b:
        gaps.append((t, b))
    return gaps


def overlap_ns(gaps, cover) -> int:
    """Nanoseconds of the disjoint, sorted `gaps` that lie under the
    disjoint, sorted `cover`."""
    total = i = 0
    for lo, hi in gaps:
        while i < len(cover) and cover[i][1] <= lo:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < hi:
            total += min(hi, cover[j][1]) - max(lo, cover[j][0])
            j += 1
    return total


def read(run, params):
    mesh = spans.device_join(run)
    n = readings.queries_in_slice(run) if mesh is not None else 0
    if n <= 0:
        return None
    if params.get("track", "spans") == "process":
        a, b = run.trace_slice
        rows = process_track.fetch(run, since_ns=int((a - 2.0) * 1e9),
                                   until_ns=int((b + 2.0) * 1e9))
    else:
        ring = spans.fetch(run)
        rows = None if ring is None else ring.spans
    if rows is None:
        return None
    cover = union([(s["start_ns"], s["end_ns"]) for s in rows
                   if s["name"] == params["name"]])
    if not cover and params.get("track", "spans") == "spans":
        return None     # a program that records no such span
    under = sum(overlap_ns(idle_gaps(jn), cover)
                for jn in mesh.planes.values()) / len(mesh.planes)
    return under / 1e6 / n
