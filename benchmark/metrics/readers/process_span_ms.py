"""Time in the spans of one `name` on the node's PROCESS track (`GET
/_telemetry/spans`, key `process`: what no request owns, on the clock
of the requests' spans: the heap's collections `gc.collect`, an index's
install), by `over`:

- `window`: the part of those spans inside the measured window, added,
  over the requests the window served (the ring's, as `span_self_mean`
  counts them): ms a request;
- `run`: the whole of those that ended before the window began
  (set-up), added and not divided.

In ms, or in seconds where `unit` is `s`. Its own requests to the node
(`benchmark/process_track.py`), filtered by `since_ns` / `until_ns`.
None where the node has no process track (a program older than it), and
for `window` where the ring shows no request."""

from benchmark import process_track, spans


def read(run, params):
    t0, t1 = spans.window_ns(run)
    if params["over"] == "run":
        track = process_track.fetch(run, until_ns=t0)
        if track is None:
            return None
        ns = sum(s["end_ns"] - s["start_ns"] for s in track
                 if s["name"] == params["name"] and s["end_ns"] <= t0)
    else:
        track = process_track.fetch(run, since_ns=t0, until_ns=t1)
        ring = spans.fetch(run)
        if track is None or ring is None:
            return None
        served = len(ring.requests(t0, t1))
        if not served:
            return None
        ns = sum(max(min(s["end_ns"], t1) - max(s["start_ns"], t0), 0)
                 for s in track if s["name"] == params["name"]) / served
    return ns / (1e9 if params.get("unit") == "s" else 1e6)
