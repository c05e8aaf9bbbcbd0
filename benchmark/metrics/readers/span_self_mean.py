"""Mean, over the served requests that lie inside the window, of time
read from the program's own spans (benchmark/spans.py), ms: the self
time (duration less what the children cover) of the spans named in
`self`, plus the whole duration of those named in `plus`. None where
the node keeps no span ring."""

from benchmark import spans


def read(run, params):
    ring = spans.fetch(run)
    if ring is None:
        return None
    requests = ring.requests(*spans.window_ns(run))
    if not requests:
        return None
    total = 0
    for req in requests:
        for name in params.get("self", []):
            total += sum(ring.self_ns(s)
                         for s in ring.named(req["trace_id"], name))
        for name in params.get("plus", []):
            total += sum(s["end_ns"] - s["start_ns"]
                         for s in ring.named(req["trace_id"], name))
    return total / len(requests) / 1e6
