"""The `q`-th percentile, over the served requests that lie inside the
window, of the time a request spent in its spans of one `name` (their
whole durations, added where a request has several), ms: what a mean
(`span_self_mean`) hides where one request in four is slow. By linear
interpolation between order statistics, as the latency percentiles are
(`loadgen.percentile`). None where the node keeps no span ring, or no
request of the window has such a span."""

from benchmark import loadgen, spans


def read(run, params):
    ring = spans.fetch(run)
    if ring is None:
        return None
    took = []
    for req in ring.requests(*spans.window_ns(run)):
        named = ring.named(req["trace_id"], params["name"])
        if named:
            took.append(sum(s["end_ns"] - s["start_ns"] for s in named))
    if not took:
        return None
    return loadgen.percentile(took, float(params["q"])) / 1e6
