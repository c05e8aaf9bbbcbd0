"""Client-side latency percentile over every counted request, in ms.
`from`: "intended" times from the intended send time (open loop),
"sent" from the actual send (closed loop service time)."""

from benchmark import loadgen


def read(run, params):
    start = params.get("from", "intended")
    lat = [(s.done - getattr(s, start)) * 1000.0 for s in run.samples]
    return loadgen.percentile(lat, float(params["p"])) if lat else None
