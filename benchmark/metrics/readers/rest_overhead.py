"""HTTP + socket + JSON outside the REST handler: the client's mean
service time (send to last byte) minus the mean of the node's
`rest.search_ms` over the same window, ms."""

from benchmark import readings


def read(run, params):
    inside = readings.hist_delta_mean(run, "rest.search_ms")
    if inside is None or not run.samples:
        return None
    client = sum((s.done - s.sent) for s in run.samples) \
        * 1000.0 / len(run.samples)
    return client - inside
