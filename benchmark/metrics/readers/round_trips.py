"""Device round trips a request: the transfer ledger's `device_get`
calls over the window, over the `_msearch` requests served in it."""

from benchmark import readings


def read(run, params):
    t0, t1 = run.transfers["before"], run.transfers["after"]
    if not t1.get("enabled"):
        return None
    batches = readings.counter_delta(run, "msearch.requests")
    if batches <= 0:
        return None
    calls = t1["device_get"]["calls"] - t0["device_get"]["calls"]
    return calls / batches
