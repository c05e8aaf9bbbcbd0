"""Device idle time of the traced slice a query, ms, for one `part` of
what the host was in (benchmark/spans.py `idle_parts`:
between_requests, before_first_op, inside_request, after_last_op). The
four parts sum to the slice's idle time. None without a device plane,
a span ring, or a feasible clock offset."""

from benchmark import readings, spans


def read(run, params):
    jn = spans.device_join(run)
    n = readings.queries_in_slice(run) if jn is not None else 0
    if n <= 0:
        return None
    if "_idle_parts" not in run.__dict__:
        a, b = run.trace_slice
        run._idle_parts = spans.idle_parts(
            spans.fetch(run), jn, (int(a * 1e9), int(b * 1e9)))
    return run._idle_parts[params["part"]] / 1e6 / n
