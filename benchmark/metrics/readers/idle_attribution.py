"""Device idle time of the traced window a query, ms, for one `part` of
what the host was in (benchmark/spans.py `idle_parts`:
between_requests, before_first_op, inside_request, after_last_op),
inside the interval each plane recorded and averaged over the planes.
The four parts sum to the window's idle time (`window_s` - `busy_s`).
None without a device plane, a span ring, or a feasible clock offset."""

from benchmark import readings, spans


def read(run, params):
    mesh = spans.device_join(run)
    n = readings.queries_in_slice(run) if mesh is not None else 0
    if n <= 0:
        return None
    if "_idle_parts" not in run.__dict__:
        run._idle_parts = spans.mesh_idle_parts(spans.fetch(run), mesh)
    return run._idle_parts[params["part"]] / 1e6 / n
