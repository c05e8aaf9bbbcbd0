"""Device-op time a query by stage, ms: the device ops of the traced
slice whose HLO instruction the executable census puts in one of
`stages` (`GET /_telemetry/kernels?scopes=true`, through the
`fingerprint` of the `dispatch` span that ran them; `~stage` is a stage
the census inferred from the op's neighbours), over the queries served
in its window, a chip (the mean over the planes). With `rest_of`
instead: the ops of every other stage and of none, so the metrics of a
cell sum to the slice's device-op time,
which is `device_ms_per_query` wherever ops do not overlap. None
without a device plane, a span ring, a feasible join, or a scope map
for an executable dispatched in the slice."""

from benchmark import readings, spans


def read(run, params):
    mesh = spans.device_join(run)
    maps = spans.scope_maps(run) if mesh is not None else None
    n = readings.queries_in_slice(run) if maps is not None else 0
    if n <= 0:
        return None
    if "_stage_ns" not in run.__dict__:
        run._stage_ns = spans.mesh_stage_ns(mesh, maps)
    by_stage = run._stage_ns
    if by_stage is None:
        return None
    if "rest_of" in params:
        ns = sum(by_stage.values()) \
            - sum(by_stage.get(st, 0) for st in params["rest_of"])
    else:
        ns = sum(by_stage.get(st, 0) for st in params["stages"])
    return ns / 1e6 / n
