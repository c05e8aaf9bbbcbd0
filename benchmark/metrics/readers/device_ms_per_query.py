"""Device time a query: the union of device-op intervals in the traced
slice over the queries served in it, ms. No kernel family has a name in
the trace yet, so this is ALL device time of the slice; it stands for one
family only because each cell runs one."""

from benchmark import readings


def read(run, params):
    if run.trace is None:
        return None
    n = readings.queries_in_slice(run)
    return run.trace.busy_s * 1000.0 / n if n > 0 else None
