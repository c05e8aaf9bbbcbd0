"""Device time a query: the union of device-op intervals in the traced
window (a chip: the mean over the planes) over the queries served in
the interval the planes recorded (readings.py `slice_shares`), ms. This
is ALL device time of the window; it stands for one kernel family only
because each cell runs one. None where no plane was recorded."""

from benchmark import readings


def read(run, params):
    if run.trace is None or not run.trace.planes:
        return None
    n = readings.queries_in_slice(run)
    return run.trace.busy_s * 1000.0 / n if n > 0 else None
