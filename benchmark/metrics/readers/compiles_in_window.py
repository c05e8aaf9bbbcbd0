"""Programs compiled (or loaded from the persistent cache: jax reports
both as a backend compile) between the window's start and its last
answer, beside the node's own `search.xla_cache_miss`; the larger of the
two. Anything but 0 is set-up that leaked into the window."""

from benchmark import readings


def read(run, params):
    t0, _ = run.window
    events = sum(1 for t in run.compile_times if t0 <= t <= run.drained)
    return max(events, readings.counter_delta(run, "search.xla_cache_miss"))
