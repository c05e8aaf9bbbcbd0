"""How unevenly the chips of a mesh worked in the traced window: (the
busiest plane's busy time - the idlest's) over the planes' mean, %
(benchmark/trace_reduce.py `busy_skew`). A straggler, or work that one
chip alone does, shows here before the mean hides it. None with fewer
than two device planes."""


def read(run, params):
    skew = None if run.trace is None else run.trace.busy_skew
    return None if skew is None else 100.0 * skew
