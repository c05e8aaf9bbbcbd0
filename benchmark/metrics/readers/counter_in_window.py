"""One of the node's counters over the window: `_nodes/stats` after
less before (`counter`). None where the node has no such counter (a
program older than the counter): the metric is then left out, not 0."""


def read(run, params):
    name = params["counter"]
    after = run.stats["after"]["telemetry"]["metrics"]["counters"]
    if name not in after:
        return None
    before = run.stats["before"]["telemetry"]["metrics"]["counters"]
    return after[name] - before.get(name, 0)
