"""How late the generator sent: p95 of actual minus intended send time,
ms. A starved generator must not read as a fast server."""

from benchmark import loadgen


def read(run, params):
    late = [(s.sent - s.intended) * 1000.0 for s in run.samples]
    return loadgen.percentile(late, 0.95) if late else None
