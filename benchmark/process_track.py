"""The node's PROCESS track, read by the benchmark: beside the
requests' spans `GET /_telemetry/spans` serves, under `process`, the
completed spans of what no request owns (`trace_id` 0, the same
`time.monotonic_ns()` clock: the heap's collections `gc.collect`, an
index's install `install.*`). `benchmark/spans.py` reads `spans` alone;
the readers that want the other key fetch it here, filtered by the
node, once a run and interval. Against a node that has no process track
(a program older than it) `fetch` returns None and nothing raises."""

from __future__ import annotations

from typing import List, Optional


def fetch(run, since_ns: Optional[int] = None,
          until_ns: Optional[int] = None) -> Optional[List[dict]]:
    """The process spans that end at or after `since_ns` and start at
    or before `until_ns`; None where the node serves none."""
    cache = run.__dict__.setdefault("_process_tracks", {})
    key = (since_ns, until_ns)
    if key not in cache:
        query = "&".join(f"{name}={ns}" for name, ns in
                         (("since_ns", since_ns), ("until_ns", until_ns))
                         if ns is not None)
        try:
            body = run.call("GET", "/_telemetry/spans"
                            + ("?" + query if query else ""))
        except (RuntimeError, ValueError):
            body = {}
        cache[key] = body.get("process")
    return cache[key]
