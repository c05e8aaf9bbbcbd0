"""Device-op time a query by stage, ms, read WITHOUT the join of waves
and program runs: every device op of the traced slice is looked up in
one instruction -> stage map, the executable census' maps (`GET
/_telemetry/kernels?scopes=true`) of the executables that the `dispatch`
spans near the slice name (by `fingerprint`), which have to agree on
every instruction that ran. That holds wherever the slice runs one
served executable, as a cell of one query class does; where two
executables put an instruction that ran in different stages only the
join can tell their runs apart, and this reader returns None (`stage_ms`
is the reader for such a cell).

Why it exists (PERF.md, section 7): `stage_ms` needs `spans.device_join`,
and with eight clients in flight the join's search for its start is too
short to find the true matching; it finds none at all in a slice where
the device queue once ran dry, and the stage metrics then drop out of
the line. The queries are counted as `device_ms_per_query` counts them
(`readings.queries_in_slice`: the joined planes' recorded intervals, or
the host's slice where there is no join). `stages` / `rest_of` as in
`stage_ms`; a chip's share is the mean over the planes. None without a
device plane, a span ring or a scope map for a dispatched executable."""

from benchmark import readings, spans

# how long before the slice a `dispatch` span may end and its program
# still run inside it: the device queue's depth in time. The k-NN cell's
# eight programs of 11.3 ms wait 0.09 s at most; 2 s covers a queue of
# ~170 of them. An executable named from further back than the queue
# reaches only has to agree with the others: it adds no time
QUEUE_LOOKBACK_NS = 2_000_000_000


def slice_stage_ns(run):
    """{stage -> device-op ns a plane} of the traced slice, once a run;
    None where it cannot be read."""
    ring = spans.fetch(run)
    maps = spans.scope_maps(run) if ring is not None else None
    if maps is None or run.trace_slice is None:
        return None
    a, b = (int(t * 1e9) for t in run.trace_slice)
    # the executables whose runs can lie in the trace: dispatched from a
    # little before the slice (programs queue) to its end
    fps = {fp for w in spans.waves_of(ring)
           if w.end_ns >= a - QUEUE_LOOKBACK_NS and w.start_ns <= b
           for fp in w.fingerprints}
    if not fps or not fps <= set(maps):
        return None
    out = {}
    for events in run.trace.planes.values():
        for name, lo, hi in events:
            instr = spans.instruction(name)
            stages = {maps[fp].get(instr) for fp in fps}
            if len(stages) != 1:
                return None     # the executables disagree: needs the join
            st = stages.pop()
            out[st] = out.get(st, 0.0) + (hi - lo) / len(run.trace.planes)
    return out


def read(run, params):
    if run.trace is None or not run.trace.planes:
        return None
    n = readings.queries_in_slice(run)
    if n <= 0:
        return None
    if "_slice_stage_ns" not in run.__dict__:
        run._slice_stage_ns = slice_stage_ns(run)
    by_stage = run._slice_stage_ns
    if by_stage is None:
        return None
    if "rest_of" in params:
        ns = sum(by_stage.values()) \
            - sum(by_stage.get(st, 0) for st in params["rest_of"])
    else:
        ns = sum(by_stage.get(st, 0) for st in params["stages"])
    return ns / 1e6 / n
