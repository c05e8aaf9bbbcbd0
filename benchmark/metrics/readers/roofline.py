"""A kernel's share of its roofline, %: the least time the chip could
take for the slice's queries (roofline.py's shape functions and peaks)
over the device time they took. Says which bound on stderr."""

import sys

from benchmark import readings, roofline


def read(run, params):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    peaks = roofline.peaks(run.jax.devices()[0].device_kind)
    least, bounds = 0.0, set()
    for s, share in readings.slice_shares(run):
        for q in run.requests[s.index]:
            nbytes, flops = roofline.KERNELS[params["kernel"]](
                run.corpus.sizes, q.work)
            t, bound = roofline.least_seconds(nbytes, flops, peaks)
            least += share * t
            bounds.add(bound)
    sys.stderr.write(f"[roofline] {params['kernel']}: bound by "
                     f"{sorted(bounds)}\n")
    return 100.0 * least / run.trace.busy_s
