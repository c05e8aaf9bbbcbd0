"""The binned aggregation's share of its roofline, %: the least time
one chip could take for the slice's panels over the device time a chip
spent on them (the mean over the planes, `busy_s`). Says which bound on
stderr. The shape function lives here, with its reader:
benchmark/roofline.py holds the peaks and `least_seconds`.

`agg_binned` says what the ALGORITHM needs for one request on one chip
of the mesh: read once, for each document of the chip's rows, the
`@timestamp` rank (4 B), the `status` ordinal (4 B), the `size` value
(4 B) and the live bit; write the counts and sums of hour_buckets x
status_values bins (4 B each, two arrays). Operations: two compares of
the range, the bin's index and two adds a document, ~8. It leaves out:
the rank -> hour table (one gather a document into a table of the
shard's distinct seconds, 8 MB a row, which a coarser column would
spare), the per-hour and per-status `doc_count`s (sums of the bins
above), the top-k over the rows that a `size` 0 request does not need,
the collectives (a few KB), and the request's literals. All of device
time counts against it, as for the other rooflines: one program serves
the cell."""

import sys

from benchmark import readings, roofline


def agg_binned(sizes: dict, work: dict, chips: int):
    docs = sizes["d_pad"] * sizes["rows"] / chips
    bins = work["hour_buckets"] * work["status_values"]
    return docs * (12.0 + 1.0 / 8.0) + bins * 2 * 4.0, docs * 8.0


def read(run, params):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    peaks = roofline.peaks(run.jax.devices()[0].device_kind)
    least, bounds = 0.0, set()
    for s, share in readings.slice_shares(run):
        for q in run.requests[s.index]:
            nbytes, flops = agg_binned(run.corpus.sizes, q.work,
                                       run.workload["chips"])
            t, bound = roofline.least_seconds(nbytes, flops, peaks)
            least += share * t
            bounds.add(bound)
    sys.stderr.write(f"[roofline] agg_binned: bound by {sorted(bounds)}\n")
    return 100.0 * least / run.trace.busy_s
