"""The one-chip aggregation program's share of its roofline, %: the
least time the chip could take for the slice's requests over the device
time it spent on them (`busy_s`). Says which bound on stderr. The shape
function lives here, with its reader: benchmark/roofline.py holds the
peaks and `least_seconds`.

`agg_env` says what the ALGORITHM needs for one request of the class
`work` names, whatever implements it: read once, for each document of
the segment, `value_columns` 4-byte values (`distance_amount_agg`: the
`trip_distance` value it filters and buckets by and the `total_amount`
value it sums; `date_histogram_agg`: the `dropoff_datetime` value) and
the live bit; write `bins` bins of 4 bytes for each array the response
needs (doc_count, and for `stats` count, sum, min, max: five arrays;
one for a bare histogram). Operations: two compares of the range, the
bin's index, and an add, a min and a max a document, ~8. It leaves out:
the rank column and the rank -> bucket table a request gathers through
(the program's way to a bucket, not the algorithm's need), the bins
outside the request's own range (the column's whole span is thousands
of buckets wide, the page 50), the `size` 0 request's top-k, the packed
row, and the request's literals. All of device time counts against it,
as for the other rooflines."""

import sys

from benchmark import readings, roofline


def agg_env(sizes: dict, work: dict):
    docs = sizes["d_pad"]
    arrays = 5 if work["value_columns"] > 1 else 1
    return (docs * (4.0 * work["value_columns"] + 1.0 / 8.0)
            + work["bins"] * arrays * 4.0), docs * 8.0


def read(run, params):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    peaks = roofline.peaks(run.jax.devices()[0].device_kind)
    least, bounds = 0.0, set()
    for s, share in readings.slice_shares(run):
        for q in run.requests[s.index]:
            nbytes, flops = agg_env(run.corpus.sizes, q.work)
            t, bound = roofline.least_seconds(nbytes, flops, peaks)
            least += share * t
            bounds.add(bound)
    sys.stderr.write(f"[roofline] agg_env: bound by {sorted(bounds)}\n")
    return 100.0 * least / run.trace.busy_s
