"""Resident lane -> bin vectors of `histogram`/`date_histogram` levels.

The lane -> bin vector `table[val_ords]` of such a level rests on nothing
of the request: the table is a function of the level's bucketing and the
segment's sorted unique values, the rank column is sealed, and the
request's own part reaches the bins through the mask alone (a delete
changes `live`, not the bins). So every route derives it once a (device
image, field, bucketing) by one small program over the resident rank
column (`lane_bins_row`) and keeps it on the device beside the image it
was derived from, in a `LaneBinsMemo` owned by whatever owns that image:
the SPMD route's `HbmShardSet` (int32 `[R_pad, n_pad]`, sharded like the
image's own columns) and the one-chip routes' `ShardReader` (int32
`[n_pad]` a segment). The served program reads it as
`seg["lane_bins"][slot]` (`engine._eval_agg`) and is the same executable
on the miss and on the hit.

A memo holds at most MAX_LANE_BINS vectors, least recently used out: one
vector is 4 B a lane, and a key is a panel's (field, interval, offset or
time zone), none of which moves from one request of a dashboard to the
next. (A `range` bucket's bounds can, `now`-relative ones with every
request: those stay out of the memo, on the table their request brings.)
Vectors found, derived and dropped (by a memo's own LRU, or with the image
they belong to) are counted on every route, always on, in `_nodes/stats`.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable

import jax.numpy as jnp

from opensearch_tpu.telemetry import TELEMETRY

MAX_LANE_BINS = 4
LANE_BINS_HIT = TELEMETRY.metrics.counter("search.agg_lane_bins.hit")
LANE_BINS_MISS = TELEMETRY.metrics.counter("search.agg_lane_bins.miss")
LANE_BINS_EVICTED = TELEMETRY.metrics.counter("search.agg_lane_bins.evicted")


def lane_bins_row(table, doc_ids, val_ords):
    """One row's lane -> bin vector: the bucket of every (doc, value)
    pair's rank, -1 where the table says no bucket or the lane is
    padding. The gather the served program did a request."""
    return jnp.where(doc_ids >= 0, table[val_ords], -1)


class LaneBinsMemo:
    """The resident vectors of one device image, by (field, bucketing
    scalars), least recently used first. They live and die with the
    image: its owner calls `release()` when it drops the image, and
    counts `nbytes` with the image in the device-memory gauges
    (`on_change` runs, under the memo's lock, whenever `nbytes` moved
    while the image is held)."""

    def __init__(self, on_change: Callable[[], None] = lambda: None):
        self.vectors: "OrderedDict[tuple, Any]" = OrderedDict()
        self.nbytes = 0
        self._on_change = on_change
        self._lock = threading.Lock()
        self._released = False

    def get(self, key: tuple, derive: Callable[[], Any]):
        """The vector under `key`; `derive()` makes it on a miss, under
        the memo's lock, so that concurrent requests of one panel derive
        it once. A memo whose image is gone keeps nothing: a request
        still running on that image derives for itself."""
        with self._lock:
            bins = self.vectors.get(key)
            if bins is not None:
                self.vectors.move_to_end(key)
                LANE_BINS_HIT.inc()
                return bins
            LANE_BINS_MISS.inc()
            bins = derive()
            if self._released:
                return bins
            while len(self.vectors) >= MAX_LANE_BINS:
                self.nbytes -= self.vectors.popitem(last=False)[1].nbytes
                LANE_BINS_EVICTED.inc()
            self.vectors[key] = bins
            self.nbytes += bins.nbytes
            self._on_change()
            return bins

    def release(self) -> None:
        """The image is dropped: its vectors go with it."""
        with self._lock:
            self._released = True
            LANE_BINS_EVICTED.inc(len(self.vectors))
            self.vectors.clear()
            self.nbytes = 0
