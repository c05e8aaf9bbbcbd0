"""`LaneBinsMemo` (search/aggs/lane_bins.py), the one memo of resident
lane -> bin vectors that the SPMD route's shard set and the one-chip
routes' segment images both keep (ISSUE 36): least recently used out at
MAX_LANE_BINS, one derivation a key under concurrent requests, bytes
that follow what is held, nothing kept once the image is released."""

import sys
import threading
import time

import numpy as np
import pytest

from opensearch_tpu.search.aggs.lane_bins import (LaneBinsMemo,
                                                  MAX_LANE_BINS,
                                                  lane_bins_row)
from opensearch_tpu.telemetry import TELEMETRY


def lane_bins():
    return {k.rsplit(".", 1)[1]: v
            for k, v in TELEMETRY.metrics.to_dict()["counters"].items()
            if k.startswith("search.agg_lane_bins.")}


def delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def vec(n):
    return np.full(256, n, np.int32)


def test_a_row_is_the_gather_with_no_bucket_in_padding_lanes():
    table = np.array([0, 0, 1, -1, 2, -1, -1, -1], np.int32)
    doc_ids = np.array([0, 1, 2, 3, 4, -1, -1, -1], np.int32)
    val_ords = np.array([4, 3, 2, 1, 0, 0, 0, 0], np.int32)
    got = np.asarray(lane_bins_row(table, doc_ids, val_ords))
    assert got.dtype == np.int32
    assert got.tolist() == [2, -1, 1, 0, 0, -1, -1, -1]


def test_the_least_recently_used_vector_makes_room():
    changes = []
    memo = LaneBinsMemo(on_change=lambda: changes.append(memo.nbytes))
    before = lane_bins()
    for n in range(MAX_LANE_BINS):
        assert memo.get(("f", n), lambda n=n: vec(n))[0] == n
    assert memo.get(("f", 0), lambda: pytest.fail("held"))[0] == 0
    assert memo.get(("f", "new"), lambda: vec(9))[0] == 9
    assert list(memo.vectors) == [("f", 2), ("f", 3), ("f", 0), ("f", "new")]
    assert memo.nbytes == MAX_LANE_BINS * vec(0).nbytes
    assert delta(lane_bins(), before) == {
        "miss": MAX_LANE_BINS + 1, "hit": 1, "evicted": 1}
    # the owner hears of every change of what is held, and of no hit
    assert changes == [vec(0).nbytes * min(n + 1, MAX_LANE_BINS)
                       for n in range(MAX_LANE_BINS + 1)]


def test_a_released_memo_drops_what_it_held_and_keeps_nothing_more():
    changes = []
    memo = LaneBinsMemo(on_change=lambda: changes.append(1))
    memo.get("a", lambda: vec(1))
    memo.get("b", lambda: vec(2))
    before = lane_bins()
    memo.release()
    assert not memo.vectors and memo.nbytes == 0
    assert delta(lane_bins(), before) == {"evicted": 2}
    # a request still running on the dropped image derives for itself
    assert memo.get("a", lambda: vec(3))[0] == 3
    assert memo.get("a", lambda: vec(4))[0] == 4
    assert not memo.vectors and memo.nbytes == 0 and len(changes) == 2
    assert delta(lane_bins(), before) == {"evicted": 2, "miss": 2}


def test_concurrent_requests_of_one_panel_derive_once():
    """More threads than cores, a short switch interval: a key is derived
    once however many ask at once, the memo never holds more than its
    bound, and its bytes are those of what it holds."""
    memo = LaneBinsMemo()
    derived = []
    lock = threading.Lock()

    def derive(key):
        with lock:
            derived.append(key)
        time.sleep(0.001)
        return vec(key)

    def worker(i):
        for r in range(40):
            key = (i + r) % 3       # three keys: within the bound
            assert memo.get(key, lambda key=key: derive(key))[0] == key

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert sorted(derived) == [0, 1, 2]
    assert len(memo.vectors) == 3 <= MAX_LANE_BINS
    assert memo.nbytes == sum(v.nbytes for v in memo.vectors.values())
