"""Concurrency contracts for the telemetry ingest paths (ISSUE 10
satellite): RollingEstimator, metrics Histogram and Counter must not
lose observations under N concurrent writer threads — concurrent
clients drive every one of them from worker threads, where an unguarded
read-modify-write silently drops samples and a doubly-applied decay
distorts the live p99 the future wave scheduler budgets against."""

import threading

from opensearch_tpu.telemetry.lifecycle import FlightRecorder
from opensearch_tpu.telemetry.metrics import MetricsRegistry
from opensearch_tpu.telemetry.rolling import RollingEstimator

N_THREADS = 8
N_PER_THREAD = 2000


def _hammer(fn, n_threads=N_THREADS, n_per_thread=N_PER_THREAD):
    errs = []

    def worker(tid):
        try:
            for i in range(n_per_thread):
                fn(tid, i)
        except Exception as e:      # surfacing beats a hung join
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs


def test_rolling_estimator_concurrent_exact_total():
    est = RollingEstimator(half_life_s=None)    # no decay: exact counts
    _hammer(lambda tid, i: est.observe(float(1 + tid)))
    assert est.total == N_THREADS * N_PER_THREAD
    q = est.quantile(0.5)
    assert q is not None and 1.0 <= q <= float(N_THREADS)


def test_rolling_estimator_concurrent_with_decay_and_readers():
    """Decay + concurrent observe/quantile: total never exceeds the
    ingested count (a doubly-applied decay or torn bucket scale would
    break monotonicity or crash the bucket walk)."""
    est = RollingEstimator(half_life_s=0.05)

    def op(tid, i):
        est.observe(float(tid + 1))
        if i % 50 == 0:
            est.quantile(0.99)
            est.summary()

    _hammer(op)
    assert 0.0 < est.total <= N_THREADS * N_PER_THREAD + 1e-6
    q = est.quantile(0.99)
    assert q is None or q <= est.max


def test_histogram_concurrent_exact_count_and_sum():
    reg = MetricsRegistry()
    h = reg.histogram("conc.test_ms")
    _hammer(lambda tid, i: h.observe(5.0))
    assert h.count == N_THREADS * N_PER_THREAD
    assert h.sum == 5.0 * N_THREADS * N_PER_THREAD
    assert sum(h.counts) == h.count
    assert h.min == h.max == 5.0
    assert h.rolling.total == N_THREADS * N_PER_THREAD


def test_counter_concurrent_exact_value():
    reg = MetricsRegistry()
    c = reg.counter("conc.test_count")
    _hammer(lambda tid, i: c.inc())
    assert c.value == N_THREADS * N_PER_THREAD


def test_registry_handles_race_free_creation():
    """Concurrent first-touch of the same histogram name must hand every
    thread the SAME instance (lost instances lose their observations)."""
    reg = MetricsRegistry()
    seen = []
    lock = threading.Lock()

    def op(tid, i):
        h = reg.histogram("conc.same")
        with lock:
            seen.append(id(h))
        h.observe(1.0)

    _hammer(op, n_per_thread=50)
    assert len(set(seen)) == 1
    assert reg.histogram("conc.same").count == N_THREADS * 50


def test_flight_recorder_concurrent_complete():
    """N threads completing timelines: completed/captured accounting
    stays exact and the bounded ring survives concurrent appends."""
    fr = FlightRecorder(ring_size=16)
    fr.enabled = True
    fr.threshold_ms = 0.0

    def op(tid, i):
        tl = fr.timeline()
        tl.event("dispatch", wave=tid)
        fr.complete(tl)

    _hammer(op, n_per_thread=200)
    st = fr.stats()
    assert st["completed"] == N_THREADS * 200
    assert st["captures"]["threshold"] == N_THREADS * 200
    assert st["captured"] == 16
