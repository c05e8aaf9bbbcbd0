#!/usr/bin/env python
"""Open-loop concurrent-clients harness: Poisson arrivals, coordinated-
omission-safe latency.

A closed loop measures "how fast can ONE caller pump requests" — it
cannot see contention, and its latency numbers suffer coordinated
omission: a stalled server delays the *sending* of the next request, so
the stall's queueing damage never appears in the recorded distribution.
This harness is the open-loop counterpart (ROADMAP item 2's acceptance
instrument):

- arrivals follow a seeded Poisson process at `arrival_rate`/s — the
  request schedule is fixed BEFORE the run and never slows down because
  the server did;
- `clients` worker threads drain the schedule; a request whose intended
  arrival has passed starts immediately (late), and its latency is
  measured FROM THE INTENDED ARRIVAL TIME — the wrk2 correction — so a
  server stall charges every request it delayed, not just the one it
  served slowly;
- `queue_wait` (service start − intended arrival) is reported
  separately: it is the number the item-2 wave scheduler's admission
  control will be judged by.

Pure stdlib; imported by tests/test_openloop.py, which pins the
coordinated-omission property against a synthetic server with an
injected stall (common/faults.py).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, List, Optional, Sequence


def poisson_schedule(n: int, rate: float, seed: int = 0) -> List[float]:
    """n intended arrival offsets (seconds from start) of a Poisson
    process at `rate` arrivals/s — seeded, so a run is reproducible."""
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    rng = random.Random(seed)
    t, out = 0.0, []
    for _ in range(n):
        t += rng.expovariate(rate)
        out.append(t)
    return out


def percentile(sorted_vals: Sequence[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(len(sorted_vals) * p))
    return sorted_vals[i]


def run_open_loop(serve: Callable, items: Sequence, clients: int = 8,
                  arrival_rate: float = 50.0, seed: int = 0,
                  schedule: Optional[Sequence[float]] = None) -> dict:
    """Drive `serve(item)` once per item from `clients` worker threads
    on an open-loop schedule. Returns the latency/queue-wait digest plus
    the raw per-request arrays (callers strip those before JSON).

    Latency[i] = completion − intended arrival (coordinated-omission
    safe); queue_wait[i] = max(service start − intended arrival, 0);
    service[i] = completion − service start (the closed-loop-style
    number, reported so the two can be compared — the CO test asserts
    they diverge under a stall).

    Goodput (the overload-sweep contract, ISSUE 11): `serve` may return
    an HTTP status int (or an object with `.status`) and each request
    classifies as ok (< 400), **rejected** (429 — an admission shed) or
    error (any other 4xx/5xx; raising still counts under `errors`). The
    digest splits the percentiles: `admitted_*` are service-time
    percentiles over OK requests only (the "admitted p99 stays bounded"
    number — open-loop latency from intended arrival grows without
    bound past saturation by construction, so it cannot be the SLO
    gate), `rejected_p99_ms` is the service-time p99 of sheds (the
    "rejected in <5 ms" check), and `goodput_qps` counts only OK
    completions. A None return keeps the old contract: everything that
    didn't raise is ok."""
    n = len(items)
    sched = list(schedule) if schedule is not None \
        else poisson_schedule(n, arrival_rate, seed)
    if len(sched) != n:
        raise ValueError(f"schedule has {len(sched)} entries for {n} items")
    lat = [0.0] * n
    qwait = [0.0] * n
    service = [0.0] * n
    status = [0] * n            # 0 = ok-by-default (None return)
    errors = [0]
    next_i = [0]
    lock = threading.Lock()
    t0 = time.monotonic()

    def worker():
        while True:
            with lock:
                i = next_i[0]
                next_i[0] += 1
            if i >= n:
                return
            intended = t0 + sched[i]
            now = time.monotonic()
            if now < intended:
                time.sleep(intended - now)
            t_start = time.monotonic()
            try:
                out = serve(items[i])
                st = getattr(out, "status", out)
                if isinstance(st, int):
                    status[i] = st
            except Exception:
                status[i] = -1
                with lock:
                    errors[0] += 1
            t_end = time.monotonic()
            lat[i] = (t_end - intended) * 1000.0
            qwait[i] = max((t_start - intended) * 1000.0, 0.0)
            service[i] = (t_end - t_start) * 1000.0

    threads = [threading.Thread(target=worker, daemon=True,
                                name=f"openloop-client-{c}")
               for c in range(max(int(clients), 1))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall_s = time.monotonic() - t0
    ok_i = [i for i in range(n) if 0 <= status[i] < 400]
    rej_i = [i for i in range(n) if status[i] == 429]
    err_i = [i for i in range(n)
             if status[i] >= 400 and status[i] != 429]
    s_lat = sorted(lat)
    s_srv = sorted(service)
    s_ok_srv = sorted(service[i] for i in ok_i)
    s_rej_srv = sorted(service[i] for i in rej_i)
    return {
        "clients": max(int(clients), 1),
        "arrival_rate": arrival_rate,
        "n_requests": n,
        "duration_s": round(wall_s, 3),
        "qps": round(n / wall_s, 2) if wall_s > 0 else 0.0,
        "p50_ms": round(percentile(s_lat, 0.50), 2),
        "p99_ms": round(percentile(s_lat, 0.99), 2),
        "p999_ms": round(percentile(s_lat, 0.999), 2),
        "max_ms": round(s_lat[-1], 2) if s_lat else 0.0,
        "mean_queue_wait_ms": round(sum(qwait) / max(n, 1), 3),
        "max_queue_wait_ms": round(max(qwait), 2) if qwait else 0.0,
        "service_p50_ms": round(percentile(s_srv, 0.50), 2),
        "service_p99_ms": round(percentile(s_srv, 0.99), 2),
        "errors": errors[0],
        # goodput split (admission-aware callers; all-ok otherwise)
        "ok": len(ok_i),
        "rejected": len(rej_i),
        "failed": len(err_i),
        "goodput_qps": round(len(ok_i) / wall_s, 2) if wall_s > 0
        else 0.0,
        "admitted_p50_ms": round(percentile(s_ok_srv, 0.50), 2),
        "admitted_p99_ms": round(percentile(s_ok_srv, 0.99), 2),
        "rejected_p50_ms": round(percentile(s_rej_srv, 0.50), 2),
        "rejected_p99_ms": round(percentile(s_rej_srv, 0.99), 2),
        # raw per-request arrays for downstream analysis; strip before
        # serializing a bench record
        "latencies_ms": lat,
        "queue_waits_ms": qwait,
        "service_ms": service,
        "statuses": status,
    }
