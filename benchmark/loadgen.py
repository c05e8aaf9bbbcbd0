"""The one general load generator: it reads a traffic file's parameters
and drives the node over its real socket. Nothing here knows a
configuration or a query language: requests arrive as bytes serialised
before the window, responses are kept as bytes and parsed after it, so
the generator takes as little of the interpreter from the server (same
process, same GIL) as it can.

Two loops, chosen by the traffic file's `loop`:

- `open`: a schedule fixed before the window (the traffic file's own
  exponential gaps, the same multiset for every `--seed`, the seed only
  shuffles their order), `threads` workers draining it; latency is
  timed FROM THE INTENDED SEND TIME (tools/openloop.py's wrk2
  correction, copied), and how late each request was actually sent is
  kept beside it.
- `closed`: `clients` callers, each sending its next request when the
  last one answered, until the window ends. A request counts only if it
  completed inside the window.

A request is one `_search` body or one `_msearch` batch (`batch` > 1).

The loops run in a CHILD PROCESS that never imports jax (`in_child`):
in the node's own process the generator's threads wait on the server's
GIL, and at 25 requests a second already sent 5% of them over a
millisecond late (my chip run, PR 24). Both processes read the same
CLOCK_MONOTONIC, so the window's start and end travel as absolute times.
"""

from __future__ import annotations

import http.client
import multiprocessing
import random
import threading
import time
from typing import List, Optional, Sequence


class Sample:
    """One request as the client saw it. Times are seconds on the
    monotonic clock, relative to nothing: compare with the window's own
    start and end."""

    __slots__ = ("index", "intended", "sent", "done", "status", "raw")

    def __init__(self, index: int, intended: float, sent: float,
                 done: float, status: int, raw: bytes):
        self.index = index
        self.intended = intended
        self.sent = sent
        self.done = done
        self.status = status
        self.raw = raw


class Connection:
    """One keep-alive connection to the node's HTTP port. A transport
    error answers status -1 (the request failed) and reconnects."""

    def __init__(self, port: int, timeout: float = 600.0):
        self.port, self.timeout = port, timeout
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def request(self, method: str, path: str, payload: Optional[bytes],
                content_type: str = "application/json"):
        try:
            self.conn.request(method, path, body=payload,
                              headers={"Content-Type": content_type})
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.conn.close()
            self.conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=self.timeout)
            return -1, repr(e).encode()

    def post(self, path: str, payload: bytes, content_type: str):
        return self.request("POST", path, payload, content_type)

    def close(self) -> None:
        self.conn.close()


def fixed_gaps(n: int, rate: float, schedule_seed: int, seed: int
               ) -> List[float]:
    """n arrival offsets (seconds from the window's start) of a Poisson
    process at `rate`/s. The multiset of gaps comes from the traffic
    file's `schedule_seed` and is the same for every run of the cell;
    `seed` only puts them in another order, so no run draws a burstier
    schedule than another."""
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    base = random.Random(schedule_seed)
    gaps = [base.expovariate(rate) for _ in range(n)]
    # the n gaps span exactly n / rate seconds in every run
    scale = (n / rate) / sum(gaps)
    gaps = [g * scale for g in gaps]
    random.Random(seed).shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        out.append(t)       # the first request is due at the start
        t += g
    return out


def run_open(port: int, path: str, content_type: str,
             payloads: Sequence[bytes], offsets: Sequence[float],
             threads: int, t0: float) -> List[Sample]:
    """Send payloads[i] at t0 + offsets[i] whether or not earlier ones
    have answered. Returns every request, in schedule order."""
    n = len(offsets)
    out: List[Optional[Sample]] = [None] * n
    next_i = [0]
    lock = threading.Lock()

    def worker():
        conn = Connection(port)
        try:
            while True:
                with lock:
                    i = next_i[0]
                    next_i[0] += 1
                if i >= n:
                    return
                intended = t0 + offsets[i]
                wait = intended - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                sent = time.monotonic()
                status, raw = conn.post(path, payloads[i], content_type)
                out[i] = Sample(i, intended, sent, time.monotonic(),
                                status, raw)
        finally:
            conn.close()

    pool = [threading.Thread(target=worker, name=f"loadgen-open-{c}")
            for c in range(max(int(threads), 1))]
    for th in pool:
        th.start()
    for th in pool:
        th.join()
    return [s for s in out if s is not None]


def run_closed(port: int, path: str, content_type: str,
               payloads: Sequence[bytes], clients: int, t0: float,
               t_end: float) -> List[Sample]:
    """`clients` callers share the payload list in order; each sends its
    next request as soon as its last one answered, and stops at the
    first request it would send after `t_end`. Raises if the payloads run
    out before the window does: the traffic file provisions too few."""
    n = len(payloads)
    out: List[Sample] = []
    next_i = [0]
    lock = threading.Lock()
    ran_out = [False]

    def worker():
        conn = Connection(port)
        try:
            time.sleep(max(t0 - time.monotonic(), 0))
            while time.monotonic() < t_end:
                with lock:
                    i = next_i[0]
                    next_i[0] += 1
                if i >= n:
                    ran_out[0] = True
                    return
                sent = time.monotonic()
                status, raw = conn.post(path, payloads[i], content_type)
                s = Sample(i, sent, sent, time.monotonic(), status, raw)
                with lock:
                    out.append(s)
        finally:
            conn.close()

    pool = [threading.Thread(target=worker, name=f"loadgen-closed-{c}")
            for c in range(max(int(clients), 1))]
    for th in pool:
        th.start()
    for th in pool:
        th.join()
    if ran_out[0]:
        raise RuntimeError(
            f"the {n} provisioned requests ran out before the window "
            f"ended: raise `provision_per_s` in the traffic file")
    out.sort(key=lambda s: s.index)
    return out


def percentile(values: Sequence[float], p: float) -> float:
    """The p-quantile by linear interpolation between order statistics
    (numpy's default), on plain floats."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no samples")
    pos = (len(vals) - 1) * p
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def _child(pipe, loop: str, kwargs: dict) -> None:
    try:
        if time.monotonic() >= kwargs["t0"]:
            raise RuntimeError("the generator's child process was not "
                               "ready when the window started")
        samples = (run_open if loop == "open" else run_closed)(**kwargs)
        pipe.send([(s.index, s.intended, s.sent, s.done, s.status, s.raw)
                   for s in samples])
    except BaseException as e:     # reported to the parent, which raises
        pipe.send(e)
        raise
    finally:
        pipe.close()


class in_child:
    """Start the loop in a spawned, jax-free child now (so that its
    start-up is set-up, not window); `result()` waits for it and returns
    its samples. The child sleeps until the window's start itself."""

    def __init__(self, loop: str, **kwargs):
        ctx = multiprocessing.get_context("spawn")
        self.pipe, theirs = ctx.Pipe(duplex=False)
        self.proc = ctx.Process(target=_child, args=(theirs, loop, kwargs),
                                name="loadgen")
        self.proc.start()
        theirs.close()

    def result(self) -> List[Sample]:
        try:
            out = self.pipe.recv()
        finally:
            self.proc.join(timeout=60)
            if self.proc.is_alive():
                self.proc.kill()
                self.proc.join()
        if isinstance(out, BaseException):
            raise out
        return [Sample(*row) for row in out]
