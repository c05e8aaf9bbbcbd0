"""Bounded transient-fault retry with jittered backoff.

The reference retries a narrow class of shard-level failures
(TransportReplicationAction's ClusterStateObserver-driven retries on
NoShardAvailableActionException et al.); here the analogous transient
surface is device dispatch, request-cache IO and warmup replay. Policy:

  - retry ONLY `TransientFault` (the designated retryable class in
    common/errors.py). Typed client errors (400s), cancellations and
    arbitrary exceptions never retry — and neither do JAX runtime
    errors: on an attached chip RESOURCE_EXHAUSTED is an HBM allocation
    that fails the same way every time, not a blip.
  - bounded (default 2 retries = 3 attempts total) with exponential
    backoff and full jitter so concurrent retriers don't re-stampede
    the device in lockstep.
  - accounted: `search.retries` counts retry attempts,
    `search.retry_success` counts operations that succeeded after at
    least one failed attempt; when a trace span is passed, `retries`
    and `retry_site` attributes land on it — the executor copies span
    attributes into the Profile API breakdown, so retry attribution
    reaches `?profile=true` responses for free.
"""

from __future__ import annotations

import random
import time
from typing import Any, Callable, Optional

from opensearch_tpu.common.errors import TransientFault

DEFAULT_RETRIES = 2
BASE_DELAY_MS = 2.0
MAX_DELAY_MS = 50.0


def is_transient(exc: BaseException) -> bool:
    """True only for the designated retryable class."""
    return isinstance(exc, TransientFault)


def call_with_retry(fn: Callable[[], Any], label: str = "",
                    retries: int = DEFAULT_RETRIES,
                    trace=None) -> Any:
    """Run `fn`, retrying up to `retries` times on transient faults with
    jittered exponential backoff. Non-transient exceptions propagate
    immediately; the last transient failure propagates when the budget
    is spent."""
    from opensearch_tpu.telemetry import TELEMETRY
    attempt = 0
    while True:
        try:
            out = fn()
        except BaseException as e:
            if attempt >= retries or not is_transient(e):
                raise
            attempt += 1
            TELEMETRY.metrics.counter("search.retries").inc()
            delay_ms = min(BASE_DELAY_MS * (2 ** (attempt - 1)),
                           MAX_DELAY_MS)
            time.sleep(random.random() * delay_ms / 1000.0)
            continue
        if attempt:
            TELEMETRY.metrics.counter("search.retry_success").inc()
            if trace is not None and getattr(trace, "recording", False):
                trace.set_attribute("retries", attempt)
                if label:
                    trace.set_attribute("retry_site", label)
        return out
