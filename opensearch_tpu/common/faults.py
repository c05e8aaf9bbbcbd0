"""Deterministic fault injection for the TPU query path.

Everything benched so far assumed every shard, device dispatch and cache
op succeeds; this module makes failure a first-class, *reproducible*
input. Named sites on the hot path call `fire(site)` behind a
module-level `ENABLED` guard:

    from opensearch_tpu.common import faults
    ...
    if faults.ENABLED:
        faults.fire("query.shard")

The disabled fast path is ONE module attribute load and a falsy test —
no dict lookups, no allocation, no function call (tools/lint's
gate-lint checks every call site, the same contract as the PR 4
disabled tracer). With
rules installed, `fire` consults the per-site rule list and raises /
sleeps per the schedule.

Schedules are SEEDED and ENUMERABLE: each rule owns a
`random.Random(seed)` stream and counts its invocations/fires, so a
chaos sweep (tools/chaos_sweep.py) reproduces the same fault sequence
run-to-run and `GET /_fault_injection` shows exactly what fired where.

Rule semantics (one rule dict per site per install):

    site         one of SITES (required)
    kind         "exception" | "transient" | "delay" (required)
    probability  seeded per-invocation draw, default 1.0
    skip         ignore the first N matching invocations, default 0
    max_fires    stop firing after N fires; default: 1 for kind=
                 "transient" at probability 1.0 (fail-once-then-succeed,
                 the retry-success shape), else unlimited
    delay_ms     sleep length for kind="delay", default 50
    seed         RNG seed for the probability stream, default 0
    reason       override the injected error message

Kinds:
    exception  raise InjectedFault (typed 500 — a permanent fault)
    transient  raise TransientFault (typed 503 — the retry helper's
               designated retryable class)
    delay      time.sleep(delay_ms) — drives timeout/deadline tests

REST control (rest/actions.py): POST /_fault_injection installs rules,
GET lists them with fire counts, DELETE clears (all or one site).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Dict, List, Optional

from opensearch_tpu.common.errors import (
    IllegalArgumentError, OpenSearchTpuError, TransientFault)

# the named hot-path sites; install() rejects anything else so a typo'd
# site can't silently never fire
SITES = frozenset({
    "canmatch.shard",        # per-shard can-match pre-filter (controller)
    "query.shard",           # per-shard query phase entry (controller)
    "query.dispatch",        # per-segment/group device dispatch (executor)
    "fetch.gather",          # device_get result collection / fetch phase
    "request_cache.get",     # shard request cache read
    "request_cache.put",     # shard request cache write
    "warmup.replay",         # warmup registry replay (warmup.py)
    "reduce.aggs",           # coordinator agg reduce (controller)
})

KINDS = frozenset({"exception", "transient", "delay"})

# Module-level disabled flag: hot sites guard with `if faults.ENABLED:`.
# Rebound ONLY by _sync() under _LOCK; readers never lock.
ENABLED = False


class InjectedFault(OpenSearchTpuError):
    """A deliberately injected permanent fault — typed so responses that
    surface it are clean error objects, never raw stack-trace 500s."""
    status = 500
    error_type = "injected_fault_exception"


class _Rule:
    __slots__ = ("site", "kind", "probability", "skip", "max_fires",
                 "delay_ms", "seed", "reason", "rng", "invocations",
                 "fires")

    def __init__(self, spec: dict):
        site = spec.get("site")
        kind = spec.get("kind")
        if site not in SITES:
            raise IllegalArgumentError(
                f"unknown fault site [{site}]; valid sites: "
                f"{sorted(SITES)}")
        if kind not in KINDS:
            raise IllegalArgumentError(
                f"unknown fault kind [{kind}]; valid kinds: "
                f"{sorted(KINDS)}")
        unknown = set(spec) - {"site", "kind", "probability", "skip",
                               "max_fires", "delay_ms", "seed", "reason"}
        if unknown:
            raise IllegalArgumentError(
                f"unknown fault rule key(s) {sorted(unknown)}")
        self.site = site
        self.kind = kind
        try:
            self.probability = float(spec.get("probability", 1.0))
            self.skip = int(spec.get("skip", 0))
            self.delay_ms = float(spec.get("delay_ms", 50.0))
            self.seed = int(spec.get("seed", 0))
            raw_max = spec.get("max_fires")
            self.max_fires = None if raw_max is None else int(raw_max)
        except (TypeError, ValueError) as e:
            raise IllegalArgumentError(f"malformed fault rule: {e}")
        if not 0.0 <= self.probability <= 1.0:
            raise IllegalArgumentError(
                "[probability] must be in [0, 1]")
        if self.max_fires is None and kind == "transient" \
                and self.probability >= 1.0:
            # p=1 transient with no cap would also fail every retry;
            # default to fail-once-then-succeed, the canonical
            # transient shape the retry helper recovers from
            self.max_fires = 1
        self.reason = str(spec.get("reason") or
                          f"injected {kind} fault at [{site}]")
        self.rng = random.Random(self.seed)
        self.invocations = 0
        self.fires = 0

    def plan(self):
        """Called under _LOCK: advance the schedule (invocation/fire
        counters, seeded RNG draw) and return the action to execute
        OUTSIDE the lock — None, a delay in seconds (float), or an
        exception instance to raise. Sleeping/raising must not happen
        under _LOCK: a delay rule at one site would otherwise convoy
        every concurrent fire() at every site (and the REST control)
        behind its sleep."""
        self.invocations += 1
        if self.max_fires is not None and self.fires >= self.max_fires:
            return None
        if self.probability < 1.0 and \
                self.rng.random() >= self.probability:
            return None
        if self.invocations <= self.skip:
            return None
        self.fires += 1
        if self.kind == "delay":
            return self.delay_ms / 1000.0
        if self.kind == "transient":
            return TransientFault(self.reason)
        return InjectedFault(self.reason)

    def snapshot(self) -> dict:
        return {"site": self.site, "kind": self.kind,
                "probability": self.probability, "skip": self.skip,
                "max_fires": self.max_fires, "delay_ms": self.delay_ms,
                "seed": self.seed, "invocations": self.invocations,
                "fires": self.fires}


_LOCK = threading.Lock()
_RULES: Dict[str, List[_Rule]] = {}


def _sync() -> None:
    """Rebind the module flag from the rule table (under _LOCK)."""
    global ENABLED
    ENABLED = bool(_RULES)


def install(spec: dict) -> dict:
    """Install one rule (validated); returns its snapshot."""
    rule = _Rule(spec or {})
    with _LOCK:
        _RULES.setdefault(rule.site, []).append(rule)
        _sync()
    return rule.snapshot()


def clear(site: Optional[str] = None) -> int:
    """Remove all rules (or one site's); returns how many were removed."""
    with _LOCK:
        if site is None:
            n = sum(len(rs) for rs in _RULES.values())
            _RULES.clear()
        else:
            n = len(_RULES.pop(site, []))
        _sync()
        return n


def snapshot() -> List[dict]:
    with _LOCK:
        return [r.snapshot() for rs in _RULES.values() for r in rs]


def fire(site: str) -> None:
    """Run the site's schedule. ONLY call behind `if faults.ENABLED:` —
    the guard is the zero-overhead contract; this function itself
    tolerates racing a concurrent clear(). Schedule state advances under
    _LOCK; the actions (sleep, raise) execute after it is released, so a
    delay at one site never serializes fires at the others."""
    with _LOCK:
        rules = _RULES.get(site)
        if not rules:
            return
        actions = [r.plan() for r in rules]
    for a in actions:
        if a is None:
            continue
        if isinstance(a, BaseException):
            raise a
        time.sleep(a)
