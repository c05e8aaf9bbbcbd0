"""Executable warmup: ahead-of-time compile registered query shapes.

The agg-config p99 cliff (hundreds of ms of p99 against a p50 of a few)
is the first-(plan-struct, shape-bucket) XLA compile landing
inside the serving path — the msearch envelope caches executables per
(plan structure, input shapes, batch bucket), so every NEW combination
pays a full compile on the query that first exhibits it.  The reference
has the same problem shape (JVM warmup + Lucene query caches) and solves
it with index warmers (index/IndexWarmer.java); here the analog is
executable-level:

- every msearch group records a (plan-struct, shape-bucket) signature plus
  one representative body into a node-wide registry (record());
- the registry persists as JSON under the node data dir, so a restarted
  node knows yesterday's traffic shapes before the first query arrives;
- an index-open / node-start hook (warm_index / warm_all) REPLAYS each
  registered entry — the representative body, duplicated to its recorded
  batch bucket — through the normal msearch path with the request cache
  bypassed, compiling exactly the executables production traffic will hit;
- the XLA compiles themselves go through jax's persistent compilation
  cache (configure_compile_cache(): wherever JAX_COMPILATION_CACHE_DIR
  says, else a fixed directory in the checkout), so a replayed compile
  after restart is a disk hit, not a fresh HLO build.

Warmup stats surface on _nodes/stats (rest/actions.py) and the
benchmark reports warm-up time as its own metric (`warmup_s`) — compile
cost is moved off the query path and accounted for, never hidden.
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from opensearch_tpu.common.errors import SettingsError
from opensearch_tpu.common.settings import _parse_bool
from opensearch_tpu.search.compile import struct_fingerprint

# registry bound: LRU over distinct (plan-struct, shape-bucket) sigs —
# a node serving a real workload sees tens of shapes, not thousands; the
# cap keeps pathological shape churn (randomized tests) bounded
MAX_ENTRIES = 256

# throttle for write-through persistence: at most one registry write per
# this many seconds (record() sits on the msearch hot path)
_PERSIST_INTERVAL_S = 5.0


# the XLA cache's default home: a FIXED path in the checkout. The path is
# part of what makes a later process find the entries again, so it never
# sits under path.data, a temporary directory, a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    return the directory in force. Where JAX_COMPILATION_CACHE_DIR is set
    the caller placed the cache and jax reads the variable itself — no
    directory is set in code; otherwise the cache lives at
    DEFAULT_COMPILE_CACHE_DIR. Every executable is kept (no compile-time
    or size floor): the serving path's programs are many and small."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    WARMUP.stats_["compile_cache_dir"] = cache_dir
    return cache_dir


class WarmupRegistry:
    """Node-wide registry of compiled-executable signatures + replay."""

    def __init__(self):
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._sig_memo: Dict[Any, str] = {}
        self._lock = threading.Lock()
        self._path: Optional[str] = None
        self._dirty = False
        self._last_persist = 0.0
        self._recording = True
        self._atexit_registered = False
        # tuned by Node from settings (search.warmup.budget_ms /
        # search.warmup_on_open); IndicesService.open_index reads them
        self.default_budget_s = 10.0
        self.warm_on_open = True
        self.stats_ = {
            "recorded": 0, "loaded": 0, "warmup_runs": 0,
            "warmed_entries": 0, "warmup_errors": 0, "skipped_entries": 0,
            "last_warmup_ms": 0.0, "compile_cache_dir": None,
        }

    # ------------------------------------------------------------ configure

    def configure(self, data_path: Optional[str]) -> None:
        """Bind the registry to a node data dir and load persisted entries.
        The registry lives under the gateway's _state dir — top-level
        directories in the data path are index data and would be reported
        as dangling indices. (The XLA compile cache is NOT placed here:
        see configure_compile_cache.)"""
        if data_path is None:
            return
        state_dir = os.path.join(data_path, "_state")
        try:
            os.makedirs(state_dir, exist_ok=True)
        except OSError:
            return
        path = os.path.join(state_dir, "warmup_registry.json")
        with self._lock:
            self._path = path
        self.load(path)
        if not self._atexit_registered:
            # dirty entries that never met the throttle window still land
            # on disk at interpreter exit
            import atexit
            atexit.register(self.flush)
            self._atexit_registered = True

    # -------------------------------------------------------------- record

    def record(self, index_name: str, body: dict, b_pad: int,
               sig_material: Any) -> None:
        """Register one msearch group's executable signature. Called per
        group per batch — memoized fingerprinting + LRU keep it O(dict)."""
        if not self._recording:
            return
        # the index is part of the identity: warm_executor filters
        # replays by index, so a same-shaped registration from another
        # index must create its OWN entry — deduping across indices
        # leaves the later index with nothing to replay
        key = (index_name, sig_material)
        sig = self._sig_memo.get(key)
        if sig is None:
            sig = struct_fingerprint(key)
            if len(self._sig_memo) > 4 * MAX_ENTRIES:
                self._sig_memo.clear()
            self._sig_memo[key] = sig
        with self._lock:
            if sig in self._entries:
                self._entries.move_to_end(sig)
                known = True
            else:
                known = False
        if known:
            # still give throttled persistence a chance: a burst of new
            # shapes inside one throttle window leaves _dirty set, and
            # steady-state traffic (all-known sigs) is what eventually
            # writes it through
            self._maybe_persist()
            return
        with self._lock:
            try:
                body_json = json.dumps(body)
            except (TypeError, ValueError):
                return                 # non-serializable body: skip
            self._entries[sig] = {"index": index_name,
                                  "body": json.loads(body_json),
                                  "b_pad": int(b_pad)}
            while len(self._entries) > MAX_ENTRIES:
                self._entries.popitem(last=False)
            self.stats_["recorded"] += 1
            self._dirty = True
        self._maybe_persist()

    # ------------------------------------------------------------- persist

    def _maybe_persist(self) -> None:
        if self._path is None or not self._dirty:
            return
        now = time.monotonic()
        if now - self._last_persist < _PERSIST_INTERVAL_S:
            return
        self.flush()

    def flush(self) -> None:
        """Write the registry through to disk (atomic rename)."""
        with self._lock:
            if self._path is None or not self._dirty:
                return
            path = self._path
            payload = json.dumps({"version": 1,
                                  "entries": self._entries}, indent=0)
            self._dirty = False
            self._last_persist = time.monotonic()
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(payload)
            os.replace(tmp, path)
        except OSError:
            pass

    def load(self, path: str) -> int:
        """Merge persisted entries (disk entries lose to in-memory ones)."""
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return 0
        loaded = 0
        with self._lock:
            for sig, entry in (data.get("entries") or {}).items():
                if not isinstance(entry, dict) or "body" not in entry:
                    continue
                if sig not in self._entries:
                    self._entries[sig] = entry
                    loaded += 1
            self.stats_["loaded"] += loaded
        return loaded

    # ---------------------------------------------------------------- warm

    def entries(self, index_name: Optional[str] = None) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._entries.values()
                    if index_name is None or e.get("index") == index_name]

    def registered_count(self, index_name: Optional[str] = None) -> int:
        """Registered (plan-struct, shape-bucket) entries for an index
        without copying bodies — the churn ledger (ISSUE 13) stamps this
        on every refresh/merge record: a `recompile` verdict with
        registered entries means a replay could pre-compile the new
        shape bucket off the serving path; zero means the first query
        pays the cliff with no warmup to ride."""
        with self._lock:
            if index_name is None:
                return len(self._entries)
            return sum(1 for e in self._entries.values()
                       if e.get("index") == index_name)

    def warm_executor(self, executor, index_name: Optional[str] = None,
                      budget_s: Optional[float] = None) -> dict:
        """Replay registered entries through one shard executor. Returns
        {"warmed": n, "errors": n, "took_ms": t}."""
        t0 = time.monotonic()
        warmed = errors = 0
        entries = self.entries(index_name)
        self._recording = False
        # replay transfers record under a `warmup.`-prefixed channel so
        # the ledger's serving channels stay uncontaminated while replay
        # traffic stays attributable (telemetry/ledger.py)
        from opensearch_tpu.telemetry import TELEMETRY as _tel
        with _tel.ledger.tagged("warmup"):
            warmed, errors = self._warm_entries(executor, entries,
                                                budget_s, t0)
        took = (time.monotonic() - t0) * 1000
        self.stats_["warmup_runs"] += 1
        self.stats_["warmed_entries"] += warmed
        self.stats_["warmup_errors"] += errors
        self.stats_["last_warmup_ms"] = round(took, 2)
        # mirror into the telemetry registry so _nodes/stats' `telemetry`
        # section carries warmup replays next to the compile counters
        _tel.metrics.counter("warmup.replays").inc(warmed)
        _tel.metrics.counter("warmup.errors").inc(errors)
        _tel.metrics.histogram("warmup.replay_ms").observe(took)
        return {"warmed": warmed, "errors": errors,
                "took_ms": round(took, 2)}

    def _warm_entries(self, executor, entries, budget_s, t0):
        """The replay loop proper; returns (warmed, errors)."""
        warmed = errors = 0
        try:
            for entry in entries:
                if budget_s is not None and \
                        time.monotonic() - t0 > budget_s:
                    self.stats_["skipped_entries"] += 1
                    continue
                try:
                    bodies = [entry["body"]] * max(int(entry.get(
                        "b_pad", 1)), 1)

                    def _replay(bodies=bodies):
                        # fault site + bounded transient retry: a flaky
                        # replay costs a retry, not the whole entry —
                        # and a permanently failing entry costs only
                        # itself (errors += 1), never index-open
                        from opensearch_tpu.common import faults
                        if faults.ENABLED:
                            faults.fire("warmup.replay")
                        # waves=1: the recorded b_pad already reflects
                        # any serving-time wave split, so the replay
                        # must not re-split it — one wave reproduces
                        # the registered (plan-struct, shape-bucket,
                        # b_pad) executable exactly
                        executor.multi_search(bodies,
                                              _bypass_request_cache=True,
                                              waves=1)
                    from opensearch_tpu.common import retry as _retry
                    _retry.call_with_retry(_replay, label="warmup.replay")
                    warmed += 1
                except Exception:   # except-ok: replay isolation -- a permanently failing entry costs only itself, never index-open
                    errors += 1
        finally:
            self._recording = True
        return warmed, errors

    def warm_index(self, index_name: str, shard_executors,
                   budget_s: Optional[float] = None) -> dict:
        """Index-open hook: AOT-compile this index's registered executables
        (reference analog: IndexWarmer running registered warmers on a new
        reader before it serves searches). `budget_s` (default
        `default_budget_s`, settable via search.warmup.budget_ms) is ONE
        deadline shared across all shards, not per shard."""
        if budget_s is None:
            budget_s = self.default_budget_s
        t0 = time.monotonic()
        out = {"warmed": 0, "errors": 0, "took_ms": 0.0}
        for ex in shard_executors:
            remaining = None if budget_s is None else \
                max(budget_s - (time.monotonic() - t0), 0.0)
            r = self.warm_executor(ex, index_name, remaining)
            out["warmed"] += r["warmed"]
            out["errors"] += r["errors"]
        out["took_ms"] = round((time.monotonic() - t0) * 1000, 2)
        self.flush()
        return out

    def warm_all(self, indices_service, budget_s: Optional[float] = 30.0
                 ) -> dict:
        """Node-start hook: warm every index that has registered entries."""
        t0 = time.monotonic()
        out = {"warmed": 0, "errors": 0, "took_ms": 0.0}
        names = {e.get("index") for e in self.entries()}
        for name in sorted(n for n in names if n):
            if name not in indices_service.indices:
                self.stats_["skipped_entries"] += 1
                continue
            svc = indices_service.indices[name]
            if getattr(svc, "closed", False):
                continue
            remaining = None if budget_s is None else \
                max(budget_s - (time.monotonic() - t0), 0.0)
            r = self.warm_index(name, [s.executor for s in svc.shards],
                                remaining)
            out["warmed"] += r["warmed"]
            out["errors"] += r["errors"]
        out["took_ms"] = round((time.monotonic() - t0) * 1000, 2)
        self.flush()
        return out

    # --------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            return {**self.stats_, "registered": len(self._entries),
                    "registry_path": self._path}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._sig_memo.clear()
            self._dirty = False


class Precompiler:
    """Off-path shape precompilation (ISSUE 16): a background worker
    that replays the warmup registry against a shard executor whenever
    a segment publish lands a novel device shape bucket, so the ~400 ms
    first-touch XLA cliff is paid on this helper thread instead of the
    first user query over the new segment.

    Flow: ShardReader collects novel shape fingerprints at upload;
    IndexShard hands them here (request()) right after the churn record
    publishes; the worker coalesces pending requests per index, replays
    the registry via WARMUP.warm_executor under offpath_compiles() (so
    the compiles count as `search.xla_compile_offpath`, not serving
    cache misses), then flips the pending churn verdicts to
    `precompiled` via the ledger's verdict lifecycle.

    No-op discipline (gate-lint row): OFF by default, `gate()` returns
    None when disabled — the refresh path pays one attribute load +
    branch. `POST /_warmup/_precompile` (sweep()) works even while
    disabled: it is an explicit operator trigger, not the hot path."""

    def __init__(self):
        self.enabled = False
        # barrier mode (second-level flag, like the shedder's
        # shape_enabled): a publish STAGES the new (segments, device)
        # pair, replays the registry against it on the publishing
        # thread with only that thread seeing the stage, then commits —
        # serving threads can never observe a segment set whose
        # executables are uncompiled, so recompile-on-serve is zero by
        # construction (async mode merely races the first query).
        # Costs the publishing thread the replay; visibility of each
        # refresh is delayed by the compile, exactly like a longer
        # refresh interval.
        self.barrier = False
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: List[dict] = []
        self._queued_sigs: set = set()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        # one replay pass's compile budget (shared deadline across the
        # registry, same semantics as warm_executor's budget_s)
        self.budget_ms = 2000.0
        self.stats_ = {"requests": 0, "runs": 0, "warmed": 0,
                       "errors": 0, "deduped": 0, "last_run_ms": 0.0}

    # ------------------------------------------------------------- gate

    def gate(self):
        """None when disabled (the no-op discipline); self when on."""
        if not self.enabled:
            return None
        return self

    # ---------------------------------------------------------- request

    def request(self, executor, index_name: str, shapes,
                churn_id: Optional[int] = None) -> None:
        """Enqueue a precompile pass for `executor` covering the given
        novel shape fingerprints. Deduplicates against already-queued
        shapes — a burst of refreshes publishing the same shape bucket
        costs one replay, not one per refresh."""
        if not self.enabled:
            return
        with self._cv:
            fresh = [s for s in shapes if s not in self._queued_sigs]
            if not fresh and churn_id is None:
                self.stats_["deduped"] += 1
                return
            self._queued_sigs.update(fresh)
            self._queue.append({
                "executor": weakref.ref(executor),
                "index": index_name,
                "shapes": fresh,
                "churn_ids": [churn_id] if churn_id is not None else [],
            })
            self.stats_["requests"] += 1
            self._cv.notify()

    # ----------------------------------------------------------- worker

    def _take_locked(self) -> Optional[dict]:
        """Pop + coalesce every queued request for the head entry's
        index into one batch (merged churn ids, shapes released from
        the dedupe set). Caller holds the lock."""
        if not self._queue:
            return None
        head = self._queue[0]
        batch = {"executor": head["executor"], "index": head["index"],
                 "churn_ids": [], "shapes": []}
        rest = []
        for req in self._queue:
            if req["index"] == batch["index"]:
                batch["churn_ids"].extend(req["churn_ids"])
                batch["shapes"].extend(req["shapes"])
            else:
                rest.append(req)
        self._queue = rest
        for s in batch["shapes"]:
            self._queued_sigs.discard(s)
        return batch

    def _service(self, batch: dict) -> None:
        executor = batch["executor"]()
        if executor is None:
            return                        # shard closed; nothing to warm
        from opensearch_tpu.search.executor import offpath_compiles
        from opensearch_tpu.telemetry import TELEMETRY as _tel
        t0 = time.monotonic()
        try:
            with offpath_compiles():
                r = WARMUP.warm_executor(executor, batch["index"],
                                         budget_s=self.budget_ms / 1000.0)
        except Exception:   # except-ok: worker isolation -- a failing replay pass must not kill the precompile thread
            self.stats_["errors"] += 1
            return
        took = (time.monotonic() - t0) * 1000
        with self._lock:
            self.stats_["runs"] += 1
            self.stats_["warmed"] += r["warmed"]
            self.stats_["errors"] += r["errors"]
            self.stats_["last_run_ms"] = round(took, 2)
        _tel.metrics.counter("precompile.runs").inc()
        _tel.metrics.histogram("precompile.run_ms").observe(took)
        if batch["churn_ids"]:
            _tel.churn.mark_precompiled(batch["churn_ids"], took)

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait(timeout=0.5)
                if self._stop and not self._queue:
                    return
                batch = self._take_locked()
            if batch is not None:
                self._service(batch)

    def run_pending(self) -> int:
        """Synchronously drain the queue on the calling thread — the
        deterministic path for tests and the REST trigger."""
        n = 0
        while True:
            with self._lock:
                batch = self._take_locked()
            if batch is None:
                return n
            self._service(batch)
            n += 1

    def precompile_staged(self, executor, index_name: str) -> float:
        """Barrier-mode replay: warm `executor` on the CALLING (i.e.
        publishing) thread — the caller holds the reader's stage open
        and made it thread-visible, so the compiles land against the
        exact pair about to publish. Returns the replay wall ms."""
        from opensearch_tpu.search.executor import offpath_compiles
        from opensearch_tpu.telemetry import TELEMETRY as _tel
        t0 = time.monotonic()
        try:
            with offpath_compiles():
                r = WARMUP.warm_executor(executor, index_name,
                                         budget_s=self.budget_ms / 1000.0)
        except Exception:   # except-ok: publish isolation -- a failing replay must not abort the refresh that triggered it
            self.stats_["errors"] += 1
            return 0.0
        took = (time.monotonic() - t0) * 1000
        with self._lock:
            self.stats_["runs"] += 1
            self.stats_["warmed"] += r["warmed"]
            self.stats_["errors"] += r["errors"]
            self.stats_["last_run_ms"] = round(took, 2)
        _tel.metrics.counter("precompile.runs").inc()
        _tel.metrics.histogram("precompile.run_ms").observe(took)
        return took

    # ------------------------------------------------------------ sweep

    def sweep(self, indices_service, index_name: Optional[str] = None,
              budget_s: Optional[float] = None) -> dict:
        """`POST /_warmup/_precompile`: replay the registry for one
        index (or all) on the calling thread, compiles attributed
        off-path. Deliberately works even while the background worker
        is disabled — an explicit operator trigger is opt-in by
        construction."""
        from opensearch_tpu.search.executor import offpath_compiles
        with offpath_compiles():
            if index_name is None:
                return WARMUP.warm_all(indices_service, budget_s)
            if index_name not in indices_service.indices:
                from opensearch_tpu.common.errors import \
                    IndexNotFoundError
                raise IndexNotFoundError(index_name)
            svc = indices_service.indices[index_name]
            return WARMUP.warm_index(
                index_name, [s.executor for s in svc.shards], budget_s)

    # --------------------------------------------------------- lifecycle

    def set_enabled(self, on: bool) -> None:
        on = bool(on)
        if on == self.enabled:
            return
        if on:
            self.enabled = True
            self._stop = False
            self._thread = threading.Thread(target=self._run,
                                            name="tpu-precompile",
                                            daemon=True)
            self._thread.start()
        else:
            self.enabled = False
            with self._cv:
                self._stop = True
                self._cv.notify_all()
            t = self._thread
            if t is not None:
                t.join(timeout=2.0)
            self._thread = None
            with self._lock:
                self._queue = []
                self._queued_sigs.clear()

    # ---------------------------------------------------------- settings

    @staticmethod
    def parse_settings(flat: dict) -> dict:
        """Strict parse of precompiler settings (WaveScheduler idiom):
        returns {enabled, budget_ms} with None for absent keys."""
        def _num(key, cast):
            if key not in flat:
                return None
            try:
                return cast(flat[key])
            except (TypeError, ValueError):
                raise SettingsError(
                    f"invalid value for [{key}]: [{flat[key]}]")
        out = {"enabled": None, "budget_ms": None, "barrier": None}
        if "search.precompile.enabled" in flat:
            out["enabled"] = _parse_bool(
                flat["search.precompile.enabled"],
                "search.precompile.enabled")
        if "search.precompile.barrier" in flat:
            out["barrier"] = _parse_bool(
                flat["search.precompile.barrier"],
                "search.precompile.barrier")
        out["budget_ms"] = _num("search.precompile.budget_ms", float)
        return out

    def apply_settings(self, flat: dict) -> None:
        parsed = self.parse_settings(flat)
        if parsed["budget_ms"] is not None:
            self.budget_ms = parsed["budget_ms"]
        if parsed["barrier"] is not None:
            self.barrier = parsed["barrier"]
        if parsed["enabled"] is not None:
            self.set_enabled(parsed["enabled"])

    # ------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            return {**self.stats_, "enabled": self.enabled,
                    "barrier": self.barrier,
                    "queued": len(self._queue),
                    "budget_ms": self.budget_ms}


# node-wide singletons, like REQUEST_CACHE / QUERY_CACHE
WARMUP = WarmupRegistry()
PRECOMPILE = Precompiler()
