"""Kernel-level device-compute profiler (ISSUE 19): executable census,
XLA cost/roofline ledger, and per-family device-time attribution.

The five committed observability layers measure host walls, transfer
bytes, scan bytes and per-chip partials — nothing attributes device
compute to the EXECUTABLES that spend it. This module is that sixth
layer, in three parts:

1. **Executable census (always-on).** Every JIT-cache miss registers an
   executable record — kernel-family label, cache-key fingerprint,
   shape bucket, synchronous compile wall — harvested inside the
   existing first-call timing wrapper (`timed_first_call`, moved here
   from search/executor.py so the ops-layer jit sites can reach it
   without an import cycle). Static cost comes from XLA's own
   `lowered.cost_analysis()` (flops / bytes accessed, captured without
   a second compile) where the backend provides it, and from the
   analytic scan formulas (telemetry/scan.py) where it does not; the
   `cost_source` field says which. Census writes happen ONLY at compile
   time — the steady state (cache hit) takes no lock and allocates
   nothing, the same discipline the <2% gate demands of every layer.
   Census `compile_ms` totals reconcile with the always-on
   `search.xla_compile_ms` histogram by construction: both are fed by
   the SAME `note_compile` call on the same wrapper.

2. **Gated timed dispatch (`telemetry.kernels.enabled`, OFF by
   default).** When on, runners wrap their cached executables in a
   sampling timer: every Nth dispatch per family (``sample_every``)
   runs synchronously under `jax.block_until_ready` and feeds a rolling
   p50/p99 (telemetry/rolling.py) plus a per-family device-ms ledger.
   The block is a measurement mechanism, not overhead — the wave's
   result pull would absorb those waits — and sampling bounds the lost
   dispatch overlap. Scaled totals (`sampled_ms * calls / sampled`)
   conserve against the transfer ledger's wave collect walls: they
   explain at least 90% of the clean-run collect wall (bench.py
   asserts this per workload); any excess is the async pipeline's
   dispatch/host overlap made measurable — the timer sees TOTAL
   compute, the collect only the part no host work hid.

3. **Roofline classification.** Arithmetic intensity flops/bytes vs the
   ridge of the attached device's published peaks (`DEVICE_PEAKS`, keyed
   by `device_kind`; `telemetry.kernels.peak_flops` / `peak_bw` override)
   marks each family compute- vs memory-bound. A device that is not in
   the table is not classified.

Kernel-family vocabulary (the label every census/timing row carries):
``bm25_candidate`` / ``bm25_dense`` (the two envelope kernels),
``agg_env`` (fused agg envelope + agg-bearing general path),
``hybrid_env`` (fused hybrid envelope), ``page_merger`` (single-round-
trip result page), ``knn`` (vector scoring + IVF k-means build),
``maxsim`` / ``maxsim_adc`` (late-interaction exact / PQ-fused),
``expand`` (delta-publish decompressors).

Surfaced via `GET /_telemetry/kernels` (+ `_enable`/`_disable`/
`_clear`), the `kernels` block of `GET /_nodes/stats`, Profile API
per-shard `kernels` entries, and tools/kernel_report.py.
"""

from __future__ import annotations

import hashlib
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

from opensearch_tpu.telemetry.rolling import RollingEstimator

KERNEL_FAMILIES = ("bm25_candidate", "bm25_dense", "agg_env",
                   "hybrid_env", "page_merger", "knn", "maxsim",
                   "maxsim_adc", "expand", "other")

# census ring cap: one record per compiled executable — real nodes hold
# hundreds of executables, not thousands; overflow counts, not crashes
MAX_CENSUS_ENTRIES = 2048

# published per-chip roofline peaks (flop/s, bytes/s) keyed by the
# `device_kind` jax reports. Source: Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 819 GB/s HBM. A device that is not listed
# gets NO classification (`bound: null`), never a guess; the
# telemetry.kernels.peak_flops / .peak_bw node settings override.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197.0e12, 819.0e9),
}
DEFAULT_SAMPLE_EVERY = 16


def fingerprint(key: Any) -> str:
    """Stable 8-hex digest of a JIT-cache key (repr is deterministic for
    the tuple-of-primitives keys the executor builds)."""
    return hashlib.md5(repr(key).encode("utf-8"),
                       usedforsecurity=False).hexdigest()[:8]


# ---------------------------------------------------------------- compiles
#
# Per-THREAD compile accounting for request attribution (moved here from
# search/executor.py so ops-layer jit sites — knn k-means, delta-publish
# expanders — share one wrapper without importing the executor): the XLA
# compile happens synchronously on the dispatching thread during the
# wrapped first call, so a thread-local is the correct request scope.

THREAD_COMPILES = threading.local()


def note_compile(ms: float) -> None:
    from opensearch_tpu.telemetry import TELEMETRY
    m = TELEMETRY.metrics
    if getattr(THREAD_COMPILES, "offpath", False):
        # precompiler replay thread (ISSUE 16): the compile happened
        # OFF the serving path — it must not count as a serving-thread
        # cache miss (the steady-state assertion is `xla_cache_miss`
        # delta == 0 under ingest), but stays visible under its own name
        m.counter("search.xla_compile_offpath").inc()
        m.histogram("search.xla_compile_ms").observe(ms)
    else:
        m.counter("search.xla_cache_miss").inc()
        m.histogram("search.xla_compile_ms").observe(ms)
        # a serving thread paid the cliff: flip any pending `recompile`
        # churn verdicts to `recompile-on-serve` (gated internally —
        # disabled ledger costs one attribute load + branch)
        TELEMETRY.churn.note_serve_compile()
    if getattr(THREAD_COMPILES, "active", False):
        THREAD_COMPILES.count += 1
        THREAD_COMPILES.ms += ms


@contextmanager
def offpath_compiles():
    """Mark this thread's XLA compiles as OFF-PATH (the precompiler's
    replay, search/warmup.py Precompiler): note_compile routes them to
    `search.xla_compile_offpath` instead of `search.xla_cache_miss`, so
    background compilation never pollutes the serving-thread compile
    counters a bench or operator watches for the first-touch cliff."""
    prev = getattr(THREAD_COMPILES, "offpath", False)
    THREAD_COMPILES.offpath = True
    try:
        yield
    finally:
        THREAD_COMPILES.offpath = prev


def timed_first_call(fn, family: Optional[str] = None, shape: str = "",
                     key: Any = None,
                     cost: Optional[Tuple[float, float]] = None):
    """Wrap a freshly jitted program so its FIRST invocation — where jax
    traces, lowers and XLA-compiles synchronously before the async
    execution dispatch — is timed and recorded as a compile event
    (`search.xla_cache_miss` counter + `search.xla_compile_ms`
    histogram, plus the current thread's request attribution). Only the
    miss occurrence gets the wrapper; cache hits return the raw jitted
    fn, so the steady state pays nothing.

    When `family` is given the call also registers an executable-census
    record (always-on — the registration is a compile-time event, never
    a steady-state cost): fingerprint from `key`, static flops/bytes
    from XLA `cost_analysis()` when the backend provides it, from the
    analytic `cost` estimate (telemetry/scan.py formulas) otherwise."""

    def first(*args):
        t0 = time.perf_counter_ns()
        out = fn(*args)
        ms = (time.perf_counter_ns() - t0) / 1e6
        note_compile(ms)
        if family is not None:
            KERNELS.census_note(fn, args, family, shape,
                                fingerprint(key), ms, cost)
        return out

    return first


# ---------------------------------------------------------------- profiler


def _xla_cost(fn, args) -> Tuple[Optional[float], Optional[float]]:
    """Best-effort static cost from XLA: `lowered.cost_analysis()` on
    jax 0.4 re-traces but does NOT compile a second time. Any failure
    (backend without cost model, non-lowerable args) degrades to the
    analytic fallback — census registration must never fail a query."""
    try:
        ca = fn.lower(*args).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None, None
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        return (float(flops) if flops is not None else None,
                float(nbytes) if nbytes is not None else None)
    except Exception:  # except-ok: census is best-effort -- cost capture must never fail the first dispatch
        return None, None


def _family_row() -> dict:
    return {"calls": 0, "sampled": 0, "sampled_ms": 0.0,
            "est": RollingEstimator(), "shapes": {}}


class KernelProfiler:
    """The sixth gated observability layer (see module docstring).

    Census methods are always-on but only run at compile time; the
    per-dispatch timing rides the None-returning `gate()` discipline —
    disabled, the hot path pays one attribute load and a branch, and
    executables are returned UNWRAPPED (no timer closure at all)."""

    def __init__(self):
        self.enabled = False
        self.sample_every = DEFAULT_SAMPLE_EVERY
        # None = take the device's row of DEVICE_PEAKS; a float is the
        # operator's override (telemetry.kernels.peak_* settings)
        self.peak_flops: Optional[float] = None
        self.peak_bw: Optional[float] = None
        self._census_lock = threading.Lock()
        self._census: List[dict] = []
        self._census_dropped = 0
        self._exec_lock = threading.Lock()
        self._families: Dict[str, dict] = {}

    # ------------------------------------------------------------- gate

    def gate(self) -> Optional["KernelProfiler"]:
        """None when disabled — callers guard with `if k is not None`,
        so the default query path never builds a timer closure."""
        if not self.enabled:
            return None
        return self

    # ----------------------------------------------------------- census

    def census_note(self, fn, args, family: str, shape: str,
                    fp: str, compile_ms: float,
                    cost: Optional[Tuple[float, float]] = None) -> None:
        """Register one compiled executable (compile-time only — called
        from the first-call wrapper, never on a cache hit)."""
        flops, nbytes = _xla_cost(fn, args)
        source = "xla"
        if flops is None and nbytes is None:
            source = "analytic" if cost is not None else "none"
        if cost is not None:
            if flops is None:
                flops = float(cost[0])
            if nbytes is None:
                nbytes = float(cost[1])
        rec = {"family": family, "fingerprint": fp, "shape": shape,
               "compile_ms": round(compile_ms, 3), "flops": flops,
               "bytes": nbytes, "cost_source": source}
        with self._census_lock:
            if len(self._census) >= MAX_CENSUS_ENTRIES:
                self._census_dropped += 1
            else:
                self._census.append(rec)

    # ----------------------------------------------------------- timing

    def timed(self, fn: Callable, family: str, shape: str = ""):
        """Wrap a cached executable in the sampling timer (enabled path
        only — reached through `gate()`). Every call counts; every Nth
        call per family runs synchronously under block_until_ready and
        feeds the rolling estimator + the per-family sampled-ms ledger."""

        def run(*args):
            if not self._tick(family, shape):
                return fn(*args)
            t0 = time.perf_counter_ns()
            out = fn(*args)
            import jax
            from opensearch_tpu.telemetry import TELEMETRY
            # the sampled sync is ledger-owned measurement by
            # construction (PR 7 sanitizer contract): the wave's result
            # pull would absorb this wait if the timer didn't take it
            with TELEMETRY.ledger.attributed():
                jax.block_until_ready(out)  # sync-ok: kernels.sample -- gated sampling timer owns this wall
            self._note_exec(family, shape,
                            (time.perf_counter_ns() - t0) / 1e6)
            return out

        return run

    def _tick(self, family: str, shape: str) -> bool:
        """Count one dispatch; True when this call is the sampled one.
        Deterministic (call-count modulus, first call always sampled) so
        tests can pin the sample schedule under threaded load."""
        with self._exec_lock:
            row = self._families.get(family)
            if row is None:
                row = self._families[family] = _family_row()
            row["calls"] += 1
            srow = row["shapes"].get(shape)
            if srow is None:
                srow = row["shapes"][shape] = {
                    "calls": 0, "sampled": 0, "sampled_ms": 0.0}
            srow["calls"] += 1
            n = max(1, int(self.sample_every))
            return (row["calls"] - 1) % n == 0

    def _note_exec(self, family: str, shape: str, ms: float) -> None:
        with self._exec_lock:
            row = self._families[family]
            row["sampled"] += 1
            row["sampled_ms"] += ms
            srow = row["shapes"][shape]
            srow["sampled"] += 1
            srow["sampled_ms"] += ms
        row["est"].observe(ms)

    # ---------------------------------------------------------- reading

    def _census_by_family(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        with self._census_lock:
            census = list(self._census)
        for rec in census:
            agg = out.setdefault(rec["family"], {
                "compiles": 0, "compile_ms": 0.0, "flops": 0.0,
                "bytes": 0.0, "cost_known": 0})
            agg["compiles"] += 1
            agg["compile_ms"] += rec["compile_ms"]
            if rec["flops"] is not None and rec["bytes"] is not None:
                agg["flops"] += rec["flops"]
                agg["bytes"] += rec["bytes"]
                agg["cost_known"] += 1
        return out

    def peaks(self) -> Tuple[Optional[float], Optional[float]]:
        """(peak flop/s, peak bytes/s) in force: the override where one
        is set, else the attached device's DEVICE_PEAKS row, else None."""
        flops, bw = self.peak_flops, self.peak_bw
        if flops is None or bw is None:
            import jax
            row = DEVICE_PEAKS.get(jax.devices()[0].device_kind,
                                   (None, None))
            flops = row[0] if flops is None else flops
            bw = row[1] if bw is None else bw
        return flops, bw

    @staticmethod
    def _roofline(flops: Optional[float], nbytes: Optional[float],
                  ridge: Optional[float]
                  ) -> Tuple[Optional[float], Optional[str]]:
        """(arithmetic intensity, bound class) against the ridge point
        peak_flops/peak_bw; no ridge (unlisted device) = no class."""
        if not flops or not nbytes:
            return None, "unknown"
        ai = flops / nbytes
        if ridge is None:
            return ai, None
        return ai, ("compute" if ai >= ridge else "memory")

    def snapshot(self, census: bool = True) -> dict:
        """The `GET /_telemetry/kernels` body (and, with census=False,
        the compact `_nodes/stats` block): per-family census aggregates
        + roofline verdicts + (when timing ran) sampled device walls
        with the scaled total estimate."""
        by_fam = self._census_by_family()
        peak_flops, peak_bw = self.peaks()
        ridge = peak_flops / max(peak_bw, 1.0) \
            if peak_flops is not None and peak_bw is not None else None
        with self._exec_lock:
            fams = {f: {"calls": r["calls"], "sampled": r["sampled"],
                        "sampled_ms": r["sampled_ms"],
                        "shapes": {s: dict(sr)
                                   for s, sr in r["shapes"].items()},
                        "est": r["est"]}
                    for f, r in self._families.items()}
        families = {}
        for fam in sorted(set(by_fam) | set(fams)):
            agg = by_fam.get(fam)
            run = fams.get(fam)
            flops = agg["flops"] if agg else None
            nbytes = agg["bytes"] if agg else None
            ai, bound = self._roofline(flops, nbytes, ridge)
            row = {"compiles": agg["compiles"] if agg else 0,
                   "compile_ms": round(agg["compile_ms"], 3)
                   if agg else 0.0,
                   "flops": flops, "bytes": nbytes,
                   "arithmetic_intensity": round(ai, 4)
                   if ai is not None else None,
                   "bound": bound,
                   "calls": run["calls"] if run else 0,
                   "sampled": run["sampled"] if run else 0,
                   "sampled_ms": round(run["sampled_ms"], 3)
                   if run else 0.0}
            if run and run["sampled"]:
                # scaled estimate: sampled walls extrapolated over every
                # dispatch — the number that conserves (within the bench
                # bound) against the ledger's wave collect walls
                row["device_ms_est"] = round(
                    run["sampled_ms"] * run["calls"] / run["sampled"], 3)
                row["p50_ms"] = _round(run["est"].quantile(0.5))
                row["p99_ms"] = _round(run["est"].quantile(0.99))
                row["shapes"] = {
                    s: {"calls": sr["calls"], "sampled": sr["sampled"],
                        "sampled_ms": round(sr["sampled_ms"], 3),
                        "device_ms_est": round(
                            sr["sampled_ms"] * sr["calls"]
                            / sr["sampled"], 3) if sr["sampled"] else 0.0}
                    for s, sr in run["shapes"].items()}
            families[fam] = row
        with self._census_lock:
            n_census = len(self._census)
            dropped = self._census_dropped
            compile_total = sum(r["compile_ms"] for r in self._census)
            dump = list(self._census) if census else None
        out = {"enabled": self.enabled,
               "sample_every": self.sample_every,
               "peak_flops": peak_flops, "peak_bw": peak_bw,
               "ridge_intensity": _round(ridge),
               "census": {"entries": n_census, "dropped": dropped,
                          "compile_ms_total": round(compile_total, 3)},
               "families": families}
        if dump is not None:
            out["census"]["executables"] = dump
        return out

    def stats(self) -> dict:
        """Compact block for `_nodes/stats` (no per-executable dump)."""
        return self.snapshot(census=False)

    def clear(self) -> None:
        """Drop census + timing state (config and gate flag survive)."""
        with self._census_lock:
            self._census = []
            self._census_dropped = 0
        with self._exec_lock:
            self._families = {}


def _round(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 4)


# process-wide singleton, like SCAN / INSIGHTS
KERNELS = KernelProfiler()
