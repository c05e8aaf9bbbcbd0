"""Kernel-level executable census and the device's naming vocabulary
(ISSUE 19, 25).

Kernel TIME has one source, the device trace the benchmark reads
(`benchmark/`): this module takes no clock on the serving path. What it
keeps is what a trace needs to be read, in two parts:

1. **Executable census (always-on, compile time only).** Every
   JIT-cache miss registers an executable record — kernel-family label,
   cache-key fingerprint, shape bucket, synchronous compile wall —
   harvested inside the first-call timing wrapper (`timed_first_call`,
   here so the ops-layer jit sites can reach it without an import
   cycle). Static cost comes from XLA's own `lowered.cost_analysis()`
   (flops / bytes accessed, captured without a second compile) where the
   backend provides it, and from the analytic scan formulas
   (telemetry/scan.py) where it does not; the `cost_source` field says
   which. Census writes happen ONLY at compile time — a cache hit
   returns the cached executable itself, takes no lock and allocates
   nothing. Census `compile_ms` totals reconcile with the always-on
   `search.xla_compile_ms` histogram by construction: both are fed by
   the SAME `note_compile` call on the same wrapper.

2. **Roofline classification.** Arithmetic intensity flops/bytes (XLA's
   counts; no clock in it) vs the ridge of the attached device's
   published peaks (`DEVICE_PEAKS`, keyed by `device_kind`;
   `telemetry.kernels.peak_flops` / `peak_bw` override) marks each
   family compute- vs memory-bound. A device that is not in the table
   is not classified.

Kernel-family vocabulary (the label every census row carries):
``bm25_candidate`` / ``bm25_dense`` (the two envelope kernels),
``agg_env`` (fused agg envelope + agg-bearing general path),
``hybrid_env`` (fused hybrid envelope), ``page_merger`` (single-round-
trip result page), ``knn`` (vector scoring + IVF k-means build),
``maxsim`` / ``maxsim_adc`` (late-interaction exact / PQ-fused),
``expand`` (delta-publish decompressors), ``spmd_query_phase`` (the
SPMD program of parallel/distributed.py).

Surfaced via `GET /_telemetry/kernels` (`?scopes=true`; `_clear`) and
the `kernels` block of `GET /_nodes/stats`.

**Names on the device (ISSUE 25).** This module also owns the two
vocabularies a device trace is read by. `jit_family` names the function
handed to `jax.jit` for its kernel family, so the XLA module of every
served program is `jit_<family>` (`jit_bm25_dense`, never `jit_run`).
`stage` is `jax.named_scope` over the fixed `STAGES` vocabulary, used
inside the programs; it is metadata only. A TPU trace names an op by
its HLO instruction and carries no `op_name`, so the census learns, per
executable and on first demand of `GET /_telemetry/kernels?scopes=true`
(a re-lower through the persistent compilation cache, off the serving
path), `scopes: {instruction name -> stage}` from the optimized HLO's
`metadata={op_name=...}`; a fused op belongs to the stage of its root
instruction, and a stage inferred from an op's neighbours is written
`~stage`. The compile cache's key leaves metadata out, so an executable
LOADED from it carries the stages of whoever compiled it: the process
that compiles one leaves the `stage_layout` of its lowering beside the
cache (`<cache dir>/stage_layouts/`), and a process that loaded one
reports a map only where that layout is its own, an `_error` otherwise.
Each executable also carries its `(family, fingerprint,
shape)` as `fn.exec_info`, which the always-on `dispatch` span copies,
so an op in a trace finds its executable's map through the request
that ran it.
"""

from __future__ import annotations

import hashlib
import os
import re
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

KERNEL_FAMILIES = ("bm25_candidate", "bm25_dense", "agg_env",
                   "hybrid_env", "page_merger", "knn", "maxsim",
                   "maxsim_adc", "expand", "spmd_query_phase", "other")

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

# the stages a device program is cut into (`stage` below). Text, dense
# and candidate kernels: postings_gather (block lanes, tf, norms),
# bm25_score, scatter (the two `.at[].add` into [d_pad]),
# eligible_total (mask and sum), top_k, pack_row, unpack_envelope,
# candidate_sort, run_sum, blockmax_mask; k-NN: distance, top_k. The
# SPMD query phase (parallel/distributed.py): filter_mask (the query
# plan's match mask where it is no text clause: a range over the rank
# columns), eligible_total, top_k, agg_bins (the binned counts and
# sums of the aggregations), collective_merge (all_gather, psum and
# the merge of what they gathered).
STAGES = ("postings_gather", "bm25_score", "scatter", "eligible_total",
          "top_k", "pack_row", "unpack_envelope", "candidate_sort",
          "run_sum", "distance", "blockmax_mask", "filter_mask",
          "agg_bins", "collective_merge")


def stage(name: str):
    """`jax.named_scope(name)` for a name of `STAGES`: the ops traced
    inside carry `.../<name>/...` in their HLO `op_name`. Runs at trace
    time only and changes no executable."""
    if name not in STAGES:
        raise ValueError(f"[{name}] is not a device stage: {STAGES}")
    import jax
    return jax.named_scope(name)


def jit_family(fn, family: str, **jit_kwargs):
    """`jax.jit(fn)` with `fn` named for its kernel family first, so the
    XLA module (and the "XLA Modules" line of a device trace) reads
    `jit_<family>`. The same `family` goes to `timed_first_call`."""
    import jax
    fn.__name__ = fn.__qualname__ = family
    return jax.jit(fn, **jit_kwargs)


class ExecInfo:
    """What the `dispatch` span says of the executable it enqueued."""

    __slots__ = ("family", "fingerprint", "shape")

    def __init__(self, family: str, fingerprint: str, shape: str):
        self.family = family
        self.fingerprint = fingerprint
        self.shape = shape


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


# whether a compile on this thread was served by the persistent
# compilation cache: jax reports each to its monitoring listeners, in
# the compiling thread
_CACHE_EVENTS = threading.local()
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_cache_listener = []        # the listener, once it is registered


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _CACHE_EVENTS.hits = getattr(_CACHE_EVENTS, "hits", 0) + 1
    elif event == _CACHE_MISS:
        _CACHE_EVENTS.misses = getattr(_CACHE_EVENTS, "misses", 0) + 1


def cache_events() -> Tuple[int, int]:
    """(hits, misses) of the persistent compilation cache on this
    thread so far; the difference over a call says whether what it
    compiled was loaded or built."""
    if not _cache_listener:
        from jax import monitoring
        monitoring.register_event_listener(_on_cache_event)
        _cache_listener.append(_on_cache_event)
    return (getattr(_CACHE_EVENTS, "hits", 0),
            getattr(_CACHE_EVENTS, "misses", 0))


_MLIR_LOC_DEF = re.compile(r'^#loc(\d+) = loc\("([^"]*)"', re.M)
_MLIR_LOC_USE = re.compile(r"loc\(#loc(\d+)\)")


def stage_layout(lowered) -> str:
    """Digest of which op of a lowering lies in which scope: the name
    stacks (`jit(bm25_dense)/vmap(scatter)/scatter-add`) of its ops in
    program order, without files and lines. Two lowerings of one
    computation share it exactly when their compiled metadata would put
    every op in the same stage."""
    text = lowered.as_text(debug_info=True)
    names = dict(_MLIR_LOC_DEF.findall(text))
    stacks = "\n".join(names.get(n, "")
                       for n in _MLIR_LOC_USE.findall(text))
    return hashlib.sha1(stacks.encode()).hexdigest()


def _layout_path(family: str, lowered) -> Optional[str]:
    """Where the stage layout of a cached executable is kept: beside
    the persistent compilation cache, or None without one. It is named
    for the computation (the lowering's text without locations, which
    is what the cache keys on), not for a census fingerprint: two
    plans that lower to one computation share the cache's entry, and
    the second is served the executable the first compiled."""
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return None
    digest = hashlib.sha1(lowered.as_text().encode()).hexdigest()[:16]
    return os.path.join(cache_dir, "stage_layouts", f"{family}-{digest}")


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

    fp = fingerprint(key) if family is not None else ""
    info = ExecInfo(family, fp, shape) if family is not None else None

    def first(*args):
        hits = cache_events()[0] if family is not None else 0
        t0 = time.monotonic_ns()
        out = fn(*args)
        t1 = time.monotonic_ns()
        ms = (t1 - t0) / 1e6
        note_compile(ms)
        if family is not None:
            KERNELS.census_note(fn, args, family, shape, fp, ms, cost,
                                from_cache=cache_events()[0] > hits)
            # the compile as a span of the request that paid it, under
            # the `dispatch` it happened in (same two clock reads)
            from opensearch_tpu.telemetry import TELEMETRY
            TELEMETRY.tracer.spans.child(
                "xla.compile", t0, t1,
                {"family": family, "fingerprint": fp, "ms": round(ms, 3)})
        return out

    if info is not None:
        # cache hits return the raw jitted fn: it carries the same info
        first.exec_info = info
        try:
            fn.exec_info = info
        except AttributeError:
            pass    # a callable without a __dict__: spans go unnamed
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


_HLO_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_COMPUTATION = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\)\s*->\s*[^{]*)?\{\s*$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the computation an instruction runs: a fusion's body (`calls=`), or
# the combiner of a scatter, reduce or sort (`to_apply=`)
_HLO_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
# `attr=%computation` / `attr={%a, %b}`: references that are no operands
_HLO_ATTR_REFS = re.compile(
    r"\b\w+=\{?%[\w.\-]+(?:,\s*%[\w.\-]+)*\}?")
_HLO_REF = re.compile(r"%([\w.\-]+)")
_HLO_TAIL = re.compile(r",\s*(?:metadata|backend_config)=")
_TRANSFORM_WRAP = re.compile(r"^(?:\w+\()+|\)+$")


def _stage_of(op_name: str) -> Optional[str]:
    """The innermost `STAGES` component of an HLO `op_name` path. A
    scope entered under a transform is wrapped in it
    (`jit(bm25_dense)/vmap(scatter)/scatter-add`), and XLA joins the
    names of merged instructions with `;` (the first decides)."""
    for part in reversed(op_name.split(";")[0].split("/")):
        part = _TRANSFORM_WRAP.sub("", part)
        if part in STAGES:
            return part
    return None


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name -> stage} from an optimized HLO module's text.
    Three rules, in order:

    1. an instruction belongs to the innermost `STAGES` scope of its
       own `metadata={op_name=...}`;
    2. one without (a fusion XLA made; the scatter a TPU pass expanded,
       which loses its metadata) to the stage of the ROOT instruction
       of the computation it runs: a fusion's body, or the combiner a
       scatter, reduce or sort applies;
    3. one still unnamed whose operands ALL belong to one stage to that
       stage (what a rewrite of `lax.top_k` leaves behind the masked
       scores: reshape, TopK custom call, the merge of its rows), in
       program order; then, to a fixed point, one whose result is read
       ONLY by instructions of one stage to that stage (the sorts the
       scatter expansion puts before its scatter, a prefetch copy).
       This is inference from the neighbours, not the op's own record,
       and the map says so: the stage is written `~stage`.

    Instructions of no stage are left out."""
    own: Dict[str, Optional[str]] = {}      # instruction -> own stage
    calls: Dict[str, str] = {}              # instruction -> computation
    roots: Dict[str, str] = {}              # computation -> ROOT instr
    operands: Dict[str, List[str]] = {}     # instruction -> its inputs
    users: Dict[str, List[str]] = {}        # instruction -> its readers
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        op = _HLO_OP_NAME.search(line)
        own[name] = _stage_of(op.group(1)) if op else None
        called = _HLO_CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = name
        body = _HLO_TAIL.split(line[m.end():], 1)[0]
        operands[name] = _HLO_REF.findall(_HLO_ATTR_REFS.sub("", body))
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        st = own.get(name)
        if st is not None or depth > 8:
            return st
        root = roots.get(calls.get(name, ""))
        return resolve(root, depth + 1) if root else None

    out: Dict[str, str] = {}
    for name in own:
        st = resolve(name)
        if st is not None:
            out[name] = st
    # rule 3, forwards: HLO text defines before it uses, so one pass in
    # program order carries a stage down a chain of unnamed instructions
    inferred = set()
    for name in own:
        if name not in out and operands[name]:
            stages = {out.get(o) for o in operands[name]}
            if len(stages) == 1 and None not in stages:
                out[name] = stages.pop()
                inferred.add(name)
    # and backwards, from the far end of a chain inwards
    changed = True
    while changed:
        changed = False
        for name in own:
            if name in out or name not in users:
                continue
            stages = {out.get(u) for u in users[name]}
            if len(stages) == 1 and None not in stages:
                out[name] = stages.pop()
                inferred.add(name)
                changed = True
    for name in inferred:
        out[name] = "~" + out[name]
    return out


def _arg_struct(a):
    """Shape, dtype and placement of one dispatched argument leaf: what
    a later `fn.lower(...)` needs in its place."""
    import jax
    sharding = getattr(a, "sharding", None)
    if sharding is not None:
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
    return jax.ShapeDtypeStruct(getattr(a, "shape", ()), a.dtype) \
        if hasattr(a, "dtype") else a


class KernelProfiler:
    """The executable census and its roofline classes (see module
    docstring). Always on; every write happens at compile time."""

    def __init__(self):
        # None = take the device's row of DEVICE_PEAKS; a float is the
        # operator's override (telemetry.kernels.peak_* settings)
        self.peak_flops: Optional[float] = None
        self.peak_bw: Optional[float] = None
        self._census_lock = threading.Lock()
        self._census: List[dict] = []
        self._census_dropped = 0
        # fingerprint -> (jitted fn, argument shapes), kept so that
        # `scopes()` can lower the executable again; fingerprint -> its
        # {instruction -> stage} map once built
        self._lowerable: Dict[str, tuple] = {}
        self._scopes: Dict[str, Dict[str, str]] = {}

    # ----------------------------------------------------------- census

    def census_note(self, fn, args, family: str, shape: str,
                    fp: str, compile_ms: float,
                    cost: Optional[Tuple[float, float]] = None,
                    from_cache: bool = False) -> None:
        """Register one compiled executable (compile-time only — called
        from the first-call wrapper, never on a cache hit).
        `from_cache`: the persistent compilation cache served it, so
        its metadata (the stages `scopes` reads) is that of whoever
        compiled it; one compiled here leaves its `stage_layout` beside
        the cache for the processes that will load it."""
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
               "bytes": nbytes, "cost_source": source,
               "from_cache": from_cache}
        try:
            import jax
            lowerable = (fn, jax.tree_util.tree_map(_arg_struct, args))
        except Exception:  # except-ok: census is best-effort -- an argument that cannot be described only loses this executable's scope map
            lowerable = None
        if lowerable is not None and not from_cache:
            try:
                lowered = fn.lower(*lowerable[1])
                path = _layout_path(family, lowered)
                if path is not None:
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}"
                    with open(tmp, "w") as f:
                        f.write(stage_layout(lowered))
                    os.replace(tmp, path)
            except Exception:  # except-ok: census is best-effort -- a layout that cannot be written only means a process that loads this executable is refused its map
                pass
        with self._census_lock:
            if len(self._census) >= MAX_CENSUS_ENTRIES:
                self._census_dropped += 1
            else:
                self._census.append(rec)
                if lowerable is not None:
                    self._lowerable[fp] = lowerable

    def scopes(self) -> Dict[str, Dict[str, str]]:
        """{fingerprint -> {HLO instruction name -> stage}} for every
        executable of the census, built on first demand and kept: the
        executable is lowered again from its recorded argument shapes,
        compiled (the process has it already; else through the
        persistent compilation cache: a load, not a cold compile), and
        its optimized HLO read by `hlo_scopes`. An executable the cache
        served carries the metadata of whoever compiled it: its map is
        given only where the `stage_layout` its compiler left beside
        the cache is that of this process' own lowering, else
        `{"_error": ...}` (not kept: the next demand asks again): a
        reader gets no stage rather than a stale one. Called from `GET /_telemetry/kernels?scopes=true` only,
        never from the serving path."""
        with self._census_lock:
            todo = [(rec, self._lowerable[rec["fingerprint"]])
                    for rec in self._census
                    if rec["fingerprint"] in self._lowerable
                    and rec["fingerprint"] not in self._scopes]
        failed: Dict[str, Dict[str, str]] = {}
        for rec, (fn, structs) in todo:
            fp = rec["fingerprint"]
            try:
                lowered = fn.lower(*structs)
                hits, misses = cache_events()
                text = lowered.compile().as_text()
                now_hits, now_misses = cache_events()
                # a compile just now decides; else the first call's does
                loaded = now_hits > hits or (now_misses == misses
                                             and rec["from_cache"])
                found = hlo_scopes(text or "")
                if loaded:
                    path = _layout_path(rec["family"], lowered)
                    try:
                        with open(path) as f:
                            theirs = f.read().strip()
                    except (OSError, TypeError):
                        theirs = None
                    if theirs != stage_layout(lowered):
                        found = {"_error": (
                            "loaded from the compile cache, compiled from "
                            "another stage layout" if theirs else
                            "loaded from the compile cache, which keeps "
                            "no stage layout for it") + ": start from an "
                            "empty cache to read its stages"}
            except Exception as e:  # except-ok: census is best-effort -- a program that will not lower again reports its error in place of a map
                found = {"_error": f"{type(e).__name__}: {e}"[:200]}
            failed[fp] = found
            if "_error" not in found:   # an error is asked again
                with self._census_lock:
                    self._scopes[fp] = found
                del failed[fp]
        with self._census_lock:
            return {**failed, **self._scopes}

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

    def snapshot(self, census: bool = True, scopes: bool = False) -> dict:
        """The `GET /_telemetry/kernels` body (and, with census=False,
        the compact `_nodes/stats` block): per-family census aggregates
        and roofline verdicts. `scopes` adds each census executable's
        {instruction -> stage} map (`?scopes=true`)."""
        peak_flops, peak_bw = self.peaks()
        ridge = peak_flops / max(peak_bw, 1.0) \
            if peak_flops is not None and peak_bw is not None else None
        families = {}
        for fam, agg in sorted(self._census_by_family().items()):
            ai, bound = self._roofline(agg["flops"], agg["bytes"], ridge)
            families[fam] = {
                "compiles": agg["compiles"],
                "compile_ms": round(agg["compile_ms"], 3),
                "flops": agg["flops"], "bytes": agg["bytes"],
                "arithmetic_intensity": _round(ai), "bound": bound}
        with self._census_lock:
            n_census = len(self._census)
            dropped = self._census_dropped
            compile_total = sum(r["compile_ms"] for r in self._census)
            dump = list(self._census) if census else None
        out = {"peak_flops": peak_flops, "peak_bw": peak_bw,
               "ridge_intensity": _round(ridge),
               "census": {"entries": n_census, "dropped": dropped,
                          "compile_ms_total": round(compile_total, 3)},
               "families": families}
        if dump is not None:
            if scopes:
                maps = self.scopes()
                dump = [dict(rec, scopes=maps.get(rec["fingerprint"]))
                        for rec in dump]
            out["census"]["executables"] = dump
        return out

    def stats(self) -> dict:
        """Compact block for `_nodes/stats` (no per-executable dump)."""
        return self.snapshot(census=False)

    def clear(self) -> None:
        """Drop the census (the peak overrides survive)."""
        with self._census_lock:
            self._census = []
            self._census_dropped = 0
            self._lowerable = {}
            self._scopes = {}


def _round(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 4)


# process-wide singleton, like SCAN / INSIGHTS
KERNELS = KernelProfiler()
