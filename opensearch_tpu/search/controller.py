"""Coordinator-side search reduce: the SearchPhaseController analog.

Reference (SURVEY.md §3.2 coordinator half): TransportSearchAction fans the
query phase out to one copy of every shard, QueryPhaseResultConsumer
incrementally reduces (mergeTopDocs SearchPhaseController.java:228 +
InternalAggregations.topLevelReduce :453), then the fetch phase loads _source
only for the global top hits. Here each shard executes its jitted query phase
(device work across shards overlaps because jax dispatch is async), and the
host merges candidates with the reference's exact tie-break
(sort keys, then shard/segment/doc order) and reduces agg partials once.

Also implemented here (reference analogs in parentheses):
  - search_after / internal scroll cursors (SearchAfterBuilder,
    scroll keep-alive contexts) with a host-driven k-doubling retry when the
    cursor reaches past the device top-k window;
  - track_total_hits true/false/threshold (TotalHitCountCollector);
  - field collapse (CollapsingTopDocsCollector);
  - rescore (QueryRescorer) re-ranking the top window with a second query;
  - fetch sub-phases per page hit (FetchPhase.java:106 → highlight, explain,
    docvalue_fields in search/fetch.py).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Tuple

from opensearch_tpu.common import faults
from opensearch_tpu.common.errors import (
    IllegalArgumentError, OpenSearchTpuError, ParsingError,
    SearchPhaseExecutionError, TaskCancelledError, shard_failure_entry)
from opensearch_tpu.search import dsl
from opensearch_tpu.search.aggs.parse import PIPELINE_TYPES, parse_aggs
from opensearch_tpu.search.aggs.pipeline import apply_pipelines
from opensearch_tpu.search.aggs.reduce import reduce_aggs
from opensearch_tpu.search.executor import (
    _compare_candidates, _parse_sort, _sort_value)


def _cmp_values(a: Any, b: Any, order: str) -> int:
    """Compare two sort values in page order (-1: a first)."""
    if a is None and b is None:
        return 0
    if a is None:
        return 1
    if b is None:
        return -1
    try:
        lt = a < b
        gt = b < a
    except TypeError:
        a, b = str(a), str(b)
        lt, gt = a < b, b < a
    if not lt and not gt:
        return 0
    if order == "desc":
        return -1 if gt else 1
    return -1 if lt else 1


def _after_cursor(candidates, sort_specs, after_values,
                  tiebreak: Optional[Tuple[int, int, int]] = None):
    """Drop candidates at or before the cursor position. `after_values`
    aligns with sort_specs; `tiebreak` is the internal (shard, seg, ord) of
    the last returned hit for fully-tied scroll continuation."""
    if len(after_values) != len(sort_specs):
        raise IllegalArgumentError(
            f"search_after has {len(after_values)} value(s) but sort has "
            f"{len(sort_specs)} field(s)")
    out = []
    for c in candidates:
        rel = 0
        for i, ((field, order), av) in enumerate(zip(sort_specs,
                                                     after_values)):
            cv = c.score if field == "_score" else c.sort_values[i]
            rel = _cmp_values(cv, av, order)
            if rel != 0:
                break
        if rel > 0:
            out.append(c)
        elif rel == 0 and tiebreak is not None and \
                (c.shard_i, c.seg_i, c.ord) > tiebreak:
            out.append(c)
    return out


def _apply_collapse(candidates, executors, collapse_field: str):
    """Keep the best candidate per collapse-field value (first in sort
    order); None-valued docs collapse into one group per the reference's
    CollapsingTopDocsCollector null policy (each null is its own group)."""
    seen = set()
    out = []
    for c in candidates:
        ex = executors[c.shard_i]
        seg = ex.reader.segments[c.seg_i]
        val = _sort_value(seg, collapse_field, "asc", c.ord)
        if val is None:
            out.append(c)
            continue
        if val in seen:
            continue
        seen.add(val)
        out.append(c)
        c.collapse_value = val
    return out


def _apply_rescore(executors, rescore_body, candidates, extra_filters):
    """QueryRescorer: re-rank the top window_size hits by combining the
    original score with a secondary query's score. Runs the rescore query
    as its own device pass per shard (k capped — see below) and combines
    host-side."""
    entries = rescore_body if isinstance(rescore_body, list) else [rescore_body]
    for entry in entries:
        window = int(entry.get("window_size", 10))
        spec = entry.get("query")
        if not spec or "rescore_query" not in spec:
            raise IllegalArgumentError("rescore malformed: missing rescore_query")
        qw = float(spec.get("query_weight", 1.0))
        rqw = float(spec.get("rescore_query_weight", 1.0))
        mode = spec.get("score_mode", "total")
        window_cands = candidates[:window]
        shard_ids = {c.shard_i for c in window_cands}
        # device pass must cover every window doc: k scales with the window
        # (docs the rescore query doesn't match at all contribute 0)
        k = max(512, window * 8)
        score_map = {}
        for shard_i in shard_ids:
            extra = extra_filters[shard_i] if extra_filters else None
            cands, _, _ = executors[shard_i].execute_query_phase(
                {"query": spec["rescore_query"]}, k, extra_filter=extra)
            for c in cands:
                score_map[(shard_i, c.seg_i, c.ord)] = c.score
        for c in window_cands:
            rs = score_map.get((c.shard_i, c.seg_i, c.ord))
            if rs is None:
                c.score = c.score * qw
                continue
            combined = {
                "total": c.score * qw + rs * rqw,
                "multiply": c.score * qw * (rs * rqw),
                "avg": (c.score * qw + rs * rqw) / 2.0,
                "max": max(c.score * qw, rs * rqw),
                "min": min(c.score * qw, rs * rqw),
            }.get(mode)
            if combined is None:
                raise IllegalArgumentError(
                    f"[rescore] illegal score_mode [{mode}]")
            c.score = combined
        window_cands.sort(key=lambda c: (-c.score, c.shard_i, c.seg_i, c.ord))
        candidates[:window] = window_cands
    return candidates


# the top-level keys SearchSourceBuilder's parser accepts — anything else
# is a parsing error (400), e.g. a query clause pasted at the top level
SEARCH_BODY_KEYS = frozenset({
    "query", "from", "size", "sort", "aggs", "aggregations", "_source",
    "fields", "stored_fields", "docvalue_fields", "script_fields",
    "track_total_hits", "track_scores", "min_score", "search_after",
    "highlight", "suggest", "rescore", "collapse", "post_filter",
    "explain", "version", "seq_no_primary_term", "slice", "pit",
    "profile", "timeout", "terminate_after", "indices_boost",
    "runtime_mappings", "search_type", "scroll", "scroll_id", "ext",
    "min_compatible_shard_node", "knn", "stats",
    "allow_partial_search_results",
    "_dfs",                       # internal: DFS-merged statistics
    "_request_cache",             # internal: ?request_cache=true|false
})


def _parse_deadline(body: dict) -> Optional[float]:
    """body['timeout'] ('10ms'/'1s'/bare-int millis) → monotonic
    deadline, or None. The long-ignored param now gates phase launches."""
    raw = body.get("timeout")
    if raw is None:
        return None
    from opensearch_tpu.common.settings import parse_time_value
    from opensearch_tpu.common.errors import SettingsError
    try:
        timeout_s = parse_time_value(raw, "timeout")
    except (SettingsError, TypeError, ValueError):
        raise IllegalArgumentError(
            f"failed to parse [timeout] with value [{raw!r}]")
    if timeout_s <= 0:
        return None                 # -1 / 0 disable, reference semantics
    return time.monotonic() + timeout_s


def _resolve_allow_partial(body: dict, default: Optional[bool]) -> bool:
    """allow_partial_search_results: body key > caller kwarg (REST param /
    cluster setting `search.default_allow_partial_results`) > true (the
    reference default)."""
    raw = body.get("allow_partial_search_results")
    if raw is None:
        return True if default is None else bool(default)
    if isinstance(raw, str):
        return raw.strip().lower() != "false"
    return bool(raw)


def _validate_search_body_keys(body: dict) -> None:
    for key in body:
        if key not in SEARCH_BODY_KEYS:
            raise ParsingError(f"unknown key [{key}] in the search body")


class _PhaseTimer:
    """Times one search phase. The ns total ALWAYS lands in the request's
    phase dict (metrics histograms and the slow log read it — a couple of
    perf_counter_ns calls per phase, paid whether or not tracing is on);
    a child span opens only when the trace records (node tracing enabled
    or a profile request), so the disabled path allocates nothing."""

    __slots__ = ("name", "phases", "span", "t0", "duration_ns")

    def __init__(self, trace, phases: dict, name: str, **attrs):
        self.name = name
        self.phases = phases
        self.span = trace.child(name, **attrs) if trace.recording else None
        self.duration_ns = 0
        self.t0 = time.perf_counter_ns()

    def set_attribute(self, key, value):
        if self.span is not None:
            self.span.set_attribute(key, value)

    def __enter__(self) -> "_PhaseTimer":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.duration_ns = time.perf_counter_ns() - self.t0
        self.phases[self.name] = self.phases.get(self.name, 0) \
            + self.duration_ns
        if self.span is not None:
            self.span.end(error=exc if exc_type is not None else None)
        return False


def _note_controller_insights(query_spec, took_ms, req_scope) -> None:
    """Per-shape cost note for controller-served requests (ISSUE 15):
    the general host loop, the SPMD path and the fused hybrid branch —
    everything the msearch envelope does NOT note itself. Shape id from
    the interned template signature (fallback: structural hash); scan
    bytes joined through the recorder's thread-local accumulator (the
    query phase / SPMD path feed it the SAME bytes the heat map
    counts); transfer bytes/round trips from the request's LedgerScope
    when the ledger is on. Also stamps the shape onto the bound
    lifecycle timeline so tail captures group by shape class — that
    annotation rides the flight recorder's own gate, not insights'.
    Both gates off = two attribute loads and branches."""
    from opensearch_tpu.telemetry import TELEMETRY
    ins = TELEMETRY.insights.gate()
    tl = TELEMETRY.flight.current() if TELEMETRY.flight.enabled else None
    if ins is None and tl is None:
        return
    from opensearch_tpu.telemetry.insights import query_shape
    label, kind = query_shape(query_spec)
    if tl is not None and tl.shape is None:
        tl.shape = label
    if ins is None:
        return
    sp, sd, spr = ins.take_scan()
    dev_ms = req_scope.device_get_ms if req_scope is not None else 0.0
    ins.note(
        label, kind=kind, took_ms=float(took_ms),
        device_ms=dev_ms,
        posting_bytes=sp, dense_bytes=sd, pruned_bytes=spr,
        h2d_bytes=req_scope.h2d_bytes if req_scope is not None else 0,
        d2h_bytes=req_scope.d2h_bytes if req_scope is not None else 0,
        round_trips=req_scope.round_trips
        if req_scope is not None else 0,
        co_batched=1, tenant=ins.current_tenant())


def _publish_scope(scope, span, phase_times: Optional[dict]) -> None:
    """Attach a request's transfer accounting (telemetry/ledger.py
    LedgerScope) to its span and to the caller's phase_times dict, where
    the slow log reads the `device_get`/`bytes_fetched` fields. The
    field set lives on LedgerScope.publish — shared with the msearch
    envelope's own publication."""
    if scope is not None:
        scope.publish(span, phase_times)


def execute_search(executors: List, body: Optional[dict],
                   total_shards: Optional[int] = None,
                   failed_shards: int = 0,
                   extra_filters: Optional[List[Optional[dict]]] = None,
                   cursor_tiebreak: Optional[Tuple[int, int, int]] = None,
                   task=None, allow_envelope: bool = False,
                   phase_processors: Optional[dict] = None,
                   trace=None,
                   phase_times: Optional[dict] = None,
                   allow_partial: Optional[bool] = None) -> dict:
    """Lifecycle wrapper around `_execute_search_impl` (which carries
    the full contract docstring): when the flight recorder
    (telemetry/lifecycle.py) is enabled and no request timeline is
    bound yet — direct callers like IndexService.search, scroll,
    reindex, tests — this opens one (admit at entry, respond at exit,
    complete through the recorder's capture gate). REST-served requests
    already carry a bound timeline with real admission events; the
    wrapper passes straight through to keep one owner per request. The
    disabled path is one attribute load and a branch."""
    from opensearch_tpu.telemetry import TELEMETRY
    flight = TELEMETRY.flight
    tl = flight.timeline() \
        if flight.enabled and flight.current() is None else None
    if tl is None:
        return _execute_search_impl(
            executors, body, total_shards, failed_shards, extra_filters,
            cursor_tiebreak, task, allow_envelope, phase_processors,
            trace, phase_times, allow_partial)
    tl.event("admit")
    prev = flight.bind(tl)
    status = "error"
    try:
        res = _execute_search_impl(
            executors, body, total_shards, failed_shards, extra_filters,
            cursor_tiebreak, task, allow_envelope, phase_processors,
            trace, phase_times, allow_partial)
        status = "ok"
        return res
    finally:
        flight.unbind(prev)
        tl.event("respond")
        flight.complete(tl, status=status, span=trace)


def _execute_search_impl(executors: List, body: Optional[dict],
                         total_shards: Optional[int] = None,
                         failed_shards: int = 0,
                         extra_filters: Optional[List[Optional[dict]]]
                         = None,
                         cursor_tiebreak: Optional[Tuple[int, int, int]]
                         = None,
                         task=None, allow_envelope: bool = False,
                         phase_processors: Optional[dict] = None,
                         trace=None,
                         phase_times: Optional[dict] = None,
                         allow_partial: Optional[bool] = None) -> dict:
    """Run the full query-then-fetch flow over shard executors and render
    the search response. `executors` are per-shard SearchExecutors;
    `extra_filters` (aligned with executors) carry per-index alias filters;
    `cursor_tiebreak` is the internal scroll cursor position; `task` (when
    given) is checked for cancellation between shard launches — the safe
    points between device programs (CancellableBulkScorer analog).
    `allow_envelope` (top-level serving entry points only — REST _search,
    IndexService.search) lets a single-shard plain request delegate to the
    msearch envelope; scroll/reindex/CCS callers need this path's page
    cursor and shard accounting, and the envelope's own fallback re-enters
    here and must not loop. `phase_processors` is the resolved search
    pipeline's normalization-processor spec for hybrid queries (None =
    defaults). `trace` is the request's root telemetry span (None = not
    traced) — child spans cover parse, can_match, per-shard query with
    device-dispatch attribution, reduce and fetch, and close on every
    exit path. `phase_times` (pass a dict) is filled with per-phase
    milliseconds for the caller's slow log.

    Partial-failure contract (reference: AbstractSearchAsyncAction's
    per-shard onShardFailure accounting): a runtime exception in ONE
    shard's can-match / query / fetch phase costs that shard's slice of
    the response, not the envelope — failures render as reference-shaped
    `_shards.failures[]` entries. `allow_partial` (body key
    `allow_partial_search_results` > this kwarg > true) decides whether
    a partially-failed request returns 200 or raises
    SearchPhaseExecutionError; all shards failing always raises. The
    `timeout` body param is enforced at phase boundaries (between shard
    launches, before fetch): past-deadline requests stop launching new
    shard phases and render `timed_out: true` with whatever accumulated.
    Cancellation (`task`) is checked at the same safe points."""
    from opensearch_tpu.telemetry import NOOP_SPAN, TELEMETRY
    if trace is None:
        trace = NOOP_SPAN
    if TELEMETRY.flight.enabled:
        # lifecycle: whatever wall accumulated between the request's
        # arrival (REST entry / wrapper) and this engine entry becomes
        # the `route` phase — pipeline resolution, plumbing, and the
        # GIL starvation a contended node inflicts right here
        _tl_route = TELEMETRY.flight.current()
        if _tl_route is not None:
            _tl_route.route()
    body = body or {}
    _validate_search_body_keys(body)
    # per-request transfer accounting (telemetry/ledger.py): None unless
    # the ledger is enabled or this request traces/profiles — the
    # zero-overhead default. Feeds the span's bytes_to_device/
    # bytes_fetched, the Profile API's transfers[] and the slow log's
    # bytes_fetched/device_get fields on EVERY dispatch path (general
    # host loop, envelope, hybrid) — the attribution used to exist only
    # in the general path's single-branch sum.
    req_scope = TELEMETRY.ledger.scope(trace)
    if TELEMETRY.insights.enabled:
        # clear stale thread-local scan residue (an earlier errored
        # request on this thread must not leak bytes into this one's
        # per-shape join)
        TELEMETRY.insights.take_scan()
    query_spec = body.get("query")
    if isinstance(query_spec, dict) and "hybrid" in query_spec:
        # hybrid dense+sparse clause: its sub-queries keep SEPARATE score
        # channels through a fused per-shard program and merge via the
        # search pipeline's normalization-processor at reduce
        # (searchpipeline/hybrid.py) — the single-score paths below
        # cannot represent it
        if cursor_tiebreak is not None:
            raise IllegalArgumentError(
                "[scroll] is not supported with a [hybrid] query")
        from opensearch_tpu.searchpipeline.hybrid import \
            execute_hybrid_search
        trace.set_attribute("query_type", "hybrid")
        with trace.child("query", path="hybrid_fused") as hq:
            res = execute_hybrid_search(
                executors, body, phase_spec=phase_processors,
                extra_filters=extra_filters, total_shards=total_shards,
                failed_shards=failed_shards, task=task,
                allow_partial=_resolve_allow_partial(body, allow_partial),
                ledger_scope=req_scope)
        _publish_scope(req_scope, hq, phase_times)
        if TELEMETRY.flight.enabled:
            tl = TELEMETRY.flight.current()
            if tl is not None and req_scope is not None:
                tl.merge_phases({"device_get": req_scope.device_get_ms})
        _note_controller_insights(query_spec, res.get("took", 0),
                                  req_scope)
        return res
    if (allow_envelope and len(executors) == 1 and total_shards is None
            and failed_shards == 0 and cursor_tiebreak is None
            and not (extra_filters and extra_filters[0])):
        from opensearch_tpu.search.executor import _msearch_batchable
        if _msearch_batchable(body):
            # single-shard plain score-sorted request: serve through the
            # B=1 msearch envelope — the same executable family as
            # dashboard batches (bit-identical scores), so the warmup
            # registry's (plan-struct, shape-bucket) coverage extends to
            # REST _search singles, not just _msearch
            with trace.child("query", path="envelope") as eq:
                # straight into the envelope (search() would re-check
                # _msearch_batchable); errors raise — the per-item error
                # objects are an _msearch-only contract. The envelope
                # sets its own transfer attribution on the child span
                # and fills phase_times for the slow log. The request's
                # `timeout=` rides along: the wave engine enforces it at
                # its wave boundaries (a B=1 envelope is the degenerate
                # single wave), rendering the timed-out shape instead of
                # silently ignoring the budget on this path.
                return executors[0].multi_search(
                    [body], _raise_item_errors=True, task=task,
                    deadline=_parse_deadline(body),
                    trace=eq, phase_times=phase_times)["responses"][0]
    start = time.monotonic()
    start_ns = time.perf_counter_ns()
    deadline = _parse_deadline(body)
    allow_partial_results = _resolve_allow_partial(body, allow_partial)
    timed_out_box = [False]
    shard_failures: List[dict] = []     # reference-shaped failures[]
    failed_shard_ids: set = set()       # dedupe: one entry per shard

    def _deadline_passed() -> bool:
        if deadline is not None and time.monotonic() > deadline:
            timed_out_box[0] = True
            return True
        return False

    def _record_failure(shard_i: int, exc: BaseException) -> None:
        if shard_i in failed_shard_ids:
            return
        failed_shard_ids.add(shard_i)
        idx = executors[shard_i].reader.index_name \
            if 0 <= shard_i < len(executors) else "_unknown"
        shard_failures.append(shard_failure_entry(shard_i, idx, exc))
        TELEMETRY.metrics.counter("search.shard_failures").inc()
    profiling = bool(body.get("profile", False))
    if profiling and not trace.recording:
        # the profile API builds from request-scoped spans even when
        # node-wide tracing is off; a forced trace records locally but is
        # never retained in the tracer's ring buffer
        trace = TELEMETRY.tracer.start_trace("search", force=True)
        if req_scope is None:
            # the scope gate ran before the forced trace existed: profile
            # requests always account transfers (ledger.scope() treats a
            # recording trace as opt-in)
            req_scope = TELEMETRY.ledger.scope(trace)
    phases: dict = {}            # phase name -> accumulated ns
    profile_shards: List[dict] = []
    with _PhaseTimer(trace, phases, "parse"):
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        if size < 0 or from_ < 0:
            raise IllegalArgumentError("[from] parameter cannot be negative" if from_ < 0
                    else "[size] parameter cannot be negative")
        # index.max_result_window (SearchService#validateSearchSource): deep
        # from+size pagination must use scroll/search_after-with-paging
        window = min((getattr(ex, "max_result_window", 10000)
                      for ex in executors), default=10000)
        if from_ + size > window and cursor_tiebreak is None:
            raise IllegalArgumentError(
                f"Result window is too large, from + size must be less than "
                f"or equal to: [{window}] but was [{from_ + size}]. See the "
                f"scroll api for a more efficient way to request large data "
                f"sets. This limit can be set by changing the "
                f"[index.max_result_window] index level setting.")

        sort_specs = _parse_sort(body.get("sort"))
        score_sorted = sort_specs[0][0] == "_score"
        wants_score = score_sorted \
            or any(f == "_score" for f, _ in sort_specs) \
            or bool(body.get("track_scores", False))
        agg_nodes = parse_aggs(body.get("aggs") or body.get("aggregations"))
        after_values = body.get("search_after")
        if after_values is not None and from_ > 0:
            raise IllegalArgumentError(
                "`from` parameter must be set to 0 when `search_after` is "
                "used")
        collapse_field = (body.get("collapse") or {}).get("field")
        track_total = body.get("track_total_hits", True)

        k = max(from_ + size, 10)
        max_k = 1 << 16

        # DFS query-then-fetch (DfsQueryPhase + aggregateDfs): collect every
        # shard's term statistics for the query, merge, and pin the merged
        # stats on every shard's compile so scores are globally comparable
        dfs_overrides: Optional[List] = None
        if body.get("search_type") == "dfs_query_then_fetch" and executors:
            from opensearch_tpu.common.errors import ParsingError
            from opensearch_tpu.search.compile import (
                StaticStats, collect_query_term_stats, merge_dfs_stats)
            try:
                qnode = dsl.parse_query(body.get("query"))
            except ParsingError:
                qnode = None         # the normal path raises it properly
            if qnode is not None:
                # any OTHER failure here is a real bug and must surface — a
                # silent fallback to shard-local stats would hand the user
                # non-comparable scores they explicitly asked to avoid
                parts = [collect_query_term_stats(qnode, ex.reader.mapper,
                                                  ex.reader.stats())
                         for ex in executors]
                fields, term_df = merge_dfs_stats(parts)
                dfs_overrides = [StaticStats(ex.reader.stats(), fields,
                                             term_df)
                                 for ex in executors]

    # can-match pre-filter (CanMatchPreFilterSearchPhase): shards whose
    # segment min/max metadata proves emptiness never compile or launch a
    # device program. Computed lazily — the SPMD program batches every
    # (shard, segment) row in one launch and never consults the flags —
    # and cached across k-growth retries. When every shard would skip, one
    # still executes so the response (empty agg structures, totals) is
    # fully shaped, exactly like the reference phase.
    from opensearch_tpu.search.canmatch import shard_can_match
    flags_box: List = [None]
    skipped_box = [0]
    pruned_box = [0]    # SPMD block-max pruned bytes: total -> "gte"
    # when the SPMD program last answered the query phase (monotonic),
    # None on the host loop: where the route's `spmd.reduce` (the
    # cross-row half) and `respond` spans start
    spmd_served_box: List = [None]

    def can_match_flags():
        if flags_box[0] is None:
            with _PhaseTimer(trace, phases, "can_match") as cm:
                flags = []
                for ex in executors:
                    # a can-match failure degrades to "don't skip": the
                    # pre-filter is an optimization, so its faults must
                    # cost an extra shard execution, never correctness
                    try:
                        if faults.ENABLED:
                            faults.fire("canmatch.shard")
                        flags.append(shard_can_match(ex, body))
                    except Exception:   # except-ok: canmatch isolation -- any failure class degrades to don't-skip, never a failed query
                        flags.append(True)
                if flags and not any(flags):
                    flags[0] = True
                cm.set_attribute("skipped",
                                 len(executors) - sum(flags))
            flags_box[0] = flags
        return flags_box[0]

    def run_query_phase(k_eff):
        candidates = []
        decoded_partials = []
        total = 0
        profile_shards.clear()
        shard_failures.clear()      # k-growth retries re-run the phase
        failed_shard_ids.clear()
        pruned_box[0] = 0           # last phase run decides the relation
        spmd_served_box[0] = None
        # SPMD path: with multiple (shard, segment) rows and enough mesh
        # devices, the query phase is ONE shard_map program with on-chip
        # all_gather/psum merge instead of a host loop (search/spmd.py).
        # Routing (rows + eligibility, incl. the cold module import) is
        # accounted under can_match — it's the same shard-routing
        # decision family
        with _PhaseTimer(trace, phases, "can_match", op="spmd_route"):
            from opensearch_tpu.search import spmd
            rows = spmd.spmd_rows(executors)
            # the fused all-shard SPMD program has no per-shard
            # boundaries: a deadline can't be checked mid-program and a
            # fault can't cost one shard's slice — deadline'd requests
            # and fault-injection runs take the per-shard host loop,
            # which has both checkpoints
            spmd_ok = deadline is None and not faults.ENABLED \
                and spmd.eligible(executors, body, rows, sort_specs)
        if spmd_ok:
            with _PhaseTimer(trace, phases, "query", path="spmd",
                             rows=len(rows)) as qt:
                try:
                    # the SPMD path attributes its transfers to the
                    # thread-ambient ledger scope (upload.literals /
                    # spmd.results in parallel/distributed.py read
                    # ledger.current()); binding the request scope here
                    # routes them onto THIS request — the per-shape
                    # transfer join (ISSUE 15) and the Profile/slow-log
                    # byte fields on SPMD-served requests both need it.
                    # Safe: the SPMD query phase is single-request.
                    if req_scope is not None:
                        with TELEMETRY.ledger.ambient(req_scope):
                            out = spmd.spmd_query_phase(
                                executors, body, k_eff, extra_filters,
                                rows)
                    else:
                        out = spmd.spmd_query_phase(
                            executors, body, k_eff, extra_filters, rows)
                except TaskCancelledError:
                    raise
                except Exception:   # except-ok: SPMD isolation -- any failure class degrades to the per-shard host loop
                    # the fused all-shard program failed as a unit:
                    # degrade to the per-shard host loop below, where
                    # failure isolation is per shard; counted, with
                    # the fallbacks the route itself decides on
                    spmd.note_fallback("error")
                    out = None
            if out is not None:
                spmd_served_box[0] = time.monotonic()
                candidates, decoded_partials, total, spmd_pruned = out
                # block-max pruning made `total` a lower bound: the
                # response's hits.total.relation degrades to "gte"
                pruned_box[0] = spmd_pruned
                with _PhaseTimer(trace, phases, "reduce"):
                    candidates.sort(key=_compare_candidates(sort_specs))
                if profiling:
                    entry = {
                        "id": f"[{executors[0].reader.index_name}][spmd]",
                        "_query_ns": qt.duration_ns,
                        "searches": [{"query": [{
                            "type": "SpmdQueryPhase",
                            "description": str(body.get("query")),
                            "time_in_nanos": qt.duration_ns,
                            "breakdown": {"rows": len(rows),
                                          "segments": len(rows)},
                        }], "rewrite_time": 0, "collector": []}],
                        "aggregations": [],
                    }
                    # per-device attribution (ISSUE 14): when the
                    # device ledger captured this query, the shard
                    # entry carries the per-chip phase breakdown —
                    # upload / partial(device, wall) / collective
                    # merge / result pull + straggler skew
                    devscope = TELEMETRY.ledger.devices.take_last()
                    if devscope is not None:
                        entry["devices"] = devscope.to_dict()
                    profile_shards.append(entry)
                return candidates, decoded_partials, total
        flags = can_match_flags()
        skipped_box[0] = len(executors) - sum(flags)
        for shard_i, ex in enumerate(executors):
            if not flags[shard_i]:
                continue                # provably empty: skipped shard
            if task is not None:
                task.check_cancelled()
            if _deadline_passed():
                # budget spent: stop launching new shard phases; what
                # accumulated so far renders with timed_out: true
                break
            extra = extra_filters[shard_i] if extra_filters else None
            try:
                with _PhaseTimer(trace, phases, "query",
                                 shard=shard_i) as qt:
                    if faults.ENABLED:
                        faults.fire("query.shard")
                    cands, decoded, shard_total = ex.execute_query_phase(
                        body, k_eff, extra_filter=extra,
                        stats_override=dfs_overrides[shard_i]
                        if dfs_overrides else None,
                        trace=qt.span, ledger_scope=req_scope)
                    qt.set_attribute("candidates", len(cands))
            except TaskCancelledError:
                raise                   # cancellation is not a failure
            except OpenSearchTpuError as e:
                if e.status < 500:
                    # a 4xx is a deterministic request defect (parse /
                    # validation), not a shard fault: every shard would
                    # fail identically, so the request keeps its 4xx
                    # contract instead of degrading to a partial
                    raise
                _record_failure(shard_i, e)
                continue
            except Exception as e:  # except-ok: per-shard isolation -- failures land in _shards.failures[], not the request
                # one shard's query fault costs that shard's slice of
                # the response, not the request
                _record_failure(shard_i, e)
                continue
            for c in cands:
                c.shard_i = shard_i
            candidates.extend(cands)
            decoded_partials.extend(decoded)
            total += shard_total
            if profiling:
                # device-dispatch attribution (compile/dispatch/collect
                # ns, bytes_to_device, compiled) rides the span the
                # executor annotated
                breakdown = {"segments": len(ex.reader.segments)}
                if qt.span is not None:
                    breakdown.update(
                        {k2: v for k2, v in qt.span.attributes.items()
                         if k2 not in ("shard", "candidates")})
                # the per-transfer list is a first-class profile field,
                # not a breakdown scalar: transfers[] per shard is the
                # ledger's contract with the Profile API
                shard_transfers = breakdown.pop("transfers", [])
                profile_shards.append({
                    "id": f"[{ex.reader.index_name}][{shard_i}]",
                    "_query_ns": qt.duration_ns,
                    "searches": [{"query": [{
                        "type": "TpuQueryPhase",
                        "description": str(body.get("query")),
                        "time_in_nanos": qt.duration_ns,
                        "breakdown": breakdown,
                    }], "rewrite_time": 0, "collector": []}],
                    "aggregations": [],
                    "transfers": shard_transfers,
                })
        with _PhaseTimer(trace, phases, "reduce"):
            candidates.sort(key=_compare_candidates(sort_specs))
        return candidates, decoded_partials, total

    candidates, decoded_partials, total = run_query_phase(k)
    raw_count = len(candidates)
    if after_values is not None:
        cursor_values = after_values
        with _PhaseTimer(trace, phases, "reduce"):
            filtered = _after_cursor(candidates, sort_specs, cursor_values,
                                     tiebreak=cursor_tiebreak)
        # the cursor may reach past the device top-k window: grow k until
        # the page is full or every match is on host (reference avoids this
        # by filtering inside the collector; here the host drives a retry)
        while len(filtered) < from_ + size and raw_count >= k and k < max_k \
                and k < total:
            k = min(max_k, k * 4)
            candidates, decoded_partials, total = run_query_phase(k)
            raw_count = len(candidates)
            with _PhaseTimer(trace, phases, "reduce"):
                filtered = _after_cursor(candidates, sort_specs,
                                         cursor_values,
                                         tiebreak=cursor_tiebreak)
        candidates = filtered

    if body.get("rescore") and score_sorted:
        with _PhaseTimer(trace, phases, "reduce", op="rescore"):
            candidates = _apply_rescore(executors, body["rescore"],
                                        candidates, extra_filters)
    if collapse_field:
        with _PhaseTimer(trace, phases, "reduce", op="collapse"):
            candidates = _apply_collapse(candidates, executors,
                                         collapse_field)

    with _PhaseTimer(trace, phases, "reduce", op="page"):
        page = candidates[from_:from_ + size]
        max_score = None
        if wants_score:
            for c in candidates:
                if max_score is None or c.score > max_score:
                    max_score = c.score

    _deadline_passed()      # the fetch-boundary timeout checkpoint:
    # accumulated hits still render (building the page from host-side
    # sources is cheap), but the response says timed_out
    if task is not None:
        task.check_cancelled()
    with _PhaseTimer(trace, phases, "fetch") as ft, \
            TELEMETRY.ledger.ambient(req_scope):
        # ambient binding: the fetch sub-phases (inner-hit docvalue
        # gathers in search/fetch.py) sit too deep to plumb the scope
        # through — they read it back via ledger.current()
        query_node = dsl.parse_query(body.get("query"))
        from opensearch_tpu.search import fetch as fetch_phase
        page_inner_specs = fetch_phase.collect_inner_hit_specs(query_node)
        page_inner_cache: dict = {}
        built = []      # (shard_i, hit): a mid-page shard failure must
        # drop the WHOLE shard's slice, including hits already built —
        # per-shard accounting (one failures[] entry per shard) with
        # per-candidate survivorship would double-count for clients that
        # retry failed shards
        for c in page:
            if c.shard_i in failed_shard_ids:
                continue
            ex = executors[c.shard_i]
            try:
                if faults.ENABLED:
                    faults.fire("fetch.gather")
                hit = _build_hit(ex, c, body,
                                 c.score if wants_score else None,
                                 query_node, sort_specs, score_sorted,
                                 inner_specs=page_inner_specs,
                                 inner_cache=page_inner_cache)
            except OpenSearchTpuError as e:
                if e.status < 500:
                    raise       # deterministic request defect: keep 4xx
                _record_failure(c.shard_i, e)
                continue
            except Exception as e:  # except-ok: per-shard isolation -- a fetch fault drops the shard's page hits, siblings render
                # a fetch fault fails the shard: its page hits drop as a
                # unit; siblings' hits still render
                _record_failure(c.shard_i, e)
                continue
            built.append((c.shard_i, hit))
        hits = [h for shard_i, h in built
                if shard_i not in failed_shard_ids]
        ft.set_attribute("hits", len(hits))

    n_shards = total_shards if total_shards is not None else len(executors)
    hits_block: dict = {"max_score": max_score, "hits": hits}
    # block-max pruning (ISSUE 20): pruned blocks' docs were never
    # counted, so `total` is a lower bound — "eq" degrades to "gte"
    # (the contract Lucene's BMW collector keeps via track_total_hits)
    exact_rel = "eq" if not pruned_box[0] else "gte"
    if track_total is False:
        pass  # total omitted entirely
    elif track_total is True:
        hits_block = {"total": {"value": total, "relation": exact_rel},
                      **hits_block}
    else:
        threshold = int(track_total)
        if total > threshold:
            hits_block = {"total": {"value": threshold, "relation": "gte"},
                          **hits_block}
        else:
            hits_block = {"total": {"value": total, "relation": exact_rel},
                          **hits_block}

    n_failed = failed_shards + len(shard_failures)
    attempted = sum(can_match_flags()) if flags_box[0] is not None \
        else len(executors)
    if shard_failures and len(failed_shard_ids) >= max(attempted, 1):
        # every shard that executed failed: no partial result exists to
        # degrade to (reference: "all shards failed" regardless of
        # allow_partial_search_results)
        raise SearchPhaseExecutionError(
            "all shards failed", phase="query", grouped=True,
            failed_shards=list(shard_failures))
    if shard_failures and not allow_partial_results:
        raise SearchPhaseExecutionError(
            "Partial shards failure", phase="query", grouped=True,
            failed_shards=list(shard_failures))
    shards_block: dict = {"total": n_shards,
                          "successful": max(n_shards - n_failed, 0),
                          "skipped": skipped_box[0], "failed": n_failed}
    if shard_failures:
        shards_block["failures"] = list(shard_failures)
    resp = {
        "took": 0,      # placeholder: set below AFTER agg reduce/suggest
        "timed_out": timed_out_box[0],
        "_shards": shards_block,
        "hits": hits_block,
    }
    t_aggs0 = t_aggs1 = 0.0     # the reads of `spmd.reduce.reduce_aggs`
    if agg_nodes:
        with _PhaseTimer(trace, phases, "reduce", op="aggs"):
            try:
                if faults.ENABLED:
                    faults.fire("reduce.aggs")
                t_aggs0 = time.monotonic()
                aggregations = reduce_aggs(decoded_partials)
                apply_pipelines(agg_nodes, aggregations)
                t_aggs1 = time.monotonic()
            except OpenSearchTpuError:
                raise               # already a clean typed error
            except Exception as e:  # except-ok: wraps into typed SearchPhaseExecutionError -- never a raw 500
                # coordinator-level reduce has no per-shard slice to
                # degrade to — surface a clean typed error, never a
                # corrupt/partial agg tree
                raise SearchPhaseExecutionError(
                    f"failed to reduce aggregations: "
                    f"{type(e).__name__}: {e}", phase="reduce")
        resp["aggregations"] = aggregations
    t_reduced = time.monotonic()
    if body.get("suggest"):
        from opensearch_tpu.search.suggest import execute_suggest
        with _PhaseTimer(trace, phases, "suggest"):
            resp["suggest"] = execute_suggest(executors, body["suggest"])
    # everything between the earlier timers and this point (hits/total
    # block shaping, the resp literal) is response rendering — attribute
    # it so the per-phase breakdown accounts for the whole request
    phases["render"] = phases.get("render", 0) \
        + (time.perf_counter_ns() - start_ns) - sum(phases.values())
    took_f = (time.monotonic() - start) * 1000
    resp["took"] = int(took_f)
    m = TELEMETRY.metrics
    m.counter("search.queries").inc()
    m.histogram("search.took_ms").observe(took_f)
    for phase_name, ns in phases.items():
        m.histogram(f"search.phase.{phase_name}_ms").observe(ns / 1e6)
    if phase_times is not None:
        phase_times.update(
            {phase_name: ns / 1e6 for phase_name, ns in phases.items()})
    # root-span + slow-log transfer attribution for the general host-loop
    # path (the envelope and hybrid paths publish their own above)
    _publish_scope(req_scope, trace, phase_times)
    if TELEMETRY.flight.enabled:
        # lifecycle phase decomposition (telemetry/lifecycle.py): the
        # request's timeline carries the same per-phase wall the metrics
        # histograms record, so a captured slow request explains its own
        # took. device_get is the ledger's sub-attribution of `query`
        # (tools/tail_report.py knows not to double-count it).
        tl = TELEMETRY.flight.current()
        if tl is not None:
            tl.merge_phases({name: ns / 1e6
                             for name, ns in phases.items()})
            if req_scope is not None:
                tl.merge_phases({"device_get": req_scope.device_get_ms})
            tl.mark_ready()
    # per-shape cost attribution (ISSUE 15) for the general/SPMD path —
    # after took/phases are final, before render-only bookkeeping
    _note_controller_insights(query_spec, took_f, req_scope)
    if profiling:
        # per-shard per-phase breakdown: coordinator phases (parse,
        # can_match, reduce, fetch, render) are shared across shards,
        # `query` is the shard's own device work — so each shard's phase
        # sum stays ≤ the request total (and ≈ it for a single shard)
        total_ns = time.perf_counter_ns() - start_ns
        for entry in profile_shards:
            q_ns = entry.pop("_query_ns", 0)
            entry["searches"][0]["rewrite_time"] = phases.get("parse", 0)
            entry["phases"] = {
                "parse": phases.get("parse", 0),
                "can_match": phases.get("can_match", 0),
                "query": q_ns,
                "reduce": phases.get("reduce", 0),
                "fetch": phases.get("fetch", 0),
                "render": phases.get("render", 0),
            }
        resp["profile"] = {"shards": profile_shards,
                           "total_ns": total_ns,
                           "phases_ns": dict(phases)}
        if req_scope is not None:
            # request-level transfer totals: the per-shard transfers[]
            # above decompose these (telemetry/ledger.py)
            resp["profile"]["bytes_to_device"] = req_scope.h2d_bytes
            resp["profile"]["bytes_fetched"] = req_scope.d2h_bytes
            resp["profile"]["device_get_ms"] = round(
                req_scope.device_get_ms, 3)
    if page:
        last = page[-1]
        resp["_page_cursor"] = {
            "values": [last.score if f == "_score" else last.sort_values[i]
                       for i, (f, _) in enumerate(sort_specs)],
            "tiebreak": (last.shard_i, last.seg_i, last.ord),
        }
    if spmd_served_box[0] is not None:
        # the SPMD route's last two spans in the always-on ring: the
        # cross-row half of `spmd.reduce` (candidate order, page, fetch,
        # reduce_aggs over the rows' partials) and `respond` (the rest
        # of the response, to this return)
        ring = TELEMETRY.tracer.spans
        reduce_id = ring.child("spmd.reduce", spmd_served_box[0],
                               t_reduced)
        if reduce_id and t_aggs1:
            ring.child("spmd.reduce.reduce_aggs", t_aggs0, t_aggs1,
                       {"rows": len(decoded_partials)}, reduce_id)
        ring.child("respond", t_reduced, time.monotonic())
    return resp


_SCRIPT_SERVICE = None


def _default_script_service():
    """Inline-script service for fetch-phase script_fields (stored-script
    lookup goes through the node's service at the REST layer)."""
    global _SCRIPT_SERVICE
    if _SCRIPT_SERVICE is None:
        from opensearch_tpu.script.service import ScriptService
        _SCRIPT_SERVICE = ScriptService()
    return _SCRIPT_SERVICE


def _build_hit(ex, c, body, score, query_node, sort_specs,
               score_sorted, inner_specs=None, inner_cache=None) -> dict:
    from opensearch_tpu.search import fetch as fetch_phase

    hit = ex._hit_dict(c.seg_i, c.ord, score, body)
    if not score_sorted or body.get("search_after") is not None:
        hit["sort"] = c.sort_values
    seg = ex.reader.segments[c.seg_i]
    mapper = ex.reader.mapper
    if body.get("highlight"):
        field_terms = fetch_phase.collect_field_terms(query_node, mapper)
        hl = fetch_phase.build_highlights(hit.get("_source"),
                                          body["highlight"], field_terms,
                                          mapper)
        if hl:
            hit["highlight"] = hl
    if body.get("explain"):
        hit["_explanation"] = fetch_phase.explain_hit(
            seg, c.ord, query_node, mapper, ex.reader.stats(),
            score if score is not None else c.score)
    if body.get("docvalue_fields"):
        fields = fetch_phase.docvalue_fields(
            seg, c.ord, body["docvalue_fields"], mapper,
            prefetched=getattr(c, "dv_page", None))
        if fields:
            hit["fields"] = fields
    if body.get("script_fields"):
        from opensearch_tpu.script.painless import collect_doc_fields
        from opensearch_tpu.script.service import doc_view
        svc = _default_script_service()
        for name, spec in body["script_fields"].items():
            fs = svc.compile((spec or {}).get("script"), "field")
            dv = doc_view(seg, c.ord, collect_doc_fields(fs.stmts) or None)
            value = fs.execute(dv, seg.sources[c.ord])
            hit.setdefault("fields", {})[name] = \
                value if isinstance(value, list) else [value]
    if body.get("version"):
        # doc_meta carries the persisted (version, seq_no, primary_term)
        meta = getattr(seg, "doc_meta", {}).get(hit["_id"])
        hit["_version"] = meta[0] if meta else 1
    nested_specs = inner_specs if inner_specs is not None \
        else fetch_phase.collect_inner_hit_specs(query_node)
    if nested_specs:
        # request-scoped eval cache: never shared across requests (stats
        # and segments may move between them)
        cache = inner_cache if inner_cache is not None else {}
        hit["inner_hits"] = fetch_phase.build_inner_hits(
            ex, c.seg_i, c.ord, nested_specs, cache)
    return hit
