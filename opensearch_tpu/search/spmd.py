"""SPMD serving integration: route the multi-shard query phase through the
shard_map + ICI-collective program.

Round-2/3 verdicts flagged that `DistributedSearcher` (the all_gather+psum
merge that IS the TPU-native scatter-gather story) was never on the serving
path — `execute_search` looped executors/segments on host. This module makes
the SPMD program the default executor for multi-row searches:

  - every (shard, segment) pair becomes one row on a 1-D device mesh
    (scatter-gather DP and intra-shard segment parallelism collapse into
    one mesh axis — SURVEY §2.2 rows 2 and 6);
  - segments live in an `HbmShardSet` cached across queries (rebuilt only
    when the segment list / live masks change, i.e. at refresh), so a
    query ships only its flat plan inputs — the Lucene-page-cache-warm
    discipline, pinned in HBM;
  - the per-shard top-k merge and total-hit count happen on-chip via
    `all_gather`/`psum` over ICI (reference contrast:
    action/search/AbstractSearchAsyncAction.java:264 does this as a
    coordinator RPC round per shard).

More rows than devices PACK: ceil(rows/devices) rows per device with an
inner vmap and an intra-device merge before the ICI gather, so a
16-segment index serves through an 8-chip mesh. Single-key numeric field
sorts ride the collective merge too (decoded f32 value keys; the host
re-keys the k winners with exact values). Falls back to the host loop
when the request shape doesn't fit (fewer rows than 2, more rows than
devices × SPMD_MAX_PACK, non-uniform plan structure across rows, keyword
or multi-key sorts).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax

from opensearch_tpu.ops.topk import NEG_INF
from opensearch_tpu.search import dsl
from opensearch_tpu.search.aggs.engine import compile_aggs
from opensearch_tpu.search.aggs.parse import PIPELINE_TYPES, parse_aggs
from opensearch_tpu.search.aggs.reduce import decode_outputs
from opensearch_tpu.search.compile import Compiler
from opensearch_tpu.telemetry import TELEMETRY

# serving-path counters, asserted by tests:
# queries answered by the SPMD program / HbmShardSet rebuilds.
# Registry-owned metrics Counters (visible in `_nodes/stats` under
# telemetry.counters, GIL-atomic inc) — replaced the module-level
# mutable-list counters shared-state-lint flags, the first fix the
# item-2 async-scheduler thread-safety audit demanded.
SPMD_QUERIES = TELEMETRY.metrics.counter("search.spmd_queries")
SPMD_UPLOADS = TELEMETRY.metrics.counter("search.spmd_uploads")
# requests that `eligible` admitted and that ended in the per-shard host
# loop on one chip all the same, and why (`search.spmd_fallbacks.<reason>`
# beside the total): `plan_structure` (the rows' compiled plans differ),
# `agg_align` (their aggregation plans cannot be brought to one
# structure), `sort` (a sort the merge cannot key), `searcher` (the
# distributed searcher refused the rows: mismatched field layouts), and
# `error` (anything the program raised, counted where the controller
# catches it). `force_host_loop` is no fallback: nothing was admitted.
SPMD_FALLBACKS = TELEMETRY.metrics.counter("search.spmd_fallbacks")
FALLBACK_REASONS = ("plan_structure", "agg_align", "sort", "searcher",
                    "error")
_FALLBACKS_BY_REASON = {
    reason: TELEMETRY.metrics.counter(f"search.spmd_fallbacks.{reason}")
    for reason in FALLBACK_REASONS}
_SPANS = TELEMETRY.tracer.spans


def note_fallback(reason: str) -> None:
    """One admitted request took the host loop; returns None so that a
    caller can `return note_fallback(...)`."""
    SPMD_FALLBACKS.inc()
    _FALLBACKS_BY_REASON[reason].inc()

# guards the searcher/residency caches below: queries mutate them at
# miss/evict/LRU-touch time, and the item-2 wave scheduler will run
# those paths from concurrent request threads
_SPMD_LOCK = threading.Lock()
_SEARCHERS: Dict[int, Any] = {}       # mesh size -> DistributedSearcher
_SHARD_SETS: Dict[Any, Any] = {}      # residency cache (bounded)
_MAX_SHARD_SETS = 4
# rows pack up to this many per device before falling back to the host
# loop (an HBM-sizing heuristic: the stacked image grows linearly)
SPMD_MAX_PACK = 8


def _searcher(n_rows: int):
    from opensearch_tpu.parallel.distributed import (DistributedSearcher,
                                                     make_mesh)
    n = min(n_rows, len(jax.devices()))
    with _SPMD_LOCK:
        s = _SEARCHERS.get(n)
        if s is None:
            s = DistributedSearcher(make_mesh(n))
            _SEARCHERS[n] = s
    return s


def spmd_rows(executors: List) -> List[Tuple[int, int]]:
    """(executor index, segment index) pairs with live documents."""
    rows = []
    for shard_i, ex in enumerate(executors):
        for seg_i, seg in enumerate(ex.reader.segments):
            if seg.num_docs > 0:
                rows.append((shard_i, seg_i))
    return rows


def _f32_sortable(col) -> bool:
    """Admission predicate for value-keyed device merges — shared with
    the result-page cross-segment merge (ops/topk.py f32_sortable): the
    merge keys sort by decoded f32 values, so a column is admitted only
    when every unique value is EXACTLY f32-representable and within the
    sentinel range. Epoch-millis dates usually fail (f32 spacing ~131 s
    at 2e12) and take the host path."""
    from opensearch_tpu.ops.topk import f32_sortable
    return f32_sortable(col)


def _spmd_sort_spec(executors: List, sort_specs):
    """None for score sort; (field, order) for a supported single-key
    numeric field sort; False when the sort needs the host path."""
    specs = list(sort_specs)
    if specs == [("_score", "desc")]:
        return None
    if len(specs) != 1:
        return False
    field, order = specs[0]
    if field == "_score":
        return False
    ft = executors[0].reader.mapper.get_field(field)
    if ft is None or not (ft.is_numeric or ft.is_date or ft.is_bool):
        return False        # keyword ords aren't comparable across rows
    for ex in executors:
        for seg in ex.reader.segments:
            col = seg.numeric_dv.get(field)
            if col is not None and not _f32_sortable(col):
                return False
    return (field, order)


class force_host_loop:
    """Context manager pinning searches to the host per-segment loop
    (tests of host-loop-only behaviors: can-match skip reporting, filter
    cache splicing; and ground-truth parity comparisons)."""

    def __enter__(self):
        global eligible
        self._orig = eligible
        globals()["eligible"] = lambda *a, **k: False
        return self

    def __exit__(self, *exc):
        globals()["eligible"] = self._orig
        return False


def merge_hybrid_bounds(per_shard_bounds: List[List[Tuple[float, float,
                                                          float, int]]],
                        n_sub: int) -> List[Tuple[float, float, float,
                                                  int]]:
    """Reduce per-shard per-sub-query hybrid score bounds to GLOBAL
    bounds: min-of-mins / max-of-maxs / sum-of-sum-of-squares / count —
    the pmin/pmax/psum shape of the SPMD collective merge, applied to the
    bounds each shard's fused hybrid program computed on device. The
    normalization-processor (searchpipeline/hybrid.py) normalizes with
    these global statistics at reduce, per reference semantics (the
    neural-search processor normalizes over the union of all shards'
    TopDocs)."""
    out = []
    for i in range(n_sub):
        mn, mx, ssq, count = float("inf"), float("-inf"), 0.0, 0
        for bounds in per_shard_bounds:
            b_mn, b_mx, b_ssq, b_count = bounds[i]
            if b_count:
                mn = min(mn, b_mn)
                mx = max(mx, b_mx)
                ssq += b_ssq
                count += b_count
        out.append((mn, mx, ssq, count))
    return out


def eligible(executors: List, body: dict, rows: List[Tuple[int, int]],
             sort_specs) -> bool:
    if isinstance(body.get("query"), dict) and "hybrid" in body["query"]:
        # hybrid executes through its own fused per-shard program with
        # per-sub-query score channels + bounds; the generic SPMD merge
        # carries a single score channel and would collapse them
        return False
    if len(rows) < 2 \
            or len(rows) > len(jax.devices()) * SPMD_MAX_PACK:
        return False
    if _spmd_sort_spec(executors, sort_specs) is False:
        return False        # keyword/multi-key sort: host sort-key path
    if body.get("search_type") == "dfs_query_then_fetch":
        return False        # DFS pins per-shard StaticStats (host loop)
    if body.get("slice") is not None:
        return False        # sliced scroll injects a host-side mask plan
    if body.get("collapse") or body.get("rescore"):
        # both operate on the candidate pool AFTER the query phase and
        # need the host loop's per-shard k+128 over-fetch; the SPMD merge
        # returns exactly k candidates, which under-fills collapsed pages
        # and clips the rescore window
        return False
    return True


def spmd_query_phase(executors: List, body: dict, k: int,
                     extra_filters: Optional[List[Optional[dict]]],
                     rows: List[Tuple[int, int]]):
    """Distributed query phase over all (shard, segment) rows.

    Returns (candidates, decoded_partials, total, pruned_bytes) — the
    first three shaped exactly like the host loop in
    controller.execute_search, pruned_bytes > 0 flagging that block-max
    pruning fired (total is then a lower bound) — or None when the
    compiled plans are not structure-uniform across rows (the program
    requires one signature; e.g. a per-segment `precomputed` host
    fallback)."""
    from opensearch_tpu.indices.request_cache import (
        REQUEST_CACHE, admits, cache_key)
    from opensearch_tpu.search.executor import _Candidate

    if TELEMETRY.ledger.devices.enabled:
        # drop any stale thread-local device scope from an earlier
        # query: a request-cache hit below executes nothing, and the
        # Profile API must not inherit another query's breakdown
        TELEMETRY.ledger.devices.take_last()

    key = None
    if admits(body, all(ex.request_cache_enabled for ex in executors)):
        all_segs = [executors[s].reader.segments[g] for s, g in rows]
        # "spmd"-tagged so it can never collide with the per-shard
        # executor cache entries (same segments/body/k, different shape)
        base = cache_key(all_segs, body, k,
                         {"filters": extra_filters} if extra_filters
                         else None)
        key = ("spmd", base) if base is not None else None
        if key is not None:
            cached = REQUEST_CACHE.get(key)
            if cached is not REQUEST_CACHE._MISS:
                cts, decoded, total, pruned = cached
                return ([_Candidate(s, g, o, sv, shard_i=si)
                         for s, g, o, sv, si in cts], decoded, total,
                        pruned)
    out = _spmd_query_phase_raw(executors, body, k, extra_filters, rows)
    if out is None:
        return None     # host-loop fallback — never cached
    SPMD_QUERIES.inc()
    if key is not None:
        REQUEST_CACHE.put(key, out)
    cts, decoded, total, pruned = out
    return ([_Candidate(s, g, o, sv, shard_i=si)
             for s, g, o, sv, si in cts], decoded, total, pruned)


def _spmd_query_phase_raw(executors: List, body: dict, k: int,
                          extra_filters, rows):
    from opensearch_tpu.parallel.distributed import (plan_struct,
                                                     resident_lane_bins)

    t_plan = time.monotonic()
    node = dsl.parse_query(body.get("query"))
    min_score = float(body["min_score"]) \
        if body.get("min_score") is not None else float(NEG_INF)
    agg_nodes = parse_aggs(body.get("aggs") or body.get("aggregations"))
    device_agg_nodes = [n for n in agg_nodes if n.type not in PIPELINE_TYPES]

    # one plan (+ agg plans) per row; all rows must share one structure
    all_stats = [ex.reader.stats() for ex in executors]
    plans, agg_plans_rows, flat_rows = [], [], []
    row_metas = []      # per-row meta captured HERE, the one read of
    # reader.device this query makes — the scan accounting below must
    # not re-read the live reader after the program ran (a concurrent
    # refresh/merge republish would mispair seg_i, or shrink the list
    # out from under the index — the PR 13 pairing hazard)
    for shard_i, seg_i in rows:
        ex = executors[shard_i]
        seg = ex.reader.segments[seg_i]
        arrays, meta = ex.reader.device[seg_i]
        row_metas.append(meta)
        compiler = Compiler(ex.reader.mapper, all_stats[shard_i])
        q = node
        extra = extra_filters[shard_i] if extra_filters else None
        if extra is not None:
            q = dsl.BoolQuery(must=[node],
                              filter=[dsl.parse_query(extra)])
        plan = compiler.compile(q, seg, meta)
        # allow_fused=False: the SPMD program is traced ONCE from row 0's
        # plans and mapped over all rows — the fused kinds close over
        # segment-specific constant bitmasks that would wrongly apply row
        # 0's tables everywhere, so SPMD keeps the envelope table path
        aps = tuple(compile_aggs(device_agg_nodes, ex.reader.mapper, seg,
                                 meta, compiler, allow_fused=False)) \
            if agg_nodes else ()
        plans.append(plan)
        agg_plans_rows.append(aps)
    # the reads `spmd.plan`'s children are made of (`_note_device_spans`)
    t_rows = time.monotonic()

    if agg_nodes:
        from opensearch_tpu.parallel.distributed import align_agg_plans
        try:
            # one program traces one agg structure: raise per-row ordinal
            # cardinalities to the cross-row max BEFORE the struct check
            # (per-row dictionary sizes land in plan statics); decode
            # stays row-local afterwards
            align_agg_plans([list(aps) for aps in agg_plans_rows])
        except ValueError:
            return note_fallback("agg_align")
    struct0 = (plan_struct(plans[0]),
               tuple(plan_struct(a) for a in agg_plans_rows[0]))
    for p, aps in zip(plans[1:], agg_plans_rows[1:]):
        if (plan_struct(p), tuple(plan_struct(a) for a in aps)) != struct0:
            return note_fallback("plan_structure")

    from opensearch_tpu.search.executor import _parse_sort, _sort_value
    sort_specs = _parse_sort(body.get("sort"))
    sort_spec = _spmd_sort_spec(executors, sort_specs)
    if sort_spec is False:
        return note_fallback("sort")
    t_aligned = time.monotonic()

    # sharded-serving observability (ISSUE 14): the per-device phase
    # capture rides two gates — the device ledger (node-wide per-chip
    # aggregates + straggler skew) and the SPMD timeline (fanout/
    # partial/merge events on the request's lifecycle timeline). Either
    # being open allocates ONE DeviceScope; both closed costs two
    # attribute loads and branches.
    devledger = TELEMETRY.ledger.devices
    devscope = devledger.scope()
    tl = None
    if TELEMETRY.spmd_timeline.gate() is not None:
        tl = TELEMETRY.flight.current()
    cap = devscope
    if cap is None and tl is not None:
        from opensearch_tpu.telemetry import DeviceScope
        cap = DeviceScope()

    searcher = _searcher(len(rows))
    if tl is not None:
        tl.event("fanout", devices=searcher.n_shards, rows=len(rows))
    marks: dict = {}
    try:
        shard_set = _resident_shard_set(searcher, executors, rows)
        t_resident = time.monotonic()
        # the static side of every `bucket_num` level leaves the request
        # before its inputs are flattened: the plans name the shard
        # set's resident lane -> bin vectors, and carry no table
        lane_bins = resident_lane_bins(searcher, shard_set,
                                       agg_plans_rows)
        flat_rows = []
        for plan, aps in zip(plans, agg_plans_rows):
            flat = plan.flatten_inputs([])
            for ap in aps:
                ap.flatten_inputs(flat)
            flat_rows.append(flat)
        keys, scores, row_idx, ords, total, agg_outs, pruned_rows = \
            searcher.search_resident(
                shard_set, flat_rows, plans[0], k, min_score=min_score,
                agg_plans=agg_plans_rows[0], sort_spec=sort_spec,
                device_scope=cap, return_pruned=True, marks=marks,
                lane_bins=lane_bins)
    except (ValueError, KeyError):
        # e.g. a cross-index search whose rows have mismatched field
        # layouts (canonical_meta rejects them) — host loop handles it
        return note_fallback("searcher")
    wave = _note_device_spans(
        (t_plan, t_rows, t_aligned, t_resident), len(rows), marks)

    # always-on scan accounting (telemetry/scan.py): every row of the
    # SPMD program gathers its plan's posting blocks and evaluates the
    # dense per-doc vector — telemetry/scan.py's byte model,
    # attributed per (index, shard, segment) and summed per query
    from opensearch_tpu.telemetry.scan import (
        DENSE_LANE_BYTES, POSTING_BLOCK_BYTES, SCAN, plan_scan_blocks)
    from opensearch_tpu.parallel.distributed import spmd_blockmax_admitted
    q_posting = q_dense = q_pruned = 0
    pruned_by_shard: dict = {}
    for r, (plan_r, meta_r, (shard_i, seg_i)) in enumerate(
            zip(plans, row_metas, rows)):
        ex = executors[shard_i]
        # heat-map shard key: the reader's REAL shard id, not the row's
        # position in the executors list — the two diverge the moment a
        # caller passes a sub-list (e.g. routing or a skipped shard),
        # which used to fold shard 3's bytes into the "0" row
        shard_key = str(getattr(ex.reader, "shard_id", shard_i))
        posting = plan_scan_blocks(plan_r) * POSTING_BLOCK_BYTES
        dense = meta_r.d_pad * DENSE_LANE_BYTES
        SCAN.note_segment(ex.reader.index_name, shard_key,
                          meta_r.seg_id, posting, dense, "spmd")
        q_posting += posting
        q_dense += dense
        # block-max pruning overlay (ISSUE 20): phase-A popcounts ride
        # the result page as one sharded int32 per row — no extra round
        # trip; the static accounting above stays the untouched ceiling
        row_pruned = int(pruned_rows[r]) * POSTING_BLOCK_BYTES
        if row_pruned:
            grp = pruned_by_shard.setdefault(
                (ex.reader.index_name, shard_key), {})
            grp[meta_r.seg_id] = grp.get(meta_r.seg_id, 0) + row_pruned
            q_pruned += row_pruned
    SCAN.note_query(q_posting, q_dense)
    if q_pruned or spmd_blockmax_admitted(plans[0], shard_set.meta, k,
                                          sort_spec, agg_plans_rows[0]):
        # the fused program is ONE query: a single per_query entry (on
        # the first shard call only) feeds the effective distribution —
        # zero-pruned admitted queries included, so pruned/unpruned
        # p50s compare like for like; shard/segment attribution lands
        # per group
        per_q = [(q_posting, q_pruned)]
        if pruned_by_shard:
            for (idx_name, shard_key), seg_pruned \
                    in pruned_by_shard.items():
                SCAN.note_pruned_batch(idx_name, shard_key, seg_pruned,
                                       per_q)
                per_q = []
        else:
            ex0 = executors[rows[0][0]]
            SCAN.note_pruned_batch(
                ex0.reader.index_name,
                str(getattr(ex0.reader, "shard_id", rows[0][0])),
                {}, per_q)
    from opensearch_tpu.telemetry import TELEMETRY as _TEL
    _ins = _TEL.insights.gate()
    if _ins is not None:
        # the per-request scan join (ISSUE 15): same bytes as the heat
        # map, thread-local, read back by the controller's shape note
        _ins.add_scan(q_posting, q_dense, q_pruned)

    if cap is not None:
        if tl is not None:
            for dev, wall in cap.partials:
                tl.event("partial", device=dev, ms=round(wall, 3))
            tl.event("merge", skew_ms=round(cap.skew_ms(), 3),
                     straggler=cap.straggler(),
                     ici_bytes=cap.merge_ici_bytes,
                     pull_ms=round(cap.pull_ms, 3))
        if devscope is not None:
            devledger.note_query(devscope)

    t_noted = time.monotonic()
    cand_tuples = []
    for score, row_i, ord_ in zip(scores, row_idx, ords):
        shard_i, seg_i = rows[int(row_i)]
        if sort_spec is None:
            sort_values = [float(score)]
        else:
            # exact host re-key: the device merged on decoded f32 values;
            # the final cross-candidate order uses exact column values
            seg = executors[shard_i].reader.segments[seg_i]
            sort_values = [float(score) if f == "_score"
                           else _sort_value(seg, f, o, int(ord_))
                           for f, o in sort_specs]
        cand_tuples.append((float(score), seg_i, int(ord_),
                            sort_values, shard_i))

    t_cands = time.monotonic()
    decoded = []
    if agg_nodes:
        for r, (shard_i, seg_i) in enumerate(rows):
            row_outs = jax.tree_util.tree_map(lambda o: o[r], agg_outs)
            decoded.append(decode_outputs(list(agg_plans_rows[r]),
                                          row_outs))
    # everything since the result page arrived: scan accounting, the
    # candidates, each row's partials decoded with its own plans; and
    # those three below it (the scan, insights and device-ledger notes
    # are `scan_note`)
    t_pulled, t_end = marks["device_wait"][1], time.monotonic()
    reduce_id = _SPANS.child("spmd.reduce", t_pulled, t_end,
                             {"wave": wave})
    if reduce_id:
        _SPANS.child("spmd.reduce.scan_note", t_pulled, t_noted, None,
                     reduce_id)
        _SPANS.child("spmd.reduce.candidates", t_noted, t_cands, None,
                     reduce_id)
        if agg_nodes:
            _SPANS.child("spmd.reduce.decode_aggs", t_cands, t_end,
                         {"rows": len(rows)}, reduce_id)
    # q_pruned > 0 makes `total` a lower bound (pruned blocks' docs were
    # never counted): the caller renders hits.total.relation = "gte",
    # the same contract Lucene's BMW path keeps via track_total_hits
    return cand_tuples, decoded, int(total), q_pruned


def _note_device_spans(planned: tuple, n_rows: int, marks: dict) -> int:
    """The SPMD route's spans in the always-on ring, under the span open
    on this thread (`rest.search`), from the clock reads
    `search_resident` made: `spmd.plan` (parse, per-row compile, align,
    structure check, flatten, stack; up to the first literal upload),
    `dispatch` (first literal upload to the jit call's return, with
    what the envelope's `dispatch` carries: wave, programs, nbytes,
    family, fingerprint, shape) and `device_wait` (the blocking pull of
    the result page). Returns the wave: how many times this request
    dispatched before (a k-growth retry runs the phase again).

    Below `spmd.plan`, from `planned` (the plan's start, its rows
    compiled, their structure checked, the shard set resident) and
    `marks["stacked"]`: `spmd.plan.compile_rows` (parse and the per-row
    compile), `spmd.plan.align` (`align_agg_plans`, the structure
    check, the sort key), `spmd.plan.stack` (the lane -> bin lookup,
    flatten, `pad_stack_trees`). Its self time is what lies between:
    the shard set looked up (built, on an index's first request:
    `install.shard_set` on the process track) and the wait for the
    dispatch lock."""
    from opensearch_tpu.search.executor import (_dispatch_attrs,
                                                _wait_attrs, _wave_attrs)
    trace = _SPANS.current()
    if trace is None:
        return 0
    wave = sum(1 for s in trace.spans
               if s[2] == "dispatch" and s[1] == trace.top)
    t0, t1, nbytes, info = marks["dispatch"]
    t_plan, t_rows, t_aligned, t_resident = planned
    plan_id = _SPANS.child("spmd.plan", t_plan, t0,
                           (_wave_attrs, wave, None))
    if plan_id:
        _SPANS.child("spmd.plan.compile_rows", t_plan, t_rows,
                     {"rows": n_rows}, plan_id)
        _SPANS.child("spmd.plan.align", t_rows, t_aligned, None, plan_id)
        _SPANS.child("spmd.plan.stack", t_resident, marks["stacked"],
                     None, plan_id)
    _SPANS.child("dispatch", t0, t1,
                 (_dispatch_attrs, wave, None, 1, nbytes,
                  [info] if info is not None else []))
    t0, t1, nbytes = marks["device_wait"]
    _SPANS.child("device_wait", t0, t1, (_wait_attrs, wave, None, nbytes, 0))
    return wave


def _resident_shard_set(searcher, executors, rows):
    """HbmShardSet cached across queries; identity = the (segment uid,
    live doc count) of every row — uid is process-unique, so same-named
    segments of different indices/engines can't collide — and a refresh
    (new segment list) or delete (live mask change) triggers exactly one
    re-upload: residency is maintained at refresh time, not per query."""
    key = (id(searcher),
           tuple((executors[s].reader.segments[g].uid,
                  executors[s].reader.segments[g].live_doc_count)
                 for s, g in rows))
    with _SPMD_LOCK:
        cached = _SHARD_SETS.get(key)
        if cached is not None:
            # LRU touch: FIFO eviction would evict the set most likely
            # to be reused when >_MAX_SHARD_SETS indices are queried
            # round-robin
            _SHARD_SETS.pop(key)
            _SHARD_SETS[key] = cached
            return cached
    from opensearch_tpu.ops.device_segment import upload_segment
    # build the stacked image from HOST arrays (to_device=False): stacking
    # the readers' per-device images would first FETCH every column back
    # from the device — a full index download per rebuild. Built OUTSIDE
    # the lock: a racing builder costs one duplicate upload (last insert
    # wins), never a convoy of queries behind a segment upload.
    t_build = time.monotonic()
    arrays, metas = [], []
    for s, g in rows:
        a, m = upload_segment(executors[s].reader.segments[g],
                              to_device=False)
        # adopt the reader's live mask state (deletes since seal)
        arrays.append(a)
        metas.append(m)
    # `install.shard_set` of the span ring's process track, from
    # `t_build` on: the rows' host images are part of the install
    shard_set = searcher.build_shard_set(arrays, metas, started=t_build)
    SPMD_UPLOADS.inc()
    evicted = None
    with _SPMD_LOCK:
        # a racing builder may have inserted this key already (the
        # documented build-outside-the-lock race): replacing it must
        # release ITS gauge too, and must not evict an unrelated entry
        evicted = _SHARD_SETS.pop(key, None)
        if evicted is None and len(_SHARD_SETS) >= _MAX_SHARD_SETS:
            evicted = _SHARD_SETS.pop(next(iter(_SHARD_SETS)))
        _SHARD_SETS[key] = shard_set
    if evicted is not None:
        # the residency cache owns the shard set's device-memory gauge
        # (HbmShardSet registers at build): release at eviction so the
        # spmd_shard_sets class tracks LIVE HBM, not history
        evicted.release()
    return shard_set
