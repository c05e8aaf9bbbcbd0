"""Aggregation engine: builder tree → device collection program → partials.

The TPU re-design of the reference's Aggregator/LeafBucketCollector machinery
(search/aggregations/Aggregator.java:60, BucketsAggregator.java:70
collectBucket, 491 files of per-doc collector loops): instead of walking docs
one at a time, every bucket aggregation becomes

    bucket_of_rank (host lookup table over the field's sorted unique values)
    → a segment-STATIC per-lane bin assignment (factored bucket context)
    → a masked binned reduction into flat [parent_card * own_card] bins

Bucket membership is FACTORED (see eval_aggs): the bin a (doc, value) lane
lands in is segment-static for field-driven bucketing, while every
query-dependent condition lives in a dynamic mask. That factorization picks
the reduction kernel (_binned_sums): bit-packed popcount for counts,
one-hot matmul (MXU) for float sums — both of which share their static side
across a whole vmapped _msearch query batch — and scatter-add only for
data-dependent bins (nested joins, dedup). Metric aggregations collect only
the partials their render needs (_METRIC_NEEDS: avg = sum+cnt, not the full
five-reduction battery). Nesting uses the classic flattened-ordinal trick
(parent_ord * child_card + child_ord), like the reference's bucketOrd
composition.

Where a numeric level's bins come from (kind `bucket_num`, `static[3]`;
`_bucket_lookup_plan`). The lane -> bin vector `table[val_ords]` rests on
nothing of the request, so no route computes it a request:
- a table that is the identity (`terms` on a numeric column) is never
  gathered through: the rank column is the bin (BINS_RANK);
- the vector of any other `histogram`/`date_histogram` level is resident
  on the device beside the image it was derived from, once a (device
  image, field, bucketing), in that image's `LaneBinsMemo`
  (search/aggs/lane_bins.py): the shard set's on the SPMD route
  (parallel/distributed.py `resident_lane_bins`), the segment's on the
  one-chip routes (host loop, agg envelope: search/executor.py
  `ShardReader.with_lane_bins`). The plan names its slot, carries no
  table, and builds one on a miss alone (`AggPlan.table_of`);
- a `range` bucket keeps its table among the request's inputs on every
  route and gathers a request (BINS_TABLE): its bounds may move with
  every request;
- a root leaf within the popcount budget on one chip is none of these:
  its lane bitmasks are precomputed and closed over (`bucket_bits`).

Approximation policy: the reference uses TDigest percentiles and HLL++
cardinality; here both are EXACT, computed from per-bucket value-rank
histograms / presence bitmaps (feasible because doc values are rank-encoded
per segment), merged on the host by value.

The compiled structure is static per (agg tree, segment); partial arrays are
merged across segments/shards host-side by bucket key (reference analog:
InternalAggregation.reduce, search/aggregations/InternalAggregation.java:64).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field as dc_field, replace as dc_replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from opensearch_tpu.common.errors import (
    IllegalArgumentError, ParsingError, QueryShardError)
from opensearch_tpu.index.mapper import MapperService, format_date_millis, parse_date_millis
from opensearch_tpu.index.segment import Segment, ident_pairs, pad_bucket
from opensearch_tpu.ops import F32_MATMUL
from opensearch_tpu.search import dsl
from opensearch_tpu.search.aggs.parse import AggNode
from opensearch_tpu.search.compile import Compiler, Plan, _resolve_date_math
from opensearch_tpu.search.plan_eval import _eval_plan
from opensearch_tpu.telemetry import TELEMETRY

MAX_AGG_BINS = 1 << 24  # guard for presence/histogram bitmaps
POS_INF = np.float32(np.inf)
NEG_INF = np.float32(-np.inf)

# Binned ADD-reductions with at most this many bins run as one-hot matmuls
# instead of scatter-adds: bin assignments are segment-static (so the
# one-hot matrix stays unbatched under a query-batch vmap and the MXU does
# the reduction), where XLA's scatter lowers to a serial loop on CPU and a
# slow path on TPU. f32 accumulation is exact for counts < 2^24.
AGG_GEMM_MAX_BINS = 256
# ...and at most this many one-hot ELEMENTS (lanes × bins): the GEMM's
# [n, bins] f32 operand is materialized, so an unbounded n would turn the
# old O(n) scatter memory into gigabytes on big segments. 2^25 f32 =
# 128 MB. The popcount path's bitmask is 32× smaller per element.
AGG_GEMM_MAX_ELEMS = 1 << 25
AGG_POPCOUNT_MAX_ELEMS = 1 << 30
# A float scatter-add accumulates a bin's addends one after another in
# float32: over the 10^4-10^5 addends of a bucket of a large segment its
# sum drifts by 1e-5 and more from the float64 sum upstream computes
# (sequential rounding grows with the count). So a float bin is summed
# in up to this many interleaved partial accumulators (lane i feeds
# accumulator i mod ways), which are then added pairwise: each partial
# sees 1/ways of the addends, their rounding errors are independent,
# and the tree adds log2(ways) roundings. Held to 1e-6 of float64 by
# the http_logs benchmark cell and tests/test_agg_sum_precision.py.
AGG_SUM_WAYS = 64
# ...as long as bins x ways stays under this many accumulators
AGG_SUM_MAX_ACCUMULATORS = 1 << 22
# A partial's drift grows with what it adds up, and on real columns it
# is no random walk: amounts on a lattice (fares in half dollars, prices
# in cents) round the same way add after add once the partial's ulp
# passes the lattice's step. 64 ways over a bin of 10^7 such addends
# read 1e-5 of float64, 256 read 1.3e-6, 1,024 6e-7, 4,096 under 1.3e-7
# (the nyc_taxis cell's 1-2 mile bucket; the CPU backend's order and the
# chip's agree). So the ways grow with the lanes ONE BIN CAN HOLD, which
# is segment-static (`_bin_room`): doubled from the above until no
# partial can be fed more than this many lanes...
AGG_SUM_LANES_A_PARTIAL = 1 << 12
# ...while every query of the batch together stays under this many
# accumulators (4,096 ways x 10,000 bins is 164 MB of float32)
AGG_SUM_MAX_GROWN = 1 << 26
# A bin's room is kept as a power of two, and never under what the base
# ways already cover: rows of one index then agree on it (one SPMD
# structure) unless a bin of one of them holds over 262,144 lanes
AGG_BIN_ROOM_MIN = AGG_SUM_WAYS * AGG_SUM_LANES_A_PARTIAL

# Input arrays that are segment/node-static by construction (host-computed
# lookup tables): their CONTENT is part of the plan signature, so a batched
# runner may legally pass one copy for a whole same-signature group
# (executor passes them with in_axes=None). Everything else is per-query.
CONST_INPUT_KEYS = frozenset({"table", "doc_bucket"})

# calendar interval lengths used for fixed bucketing (calendar-aware month/
# year boundaries are generated host-side as explicit boundary arrays)
_FIXED_MS = {"ms": 1, "1ms": 1, "s": 1000, "1s": 1000, "second": 1000,
             "m": 60000, "1m": 60000, "minute": 60000,
             "h": 3600000, "1h": 3600000, "hour": 3600000,
             "d": 86400000, "1d": 86400000, "day": 86400000,
             "w": 604800000, "1w": 604800000, "week": 604800000}


@dataclass
class AggPlan:
    """Compiled aggregation node for one segment."""
    name: str
    kind: str
    static: tuple = ()
    inputs: Dict[str, np.ndarray] = dc_field(default_factory=dict)
    children: List["AggPlan"] = dc_field(default_factory=list)
    query_plan: Optional[Plan] = None      # filter aggs
    query_plans: List[Plan] = dc_field(default_factory=list)  # adjacency
    render: Dict[str, Any] = dc_field(default_factory=dict)  # host-only
    # a `histogram`/`date_histogram` level of kind bucket_num: the scalars
    # that, with the field and the segment's sorted unique values, define
    # its rank -> bucket table (interval and shift, or the calendar unit),
    # and the function that builds the table, padded, when asked. What a
    # level's resident lane -> bin vector is keyed by and derived from: a
    # hit neither hashes nor builds a table
    bins_key: Optional[tuple] = None
    table_of: Optional[Callable[[], np.ndarray]] = None
    # segment-static arrays CLOSED OVER by the device program instead of
    # riding the input envelope (fused bucket_bits/presence_bits kinds):
    # zero per-batch pack/upload bytes, zero in-program recompute. Content
    # is hashed into sig() so two plans share an executable only when the
    # embedded constants are identical.
    const_inputs: Dict[str, np.ndarray] = dc_field(default_factory=dict)

    def sig(self):
        cached = getattr(self, "_sig", None)
        if cached is not None:
            return cached
        import hashlib

        def leaf_sig(k, v):
            if k in CONST_INPUT_KEYS:
                # content hash: two queries share an executable (and the
                # executable may close over / share ONE copy of the array)
                # only when the table itself is identical
                return (k, v.shape, str(v.dtype),
                        hashlib.sha1(np.ascontiguousarray(v).tobytes())
                        .hexdigest())
            return (k, v.shape, str(v.dtype))

        # a level that reads a resident vector takes the place of its
        # table's content with what the vector is keyed by: two queries
        # share a program RUN only where they name the same vectors
        out = (self.kind, self.static,
               self.bins_key if bins_slot(self) is not None else None,
               tuple(sorted(leaf_sig(k, v)
                            for k, v in self.inputs.items())),
               self.query_plan.sig() if self.query_plan is not None else None,
               tuple(q.sig() for q in self.query_plans),
               tuple(c.sig() for c in self.children),
               tuple(sorted(
                   (k, v.shape, str(v.dtype),
                    hashlib.sha1(np.ascontiguousarray(v).tobytes())
                    .hexdigest())
                   for k, v in self.const_inputs.items())))
        # plans are immutable post-compile and now shared across queries
        # via the reader memo — hash the const tables once
        object.__setattr__(self, "_sig", out)
        return out

    def flatten_inputs(self, out):
        out.append(self.inputs)
        if self.query_plan is not None:
            self.query_plan.flatten_inputs(out)
        for q in self.query_plans:
            q.flatten_inputs(out)
        for c in self.children:
            c.flatten_inputs(out)
        return out


@dataclass(frozen=True)
class _Ctx:
    mapper: MapperService
    seg: Segment
    meta: Any
    compiler: Compiler
    d_pad: int
    # True only while compiling a TOP-LEVEL agg node: root nodes see the
    # sentinel parent context (pbin=None, parent_card=1) at eval time, the
    # precondition for the fused bucket_bits/presence_bits kinds
    root: bool = False
    # False for cross-row tracing paths (SPMD): fused kinds embed
    # segment-specific constants in the executable, which a single program
    # traced from row 0 would wrongly apply to every row. That route
    # also names the slots of its resident lane -> bin vectors itself,
    # once its rows agree on a structure (`compile_aggs`)
    fused: bool = True


def _register_const_bytes(plans: List[AggPlan], seg: Segment) -> None:
    """Account the fused kinds' embedded constant tables (bucket_bits /
    presence_bits bitmask words etc.) for the device-memory gauge: they
    are content-baked into the executable, so they occupy HBM for the
    executable's lifetime. The per-(sig, input) byte map lives ON the
    segment object and is summed by the executor's weak-ref reader
    provider — lifetime tracks liveness exactly (index delete, shard
    close, clone replacement all drop the object from the sum), with no
    release hook to forget."""
    table = getattr(seg, "_agg_const_bytes", None)
    if table is None:
        table = seg._agg_const_bytes = {}
    for p in plans:
        consts = getattr(p, "const_inputs", None) or {}
        for name, arr in consts.items():
            table[(p.sig(), name)] = int(getattr(arr, "nbytes", 0))
        if p.children:
            _register_const_bytes(p.children, seg)


def compile_aggs(nodes: List[AggNode], mapper: MapperService, seg: Segment,
                 meta, compiler: Compiler,
                 allow_fused: bool = True) -> List[AggPlan]:
    """The plans of one program over one segment. `allow_fused` says the
    program is this segment's own (the one-chip routes): root leaves may
    close over the segment's bitmasks, and the levels that read a
    resident lane -> bin vector name their slots here, in the order
    `resident_levels` walks them. A cross-row compile (the SPMD route)
    names them once its rows agree (parallel/distributed.py
    `resident_lane_bins`)."""
    ctx = _Ctx(mapper, seg, meta, compiler, pad_bucket(max(seg.num_docs, 1)),
               fused=allow_fused)
    plans = [_compile_node(n, ctx, root=True) for n in nodes]
    if allow_fused:
        for slot, p in enumerate(resident_levels(plans)):
            p.static = p.static[:3] + (slot,) + p.static[4:]
    _register_const_bytes(plans, seg)
    return plans


def resident_levels(plans: List[AggPlan]):
    """The levels of a program whose bins are a resident lane -> bin
    vector (a `histogram`/`date_histogram` whose table is not the
    identity), in the order of their slots: pre-order."""
    for p in plans:
        if p.kind == "bucket_num" and p.table_of is not None \
                and p.static[3] != BINS_RANK:
            yield p
        yield from resident_levels(p.children)


def bins_slot(plan: AggPlan) -> Optional[int]:
    """The slot of the resident vector a level reads, if it reads one."""
    if plan.kind == "bucket_num" and isinstance(plan.static[3], int):
        return plan.static[3]
    return None


# Bucket levels the one-chip routes planned (host loop: once a segment
# compiled; agg envelope: once an item and segment program dispatched, a
# plan-memo hit included), by where the level's bins come from: the rank
# column itself (BINS_RANK), the segment's resident lane -> bin vector (a
# slot), a rank -> bucket table gathered through a request (BINS_TABLE: a
# `range` bucket), lane bitmasks closed over by the executable
# (`bucket_bits`). The SPMD route counts its levels as
# `search.agg_lane_bins.*` (parallel/distributed.py).
_BIN_SOURCES = {src: TELEMETRY.metrics.counter(f"search.agg_bins.level.{src}")
                for src in ("rank", "resident", "table", "bits")}


def note_bin_sources(plans: List["AggPlan"], times: int = 1) -> None:
    for p in plans:
        if p.kind == "bucket_bits":
            _BIN_SOURCES["bits"].inc(times)
        elif bins_slot(p) is not None:
            _BIN_SOURCES["resident"].inc(times)
        elif p.kind == "bucket_num":
            _BIN_SOURCES[p.static[3]].inc(times)
        if p.children:
            note_bin_sources(p.children, times)


def _num_col(ctx: _Ctx, field: str):
    return ctx.seg.numeric_dv.get(field)


def _ident_pairs(col) -> bool:
    return ident_pairs(col)


# Where a `bucket_num` level's lane -> bin vector comes from
# (`static[3]`, so in `plan_struct` and the program's fingerprint):
# - BINS_RANK: the rank -> bucket table is the identity (`terms` on a
#   numeric column; a histogram whose every unique value opens its own
#   bucket), so the rank column `val_ords` IS the bin: no table input, no
#   gather.
# - an int slot: any other `histogram`/`date_histogram` level. The
#   gather's result is segment-static, so it is derived once a (device
#   image, field, `bins_key`), kept on the device beside that image
#   (search/aggs/lane_bins.py) and handed to the program as
#   `seg["lane_bins"][slot]`; the plan carries no table, and builds none
#   on a hit. The one-chip routes' slots are named by `compile_aggs`, the
#   SPMD route's by `resident_lane_bins` (until then such a level reads
#   BINS_TABLE without a table).
# - BINS_TABLE: the table rides the request's inputs and the program
#   gathers `table[val_ords]` over every lane: a `range` bucket, on every
#   route.
BINS_RANK = "rank"
BINS_TABLE = "table"


def _rank_table(bucket_of_rank: np.ndarray) -> np.ndarray:
    """A rank -> bucket table as the program takes it: int32, padded to a
    shape bucket with -1 ("no bucket") past the column's last rank."""
    n = len(bucket_of_rank)
    table = np.full(pad_bucket(max(n, 1), minimum=8), -1, dtype=np.int32)
    table[:n] = bucket_of_rank
    return table


def _bin_room(ctx: _Ctx, node: AggNode, key: tuple,
              populations: Callable[[], np.ndarray]) -> Optional[int]:
    """The lanes ONE bucket of this level can hold, whatever the query:
    the largest population among its buckets (`populations()`, counted
    from the sealed column), as a power of two no smaller than
    AGG_BIN_ROOM_MIN. Segment-static, so found once a (segment, field,
    bucketing) and in the plan's `static`; what `_sum_ways` sizes the
    float sums under this level by. None for a level nothing is summed
    under (no sub-aggregation)."""
    if not node.children:
        return None
    memo = ctx.seg.__dict__.setdefault("_agg_bin_room", {})
    room = memo.get(key)
    if room is None:
        pops = populations()
        room = memo[key] = pad_bucket(int(pops.max()) if len(pops) else 0,
                                      minimum=AGG_BIN_ROOM_MIN)
    return room


def plan_bin_room(plan: "AggPlan") -> Optional[int]:
    """A bucket level's `_bin_room`, where its plan carries one: the
    last entry of a `bucket_num` (fifth) or `bucket_ord` (fourth)
    `static`."""
    n = {"bucket_num": 4, "bucket_ord": 3}.get(plan.kind)
    return plan.static[n] if n is not None and len(plan.static) > n \
        else None


def _bucket_lookup_plan(node: AggNode, ctx: _Ctx, card: int, render: dict,
                        bucket_of_rank: Callable[[], np.ndarray],
                        bins_key: tuple) -> AggPlan:
    """A `histogram`/`date_histogram` level over a numeric column.
    `bucket_of_rank()` builds the bucket of every unique value of the
    segment's column, `card` is its last entry + 1. The table can be the
    identity only where `card` is the number of unique values, so it is
    built here only then (to look); any other level reads a resident
    lane -> bin vector, and its table is `AggPlan.table_of`'s to build
    when that vector is derived."""
    col = _num_col(ctx, node.field)
    n = len(col.unique)
    identity = card == n and np.array_equal(bucket_of_rank(), np.arange(n))
    room = _bin_room(
        ctx, node, (node.field,) + bins_key,
        lambda: np.bincount(bucket_of_rank()[col.value_ords],
                            minlength=card))
    return AggPlan(name=node.name, kind="bucket_num",
                   static=(node.field, card, _ident_pairs(col),
                           BINS_RANK if identity else BINS_TABLE, room),
                   children=[_compile_node(c, ctx) for c in node.children],
                   render=render, bins_key=bins_key,
                   table_of=lambda: _rank_table(bucket_of_rank()))


# ------------------------------------------------- fused leaf bucketing
#
# Root-level bucket aggregations with no sub-aggregations (the dashboard
# hot shape: date_histogram / histogram / range / cardinality next to a
# query) compile to ONE popcount reduction against per-bucket lane
# bitmasks precomputed on the host at (agg, segment) compile time and
# embedded in the executable as constants. The round-5 kernel rebuilt the
# [bins, lanes] membership mask + bit-packing INSIDE the device program on
# every batch (the "static side" of _binned_sums) — ~6M ops per
# date_histogram batch that depend only on segment-static tables. Here
# that work runs once per compile (memoized with the agg plan), the
# envelope carries zero table bytes, and the per-query device work drops
# to pack(ok) + popcount(ok & binbits).

def _pack_lane_bits(bins: np.ndarray, card: int, n_pad: int) -> np.ndarray:
    """Host bit-pack: lane→bin assignment (int, <0 = none) → uint32
    [card, n_pad/32] per-bucket lane masks, bit order matching the device
    _pack_bits (bit j of word w = lane w*32+j)."""
    words = np.zeros((card, n_pad // 32), dtype=np.uint32)
    lanes = np.nonzero((bins >= 0) & (bins < card))[0].astype(np.int64)
    if len(lanes):
        np.bitwise_or.at(
            words, (bins[lanes], lanes // 32),
            np.left_shift(np.uint32(1), (lanes % 32).astype(np.uint32)))
    return words


def _fused_gate(ctx: _Ctx, node: AggNode, card: int, nv_pad: int) -> bool:
    return (ctx.root and ctx.fused and not node.children and card >= 1
            and card <= AGG_GEMM_MAX_BINS and nv_pad % 32 == 0
            and card * nv_pad <= AGG_POPCOUNT_MAX_ELEMS)


def _fused_bits_plan(node: AggNode, ctx: _Ctx, col, src: str,
                     lane_bins: np.ndarray, card: int, render: dict,
                     kind: str = "bucket_bits") -> AggPlan:
    nv_pad = pad_bucket(max(len(col.doc_ids), 1))
    bins = np.full(nv_pad, -1, dtype=np.int64)
    bins[:len(lane_bins)] = lane_bins
    binbits = _pack_lane_bits(bins, card, nv_pad)
    return AggPlan(node.name, kind,
                   static=(node.field, card, _ident_pairs(col), src),
                   const_inputs={"binbits": binbits}, render=render)


def _parse_duration_ms(v) -> int:
    """Date-histogram offset: "1h" / "-30m" / raw millis."""
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return int(v)
    s = str(v).strip()
    sign = 1
    if s[:1] in ("+", "-"):
        sign = -1 if s[0] == "-" else 1
        s = s[1:]
    if s in _FIXED_MS:
        return sign * _FIXED_MS[s]
    if s[-2:] == "ms" and s[:-2].isdigit():
        # before the single-char suffix branch: '500ms' must not parse
        # as '500m' + trailing junk or fail outright
        return sign * int(s[:-2])
    if s[:-1].isdigit() and s[-1:] in "smhdw":
        return sign * int(s[:-1]) * _FIXED_MS[s[-1]]
    if s.isdigit():
        return sign * int(s)
    raise ParsingError(f"failed to parse [offset]: [{v}]")


def _parse_time_zone(tz) -> int:
    """time_zone → fixed UTC offset in ms. Fixed offsets exact; named
    zones use their standard offset at a representative instant (DST
    transitions inside one histogram are out of scope — documented)."""
    if tz in (None, "", "UTC", "Z"):
        return 0
    s = str(tz)
    m = re.match(r"^([+-])(\d{1,2})(?::?(\d{2}))?$", s)
    if m:
        sign = -1 if m.group(1) == "-" else 1
        return sign * (int(m.group(2)) * 3600_000
                       + int(m.group(3) or 0) * 60_000)
    try:
        from zoneinfo import ZoneInfo
        import datetime as _dt
        off = ZoneInfo(s).utcoffset(
            _dt.datetime(2024, 1, 15, tzinfo=_dt.timezone.utc))
        return int(off.total_seconds() * 1000)
    except (KeyError, ValueError, OSError, ImportError):
        raise ParsingError(f"failed to parse time zone [{tz}]")


def hist_step_shift(body: dict, kind: str):
    """(step, shift) of a fixed-interval histogram/date_histogram body,
    where bucket key = floor((v + shift) / step) * step - shift.
    None for calendar intervals. Shared with the reduce-side renderers
    (gap fill / extended_bounds need the key lattice, not just the
    observed keys)."""
    if kind == "histogram":
        interval = float(body.get("interval", 0) or 0)
        if interval <= 0:
            return None
        return interval, -float(body.get("offset", 0.0))
    unit = str(body.get("calendar_interval") or body.get("fixed_interval")
               or body.get("interval") or "")
    if unit in _FIXED_MS:
        step = _FIXED_MS[unit]
    elif unit[:-1].isdigit() and unit[-1:] in "smhdw":
        step = int(unit[:-1]) * _FIXED_MS[unit[-1]]
    else:
        return None
    shift = (_parse_time_zone(body.get("time_zone"))
             - _parse_duration_ms(body.get("offset", 0)))
    return step, shift


def _compile_node(node: AggNode, ctx: _Ctx, root: bool = False) -> AggPlan:
    fn = _COMPILERS.get(node.type)
    if fn is None:
        raise QueryShardError(f"aggregation type [{node.type}] is not supported")
    if ctx.root != root:
        # child compiles (the default) demote the root flag; only
        # compile_aggs promotes it for top-level nodes
        ctx = dc_replace(ctx, root=root)
    return fn(node, ctx)


# ----------------------------------------------------------------- buckets

def _c_terms(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    if field is None:
        raise ParsingError(f"[terms] aggregation [{node.name}] requires a field")
    ocol = ctx.seg.ordinal_dv.get(field)
    if ocol is not None:
        card = max(len(ocol.dictionary), 1)
        children = [_compile_node(c, ctx) for c in node.children]
        room = _bin_room(ctx, node, (field, "ord"),
                         lambda: np.bincount(ocol.ords, minlength=card))
        return AggPlan(node.name, "bucket_ord",
                       static=(field, card, _ident_pairs(ocol), room),
                       children=children,
                       render={"keys": list(ocol.dictionary), "body": node.body,
                               "kind": "terms"})
    col = _num_col(ctx, field)
    if col is None:
        return AggPlan(node.name, "empty", render={"body": node.body,
                                                   "kind": "terms", "keys": []})
    # a rank is its own bucket: the table would be the identity
    ft = ctx.mapper.get_field(field)
    room = _bin_room(ctx, node, (field, "rank"),
                     lambda: np.bincount(col.value_ords))
    return AggPlan(node.name, "bucket_num",
                   static=(field, max(len(col.unique), 1), _ident_pairs(col),
                           BINS_RANK, room),
                   children=[_compile_node(c, ctx) for c in node.children],
                   render={"keys": [_render_numeric_key(v, ft)
                                    for v in col.unique],
                           "body": node.body, "kind": "terms"})


def _render_numeric_key(v: float, ft) -> Any:
    if ft is not None and ft.is_bool:
        return bool(v)
    if ft is not None and ft.is_date:
        return int(v)
    return int(v) if float(v).is_integer() else float(v)


def _c_histogram(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    interval = node.body.get("interval")
    if not field or not interval:
        raise ParsingError("[histogram] requires [field] and [interval]")
    interval = float(interval)
    if interval <= 0:
        raise ParsingError("[interval] must be > 0")
    offset = float(node.body.get("offset", 0.0))
    col = _num_col(ctx, field)
    if col is None or len(col.unique) == 0:
        return AggPlan(node.name, "empty",
                       render={"body": node.body, "kind": "histogram",
                               "interval": interval, "offset": offset,
                               "step": interval, "shift": -offset,
                               "keys": []})
    # the first and the last bucket say `card` and the keys; the bucket of
    # every unique value in between is built only where someone reads it
    lo_key = np.floor((col.unique[0] - offset) / interval)
    card = int(np.floor((col.unique[-1] - offset) / interval) - lo_key) + 1

    def buckets():
        return (np.floor((col.unique - offset) / interval)
                - lo_key).astype(np.int32)
    keys = [float(lo_key + i) * interval + offset for i in range(card)]
    render = {"keys": keys, "body": node.body, "kind": "histogram",
              "step": interval, "shift": -offset}
    nv_pad = pad_bucket(max(len(col.doc_ids), 1))
    if _fused_gate(ctx, node, card, nv_pad):
        lane_bins = buckets().astype(np.int64)[col.value_ords]
        return _fused_bits_plan(node, ctx, col, "numeric", lane_bins, card,
                                render)
    return _bucket_lookup_plan(node, ctx, card, render, buckets,
                               bins_key=("histogram", interval, offset))


def _calendar_boundaries(lo_ms: float, hi_ms: float, unit: str) -> List[int]:
    """Host-generated calendar-aware bucket boundaries (month/quarter/year)."""
    import datetime as _dt
    start = _dt.datetime.fromtimestamp(lo_ms / 1000.0, tz=_dt.timezone.utc)
    out = []
    if unit in ("M", "1M", "month"):
        cur = start.replace(day=1, hour=0, minute=0, second=0, microsecond=0)
        step_months = 1
    elif unit in ("q", "1q", "quarter"):
        cur = start.replace(month=((start.month - 1) // 3) * 3 + 1, day=1,
                            hour=0, minute=0, second=0, microsecond=0)
        step_months = 3
    else:  # year
        cur = start.replace(month=1, day=1, hour=0, minute=0, second=0,
                            microsecond=0)
        step_months = 12
    while cur.timestamp() * 1000 <= hi_ms:
        out.append(int(cur.timestamp() * 1000))
        month = cur.month - 1 + step_months
        cur = cur.replace(year=cur.year + month // 12, month=month % 12 + 1)
    out.append(int(cur.timestamp() * 1000))
    return out


_CALENDAR_APPROX_MS = {"M": 2_592_000_000, "1M": 2_592_000_000,
                       "month": 2_592_000_000,
                       "q": 7_776_000_000, "1q": 7_776_000_000,
                       "quarter": 7_776_000_000,
                       "y": 31_536_000_000, "1y": 31_536_000_000,
                       "year": 31_536_000_000}


def _interval_ms(body: dict) -> int:
    """Interval in ms for fixed bucketing. Calendar month/quarter/year use
    fixed approximations (30/90/365 days) — composite sources bucket on
    fixed widths; the standalone date_histogram path uses true calendar
    boundaries via _calendar_boundaries."""
    unit = str(body.get("calendar_interval") or body.get("fixed_interval")
               or body.get("interval") or "1d")
    if unit in _FIXED_MS:
        return _FIXED_MS[unit]
    if unit in _CALENDAR_APPROX_MS:
        return _CALENDAR_APPROX_MS[unit]
    if unit[:-1].isdigit() and unit[-1] in "smhdw":
        return int(unit[:-1]) * _FIXED_MS[unit[-1]]
    raise ParsingError(f"unknown date interval [{unit}]")


def _c_date_histogram(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    interval = (node.body.get("calendar_interval")
                or node.body.get("fixed_interval")
                or node.body.get("interval"))
    if not field or not interval:
        raise ParsingError("[date_histogram] requires [field] and an interval")
    # shift = tz - offset: bucket ordinal of a timestamp is
    # floor((ts + shift) / step) and the reported UTC key is
    # ordinal * step - shift (rounding happens in offset-shifted local
    # time — DateHistogramAggregationBuilder's Rounding semantics)
    tz = _parse_time_zone(node.body.get("time_zone"))
    off = _parse_duration_ms(node.body.get("offset", 0))
    shift = tz - off
    col = _num_col(ctx, field)
    unit = str(interval)
    fixed = hist_step_shift(node.body, "date_histogram")
    empty_render = {"body": node.body, "kind": "date_histogram",
                    "keys": [], "interval": interval}
    if fixed is not None:
        empty_render["step"], empty_render["shift"] = fixed
    else:
        empty_render["calendar"] = True
    if col is None or len(col.unique) == 0:
        return AggPlan(node.name, "empty", render=empty_render)
    # the first and the last bucket say `card` and the keys; the bucket of
    # every unique value in between is built only where someone reads it
    if fixed is not None:
        step, _ = fixed
        lo_key = int(np.floor((col.unique[0] + shift) / step))
        card = int(np.floor((col.unique[-1] + shift) / step)) - lo_key + 1

        def buckets():
            return (np.floor((col.unique + shift) / step).astype(np.int64)
                    - lo_key).astype(np.int32)
        keys = [(lo_key + i) * step - shift for i in range(card)]
        render = {"keys": keys, "body": node.body, "kind": "date_histogram",
                  "step": step, "shift": shift}
    else:
        bounds = _calendar_boundaries(float(col.unique[0]) + shift,
                                      float(col.unique[-1]) + shift, unit)
        bounds = [b - shift for b in bounds]

        def buckets():
            return (np.searchsorted(np.asarray(bounds, dtype=np.float64),  # sync-ok: host -- compile-time bucket table from a Python list
                                    col.unique, side="right")
                    - 1).astype(np.int32)
        card = len(bounds) - 1
        keys = bounds[:-1]
        render = {"keys": keys, "body": node.body, "kind": "date_histogram",
                  "calendar": True}
    # key strings rendered once per (agg, segment) compile — the memoized
    # plan serves every query of a dashboard workload, where the old path
    # re-formatted every bucket of every query in the respond phase
    render["keys_str"] = [format_date_millis(int(k)) for k in keys]
    nv_pad = pad_bucket(max(len(col.doc_ids), 1))
    if _fused_gate(ctx, node, card, nv_pad):
        lane_bins = buckets().astype(np.int64)[col.value_ords]
        return _fused_bits_plan(node, ctx, col, "numeric", lane_bins, card,
                                render)
    return _bucket_lookup_plan(
        node, ctx, card, render, buckets,
        bins_key=("date_histogram", fixed[0] if fixed is not None else unit,
                  shift))


def _c_range(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    ranges = node.body.get("ranges")
    if not field or not ranges:
        raise ParsingError("[range] aggregation requires [field] and [ranges]")
    ft = ctx.mapper.get_field(field)
    col = _num_col(ctx, field)
    is_date = node.type == "date_range" or (ft is not None and ft.is_date)

    def conv(v):
        if v is None:
            return None
        if is_date and isinstance(v, str):
            v = _resolve_date_math(v)
            return float(parse_date_millis(v) if isinstance(v, str) else v)
        return float(ft.to_comparable(v)) if ft is not None else float(v)

    specs = []
    for r in ranges:
        frm, to = conv(r.get("from")), conv(r.get("to"))
        key = r.get("key")
        if key is None:
            f_str = "*" if frm is None else (
                format_date_millis(int(frm)) if is_date else _fmt_num(frm))
            t_str = "*" if to is None else (
                format_date_millis(int(to)) if is_date else _fmt_num(to))
            key = f"{f_str}-{t_str}"
        specs.append((key, frm, to))
    render = {"kind": node.type, "specs": specs, "body": node.body,
              "is_date": is_date}
    if col is None or len(col.unique) == 0:
        return AggPlan(node.name, "empty", render=render)
    u = col.unique
    nv_pad = pad_bucket(max(len(col.doc_ids), 1))
    if _fused_gate(ctx, node, max(len(specs), 1), nv_pad):
        # fused leaf ranges: one bitmask row per range (rows independent,
        # so overlapping ranges need no sub-plan slots), one popcount
        # reduction for the whole [ranges] agg
        words = np.zeros((len(specs), nv_pad // 32), dtype=np.uint32)
        lanes = np.arange(len(col.doc_ids), dtype=np.int64)
        vo = col.value_ords
        for i, (_, frm, to) in enumerate(specs):
            lo = 0 if frm is None else int(np.searchsorted(u, frm, "left"))
            hi = len(u) if to is None else int(np.searchsorted(u, to, "left"))
            sel = lanes[(vo >= lo) & (vo < hi)]
            if len(sel):
                np.bitwise_or.at(
                    words[i], sel // 32,
                    np.left_shift(np.uint32(1),
                                  (sel % 32).astype(np.uint32)))
        return AggPlan(node.name, "bucket_bits",
                       static=(field, len(specs), _ident_pairs(col),
                               "numeric"),
                       const_inputs={"binbits": words}, render=render)
    # ranges can overlap → one sub-plan slot per range (card = len ranges),
    # membership computed per range via rank-interval table
    sub_plans = []
    for i, (_, frm, to) in enumerate(specs):
        lo = 0 if frm is None else int(np.searchsorted(u, frm, "left"))
        hi = len(u) if to is None else int(np.searchsorted(u, to, "left"))
        u_pad = pad_bucket(max(len(u), 1), minimum=8)
        table = np.full(u_pad, -1, dtype=np.int32)
        table[lo:hi] = 0
        sub_plans.append(AggPlan(f"{node.name}#{i}", "bucket_num",
                                 static=(field, 1, _ident_pairs(col),
                                         BINS_TABLE),
                                 inputs={"table": table},
                                 children=[_compile_node(c, ctx)
                                           for c in node.children]))
    return AggPlan(node.name, "multi", static=(len(sub_plans),),
                   children=sub_plans, render=render)


def _fmt_num(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else str(v)


def _c_nested(node: AggNode, ctx: _Ctx) -> AggPlan:
    """Switch the doc set to a nested path's child rows; bucket ordinals
    follow each child's root (bucket/nested/NestedAggregator.java)."""
    path = (node.body or {}).get("path")
    paths = getattr(ctx.seg, "nested_paths", [])
    path_ord = paths.index(path) if path in paths else -1
    children = [_compile_node(c, ctx) for c in node.children]
    return AggPlan(node.name, "nested",
                   inputs={"path_ord": np.asarray(path_ord, np.int32)},  # sync-ok: host -- scalar plan constant
                   children=children, render={"kind": "filter"})


def _c_reverse_nested(node: AggNode, ctx: _Ctx) -> AggPlan:
    if (node.body or {}).get("path"):
        # intermediate-level join-back needs hierarchical parent
        # pointers the flat block encoding doesn't keep — refuse loudly
        raise QueryShardError(
            "[reverse_nested] with an explicit [path] is not supported; "
            "omit path to join back to the root level")
    children = [_compile_node(c, ctx) for c in node.children]
    return AggPlan(node.name, "reverse_nested", children=children,
                   render={"kind": "filter"})


def _c_filter(node: AggNode, ctx: _Ctx) -> AggPlan:
    qnode = dsl.parse_query(node.body if node.body else {"match_all": {}})
    qplan = ctx.compiler.compile(qnode, ctx.seg, ctx.meta)
    children = [_compile_node(c, ctx) for c in node.children]
    return AggPlan(node.name, "filter", query_plan=qplan, children=children,
                   render={"kind": "filter"})


def _c_filters(node: AggNode, ctx: _Ctx) -> AggPlan:
    filters = node.body.get("filters")
    if filters is None:
        raise ParsingError("[filters] aggregation requires [filters]")
    if isinstance(filters, dict):
        names = list(filters.keys())
        queries = [filters[n] for n in names]
        keyed = True
    else:
        names = [str(i) for i in range(len(filters))]
        queries = list(filters)
        keyed = False
    subs = []
    for n, q in zip(names, queries):
        qplan = ctx.compiler.compile(dsl.parse_query(q), ctx.seg, ctx.meta)
        subs.append(AggPlan(n, "filter", query_plan=qplan,
                            children=[_compile_node(c, ctx)
                                      for c in node.children]))
    return AggPlan(node.name, "multi", static=(len(subs),), children=subs,
                   render={"kind": "filters", "names": names, "keyed": keyed})


def _c_global(node: AggNode, ctx: _Ctx) -> AggPlan:
    children = [_compile_node(c, ctx) for c in node.children]
    return AggPlan(node.name, "global", children=children,
                   render={"kind": "global"})


def _c_missing(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    if field is None:
        raise ParsingError("[missing] aggregation requires a field")
    if field in ctx.seg.numeric_dv:
        static = ("numeric", field)
    elif field in ctx.seg.ordinal_dv:
        static = ("ordinal", field)
    elif field in ctx.seg.vector_dv:
        static = ("vector", field)
    else:
        static = ("none", field)
    children = [_compile_node(c, ctx) for c in node.children]
    return AggPlan(node.name, "missing", static=static, children=children,
                   render={"kind": "missing"})


# ----------------------------------------------------------------- metrics

# which device partials each metric render consumes (reduce._merge_metric);
# cnt also powers the has-any-value null handling for min/max/avg
_METRIC_NEEDS = {
    "min": ("cnt", "min"), "max": ("cnt", "max"), "avg": ("cnt", "sum"),
    "sum": ("cnt", "sum"), "value_count": ("cnt",),
    "stats": ("cnt", "max", "min", "sum"),
    "extended_stats": ("cnt", "max", "min", "sum", "sumsq"),
}


def _c_metric(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    if field is None:
        raise ParsingError(f"[{node.type}] aggregation [{node.name}] requires "
                           f"a field")
    render = {"kind": node.type, "body": node.body}
    if field in ctx.seg.numeric_dv:
        ft = ctx.mapper.get_field(field)
        render["is_date"] = bool(ft is not None and ft.is_date)
        # collect only the partials the metric's render needs: avg wants
        # (sum, cnt), not the full 5-reduction stats battery
        needs = _METRIC_NEEDS.get(node.type,
                                  ("cnt", "max", "min", "sum", "sumsq"))
        missing = node.body.get("missing")
        return AggPlan(node.name, "metric_num",
                       static=(field, needs,
                               _ident_pairs(ctx.seg.numeric_dv[field]),
                               None if missing is None else float(missing)),
                       render=render)
    if node.body.get("missing") is not None and field not in \
            ctx.seg.ordinal_dv:
        # field absent from the whole segment but a missing substitute is
        # given: every doc contributes the substitute (metric over a
        # constant) — compile as metric_missing_only
        needs = _METRIC_NEEDS.get(node.type,
                                  ("cnt", "max", "min", "sum", "sumsq"))
        return AggPlan(node.name, "metric_missing_only",
                       static=(needs, float(node.body["missing"])),
                       render=render)
    if field in ctx.seg.ordinal_dv and node.type == "value_count":
        return AggPlan(node.name, "count_ord",
                       static=(field,
                               _ident_pairs(ctx.seg.ordinal_dv[field])),
                       render=render)
    return AggPlan(node.name, "empty", render=render)


def _c_cardinality(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    if field is None:
        raise ParsingError("[cardinality] aggregation requires a field")
    render = {"kind": "cardinality", "body": node.body}
    if field in ctx.seg.ordinal_dv:
        col = ctx.seg.ordinal_dv[field]
        card = max(len(col.dictionary), 1)
        render["keys"] = list(col.dictionary)
        nv_pad = pad_bucket(max(len(col.doc_ids), 1))
        if _fused_gate(ctx, node, card, nv_pad):
            return _fused_bits_plan(node, ctx, col, "ordinal",
                                    col.ords.astype(np.int64), card, render,
                                    kind="presence_bits")
        return AggPlan(node.name, "presence_ord",
                       static=(field, card, _ident_pairs(col)),
                       render=render)
    if field in ctx.seg.numeric_dv:
        col = ctx.seg.numeric_dv[field]
        u = col.unique
        render["values"] = u
        card = max(len(u), 1)
        nv_pad = pad_bucket(max(len(col.doc_ids), 1))
        if _fused_gate(ctx, node, card, nv_pad):
            return _fused_bits_plan(node, ctx, col, "numeric",
                                    col.value_ords.astype(np.int64), card,
                                    render, kind="presence_bits")
        return AggPlan(node.name, "presence_num",
                       static=(field, card, _ident_pairs(col)),
                       render=render)
    return AggPlan(node.name, "empty", render=render)


def _c_percentiles(node: AggNode, ctx: _Ctx) -> AggPlan:
    field = node.field
    if field is None:
        raise ParsingError(f"[{node.type}] aggregation requires a field")
    render = {"kind": node.type, "body": node.body}
    if field in ctx.seg.numeric_dv:
        u = ctx.seg.numeric_dv[field].unique
        render["values"] = u
        return AggPlan(node.name, "value_hist",
                       static=(field, max(len(u), 1),
                               _ident_pairs(ctx.seg.numeric_dv[field])),
                       render=render)
    return AggPlan(node.name, "empty", render=render)


def _c_weighted_avg(node: AggNode, ctx: _Ctx) -> AggPlan:
    vspec = node.body.get("value", {})
    wspec = node.body.get("weight", {})
    vf, wf = vspec.get("field"), wspec.get("field")
    if not vf or not wf:
        raise ParsingError("[weighted_avg] requires value.field and weight.field")
    render = {"kind": "weighted_avg", "body": node.body}
    if vf in ctx.seg.numeric_dv and wf in ctx.seg.numeric_dv:
        return AggPlan(node.name, "weighted_avg",
                       static=(vf, wf,
                               _ident_pairs(ctx.seg.numeric_dv[vf])),
                       render=render)
    return AggPlan(node.name, "empty", render=render)


# ---------------------------------------------------- dense-bucket family
#
# A host-precomputed per-doc bucket id (int32[d_pad], -1 = no bucket) feeds
# one generic device kind ("bucket_dense"): the host does the irregular
# string/tuple work once per (agg, segment) compile, the device does the
# massively-regular scatter-count. geohash grids, composite tuples,
# multi_terms and auto intervals all ride this path.

def _dense_first_value(ctx: _Ctx, field: str):
    """Per-doc first numeric value + exists (host numpy)."""
    col = _num_col(ctx, field)
    d = ctx.seg.num_docs
    if col is None:
        return None, np.zeros(d, dtype=bool)
    value = np.zeros(d, dtype=np.float64)
    # doc_ids are grouped ascending: first occurrence = smallest value
    docs, first_idx = np.unique(col.doc_ids, return_index=True)
    value[docs] = col.values[first_idx]
    return value, col.exists.copy()


def _dense_first_ord(ctx: _Ctx, field: str):
    col = ctx.seg.ordinal_dv.get(field)
    d = ctx.seg.num_docs
    if col is None:
        return None, np.zeros(d, dtype=bool), []
    ords = np.zeros(d, dtype=np.int64)
    docs, first_idx = np.unique(col.doc_ids, return_index=True)
    ords[docs] = col.ords[first_idx]
    return ords, col.exists.copy(), list(col.dictionary)


def _bucket_dense_plan(node: AggNode, ctx: _Ctx, doc_bucket: np.ndarray,
                       card: int, render: dict) -> AggPlan:
    padded = np.full(ctx.d_pad, -1, dtype=np.int32)
    padded[:len(doc_bucket)] = doc_bucket
    children = [_compile_node(c, ctx) for c in node.children]
    return AggPlan(node.name, "bucket_dense", static=(card,),
                   inputs={"doc_bucket": padded}, children=children,
                   render=render)


def _source_encoding(ctx: _Ctx, name: str, spec: dict):
    """One composite/multi_terms source → (per-doc code, exists, keys)."""
    stype, body = next(iter(spec.items())) if len(spec) == 1 \
        else ("terms", spec)
    field = body.get("field")
    ocol = ctx.seg.ordinal_dv.get(field)
    if ocol is not None:
        ords, exists, keys = _dense_first_ord(ctx, field)
        return ords, exists, keys
    value, exists = _dense_first_value(ctx, field)
    if value is None:
        return None, exists, []
    ft = ctx.mapper.get_field(field)
    if stype == "histogram":
        interval = float(body["interval"])
        codes_raw = np.floor(value / interval) * interval
    elif stype == "date_histogram":
        iv = _interval_ms(body)
        codes_raw = np.floor(value / iv) * iv
    else:
        codes_raw = value
    uniq = np.unique(codes_raw[exists]) if exists.any() else np.array([])
    code_of = {v: i for i, v in enumerate(uniq)}
    codes = np.array([code_of.get(v, -1) for v in codes_raw], dtype=np.int64)
    keys = [_render_numeric_key(v, ft) for v in uniq]
    return codes, exists, keys


def _c_composite(node: AggNode, ctx: _Ctx) -> AggPlan:
    sources = node.body.get("sources")
    if not sources:
        raise ParsingError(f"[composite] aggregation [{node.name}] requires "
                           f"[sources]")
    source_specs = []
    for s in sources:
        if len(s) != 1:
            raise ParsingError("[composite] source must have one name")
        sname, sbody = next(iter(s.items()))
        source_specs.append((sname, sbody))
    d = ctx.seg.num_docs
    combined = np.zeros(d, dtype=np.int64)
    all_exist = np.ones(d, dtype=bool)
    key_lists = []
    names = []
    for sname, sbody in source_specs:
        codes, exists, keys = _source_encoding(ctx, sname, sbody)
        names.append(sname)
        key_lists.append(keys)
        if codes is None or not keys:
            all_exist[:] = False
            combined[:] = -1
            continue
        combined = combined * len(keys) + np.where(exists, codes, 0)
        all_exist &= exists
    card = max(int(np.prod([max(len(k), 1) for k in key_lists])), 1)
    doc_bucket = np.where(all_exist, combined, -1).astype(np.int32)
    render = {"kind": node.type, "body": node.body, "sources": names,
              "key_lists": key_lists}
    return _bucket_dense_plan(node, ctx, doc_bucket, card, render)


def _c_multi_terms(node: AggNode, ctx: _Ctx) -> AggPlan:
    terms = node.body.get("terms")
    if not terms or len(terms) < 2:
        raise ParsingError(f"[multi_terms] aggregation [{node.name}] "
                           f"requires at least 2 [terms]")
    synthetic = AggNode(node.name, "multi_terms",
                        {"sources": [{f"t{i}": {"terms": t}}
                                     for i, t in enumerate(terms)],
                         **node.body},
                        children=node.children)
    plan = _c_composite(synthetic, ctx)
    plan.render["kind"] = "multi_terms"
    return plan


def _c_auto_date_histogram(node: AggNode, ctx: _Ctx) -> AggPlan:
    """Pick the smallest calendar interval that keeps bucket count under
    `buckets` (AutoDateHistogramAggregationBuilder.RoundingInfos)."""
    target = int(node.body.get("buckets", 10))
    col = _num_col(ctx, node.field)
    if col is None or not len(col.unique):
        return AggPlan(node.name, "empty",
                       render={"kind": "auto_date_histogram", "keys": [],
                               "body": node.body})
    lo, hi = float(col.unique[0]), float(col.unique[-1])
    candidates = [("1s", 1000), ("1m", 60_000), ("1h", 3_600_000),
                  ("1d", 86_400_000), ("7d", 7 * 86_400_000),
                  ("1M", 30 * 86_400_000), ("3M", 90 * 86_400_000),
                  ("1y", 365 * 86_400_000)]
    chosen_label, chosen_ms = candidates[-1]
    for label, ms in candidates:
        if (hi - lo) / ms + 1 <= target:
            chosen_label, chosen_ms = label, ms
            break
    clone = AggNode(node.name, "date_histogram",
                    {**node.body,
                     "fixed_interval": f"{chosen_ms // 1000}s"},
                    children=node.children)
    plan = _c_date_histogram(clone, ctx)
    plan.render["kind"] = "auto_date_histogram"
    plan.render["interval"] = chosen_label
    return plan


def _c_significant_terms(node: AggNode, ctx: _Ctx) -> AggPlan:
    """Foreground counts on device; background (index-wide) doc counts
    gathered host-side at compile. Scores reduce with the JLH heuristic.
    Exact for single-valued fields (subset size = Σ fg counts)."""
    field = node.field
    ocol = ctx.seg.ordinal_dv.get(field)
    if ocol is None:
        return AggPlan(node.name, "empty",
                       render={"kind": "significant_terms", "keys": [],
                               "body": node.body})
    plan = _c_terms(node, ctx)
    bg = np.zeros(len(ocol.dictionary), dtype=np.int64)
    seen_pairs = set()
    for doc, o in zip(ocol.doc_ids, ocol.ords):
        if (doc, o) not in seen_pairs:
            seen_pairs.add((doc, o))
            bg[o] += 1
    plan.render = {"kind": "significant_terms", "keys": list(ocol.dictionary),
                   "body": node.body, "bg": bg.tolist(),  # sync-ok: host -- bg counts are a host numpy accumulator
                   "bg_total": int(ctx.seg.num_docs)}
    return plan


def _c_adjacency_matrix(node: AggNode, ctx: _Ctx) -> AggPlan:
    filters = node.body.get("filters")
    if not isinstance(filters, dict) or not filters:
        raise ParsingError(f"[adjacency_matrix] aggregation [{node.name}] "
                           f"requires [filters]")
    names = sorted(filters)
    children = []
    for name in names:
        qnode = dsl.parse_query(filters[name])
        children.append(ctx.compiler.compile(qnode, ctx.seg, ctx.meta))
    return AggPlan(node.name, "adjacency", static=(len(names),),
                   query_plans=children,
                   render={"kind": "adjacency_matrix", "names": names,
                           "body": node.body})


def _c_geo_bounds(node: AggNode, ctx: _Ctx) -> AggPlan:
    return AggPlan(node.name, "geo_metric",
                   static=(node.field,),
                   render={"kind": node.type, "body": node.body})


def _c_geohash_grid(node: AggNode, ctx: _Ctx) -> AggPlan:
    precision = int(node.body.get("precision", 5))
    lat, lat_exists = _dense_first_value(ctx, f"{node.field}.lat")
    lon, _ = _dense_first_value(ctx, f"{node.field}.lon")
    if lat is None or lon is None:
        return AggPlan(node.name, "empty",
                       render={"kind": "grid", "keys": [], "body": node.body})
    if node.type == "geotile_grid":
        keys_raw = [_geotile(la, lo, precision) if e else None
                    for la, lo, e in zip(lat, lon, lat_exists)]
    else:
        keys_raw = [_geohash(la, lo, precision) if e else None
                    for la, lo, e in zip(lat, lon, lat_exists)]
    uniq = sorted({k for k in keys_raw if k is not None})
    code_of = {k: i for i, k in enumerate(uniq)}
    doc_bucket = np.array([code_of.get(k, -1) for k in keys_raw],
                          dtype=np.int32)
    return _bucket_dense_plan(node, ctx, doc_bucket, max(len(uniq), 1),
                              render={"kind": "grid", "keys": uniq,
                                      "body": node.body})


_BASE32 = "0123456789bcdefghjkmnpqrstuvwxyz"


def _geohash(lat: float, lon: float, precision: int) -> str:
    lat_lo, lat_hi = -90.0, 90.0
    lon_lo, lon_hi = -180.0, 180.0
    out = []
    bit = 0
    ch = 0
    even = True
    while len(out) < precision:
        if even:
            mid = (lon_lo + lon_hi) / 2
            if lon >= mid:
                ch = ch * 2 + 1
                lon_lo = mid
            else:
                ch = ch * 2
                lon_hi = mid
        else:
            mid = (lat_lo + lat_hi) / 2
            if lat >= mid:
                ch = ch * 2 + 1
                lat_lo = mid
            else:
                ch = ch * 2
                lat_hi = mid
        even = not even
        bit += 1
        if bit == 5:
            out.append(_BASE32[ch])
            bit = 0
            ch = 0
    return "".join(out)


def _geotile(lat: float, lon: float, zoom: int) -> str:
    import math
    n = 2 ** zoom
    x = int((lon + 180.0) / 360.0 * n)
    lat_r = math.radians(max(min(lat, 85.0511), -85.0511))
    y = int((1.0 - math.log(math.tan(lat_r) + 1 / math.cos(lat_r))
             / math.pi) / 2.0 * n)
    return f"{zoom}/{min(x, n - 1)}/{min(y, n - 1)}"


def _c_matrix_stats(node: AggNode, ctx: _Ctx) -> AggPlan:
    fields = node.body.get("fields")
    if not fields:
        raise ParsingError(f"[matrix_stats] aggregation [{node.name}] "
                           f"requires [fields]")
    return AggPlan(node.name, "matrix_stats", static=(tuple(fields),),
                   render={"kind": "matrix_stats", "fields": list(fields),
                           "body": node.body})


_COMPILERS = {
    "terms": _c_terms,
    "histogram": _c_histogram,
    "date_histogram": _c_date_histogram,
    "range": _c_range,
    "date_range": _c_range,
    "ip_range": _c_range,
    "filter": _c_filter,
    "filters": _c_filters,
    "nested": _c_nested,
    "reverse_nested": _c_reverse_nested,
    "global": _c_global,
    "missing": _c_missing,
    "min": _c_metric, "max": _c_metric, "sum": _c_metric, "avg": _c_metric,
    "value_count": _c_metric, "stats": _c_metric, "extended_stats": _c_metric,
    "median_absolute_deviation": _c_percentiles,
    "cardinality": _c_cardinality,
    "percentiles": _c_percentiles,
    "percentile_ranks": _c_percentiles,
    "weighted_avg": _c_weighted_avg,
    "composite": _c_composite,
    "multi_terms": _c_multi_terms,
    "auto_date_histogram": _c_auto_date_histogram,
    "significant_terms": _c_significant_terms,
    "adjacency_matrix": _c_adjacency_matrix,
    "geohash_grid": _c_geohash_grid,
    "geotile_grid": _c_geohash_grid,
    "geo_bounds": _c_geo_bounds,
    "geo_centroid": _c_geo_bounds,
    "matrix_stats": _c_matrix_stats,
}


# ---------------------------------------------------------------- device eval

def eval_aggs(plans: List[AggPlan], seg: Dict, inputs: List[Dict],
              cursor: List[int], mask, outs: List, batch: int = 1):
    """Trace the collection program. mask: eligible docs [Dp] bool (the
    query's result set). Appends each node's partial arrays dict to
    `outs` in traversal order.

    Bucket membership is threaded as a FACTORED context (bin, pmask,
    card, static) instead of the dense parent_eff ordinal vector of the
    scatter design: `bin` [Dp] int32 is the parent bucket id (-1 = none)
    and is segment-STATIC for field-driven bucketing (terms / histogram /
    filter / missing / dense-bucket trees), while every query-dependent
    condition accumulates in `pmask` [Dp] bool. With static bins, binned
    add-reductions become one-hot matmuls whose one-hot matrix is shared
    across a vmapped query batch (see _binned_sums) — the MXU path the
    reference's per-doc collector loops can't express. Kinds whose bins
    are genuinely data-dependent (nested joins, dedup) drop to the
    scatter path by passing static=False."""
    # root context sentinels: pbin=None ⇒ every doc is in bucket 0 (no
    # per-doc gather needed), pmask=None ⇒ no accumulated dynamic parent
    # constraint (skips a gather + AND per agg node on the hot path)
    # room: (the lanes one bin of the context can hold, None while that
    # is all of them; the `batch` queries the caller vmaps this trace
    # over): static, and all `_scatter_sum` reads of it
    ctx = (None, None, 1, True, (None, batch))
    for plan in plans:
        _eval_agg(plan, seg, inputs, cursor, mask, ctx, outs)


def _pack_bits(ok):
    """bool [..., n] → uint32 [..., n/32] bitmask (n % 32 == 0)."""
    x = ok.reshape(ok.shape[:-1] + (-1, 32)).astype(jnp.uint32)
    w = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return (x * w).sum(-1).astype(jnp.uint32)


def _sum_ways(total: int, room: int, batch: int = 1) -> int:
    """How many interleaved partial accumulators a float bin gets when
    one bin can hold `room` of the lanes scattered into `total` bins:
    the largest power of two up to AGG_SUM_WAYS that keeps bins x ways
    under AGG_SUM_MAX_ACCUMULATORS (1: the plain scatter-add), doubled
    until no partial can be fed more than AGG_SUM_LANES_A_PARTIAL lanes,
    for as long as the `batch` queries of the program together stay
    under AGG_SUM_MAX_GROWN accumulators."""
    ways = AGG_SUM_WAYS
    while ways > 1 and total * ways > AGG_SUM_MAX_ACCUMULATORS:
        ways //= 2
    while ways * AGG_SUM_LANES_A_PARTIAL < room \
            and batch * total * ways * 2 <= AGG_SUM_MAX_GROWN:
        ways *= 2
    return ways


def _scatter_sum(safe, total: int, v, dt, room=(None, 1)):
    """Σ of `v` into `total` bins by scatter-add; `safe` [n] int32 holds
    the bin of each lane, `total` for a lane that drops. Integer sums
    are exact in any order. A float sum goes through `_sum_ways`
    interleaved partials a bin and a pairwise tree over them (see
    AGG_SUM_WAYS): the same number of lanes scattered, blockwise float32
    partial sums instead of one sequential accumulation. `room`: (the
    lanes one bin can hold, where the caller knows a bound under all of
    them; the queries the program batches)."""
    lanes_a_bin, batch = room
    ways = _sum_ways(total, min(lanes_a_bin or safe.shape[0],
                                safe.shape[0]), batch) \
        if jnp.issubdtype(dt, jnp.floating) else 1
    if ways == 1:
        return jnp.zeros(total, dt).at[safe].add(v.astype(dt), mode="drop")
    lane = jnp.arange(safe.shape[0], dtype=safe.dtype) & (ways - 1)
    # a dropped lane's bin is `total`: past the last accumulator
    part = jnp.zeros(total * ways, dt).at[safe * ways + lane].add(
        v.astype(dt), mode="drop").reshape(total, ways)
    while part.shape[-1] > 1:
        part = part[:, 0::2] + part[:, 1::2]
    return part[:, 0]


def _binned_sums(bin_lanes, total: int, contribs, static_bins: bool,
                 room=(None, 1)):
    """Per-bin Σ of each (values, out_dtype) contrib. bin_lanes: [n]
    int32; entries outside [0, total) drop. Contribs carry the DYNAMIC
    eligibility (ineligible lanes contribute 0); bin_lanes carries the
    static structure.

    Kernel choice, fastest first:
    - bool contribs (bucket/count/presence — the hot shapes): bit-packed
      popcount against static per-bin bitmasks. Exact integer counts at
      ~1/20th the ops of the one-hot matmul; pure VPU/AVX work.
    - float contribs with static bins: ONE [n, total] one-hot serves
      every query of a vmapped batch, reduced as a [B, n] × [n, total]
      matmul (the MXU path) at full f32 operand precision
      (ops.F32_MATMUL). f32 accumulation exact below 2^24.
    - dynamic bins or many bins: scatter-add (`_scatter_sum`; a float
      sum in interleaved partials, so that it stays within 1e-6 of the
      float64 sum).
    """
    n = bin_lanes.shape[0]
    out: List[Any] = [None] * len(contribs)
    if static_bins and total <= AGG_GEMM_MAX_BINS:
        bool_idx = [i for i, (v, dt) in enumerate(contribs)
                    if v.dtype == jnp.bool_ and n % 32 == 0
                    and n * total <= AGG_POPCOUNT_MAX_ELEMS]
        if bool_idx:
            binmask = (bin_lanes[None, :]
                       == jnp.arange(total, dtype=bin_lanes.dtype)[:, None])
            binbits = _pack_bits(binmask)            # [total, n/32] static
            for i in bool_idx:
                v, dt = contribs[i]
                okbits = _pack_bits(v)               # [n/32]
                inter = okbits[None, :] & binbits    # [total, n/32]
                out[i] = jax.lax.population_count(inter).sum(-1).astype(dt)
        rest = [i for i in range(len(contribs)) if out[i] is None]
        if rest and n * total <= AGG_GEMM_MAX_ELEMS:
            onehot = (bin_lanes[:, None]
                      == jnp.arange(total, dtype=bin_lanes.dtype)).astype(
                jnp.float32)
            for i in rest:
                v, dt = contribs[i]
                s = jnp.matmul(v.astype(jnp.float32), onehot,
                               precision=F32_MATMUL)
                out[i] = s.astype(dt)
            return out
        if not rest:
            return out
        safe = jnp.where((bin_lanes >= 0) & (bin_lanes < total),
                         bin_lanes, total)
        for i in rest:
            v, dt = contribs[i]
            out[i] = _scatter_sum(safe, total, v, dt, room)
        return out
    safe = jnp.where((bin_lanes >= 0) & (bin_lanes < total),
                     bin_lanes, total)
    return [_scatter_sum(safe, total, v, dt, room) for v, dt in contribs]


def _pairs_context(seg, col, mask, parent_eff, d_pad):
    doc_ids = col["doc_ids"]
    valid = doc_ids >= 0
    safe_doc = jnp.where(valid, doc_ids, 0)
    ok = valid & mask[safe_doc]
    parent = parent_eff[safe_doc]
    return safe_doc, ok & (parent >= 0), parent


def _ctx_parent_eff(ctx, d_pad):
    """Collapse the factored context back to the dense parent ordinal
    vector ([Dp] int32, -1 = no bucket) for kinds on the scatter path."""
    pbin, pmask, pcard = ctx[:3]
    if pbin is None and pmask is None:
        return jnp.zeros(d_pad, jnp.int32)
    if pbin is None:
        return jnp.where(pmask, 0, -1)
    if pmask is None:
        return pbin
    return jnp.where(pmask & (pbin >= 0), pbin, -1)


def _take_doc(arr, safe_doc, ident: bool):
    """arr[safe_doc], but a contiguous SLICE when the pairs layout is the
    identity (doc k ↔ lane k): XLA gathers are scalar loops on CPU and a
    serial path on TPU; slices vectorize. Tail lanes then carry arr[k]
    for padding k — every consumer masks them with the static bin_ok."""
    if ident:
        n = safe_doc.shape[0]
        m = arr.shape[-1]
        if n == m:
            return arr
        if n < m:
            return arr[..., :n]
    return arr[..., safe_doc] if arr.ndim > 1 else arr[safe_doc]


def _gather_ok(mask, pmask, safe_doc, ident: bool = False):
    """Dynamic doc-eligibility for a pairs gather, skipping the parent
    gather when no dynamic parent constraint exists (root sentinel)."""
    ok = _take_doc(mask, safe_doc, ident)
    if pmask is not None:
        ok = ok & _take_doc(pmask, safe_doc, ident)
    return ok


def _and_pmask(pmask, extra):
    return extra if pmask is None else (pmask & extra)


def _eval_agg(plan: AggPlan, seg: Dict, inputs: List[Dict], cursor: List[int],
              mask, ctx, outs: List):
    my = inputs[cursor[0]]
    cursor[0] += 1
    d_pad = seg["live"].shape[0]
    kind = plan.kind
    pbin, pmask, parent_card, pstatic, room = ctx

    if kind == "empty":
        outs.append({})
        child_ctx = (jnp.full(d_pad, -1, jnp.int32), pmask, parent_card,
                     True, room)
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, mask, child_ctx, outs)
        return

    if kind == "multi":
        outs.append({})
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, mask, ctx, outs)
        return

    if kind in ("bucket_bits", "presence_bits"):
        # fused leaf bucketing: the whole static side (lane→bin mapping,
        # membership bitmasks, bit packing) was precomputed at compile and
        # rides the executable as a constant — per query the device packs
        # the dynamic eligibility and popcounts it against each bucket row
        field, card, ident, src = plan.static
        col = seg[src][field]
        doc_ids = col["doc_ids"]
        valid = doc_ids >= 0
        safe_doc = jnp.where(valid, doc_ids, 0)
        ok = _gather_ok(mask, pmask, safe_doc, ident)
        binbits = jnp.asarray(plan.const_inputs["binbits"])  # [card, n/32]
        okbits = _pack_bits(ok)                              # [n/32]
        counts = jax.lax.population_count(
            okbits[None, :] & binbits).sum(-1).astype(jnp.int32)
        outs.append({"counts": counts} if kind == "bucket_bits"
                    else {"present": counts > 0})
        return

    if kind in ("bucket_ord", "bucket_num"):
        field, card, ident = plan.static[:3]
        col = seg["ordinal" if kind == "bucket_ord" else "numeric"][field]
        doc_ids = col["doc_ids"]
        valid = doc_ids >= 0
        safe_doc = jnp.where(valid, doc_ids, 0)
        # static side: which bin each (doc, value) pair lands in. A
        # dictionary ordinal, and a rank whose table is the identity, is
        # the bin; any other table says -1 for "no bucket"
        bins = None if kind == "bucket_ord" else plan.static[3]
        if bins is None:
            b = col["ords"]
        elif bins == BINS_RANK:
            b = col["val_ords"]
        elif bins == BINS_TABLE:
            b = my["table"][col["val_ords"]]
        else:
            b = seg["lane_bins"][bins]
        bin_ok = valid if bins in (None, BINS_RANK) else valid & (b >= 0)
        total = parent_card * card
        base = 0
        if pbin is not None:
            pb = _take_doc(pbin, safe_doc, ident)
            bin_ok = bin_ok & (pb >= 0)
            base = pb * card
        bin_lanes = jnp.where(bin_ok, base + b, total)
        # dynamic side: whether the pair's doc is in the query/parent set
        ok_dyn = _gather_ok(mask, pmask, safe_doc, ident)
        (counts,) = _binned_sums(bin_lanes, total,
                                 [(ok_dyn & bin_ok, jnp.int32)], pstatic)
        outs.append({"counts": counts})
        if plan.children:
            # dense per-doc child bucket from the STATIC pair structure
            # (multi-valued docs keep the max bin — the engine's
            # single-bucket simplification); dynamic membership rides the
            # child pmask, so this scatter stays unbatched under vmap
            if ident and bin_lanes.shape[0] <= d_pad:
                # identity pairs (doc k <-> lane k): the lanes ARE the
                # docs, so the scatter-max is a select, padded to d_pad
                child_bin = jnp.where(bin_ok, bin_lanes, -1)
                if child_bin.shape[0] < d_pad:
                    child_bin = jnp.pad(
                        child_bin, (0, d_pad - child_bin.shape[0]),
                        constant_values=-1)
            else:
                child_bin = jnp.full(d_pad, -1, jnp.int32).at[
                    jnp.where(bin_ok, safe_doc, d_pad)].max(
                    jnp.where(bin_ok, bin_lanes, -1), mode="drop")
            # a child's bin is a part of one of this level's buckets
            # and of its parent's: it holds no more lanes than either
            own = plan_bin_room(plan)
            child_room = room if own is None \
                else (min(room[0] or own, own), room[1])
            child_ctx = (child_bin, _and_pmask(pmask, mask), total,
                         pstatic, child_room)
            for c in plan.children:
                _eval_agg(c, seg, inputs, cursor, mask, child_ctx, outs)
        return

    if kind == "filter":
        scores, matches = _eval_plan(plan.query_plan, seg, inputs, cursor)
        bin_lanes = jnp.zeros(d_pad, jnp.int32) if pbin is None \
            else jnp.where(pbin >= 0, pbin, parent_card)
        own_dyn = matches & mask
        if pmask is not None:
            own_dyn = own_dyn & pmask
        (counts,) = _binned_sums(bin_lanes, parent_card,
                                 [(own_dyn, jnp.int32)], pstatic)
        outs.append({"counts": counts})
        child_ctx = (pbin, _and_pmask(pmask, mask & matches), parent_card,
                     pstatic, room)
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, mask, child_ctx, outs)
        return

    if kind == "global":
        gmask = seg["live"] & (jnp.arange(d_pad, dtype=jnp.int32)
                               < seg["live"].shape[0])
        # num_docs bound is enforced by live padding (padding rows are
        # dead); the query mask is deliberately IGNORED (GlobalAggregator)
        bin_lanes = jnp.zeros(d_pad, jnp.int32) if pbin is None \
            else jnp.where(pbin >= 0, pbin, parent_card)
        own_dyn = gmask if pmask is None else (gmask & pmask)
        (counts,) = _binned_sums(bin_lanes, parent_card,
                                 [(own_dyn, jnp.int32)], pstatic)
        outs.append({"counts": counts})
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, gmask, ctx, outs)
        return

    if kind == "missing":
        ctype, field = plan.static
        if ctype == "numeric":
            exists = seg["numeric"][field]["exists"]
        elif ctype == "ordinal":
            exists = seg["ordinal"][field]["exists"]
        elif ctype == "vector":
            exists = seg["vector"][field]["exists"]
        else:
            exists = jnp.zeros(d_pad, jnp.bool_)
        # field existence is segment-static: fold it into the bin side
        miss_bin = jnp.where(exists, -1,
                             jnp.zeros(d_pad, jnp.int32)
                             if pbin is None else pbin)
        bin_lanes = jnp.where(miss_bin >= 0, miss_bin, parent_card)
        own_dyn = mask if pmask is None else (mask & pmask)
        (counts,) = _binned_sums(bin_lanes, parent_card,
                                 [(own_dyn, jnp.int32)], pstatic)
        outs.append({"counts": counts})
        child_ctx = (miss_bin, _and_pmask(pmask, mask), parent_card,
                     pstatic, room)
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, mask, child_ctx, outs)
        return

    if kind == "bucket_dense":
        card, = plan.static
        b = my["doc_bucket"]
        total = parent_card * card
        bin_ok = b >= 0
        base = 0
        if pbin is not None:
            bin_ok = bin_ok & (pbin >= 0)
            base = pbin * card
        bin_lanes = jnp.where(bin_ok, base + b, total)
        own_dyn = mask if pmask is None else (mask & pmask)
        (counts,) = _binned_sums(bin_lanes, total,
                                 [(own_dyn & bin_ok, jnp.int32)],
                                 pstatic)
        outs.append({"counts": counts})
        child_bin = jnp.where(bin_ok, bin_lanes, -1)
        child_ctx = (child_bin, _and_pmask(pmask, mask), total, pstatic,
                     room)
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, mask, child_ctx, outs)
        return

    if kind == "metric_missing_only":
        needs, missing = plan.static
        bin_lanes = (jnp.zeros(d_pad, jnp.int32) if pbin is None
                     else jnp.where(pbin >= 0, pbin, parent_card))
        okm = mask if pmask is None else (mask & pmask)
        out = {}
        parts = []
        if "cnt" in needs:
            parts.append(("cnt", okm, jnp.int32))
        if "sum" in needs:
            parts.append(("sum", okm.astype(jnp.float32) * missing,
                          jnp.float32))
        if "sumsq" in needs:
            parts.append(("sumsq",
                          okm.astype(jnp.float32) * (missing * missing),
                          jnp.float32))
        if parts:
            sums = _binned_sums(bin_lanes, parent_card,
                                [(v, dt) for _, v, dt in parts], pstatic,
                                room)
            for (nm, _, _), v in zip(parts, sums):
                out[nm] = v
        eff = jnp.where(okm & (bin_lanes < parent_card), bin_lanes,
                        parent_card)
        if "min" in needs:
            out["min"] = jnp.full(parent_card, POS_INF, jnp.float32).at[
                eff].min(jnp.where(okm, missing, POS_INF), mode="drop")
        if "max" in needs:
            out["max"] = jnp.full(parent_card, NEG_INF, jnp.float32).at[
                eff].max(jnp.where(okm, missing, NEG_INF), mode="drop")
        outs.append(out)
        return

    if kind == "metric_num":
        field, needs, ident, missing = (plan.static + (None,))[:4] \
            if len(plan.static) < 4 else plan.static
        col = seg["numeric"][field]
        doc_ids = col["doc_ids"]
        valid = doc_ids >= 0
        safe_doc = jnp.where(valid, doc_ids, 0)
        bin_ok = valid
        pb = 0
        if pbin is not None:
            pb = _take_doc(pbin, safe_doc, ident)
            bin_ok = bin_ok & (pb >= 0)
        bin_lanes = jnp.where(bin_ok, pb, parent_card)
        ok_dyn = _gather_ok(mask, pmask, safe_doc, ident) & bin_ok
        v = col["values_f32"]
        out: Dict[str, Any] = {}
        gemm_parts = []
        if "cnt" in needs:
            gemm_parts.append(("cnt", ok_dyn, jnp.int32))
        if "sum" in needs:
            gemm_parts.append(("sum", jnp.where(ok_dyn, v, 0.0),
                               jnp.float32))
        if "sumsq" in needs:
            gemm_parts.append(("sumsq", jnp.where(ok_dyn, v * v, 0.0),
                               jnp.float32))
        if gemm_parts:
            sums = _binned_sums(bin_lanes, parent_card,
                                [(c, dt) for _, c, dt in gemm_parts],
                                pstatic, room)
            for (name, _, _), s in zip(gemm_parts, sums):
                out[name] = s
        # min/max have no matmul form — masked scatter reductions
        eff = jnp.where(ok_dyn, bin_lanes, parent_card)
        if "min" in needs:
            out["min"] = jnp.full(parent_card, POS_INF, jnp.float32).at[
                eff].min(jnp.where(ok_dyn, v, POS_INF), mode="drop")
        if "max" in needs:
            out["max"] = jnp.full(parent_card, NEG_INF, jnp.float32).at[
                eff].max(jnp.where(ok_dyn, v, NEG_INF), mode="drop")
        if missing is not None:
            # docs WITHOUT the field contribute the substitute value
            # (ValuesSourceConfig#missing) — doc-space contributions on
            # top of the pairs-space reductions above
            exists = col["exists"]
            bin_m = (jnp.zeros(d_pad, jnp.int32) if pbin is None
                     else jnp.where(pbin >= 0, pbin, parent_card))
            okm = (mask if pmask is None else (mask & pmask)) & ~exists
            parts = []
            if "cnt" in needs:
                parts.append(("cnt", okm, jnp.int32))
            if "sum" in needs:
                parts.append(("sum", okm.astype(jnp.float32) * missing,
                              jnp.float32))
            if "sumsq" in needs:
                parts.append(("sumsq", okm.astype(jnp.float32)
                              * (missing * missing), jnp.float32))
            if parts:
                sums_m = _binned_sums(bin_m, parent_card,
                                      [(vv, dt) for _, vv, dt in parts],
                                      pstatic, room)
                for (nm, _, _), vv in zip(parts, sums_m):
                    out[nm] = out[nm] + vv
            eff_m = jnp.where(okm & (bin_m < parent_card), bin_m,
                              parent_card)
            if "min" in needs:
                out["min"] = out["min"].at[eff_m].min(
                    jnp.where(okm, jnp.float32(missing), POS_INF),
                    mode="drop")
            if "max" in needs:
                out["max"] = out["max"].at[eff_m].max(
                    jnp.where(okm, jnp.float32(missing), NEG_INF),
                    mode="drop")
        outs.append(out)
        return

    if kind == "count_ord":
        field, ident = plan.static
        col = seg["ordinal"][field]
        doc_ids = col["doc_ids"]
        valid = doc_ids >= 0
        safe_doc = jnp.where(valid, doc_ids, 0)
        bin_ok = valid
        pb = 0
        if pbin is not None:
            pb = _take_doc(pbin, safe_doc, ident)
            bin_ok = bin_ok & (pb >= 0)
        bin_lanes = jnp.where(bin_ok, pb, parent_card)
        ok_dyn = _gather_ok(mask, pmask, safe_doc, ident) & bin_ok
        (cnt,) = _binned_sums(bin_lanes, parent_card,
                              [(ok_dyn, jnp.int32)], pstatic)
        outs.append({"cnt": cnt})
        return

    if kind in ("presence_ord", "presence_num", "value_hist"):
        field, card, ident = plan.static
        col = seg["ordinal" if kind == "presence_ord" else "numeric"][field]
        ords = col["ords"] if kind == "presence_ord" else col["val_ords"]
        doc_ids = col["doc_ids"]
        total = parent_card * card
        if total > MAX_AGG_BINS:
            raise IllegalArgumentError(
                f"aggregation [{plan.name}] needs {total} bins "
                f"(> {MAX_AGG_BINS}); reduce bucket count or cardinality")
        valid = doc_ids >= 0
        safe_doc = jnp.where(valid, doc_ids, 0)
        bin_ok = valid
        base = 0
        if pbin is not None:
            pb = _take_doc(pbin, safe_doc, ident)
            bin_ok = bin_ok & (pb >= 0)
            base = pb * card
        bin_lanes = jnp.where(bin_ok, base + ords, total)
        ok_dyn = _gather_ok(mask, pmask, safe_doc, ident) & bin_ok
        (hist,) = _binned_sums(bin_lanes, total,
                               [(ok_dyn, jnp.int32)], pstatic)
        if kind == "value_hist":
            outs.append({"hist": hist})
        else:
            outs.append({"present": hist > 0})
        return

    if kind == "weighted_avg":
        vf, wf, ident = plan.static
        vcol = seg["numeric"][vf]
        wcol = seg["numeric"][wf]
        doc_ids = vcol["doc_ids"]
        valid = doc_ids >= 0
        safe_doc = jnp.where(valid, doc_ids, 0)
        bin_ok = valid
        pb = 0
        if pbin is not None:
            pb = _take_doc(pbin, safe_doc, ident)
            bin_ok = bin_ok & (pb >= 0)
        bin_lanes = jnp.where(bin_ok, pb, parent_card)
        # dense single-value weight per doc via min_rank decode
        w_dense = wcol["unique_f32"][jnp.clip(wcol["min_rank"], 0,
                                              wcol["unique_f32"].shape[0] - 1)]
        w = jnp.where(wcol["exists"][safe_doc], w_dense[safe_doc], 0.0)
        ok_dyn = (_gather_ok(mask, pmask, safe_doc, ident) & bin_ok
                  & wcol["exists"][safe_doc])
        v = vcol["values_f32"]
        sum_wv, sum_w = _binned_sums(
            bin_lanes, parent_card,
            [(jnp.where(ok_dyn, v * w, 0.0), jnp.float32),
             (jnp.where(ok_dyn, w, 0.0), jnp.float32)], pstatic, room)
        outs.append({"sum_wv": sum_wv, "sum_w": sum_w})
        return

    # ---- scatter-path kinds: bins are data-dependent (joins, dedup) or
    # rarely hot; they consume the dense parent ordinal vector and hand
    # their children a dynamic (static=False) context
    parent_eff = _ctx_parent_eff(ctx, d_pad)

    if kind == "nested":
        # doc set becomes the path's child rows whose ROOT is in the
        # current bucket set; each child inherits its root's bucket ord
        # (bucket/nested/NestedAggregator.java)
        pptr = seg["parent_ptr"]
        safe_p = jnp.where(pptr >= 0, pptr, 0)
        own = (seg["nested_path"] == my["path_ord"]) \
            & (my["path_ord"] >= 0) & seg["live"] & (pptr >= 0) \
            & mask[safe_p] & (parent_eff[safe_p] >= 0)
        child_eff = jnp.where(own, parent_eff[safe_p], -1)
        eff = jnp.where(own, child_eff, parent_card)
        counts = jnp.zeros(parent_card, jnp.int32).at[eff].add(
            own.astype(jnp.int32), mode="drop")
        outs.append({"counts": counts})
        # child rows are another doc space: a parent bin's room says
        # nothing of how many of them it holds
        child_ctx = (child_eff, jnp.ones(d_pad, jnp.bool_), parent_card,
                     False, (None, room[1]))
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, own, child_ctx, outs)
        return

    if kind == "reverse_nested":
        # back to root rows (ReverseNestedAggregator.java): the bucket
        # count is DISTINCT roots per bucket — dedup (bucket, root) pairs
        # with a two-key sort + run-start flags, since one root's children
        # may sit in several buckets
        import jax as _jax
        pptr = seg["parent_ptr"]
        sel = mask & (parent_eff >= 0) & (pptr >= 0)
        eff_k = jnp.where(sel, parent_eff, parent_card)
        root_k = jnp.where(sel, pptr, d_pad)
        se, sr = _jax.lax.sort([eff_k, root_k], num_keys=2)
        first = jnp.concatenate([
            jnp.ones((1,), bool),
            (se[1:] != se[:-1]) | (sr[1:] != sr[:-1])])
        valid = first & (se < parent_card)
        counts = jnp.zeros(parent_card, jnp.int32).at[
            jnp.where(valid, se, parent_card)].add(
            valid.astype(jnp.int32), mode="drop")
        outs.append({"counts": counts})
        # sub-aggs evaluate over root rows; a root carries ONE bucket ord
        # (the engine's dense child_eff convention — same single-bucket
        # simplification bucket_ord applies to multi-valued fields)
        idx = jnp.where(sel, pptr, d_pad)
        root_eff = jnp.full(d_pad, -1, jnp.int32).at[idx].max(
            jnp.where(sel, parent_eff, -1), mode="drop")
        own = root_eff >= 0
        child_ctx = (root_eff, jnp.ones(d_pad, jnp.bool_), parent_card,
                     False, (None, room[1]))
        for c in plan.children:
            _eval_agg(c, seg, inputs, cursor, own, child_ctx, outs)
        return

    if kind == "adjacency":
        n_filters, = plan.static
        masks = []
        for qp in plan.query_plans:
            _, m = _eval_plan(qp, seg, inputs, cursor)
            masks.append(m & mask & (parent_eff >= 0))
        parent = jnp.where(parent_eff >= 0, parent_eff, 0)
        out: Dict[str, Any] = {}
        for i in range(n_filters):
            for j in range(i, n_filters):
                own = masks[i] & masks[j]
                eff = jnp.where(own, parent, parent_card)
                out[f"c_{i}_{j}"] = jnp.zeros(
                    parent_card, jnp.int32).at[eff].add(
                    own.astype(jnp.int32), mode="drop")
        outs.append(out)
        return

    if kind == "matrix_stats":
        from opensearch_tpu.search.plan_eval import dense_numeric
        fields = plan.static[0]
        dense = {}
        for f in fields:
            if f in seg["numeric"]:
                dense[f] = dense_numeric(seg, f, d_pad)
        out = {}
        parent = jnp.where(parent_eff >= 0, parent_eff, 0)
        for f in fields:
            if f not in dense:
                continue
            v, exists, _ = dense[f]
            own = mask & (parent_eff >= 0) & exists
            eff = jnp.where(own, parent, parent_card)
            zeros = lambda: jnp.zeros(parent_card, jnp.float32)  # noqa: E731
            vv = jnp.where(own, v, 0.0)
            out[f"{f}::cnt"] = jnp.zeros(parent_card, jnp.int32).at[eff].add(
                own.astype(jnp.int32), mode="drop")
            out[f"{f}::sum"] = zeros().at[eff].add(vv, mode="drop")
            out[f"{f}::sum2"] = zeros().at[eff].add(vv * vv, mode="drop")
            out[f"{f}::sum3"] = zeros().at[eff].add(vv ** 3, mode="drop")
            out[f"{f}::sum4"] = zeros().at[eff].add(vv ** 4, mode="drop")
        for i, fa in enumerate(fields):
            for fb in fields[i + 1:]:
                if fa not in dense or fb not in dense:
                    continue
                va, ea, _ = dense[fa]
                vb, eb, _ = dense[fb]
                own = mask & (parent_eff >= 0) & ea & eb
                eff = jnp.where(own, parent, parent_card)
                out[f"{fa}*{fb}::sumxy"] = jnp.zeros(
                    parent_card, jnp.float32).at[eff].add(
                    jnp.where(own, va * vb, 0.0), mode="drop")
                out[f"{fa}*{fb}::cnt"] = jnp.zeros(
                    parent_card, jnp.int32).at[eff].add(
                    own.astype(jnp.int32), mode="drop")
                out[f"{fa}*{fb}::sumx"] = jnp.zeros(
                    parent_card, jnp.float32).at[eff].add(
                    jnp.where(own, va, 0.0), mode="drop")
                out[f"{fa}*{fb}::sumy"] = jnp.zeros(
                    parent_card, jnp.float32).at[eff].add(
                    jnp.where(own, vb, 0.0), mode="drop")
        outs.append(out)
        return

    if kind == "geo_metric":
        from opensearch_tpu.search.plan_eval import dense_numeric
        field = plan.static[0]
        lat_key, lon_key = f"{field}.lat", f"{field}.lon"
        if lat_key not in seg["numeric"]:
            outs.append({})
            return
        lat, exists, _ = dense_numeric(seg, lat_key, d_pad)
        lon, _, _ = dense_numeric(seg, lon_key, d_pad)
        own = mask & (parent_eff >= 0) & exists
        parent = jnp.where(parent_eff >= 0, parent_eff, 0)
        eff = jnp.where(own, parent, parent_card)
        outs.append({
            "cnt": jnp.zeros(parent_card, jnp.int32).at[eff].add(
                own.astype(jnp.int32), mode="drop"),
            "sum_lat": jnp.zeros(parent_card, jnp.float32).at[eff].add(
                jnp.where(own, lat, 0.0), mode="drop"),
            "sum_lon": jnp.zeros(parent_card, jnp.float32).at[eff].add(
                jnp.where(own, lon, 0.0), mode="drop"),
            "min_lat": jnp.full(parent_card, POS_INF, jnp.float32)
                .at[eff].min(jnp.where(own, lat, POS_INF), mode="drop"),
            "max_lat": jnp.full(parent_card, NEG_INF, jnp.float32)
                .at[eff].max(jnp.where(own, lat, NEG_INF), mode="drop"),
            "min_lon": jnp.full(parent_card, POS_INF, jnp.float32)
                .at[eff].min(jnp.where(own, lon, POS_INF), mode="drop"),
            "max_lon": jnp.full(parent_card, NEG_INF, jnp.float32)
                .at[eff].max(jnp.where(own, lon, NEG_INF), mode="drop"),
        })
        return

    raise QueryShardError(f"unknown aggregation plan kind [{plan.kind}]")
