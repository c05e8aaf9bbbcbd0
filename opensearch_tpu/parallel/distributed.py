"""SPMD scatter-gather search: one shard per device on a `Mesh`.

Re-design of the reference's coordinator fan-out + incremental reduce
(action/search/TransportSearchAction.java:284 scatters the query phase to one
copy of every shard; action/search/QueryPhaseResultConsumer.java:72 and
SearchPhaseController.java:228 mergeTopDocs reduce partial top-docs; 453
reducedQueryPhase merges agg trees). On TPU the fan-out is a mesh axis: every
device holds one shard's columnar segment image in HBM, shard_map evaluates
the compiled plan locally, then the partial reduce happens on-chip —
`all_gather` of per-shard top-k candidates over ICI followed by a replicated
`top_k` merge, and `psum` for total-hit counts. Aggregation partials stay
sharded on the way out; the host runs the existing cross-segment reduce
(search/aggs/reduce.py), mirroring the reference's coordinator-side
InternalAggregations.topLevelReduce.

Shape discipline: all shards must share one padded bucket shape (the segment
uploader's power-of-two bucketing — ops/device_segment.py — makes unequal
shards stackable) and one plan signature; the compiler guarantees equal
signatures for the same query because plan structure depends only on the
query and mapper, while per-shard constants live in the stacked inputs.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map_impl
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import dataclasses


def _shard_map(f, mesh, in_specs, out_specs):
    return _shard_map_impl(f, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)

# host→device transfer accounting (bytes), for tests/benchmarks asserting
# that segments are NOT re-uploaded per query:
# every explicit upload in this module increments it
TRANSFER_BYTES = [0]    # shared-state-ok: test-only accounting slot; the int write is GIL-atomic and tests serialize


def mesh_device_split(mesh: Mesh, nbytes: int):
    """Equal per-device byte shares of a leading-axis-sharded upload
    [(device_id, nbytes), ...], summing EXACTLY to `nbytes` (the
    remainder lands on the first device) — the conservation invariant
    the per-device ledger table is pinned against. Equal shares are
    exact for this module's uploads: every stacked leading axis is
    n_devices × rows_per_dev."""
    devs = [int(d.id) for d in mesh.devices.flatten()]
    share, rem = divmod(int(nbytes), len(devs))
    return [(d, share + (rem if i == 0 else 0))
            for i, d in enumerate(devs)]


def _device_put_sharded_tree(tree, mesh: Mesh, axis: str,
                             channel: str = "upload.corpus"):
    """Upload a stacked host pytree to device HBM, leading axis sharded
    over the mesh; counts the bytes moved — both in the module's
    TRANSFER_BYTES test slot and on the transfer ledger's named channel
    (`upload.corpus` for shard-set builds, `upload.literals` for
    per-query flat inputs), so the SPMD path's h2d traffic shows up in
    `GET /_telemetry/transfers` like the host loop's does. When the
    per-device ledger is on (ISSUE 14), the record carries the exact
    per-device byte split of the sharded upload."""
    from opensearch_tpu.telemetry import TELEMETRY
    sharding = NamedSharding(mesh, P(axis))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    ledger = TELEMETRY.ledger
    scope = ledger.current()
    nbytes = sum(np.asarray(l).nbytes for l in leaves)
    if ledger.enabled or scope is not None:
        splits = mesh_device_split(mesh, nbytes) \
            if ledger.devices.enabled else None
        ledger.record(channel, "h2d", nbytes, scope=scope,
                      devices=splits)
    TRANSFER_BYTES[0] += nbytes
    put = [jax.device_put(np.asarray(l), sharding) for l in leaves]
    return jax.tree_util.tree_unflatten(treedef, put)

from opensearch_tpu.ops import bm25 as _bm25
from opensearch_tpu.ops.bm25 import blockmax_keep_mask, score_text_clause
from opensearch_tpu.ops.topk import NEG_INF, value_merge_key
from opensearch_tpu.search.compile import Plan
from opensearch_tpu.search.plan_eval import _eval_plan
from opensearch_tpu.search.aggs.engine import (BINS_RANK, BINS_TABLE,
                                               eval_aggs, plan_bin_room)
from opensearch_tpu.search.aggs.lane_bins import LaneBinsMemo, lane_bins_row
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.kernels import (jit_family, stage,
                                              timed_first_call)

# one SPMD program is enqueued at a time, literals first: every chip of
# the mesh then runs the programs of concurrent requests in one order
# (their collectives pair up), which is also the order of the requests'
# `dispatch` spans, what lays a device trace on the host's clock
_DISPATCH_LOCK = threading.Lock()


def spmd_blockmax_admitted(plan: Plan, meta, k: int, sort_spec,
                           agg_plans) -> bool:
    """Block-max admission for the SPMD program (ISSUE 20): a pure
    function of facts already in the runner cache key — plan structure
    covers kind/static/input names (the compiler only emits "tid" when
    the gate was on at compile time), _tree_shapes covers the block
    count, meta carries block_bounds, and k/sort_spec/agg arity are key
    components, so admission never needs its own key part. Only single
    bare text clauses prune: a nested or bool context has no per-clause
    competitive threshold, and sorts/aggs consume non-top-k docs the
    mask would hide. Per-row pruning against the row-local k_eff
    threshold stays rank-exact for the merged page (see one_row)."""
    k_eff = min(k, meta.d_pad)
    return (plan.kind == "text" and len(plan.static) > 1
            and not plan.static[0] and "tid" in plan.inputs
            and sort_spec is None and not agg_plans
            and getattr(meta, "block_bounds", False)
            and 0 < k_eff <= _bm25.BLOCKMAX_SLICE_BLOCKS * 128
            and plan.inputs["ids"].shape[-1] >= _bm25.BLOCKMAX_MIN_BLOCKS)


def make_mesh(n_devices: Optional[int] = None, axis: str = "shards") -> Mesh:
    """A 1-D mesh over the first n devices; the `shards` axis is the DP axis
    of SURVEY.md §2.2 (one index shard per device)."""
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))  # sync-ok: host -- device handles, not device arrays


# Fill values that keep padding semantically inert when leaves are grown to
# the cross-shard shape envelope. Names are leaf dict keys from
# ops/device_segment.py (segment arrays) and search/compile.py (plan inputs);
# anything unlisted pads with 0/False, which those layouts treat as "absent"
# (w=0, hit=0, live=False, mask=False, matches=False, ...).
_PAD_FILL: Dict[str, Any] = {
    "post_docs": -1,    # -1 = empty postings lane
    "doc_ids": -1,      # -1 = padding value-pair
    "min_rank": np.int32(2 ** 31 - 1),
    "max_rank": -1,
    "avgdl": 1.0,       # divisor — must stay nonzero
    "ids": -1,          # -1 = padding postings-block lane (no hit)
}


def _grow(arr: np.ndarray, shape: Tuple[int, ...], name: str) -> np.ndarray:
    arr = np.asarray(arr)   # sync-ok: host -- pad_stack_trees operates on host leaves pre-upload
    if arr.shape == tuple(shape):
        return arr
    fill = _PAD_FILL.get(name, False if arr.dtype == np.bool_ else 0)
    out = np.full(shape, fill, dtype=arr.dtype)
    out[tuple(slice(0, s) for s in arr.shape)] = arr
    return out


def pad_stack_trees(trees: Sequence[Any]):
    """Stack per-shard pytrees, growing each leaf to the max shape across
    shards first (trailing padding, per-name inert fill values).

    This is the cross-shard shape envelope: shards whose segments landed in
    different power-of-two buckets (ops/device_segment.py) still execute as
    one SPMD program — the device-side masks treat the grown region as dead
    (live=False, postings lane -1, hit 0)."""
    paths_and_leaves = [jax.tree_util.tree_flatten_with_path(t)
                        for t in trees]
    treedef = paths_and_leaves[0][1]
    for _, td in paths_and_leaves[1:]:
        if td != treedef:
            raise ValueError("shard trees must share structure for SPMD")
    n_leaves = len(paths_and_leaves[0][0])
    stacked = []
    for i in range(n_leaves):
        path = paths_and_leaves[0][0][i][0]
        name = ""
        for p in reversed(path):
            if hasattr(p, "key"):
                name = str(p.key)
                break
        leaves = [np.asarray(pl[0][i][1]) for pl in paths_and_leaves]  # sync-ok: host -- host leaves pre-upload
        ndim = leaves[0].ndim
        if any(l.ndim != ndim for l in leaves):
            raise ValueError(f"leaf {path} rank mismatch across shards")
        shape = tuple(max(l.shape[d] for l in leaves) for d in range(ndim))
        stacked.append(np.stack([_grow(l, shape, name) for l in leaves]))
    return jax.tree_util.tree_unflatten(treedef, stacked)


# agg plan kinds whose static[1] is a bucket cardinality that sizes the
# output arrays and the flattened-ordinal stride (parent_ord * card + ord)
_CARD_KINDS = frozenset(
    {"bucket_ord", "bucket_num", "presence_ord", "presence_num", "value_hist"})


def align_agg_plans(per_shard: Sequence[Sequence[Any]]) -> None:
    """Raise every shard's card statics to the cross-shard max, in place.

    One SPMD program traces a single agg-plan structure, so output bins and
    ordinal strides must agree across shards; per-shard cardinalities (terms
    dictionary size, histogram bucket count) differ, and the max is safe:
    shard-local bucket ordinals are always < their own card ≤ max. Decoding
    each shard's slice with its own (aligned) plans keeps keys segment-local.
    Raises ValueError when plan structures genuinely diverge (e.g. a field
    with no values in one shard compiled to an `empty` node) — callers fall
    back to per-shard host execution then."""

    def walk(nodes: Sequence[Any]):
        for group in zip(*nodes):
            kinds = {p.kind for p in group}
            if len(kinds) != 1:
                raise ValueError(
                    f"agg plan kinds diverge across shards: {kinds}")
            kind = kinds.pop()
            if kind.endswith("_bits"):
                # fused kinds close over per-segment constant bitmasks —
                # no cross-shard alignment can make ONE traced program
                # correct for every row; callers fall back to host loop
                # (compile paths that trace cross-row pass
                # allow_fused=False, so this is defense in depth)
                raise ValueError(
                    f"fused agg kind [{kind}] cannot align across shards")
            if kind == "bucket_num" \
                    and len({p.static[3] for p in group}) > 1:
                # a row whose rank -> bucket table happened to be the
                # identity beside rows whose is not: it takes its own
                # table back (`table_of`: an entry a rank of ITS column;
                # stacked rows grow to the widest), so that the rows
                # keep one structure
                for p in group:
                    if p.static[3] == BINS_RANK:
                        p.static = p.static[:3] + (BINS_TABLE,) \
                            + p.static[4:]
            if kind in _CARD_KINDS:
                card = max(p.static[1] for p in group)
                for p in group:
                    p.static = (p.static[0], card) + tuple(p.static[2:])
                # likewise the lanes a bucket can hold (`_bin_room`, the
                # last static of a level with sub-aggregations): the
                # widest row's, which bounds every row's
                rooms = [plan_bin_room(p) for p in group]
                if any(r is not None for r in rooms):
                    room = max(r for r in rooms if r is not None)
                    for p in group:
                        p.static = p.static[:-1] + (room,)
            elif any(p.static != group[0].static for p in group):
                raise ValueError(
                    f"agg statics diverge across shards for kind {kind}")
            walk([p.children for p in group])
            qps = [p.query_plan for p in group]
            if any((q is None) != (qps[0] is None) for q in qps):
                raise ValueError("filter-agg query plans diverge across shards")

    walk(list(per_shard))


# The lane -> bin vector of a `histogram` or `date_histogram` level is
# derived once a (shard set, field, bucketing) and kept on the mesh,
# int32 `[R_pad, n_pad]` sharded like the image's own columns, in the
# shard set's `LaneBinsMemo`: why, how many and what is counted are in
# search/aggs/lane_bins.py, which the one-chip routes share
# (`search.agg_lane_bins.hit`, `.miss`, `.evicted`). This route alone
# counts a level whose table is the identity here, since it compiles
# every row for every request (the one-chip routes count theirs as
# `search.agg_bins.level.rank`).
LANE_BINS_IDENTITY = TELEMETRY.metrics.counter(
    "search.agg_lane_bins.identity")


def resident_lane_bins(searcher: "DistributedSearcher",
                       shard_set: "HbmShardSet",
                       per_shard: Sequence[Sequence[Any]]) -> List[Any]:
    """Take the static side of the `bucket_num` levels out of the
    request, in place, on rows `align_agg_plans` has brought to one
    structure: a level whose table is the identity reads the rank column
    itself (nothing to do here but count it); a `histogram` or
    `date_histogram` level names a slot of the returned list, which
    holds the shard set's resident lane -> bin vector of that (field,
    bucketing): found (a hit builds, hashes, stacks and uploads no
    table) or derived now from the rows' tables by one program over the
    resident rank column (a miss). A `range` bucket keeps the table it
    brought. `search_resident(lane_bins=...)` hands the list to the
    served program, which is the same executable on the miss and on the
    hit."""
    slots: List[Any] = []

    def walk(nodes: Sequence[Sequence[Any]]):
        for group in zip(*nodes):
            p0 = group[0]
            if p0.kind == "bucket_num" and p0.static[3] == BINS_RANK:
                LANE_BINS_IDENTITY.inc()
            elif p0.kind == "bucket_num" and p0.table_of is not None:
                field = p0.static[0]
                slots.append(shard_set.lane_bins.get(
                    (field,) + p0.bins_key,
                    lambda: searcher.derive_lane_bins(
                        shard_set, field, [p.table_of() for p in group])))
                for p in group:
                    p.static = p.static[:3] + (len(slots) - 1,) \
                        + p.static[4:]
            walk([p.children for p in group])

    walk(list(per_shard))
    return slots


def _count_agg_nodes(p) -> int:
    return 1 + sum(_count_agg_nodes(c) for c in p.children)


def plan_struct(p) -> tuple:
    """Shape-free structural signature (kind/static/children) shared by query
    Plans and AggPlans — the cross-shard compatibility check. Input shapes are
    intentionally excluded: the shape envelope aligns them."""
    qp = getattr(p, "query_plan", None)
    return (p.kind, p.static,
            plan_struct(qp) if qp is not None else None,
            tuple(plan_struct(c) for c in p.children))


def _tree_shapes(tree) -> tuple:
    # NB: v.dtype directly — np.asarray on a device array would fetch it
    return tuple((jax.tree_util.keystr(kp), tuple(v.shape), str(v.dtype))
                 for kp, v in jax.tree_util.tree_flatten_with_path(tree)[0])


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


class HbmShardSet:
    """Cross-query device residency for the stacked shard segments.

    Segments upload ONCE (at refresh/build time) into HBM, sharded one
    shard per device over the mesh; queries then ship only their flat plan
    inputs. This is the HBM-resident discipline of the north star — the
    analog of Lucene's page-cache-warm immutable segment files, but pinned
    in device memory (reference contrast: every query re-reading the full
    index would be absurd; so is re-uploading it per query).
    """

    def __init__(self, searcher: "DistributedSearcher",
                 shard_arrays: Sequence[Dict], metas: Sequence[Any],
                 started: Optional[float] = None):
        if not shard_arrays or len(shard_arrays) != len(metas):
            raise ValueError(
                f"{len(shard_arrays)} shard trees / {len(metas)} metas")
        n = searcher.n_shards
        # rows pack: ceil(R / n) rows per device, padded with copies of
        # row 0 (made inert at query time via a +inf per-row min_score)
        rpd = -(-len(shard_arrays) // n)
        pad = n * rpd - len(shard_arrays)
        shard_arrays = list(shard_arrays) + [shard_arrays[0]] * pad
        metas = list(metas) + [metas[0]] * pad
        self.n_rows = len(shard_arrays) - pad
        self.rows_per_dev = rpd
        self.mesh = searcher.mesh
        self.meta = canonical_meta(metas)
        t0 = time.monotonic()
        stack = pad_stack_trees(shard_arrays)
        t1 = time.monotonic()
        self.seg_stack = _device_put_sharded_tree(
            stack, searcher.mesh, searcher.axis)
        t2 = time.monotonic()
        self.shapes = _tree_shapes(self.seg_stack)
        self.nbytes = sum(
            int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
            for _, v in jax.tree_util.tree_flatten_with_path(
                self.seg_stack)[0])
        # the build on the process track of the always-on span ring
        # (telemetry/tracer.py; the benchmark's `install_shard_set_*_s`):
        # it runs inside the index's first request and belongs to the
        # index. `started`: when the caller began the rows' host images
        ring = TELEMETRY.tracer.spans
        set_id = ring.process(
            "install.shard_set", t0 if started is None else started, t2,
            {"rows": self.n_rows, "devices": n, "nbytes": self.nbytes})
        if started is not None:
            ring.process("install.shard_set.host_images", started, t0,
                         None, set_id)
        ring.process("install.shard_set.stack", t0, t1, None, set_id)
        ring.process("install.shard_set.device_put", t1, t2, None, set_id)
        # the resident lane -> bin vectors of this set's rows
        # (`resident_lane_bins`): they live and die with the set, so a
        # refresh's new set starts with none
        self.lane_bins = LaneBinsMemo(on_change=self._register)
        self._register()

    def _register(self) -> None:
        """Per-device HBM accounting (ISSUE 14): the stacked image and
        the lane -> bin vectors beside it, by their exact per-device
        split, as ONE entry of the device-memory gauges, which the
        residency cache (search/spmd.py) releases at eviction."""
        total = self.nbytes + self.lane_bins.nbytes
        TELEMETRY.device_memory.register(
            "spmd_shard_sets", id(self), total,
            devices=mesh_device_split(self.mesh, total))

    def release(self) -> None:
        """The residency cache dropped this set: the image and its
        lane -> bin vectors leave the device-memory gauges together (a
        request still running on the set adds to them no more)."""
        self.lane_bins.release()
        TELEMETRY.device_memory.release("spmd_shard_sets", id(self))


class DistributedSearcher:
    """Compiles and caches the one-program distributed query phase.

    Per (plan signature, meta, k, n_aggs) a single jitted shard_map program:
      in:  stacked segment arrays [N, ...] (sharded over `shards`),
           stacked flat plan inputs [N, ...] (sharded), min_score (replicated)
      out: merged (keys, scores, global_doc_ids) [k] replicated,
           total hits (psum), agg partials still sharded [N, ...]
    Global doc id = shard_index * d_pad + local ordinal, decoded by the host.
    Tie-break on equal scores follows gather order (shard asc, then local
    score rank), matching the reference's shard-index tie-break in
    SearchPhaseController.mergeTopDocs.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = int(np.prod(mesh.devices.shape))
        self._cache: Dict[Any, Any] = {}

    def runner(self, cache_key, plan: Plan, meta, k: int,
               agg_plans: Tuple = (), rows_per_dev: int = 1,
               sort_spec: Optional[Tuple[str, str]] = None):
        key = (cache_key, meta, k, rows_per_dev, sort_spec)
        fn = self._cache.get(key)
        if fn is not None:
            return fn

        axis = self.axis
        d_pad = meta.d_pad
        # per-row capacity is d_pad, but the MERGED result may need up to
        # k candidates drawn from many small rows — each merge level keeps
        # min(k, what its inputs can hold)
        k_eff = min(k, d_pad)
        rpd = rows_per_dev
        k_local = min(k, rpd * k_eff)
        k_merge = min(k, self.n_shards * k_local)
        bm = spmd_blockmax_admitted(plan, meta, k, sort_spec, agg_plans)
        n_terms = plan.static[1] if bm else 0

        def one_row(seg, flat_inputs, min_score):
            cursor = [0]
            if bm:
                # block-max fast path: identical to _eval_plan's text
                # branch (search/plan_eval.py) except non-competitive
                # posting blocks are masked out of the gather. Per-row
                # pruning stays rank-exact for the merged page: a global
                # top-k doc is beaten by fewer than k docs overall, hence
                # by fewer than k_eff in its own row, so it survives the
                # row-local threshold. Padding rows carry min_score=+inf,
                # which blockmax_keep_mask treats as prune-disable.
                cursor[0] = 1
                my = flat_inputs[0]
                keep, pruned = blockmax_keep_mask(
                    seg, my, my["k1"], n_terms, k_eff, min_score)
                scores, matches = score_text_clause(
                    seg, my, my["k1"], block_keep=keep,
                    score_only=plan.static[2])
                scores = jnp.where(matches, scores, 0.0)
            else:
                pruned = jnp.int32(0)
                # a text clause keeps its own stages inside (the
                # innermost scope names an op); what is left is the
                # match mask of a filter: a range over the rank columns
                with stage("filter_mask"):
                    scores, matches = _eval_plan(plan, seg, flat_inputs,
                                                 cursor)
            # `live` is False on padding rows (ops/device_segment.py), so no
            # per-shard num_docs mask is needed — metas stay shape-only here.
            with stage("eligible_total"):
                eligible = matches & seg["live"] & seg["root"] \
                    & (scores >= min_score)
                local_total = jnp.sum(eligible.astype(jnp.int32))
            if sort_spec is None:
                keys = scores
            else:
                # numeric field sort: the merge key is the doc's decoded
                # f32 VALUE (comparable across segments, unlike the
                # host path's segment-local ranks); eligibility
                # (search/spmd.py:_spmd_sort_spec) admits only columns
                # whose values are EXACTLY f32-representable and within
                # ±1e29, so selection matches the host path's exact-key
                # selection; the host re-keys the k winners with exact
                # f64 values for the final order. The key builder is
                # shared with the result-page merge (ops/topk.py)
                field, order = sort_spec
                keys = value_merge_key(seg["numeric"].get(field), order,
                                       d_pad)
            with stage("top_k"):
                masked = jnp.where(eligible, keys, NEG_INF)
                top_keys, top_idx = jax.lax.top_k(masked, k_eff)
                top_scores = scores[top_idx]

            agg_outs = []
            if agg_plans:
                with stage("agg_bins"):
                    eval_aggs(list(agg_plans), seg, flat_inputs, cursor,
                              eligible, agg_outs)
            return (top_keys, top_scores, top_idx.astype(jnp.int32),
                    local_total, pruned, agg_outs)

        def local_query_phase(seg, flat_inputs, min_scores):
            # block shape: [rpd, ...] rows packed on this device
            tk, ts, ti, tot, prn, agg_outs = jax.vmap(one_row)(
                seg, flat_inputs, min_scores)
            with stage("collective_merge"):
                shard_i = jax.lax.axis_index(axis)
                row_ids = shard_i * rpd + jnp.arange(rpd, dtype=jnp.int32)
                gids = row_ids[:, None] * d_pad + ti            # [rpd, k]
                # intra-device merge across packed rows, then the ICI
                # merge: gather every device's candidates, replicated
                # top-k — SearchPhaseController.mergeTopDocs as one
                # collective + one sort instead of a coordinator RPC
                # round per shard
                lk, li = jax.lax.top_k(tk.reshape(-1), k_local)
                lg = gids.reshape(-1)[li]
                ls = ts.reshape(-1)[li]
                gk = jax.lax.all_gather(lk, axis, tiled=True)
                gg = jax.lax.all_gather(lg, axis, tiled=True)
                gs = jax.lax.all_gather(ls, axis, tiled=True)
                mk, mi = jax.lax.top_k(gk, k_merge)
                mg = gg[mi]
                ms = gs[mi]
                total = jax.lax.psum(jnp.sum(tot), axis)
            # per-row pruned-block counts stay sharded ([rpd] per device →
            # [R_pad]); rows without block-max admission report 0
            return mk, ms, mg, total, prn, agg_outs

        in_specs = (P(axis), P(axis), P(axis))
        # eval_aggs appends one output dict per node in traversal order
        # (children included), not one per top-level plan; vmapped rows
        # keep a leading [rpd] axis that P(axis) concatenates to [R_pad]
        n_agg_outs = sum(_count_agg_nodes(a) for a in agg_plans)
        out_specs = (P(), P(), P(), P(), P(axis), [P(axis)] * n_agg_outs)
        mapped = _shard_map(local_query_phase, mesh=self.mesh,
                            in_specs=in_specs, out_specs=out_specs)

        def spmd_query_phase(seg, flat_inputs, min_scores):
            return mapped(seg, flat_inputs, min_scores)

        # named for its family like every served program: the module of
        # a device trace reads `jit_spmd_query_phase(<fingerprint>)`
        fn = jit_family(spmd_query_phase, "spmd_query_phase")
        self._cache[key] = fn   # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
        # the miss gets the first-call timer (the compile reaches
        # search.xla_cache_miss and the executable census, whose scope
        # map names the stages above); hits get the raw executable,
        # which carries the same `exec_info`
        return timed_first_call(
            fn, family="spmd_query_phase",
            shape=f"r{self.n_shards}x{rpd}xd{d_pad}k{k}", key=key)

    def derive_lane_bins(self, shard_set: HbmShardSet, field: str,
                         tables: Sequence[np.ndarray]):
        """`table[val_ords]` of every row of the set, -1 where the table
        says no bucket or the lane is padding: int32 `[R_pad, n_pad]` on
        the mesh, sharded like the image. One gather a row over the
        resident rank column, what the served program did a request; the
        rows' tables (`[u_pad]` each) are uploaded for it and dropped."""
        col = shard_set.seg_stack["numeric"][field]
        r_pad = self.n_shards * shard_set.rows_per_dev
        # padding rows take row 0's, as their flat inputs do; a table
        # grown to the widest holds ranks its row never reaches
        stack = pad_stack_trees(
            list(tables) + [tables[0]] * (r_pad - len(tables)))
        key = ("lane_bins", stack.shape, tuple(col["val_ords"].shape))
        fn = self._cache.get(key)
        if fn is None:
            spec = P(self.axis)
            mapped = _shard_map(jax.vmap(lane_bins_row), mesh=self.mesh,
                                in_specs=(spec, spec, spec), out_specs=spec)

            def agg_lane_bins(table, doc_ids, val_ords):
                return mapped(table, doc_ids, val_ords)

            fn = jax.jit(agg_lane_bins)     # module `jit_agg_lane_bins`
            self._cache[key] = fn   # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
            # a compile on the serving thread counts as one
            # (search.xla_cache_miss); no census record: not a served
            # program, and it runs once a (shard set, field, bucketing)
            fn = timed_first_call(fn)
        with _DISPATCH_LOCK:
            stack = _device_put_sharded_tree(stack, self.mesh, self.axis)
            return fn(stack, col["doc_ids"], col["val_ords"])

    def build_shard_set(self, shard_arrays: Sequence[Dict],
                        metas: Sequence[Any],
                        started: Optional[float] = None) -> HbmShardSet:
        """Upload the shard segments to HBM once; reuse across queries.
        `started`: the `time.monotonic()` at which the caller began to
        build `shard_arrays` (the set's `install.shard_set` span then
        holds that part too)."""
        return HbmShardSet(self, shard_arrays, metas, started)

    def search(self, shard_payloads: List[Tuple[Dict, List[Dict], Any]],
               plan: Plan, k: int, min_score: float = float(NEG_INF),
               agg_plans: Tuple = (),
               sort_spec: Optional[Tuple[str, str]] = None):
        """One-shot convenience: uploads per-shard (arrays, flat_inputs,
        meta) payloads and queries them. For repeated queries over the same
        segments use build_shard_set() + search_resident() — this path pays
        a full segment upload per call."""
        shard_set = self.build_shard_set([p[0] for p in shard_payloads],
                                         [p[2] for p in shard_payloads])
        try:
            return self.search_resident(shard_set,
                                        [p[1] for p in shard_payloads],
                                        plan, k, min_score=min_score,
                                        agg_plans=agg_plans,
                                        sort_spec=sort_spec)
        finally:
            # no residency cache owns a one-shot set: its device-memory
            # gauge leaves with the call, or `spmd_shard_sets` keeps it
            shard_set.release()

    def search_resident(self, shard_set: HbmShardSet,
                        flat_inputs: Sequence[List[Dict]], plan: Plan,
                        k: int, min_score: float = float(NEG_INF),
                        agg_plans: Tuple = (),
                        sort_spec: Optional[Tuple[str, str]] = None,
                        device_scope=None, return_pruned: bool = False,
                        marks: Optional[dict] = None,
                        lane_bins: Sequence[Any] = ()):
        """Run the distributed query phase against HBM-resident segments:
        only the flat plan inputs (query constants — term ids, weights,
        range bounds) travel host→device per query.

        More rows than devices pack `rows_per_dev` rows per device (an
        inner vmap; the intra-device merge happens before the ICI
        gather). sort_spec=(numeric_field, order) merges by decoded field
        value instead of score.

        `device_scope` (a telemetry DeviceScope or None, ISSUE 14)
        collects the per-chip phase breakdown: flat-input upload wall,
        per-device dispatch→replica-ready walls (blocked in device
        order — the collective aligns chips at the merge, so the walls
        bound each chip's partial top-k + its wait at the gather, and
        the max−median SKEW is the straggler signal), the analytic
        collective-merge bytes (k_local × 3 channels × 4 B over the
        mesh — program statics, never a device sync), and the result
        pull.

        Returns (merged_keys [<=k], scores [<=k], row_idx [<=k],
        local_ords [<=k], total, per-row agg partial outputs). Agg
        partials keep a leading row dimension; the caller decodes each
        row's slice with that row's own agg plans (ordinal spaces are
        segment-local). With return_pruned=True a 7th element is
        appended: per-row pruned posting-block counts [n_rows] (int32,
        all zeros unless block-max pruning was admitted — ISSUE 20).

        `marks`, where given, gets the clock reads the caller's spans
        are made of (`time.monotonic()`): `stacked` (the literals
        stacked on the host, before the wait for the dispatch lock),
        `dispatch` = (first literal upload, the jit call's return, bytes
        uploaded, the executable's `exec_info`) and `device_wait` =
        (start, end, bytes) of the blocking pull of the result page.

        `lane_bins`: what `resident_lane_bins` returned for these
        `agg_plans`; already on the mesh, the program reads them as
        `seg["lane_bins"][slot]`."""
        if len(flat_inputs) != shard_set.n_rows:
            raise ValueError(
                f"{len(flat_inputs)} flat-input lists for a "
                f"{shard_set.n_rows}-row shard set")
        if shard_set.mesh is not self.mesh:
            # a foreign-mesh shard set would be silently re-sharded (a full
            # segment copy) by jit on every call — exactly what residency
            # exists to prevent
            raise ValueError("shard_set was built for a different mesh")
        meta = shard_set.meta
        rpd = shard_set.rows_per_dev
        r_pad = self.n_shards * rpd
        pad = r_pad - len(flat_inputs)
        flat_inputs = list(flat_inputs) + [flat_inputs[0]] * pad
        # padding rows are neutralized by a +inf min_score: nothing is
        # eligible, so they add no candidates, no totals, empty aggs
        min_scores = np.full(r_pad, np.inf, np.float32)
        min_scores[:shard_set.n_rows] = min_score
        t_up = time.monotonic()
        flat_stack = pad_stack_trees(flat_inputs)
        literal_bytes = min_scores.nbytes + sum(
            l.nbytes for l in jax.tree_util.tree_leaves(flat_stack))
        # collect under an attributed region: the np.asarray conversions
        # ARE the d2h sync of the SPMD path (there is no jax.device_get
        # here), and the ledger decomposes them as its own channel
        from opensearch_tpu.telemetry import TELEMETRY
        ledger = TELEMETRY.ledger
        scope = ledger.current()
        accounting = ledger.enabled or scope is not None
        t_stacked = time.monotonic()
        with ledger.attributed():
            with _DISPATCH_LOCK:
                t_dispatch = time.monotonic()
                flat_stack = _device_put_sharded_tree(
                    flat_stack, self.mesh, self.axis,
                    channel="upload.literals")
                min_stack = _device_put_sharded_tree(
                    min_scores, self.mesh, self.axis,
                    channel="upload.literals")
                t_uploaded = time.monotonic()
                cache_key = (plan_struct(plan),
                             tuple(plan_struct(a) for a in agg_plans),
                             shard_set.shapes, _tree_shapes(flat_stack))
                fn = self.runner(cache_key, plan, meta, k, agg_plans,
                                 rows_per_dev=rpd, sort_spec=sort_spec)
                # dispatch BEFORE starting the clock: fn's first call per
                # signature XLA-compiles synchronously (seconds), and
                # that wall must not pollute the wave_ms percentiles the
                # item-2 scheduler budgets against — only the
                # conversions below (which block on compute + transfer,
                # like the executor's device_get) are the collect wall
                seg_stack = shard_set.seg_stack
                if lane_bins:
                    seg_stack = dict(seg_stack, lane_bins=list(lane_bins))
                keys, scores, gids, total, pruned_rows, agg_outs = fn(
                    seg_stack, flat_stack, min_stack)
            t_enqueued = time.monotonic()
            if device_scope is not None:
                device_scope.devices = self.n_shards
                device_scope.rows = shard_set.n_rows
                device_scope.upload_ms = (t_uploaded - t_up) * 1000
                device_scope.upload_bytes = sum(
                    np.asarray(v).nbytes  # sync-ok: host -- flat inputs are host leaves pre-upload
                    for flat in flat_inputs for d in flat
                    for v in d.values())
            # ONE post-dispatch clock (t0) for both the per-chip walls
            # and note_device_get below: a cold call's synchronous XLA
            # compile (seconds) must not read as a straggling chip, and
            # the ledger's collect wall must measure the same interval
            # whether or not the device gate is on — the per-chip
            # blocks merely move wait out of the np.asarray conversions,
            # they must not shrink the recorded d2h wall
            t0 = t_enqueued
            t_disp = t0
            if device_scope is not None:
                # per-chip walls: block on each device's replica of the
                # merged keys in device order — device d's replica is
                # ready when ITS slice of the program (partial top-k +
                # its side of the collective) finished. Walls of chips
                # later in the order include any wait for earlier
                # chips' blocks; the MAX (the straggler) is exact, so
                # max − median remains an honest skew lower bound.
                k_eff = min(k, meta.d_pad)
                k_local = min(k, rpd * k_eff)
                n = self.n_shards
                try:
                    shards = sorted(keys.addressable_shards,
                                    key=lambda s: s.device.id)
                    for sh in shards:
                        sh.data.block_until_ready()  # sync-ok: gated device-phase capture -- the result is fetched right below anyway
                        device_scope.partials.append(
                            (int(sh.device.id),
                             (time.monotonic() - t_disp) * 1000))
                except (AttributeError, TypeError):
                    # backend without addressable_shards: whole-array
                    # wall attributed to the first mesh device
                    jax.block_until_ready(keys)  # sync-ok: gated device-phase capture -- the result is fetched right below anyway
                    device_scope.partials.append(
                        (int(self.mesh.devices.flatten()[0].id),
                         (time.monotonic() - t_disp) * 1000))
                # analytic collective-merge accounting from program
                # statics: each device gathers 3 channels (keys, gids,
                # scores) × k_local × 4 B from every mesh device, plus
                # the psum'd total
                per_dev_payload = 3 * 4 * k_local * n + 4
                device_scope.merge_payload_bytes = per_dev_payload * n
                device_scope.merge_ici_bytes = \
                    3 * 4 * k_local * n * (n - 1)
            # the scope's pull wall starts AFTER the per-chip blocks
            # (it isolates the host-copy cost the blocks can't absorb)
            t_pull = time.monotonic() if device_scope is not None \
                else t0
            keys = np.asarray(keys)
            scores = np.asarray(scores)
            gids = np.asarray(gids)
            total = int(total)
            pruned_rows = np.asarray(pruned_rows)
            agg_outs = jax.tree_util.tree_map(np.asarray, agg_outs)
        nb = keys.nbytes + scores.nbytes + gids.nbytes + 8 \
            + pruned_rows.nbytes + sum(
            a.nbytes for a in jax.tree_util.tree_leaves(agg_outs))
        if marks is not None:
            marks["stacked"] = t_stacked
            marks["dispatch"] = (t_dispatch, t_enqueued, literal_bytes,
                                 getattr(fn, "exec_info", None))
            marks["device_wait"] = (t_enqueued, time.monotonic(), nb)
        pull_dev = int(self.mesh.devices.flatten()[0].id)
        if accounting:
            # the replicated result page is pulled from the first mesh
            # device — the per-device table attributes it there
            ledger.record("spmd.results", "d2h", nb,
                          wave=ledger.new_wave(), scope=scope,
                          devices=[(pull_dev, nb)]
                          if ledger.devices.enabled else None)
            ledger.note_device_get((time.monotonic() - t0) * 1000,
                                   nbytes=nb, scope=scope)
        if device_scope is not None:
            device_scope.pull_ms = (time.monotonic() - t_pull) * 1000
            device_scope.pull_bytes = nb
            device_scope.pull_device = pull_dev
        row_idx = gids // meta.d_pad
        ords = gids % meta.d_pad
        valid = keys > NEG_INF / 2
        base = (keys[valid], scores[valid], row_idx[valid], ords[valid],
                total, agg_outs)
        if return_pruned:
            return base + (pruned_rows[:shard_set.n_rows],)
        return base


def canonical_meta(metas: Sequence[Any]):
    """Collapse per-shard DeviceSegmentMeta into the shape envelope meta.

    Field layout (norm rows, doc-value field sets) must match across shards —
    it is mapper-derived, so same-index shards agree. Bucket sizes may differ;
    the envelope takes the max (pad_stack_trees grows the arrays to match).
    num_docs is unused by the distributed runner — the live mask covers
    padding."""
    base = metas[0]
    for m in metas[1:]:
        if (m.norm_rows != base.norm_rows
                or m.numeric_fields != base.numeric_fields
                or m.ordinal_fields != base.ordinal_fields
                or m.vector_fields != base.vector_fields):
            raise ValueError(
                "shards have mismatched field layouts; SPMD search requires "
                f"same-index shards: {base} vs {m}")
    return dataclasses.replace(
        base, seg_id="<spmd>", num_docs=0,
        d_pad=max(m.d_pad for m in metas),
        nb_pad=max(m.nb_pad for m in metas))
