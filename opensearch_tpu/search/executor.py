"""Shard-level search execution: the TPU QueryPhase + FetchPhase.

Reference flow being re-designed (SURVEY.md §3.2): SearchService.executeQueryPhase
(search/SearchService.java:529) builds a collector chain and runs Lucene's
BulkScorer leaf-by-leaf; FetchPhase (search/fetch/FetchPhase.java:106) then
loads _source for the top hits. Here the whole query phase for a segment is ONE
jitted XLA program: evaluate the plan tree → dense (scores, matches) → masked
top-k + total-hit count on device; the host merges per-segment candidates
(stable score-desc/doc-asc, Lucene's tie-break) and runs the fetch phase from
the host-side _source store.

Field sort: the device selects per-segment top-k by segment-local value rank
(correct within a segment); the host then re-keys candidates with the real
values (exact f64 / dictionary strings) for the cross-segment merge, since
ranks from different segments are not comparable. Docs missing the sort field
get a sentinel key so they are fetched and sorted last, per the reference's
missing:_last default.

Compiled executables are cached by (plan signature, segment meta, k) — the
analog of Lucene's per-(query,reader) Weight caching, but at XLA level.
"""

from __future__ import annotations

import functools
import json
import queue
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from opensearch_tpu.common import faults, retry
from opensearch_tpu.common.admission import WAVE_BREAKER
from opensearch_tpu.common.errors import (
    IllegalArgumentError, OpenSearchTpuError, QueryShardError)
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import Segment, pad_bucket
from opensearch_tpu.ops import bm25 as _bm25
from opensearch_tpu.ops.bm25 import (
    blockmax_keep_mask, ordinal_terms_match, posting_lengths,
    range_match_on_ranks, score_text_clause)
from opensearch_tpu.ops import device_segment as _devseg
from opensearch_tpu.ops.device_segment import (
    DeviceSegmentMeta, refresh_live, tree_nbytes, upload_segment)
from opensearch_tpu.ops.knn import knn_page
from opensearch_tpu.ops.select import blocked_select_width, select_top_k
from opensearch_tpu.ops.topk import (NEG_INF, f32_sortable, single_valued,
                                     value_merge_key)
from opensearch_tpu.search import dsl
from opensearch_tpu.search.compile import (Compiler, Plan, ShardStats,
                                           _PartialBundle, carry_memo,
                                           struct_fingerprint)
from opensearch_tpu.search.plan_eval import _eval_plan, eval_knn_winners
from opensearch_tpu.search.aggs.engine import (compile_aggs, eval_aggs,
                                               note_bin_sources,
                                               resident_levels)
from opensearch_tpu.search.aggs.lane_bins import LaneBinsMemo, lane_bins_row
from opensearch_tpu.search.aggs.parse import parse_aggs
from opensearch_tpu.search.aggs.reduce import decode_outputs, reduce_aggs
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.ledger import LedgerScope

# sort key for eligible docs that lack the sort field: far below any real
# rank key, far above NEG_INF (which marks ineligible docs) → fetched last
MISSING_KEY = np.float32(-1e30)

# Single-round-trip result pages (ISSUE 17): cross-segment top-k merge,
# on-device sort-key extraction and the fused docvalue gather assemble a
# wave's whole response body from ONE device_get instead of the legacy
# multi-channel host merge + per-leaf column reads. OFF by default
# (faults-style module flag, registered in tools/lint/gate_lint.py);
# wired from the static node setting `search.result_page.enabled`
# (node.py) — flipping it mid-flight would split the ledger's
# round-trip accounting across two regimes. With the flag False the
# general path keeps the legacy collect byte-for-byte.
RESULT_PAGE = False

# transfer ledger + device-memory accounting (telemetry/ledger.py):
# module-level handles — the guards on the query path are one attribute
# load, the tracer/fault-injector no-op discipline
_LEDGER = TELEMETRY.ledger
_DEVMEM = TELEMETRY.device_memory
_FLIGHT = TELEMETRY.flight
_CHURN = TELEMETRY.churn
# query insights (telemetry/insights.py, ISSUE 15): per-shape cost
# attribution — the envelope notes every completed sub-request at wave
# merge, joined to its interned template signature / structural hash
_INSIGHTS = TELEMETRY.insights


def _item_shape(node, body: dict) -> Tuple[str, str]:
    """(shape id, kind) for one envelope item: the interned template's
    signature when the item interned (`node` is the QueryTemplate the
    parse loop resolved — no second intern walk), else the structural
    hash of the raw query body."""
    from opensearch_tpu.telemetry.insights import (
        structural_shape, template_shape)
    if isinstance(node, dsl.QueryTemplate):
        return template_shape(node.sig), "template"
    return structural_shape(body.get("query")), "hash"


def _live_sig(seg) -> bytes:
    """Packed live-mask bytes — the skip key delta publish compares to
    decide whether a refresh must re-ship a segment's liveness bitmap
    at all (ISSUE 16 tentpole d). One packbits over num_docs bools per
    segment per refresh, write-path only."""
    return np.packbits(np.asarray(seg.live, dtype=bool)).tobytes()  # sync-ok: host -- seg.live is the engine's host-side bitmap


def _shape_sig(tree, prefix="") -> tuple:
    """Flattened (path, shape, dtype) signature of a device pytree — the
    shape-bucket identity that decides XLA executable reuse (plan
    signatures embed input shapes, so two segments with identical device
    array shapes share every compiled executable). Power-of-two padding
    (ops/device_segment.py) makes collisions the COMMON case by design;
    the churn ledger's recompile/warmup-hit verdict keys on this."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(_shape_sig(tree[k], f"{prefix}{k}."))
        return tuple(out)
    return ((prefix, tuple(getattr(tree, "shape", ())),
             str(getattr(tree, "dtype", ""))),)

# live ShardReaders, sampled by the corpus-columns memory gauge: weak
# refs so a dropped reader (closed index, finished test) leaves the
# gauge without an unregistration hook
_LIVE_READERS: "weakref.WeakSet" = weakref.WeakSet()


def _corpus_memory_stats() -> dict:
    readers = list(_LIVE_READERS)
    return {"live_bytes": sum(r.device_bytes for r in readers),
            "segments": sum(len(r.segments) for r in readers),
            "readers": len(readers)}


def _agg_const_memory_stats() -> dict:
    """Fused-agg executable constants (aggs/engine.py stashes the byte
    map on each segment): summed over LIVE readers' segments only, so
    index deletes, shard closes and clone replacements all leave the
    gauge by construction."""
    tables = [getattr(seg, "_agg_const_bytes", None)
              for r in list(_LIVE_READERS) for seg in r.segments]
    return {"live_bytes": sum(sum(t.values()) for t in tables if t),
            "entries": sum(len(t) for t in tables if t)}


_DEVMEM.add_provider("corpus_columns", _corpus_memory_stats)
_DEVMEM.add_provider("agg_constants", _agg_const_memory_stats)


# --------------------------------------------------------------- shard reader

class ShardReader:
    """Holds a shard's sealed segments + their device images.

    Reference: the Engine.Searcher / ReaderContext pair pinned by
    search/SearchService.java:585 createContext.

    Concurrent-publish contract (ISSUE 13, refresh/merge while queries
    fly): `segments` and `device` are views over ONE atomically-swapped
    `_published` pair — every mutation builds fresh lists and publishes
    them in a single attribute store, so a search thread can never see
    segment i paired with another segment's device arrays. Readers that
    need the pair must take `snapshot()` ONCE (one attribute read) and
    zip the result; reading the two properties separately can straddle
    a publish. Writers (the refreshing/merging thread) serialize on
    `_publish_lock`; readers stay lock-free."""

    def __init__(self, mapper: MapperService, segments: Optional[List[Segment]] = None,
                 index_name: str = "_index"):
        self.mapper = mapper
        self.index_name = index_name
        # (segments, device) published as one tuple — see class doc
        self._published: Tuple[List[Segment],
                               List[Tuple[Dict, DeviceSegmentMeta]]] = \
            ([], [])
        self._publish_lock = threading.Lock()
        self._stats_cache: Optional[ShardStats] = None
        self._seg_bytes: Dict[str, int] = {}    # seg_id → device bytes
        # seg_id → (the meta of the image now held, its resident
        # lane -> bin vectors): `with_lane_bins`. They are derived from
        # that image's rank columns, so they go when it does
        # (`_hold_image_locked`, `remove_segment`); a delete moves `live`
        # alone and leaves them
        self._lane_bins: Dict[str, Tuple[DeviceSegmentMeta,
                                         LaneBinsMemo]] = {}
        # segment-keyed memo carry (ISSUE 16 tentpole b, gate-lint row):
        # OFF by default — a publish drops the whole ShardStats memo
        # exactly as before; ON, _build_stats copies still-valid interned
        # entries into the fresh memo (see compile.carry_memo)
        self.memo_carry = False
        # the retiring ShardStats a publish displaced — carry_memo's
        # source (publish sites null _stats_cache, so without this
        # stash the carry would never see the old memo)
        self._carry_prev: Optional[ShardStats] = None
        # novel device shape fingerprints accumulated by uploads since
        # the last take_novel_shapes() — the precompiler's trigger feed.
        # Swapped wholesale on take; a racing append into the retiring
        # list can drop a fingerprint, which only delays (never breaks)
        # precompilation — the warmup replay covers the whole registry.
        self._novel_shapes: List[str] = []
        # last-uploaded packed live mask per seg_id, kept only while
        # delta publish is on: lets a refresh skip the per-segment
        # live-mask re-upload when no delete touched the mask
        self._live_sigs: Dict[str, bytes] = {}
        # staged-publish barrier (ISSUE 16 tentpole a, barrier mode):
        # while a publisher holds the stage, mutations build `_staged`
        # instead of `_published`; only a thread inside staged_visible()
        # (the precompile replay) sees the staged pair — every serving
        # thread keeps reading the old published pair until commit, so
        # queries never observe a segment set whose executables were
        # not compiled yet.
        self._staged: Optional[Tuple[List[Segment],
                                     List[Tuple[Dict,
                                                DeviceSegmentMeta]]]] = None
        self._staged_stats: Optional[ShardStats] = None
        self._staging = False
        self._stage_tls = threading.local()
        self._stage_lock = threading.Lock()
        _LIVE_READERS.add(self)
        for seg in (segments or []):
            self.add_segment(seg)

    @property
    def segments(self) -> List[Segment]:
        return self._published[0]

    @property
    def device(self) -> List[Tuple[Dict, DeviceSegmentMeta]]:
        return self._published[1]

    def snapshot(self) -> Tuple[List[Segment],
                                List[Tuple[Dict, DeviceSegmentMeta]]]:
        """One consistent (segments, device) pair — the per-request
        anchor every query/fetch phase must zip from. On the barrier
        replay thread (staged_visible) the staged pair IS the pair."""
        if getattr(self._stage_tls, "on", False) and \
                self._staged is not None:
            return self._staged
        return self._published

    @property
    def device_bytes(self) -> int:
        """Live device bytes held by this reader's segment images and
        the lane -> bin vectors beside them — the corpus-columns slice
        of the device-memory stats."""
        return sum(self._seg_bytes.values()) \
            + sum(memo.nbytes for _, memo in list(self._lane_bins.values()))

    def _hold_image_locked(self, meta: DeviceSegmentMeta, nb: int) -> None:
        """A segment's image was uploaded (anew): count its bytes, and
        start its lane -> bin vectors from none, dropping those of the
        image it replaces. Caller holds _publish_lock."""
        self._seg_bytes[meta.seg_id] = nb
        old = self._lane_bins.get(meta.seg_id)
        if old is not None:
            old[1].release()
        self._lane_bins[meta.seg_id] = (meta, LaneBinsMemo())

    def with_lane_bins(self, arrays: Dict, meta: DeviceSegmentMeta,
                       agg_plans) -> Dict:
        """The segment image as a program of `agg_plans` takes it: with
        the resident lane -> bin vectors its levels name, in slot order,
        under `lane_bins` (search/aggs/lane_bins.py). Each is found in
        the image's memo or derived now from the level's table by one
        program over the resident rank column. An image this reader no
        longer holds (a request that outlived a merge, a pinned reader)
        derives for itself and keeps nothing."""
        levels = list(resident_levels(agg_plans))
        if not levels:
            return arrays
        held = self._lane_bins.get(meta.seg_id)
        if held is not None and held[0] is meta:
            memo = held[1]
        else:
            memo = LaneBinsMemo()
            memo.release()
        return dict(arrays, lane_bins=[
            memo.get((p.static[0],) + p.bins_key,
                     lambda p=p: _derive_lane_bins(
                         p.table_of(), arrays["numeric"][p.static[0]]))
            for p in levels])

    # ------------------------------------------------- staged publish

    def _cur_pair_locked(self):
        """The pair mutations build on: the staged pair while a barrier
        publish is open, the published pair otherwise. Caller holds
        _publish_lock."""
        return self._staged if self._staging else self._published

    def _set_pair_locked(self, pair) -> None:
        """Install a mutated pair: into the stage while a barrier
        publish is open (the live published pair — and its stats cache
        — keep serving untouched), directly into _published otherwise.
        Caller holds _publish_lock."""
        if self._staging:
            self._staged = pair
            self._staged_stats = None
        else:
            self._published = pair
            self._retire_stats_locked()

    def begin_staged_publish(self) -> None:
        """Open a barrier publish: subsequent mutations land in a
        staged copy of the published pair, invisible to serving threads
        until commit_staged_publish(). Single-publisher: a concurrent
        refresh/merge blocks here until the holder commits."""
        self._stage_lock.acquire()
        with self._publish_lock:
            self._staged = self._published
            self._staged_stats = self._stats_cache
            self._staging = True

    def commit_staged_publish(self) -> None:
        """Atomically publish the staged pair (with whatever stats the
        precompile replay built against it — its memo already holds the
        carried + freshly-compiled bundles) and release the stage."""
        try:
            with self._publish_lock:
                pair, stats = self._staged, self._staged_stats
                self._staging = False
                self._staged = None
                self._staged_stats = None
                if pair is not None and pair is not self._published:
                    self._retire_stats_locked()
                    self._published = pair
                    self._stats_cache = stats
        finally:
            self._stage_lock.release()

    @contextmanager
    def staged_visible(self):
        """Make the staged pair THIS thread's snapshot source — the
        precompile replay runs its warm searches under this, compiling
        against the exact pair the commit will publish."""
        prev = getattr(self._stage_tls, "on", False)
        self._stage_tls.on = True
        try:
            yield
        finally:
            self._stage_tls.on = prev

    def add_segment(self, seg: Segment):
        # delta publish (ISSUE 16 tentpole d): gated inside
        # publish_segment — disabled it IS upload_segment and
        # xfer == resident bytes, byte-for-byte the legacy accounting
        arrays, meta, xfer = _devseg.publish_segment(seg)
        nb = tree_nbytes(arrays)
        with self._publish_lock:
            segs, dev = self._cur_pair_locked()
            self._set_pair_locked((segs + [seg], dev + [(arrays, meta)]))
            self._hold_image_locked(meta, nb)
        if _devseg.DELTA_PUBLISH:
            self._live_sigs[seg.seg_id] = _live_sig(seg)
        if _LEDGER.enabled:
            _LEDGER.record("upload.corpus", "h2d", xfer)
        # churn attribution (ISSUE 13): the seen-shape set is fed on
        # EVERY upload (the verdict is only honest if pre-enable uploads
        # count); the per-event scope records only while a refresh/merge
        # holds one bound. The signature is the TRUE executable-reuse
        # identity: meta.compile_key() (the constants traced programs
        # close over) + every device array's (path, shape, dtype).
        fp = struct_fingerprint((meta.compile_key(), _shape_sig(arrays)))
        known = _CHURN.observe_shape(fp)
        if not known:
            ns = self._novel_shapes
            ns.append(fp)
            if len(ns) > 64:        # bounded when nothing drains it
                del ns[:len(ns) - 64]
        cs = _CHURN.current()
        if cs is not None:
            cs.note_upload(seg.seg_id, xfer, known)

    def remove_segment(self, seg_id: str):
        with self._publish_lock:
            segs, dev = self._cur_pair_locked()
            for i, seg in enumerate(segs):
                if seg.seg_id == seg_id:
                    self._set_pair_locked((segs[:i] + segs[i + 1:],
                                           dev[:i] + dev[i + 1:]))
                    self._seg_bytes.pop(seg_id, None)
                    held = self._lane_bins.pop(seg_id, None)
                    if held is not None:
                        held[1].release()
                    self._live_sigs.pop(seg_id, None)
                    return

    def notify_deletes(self, seg: Segment):
        live_nbytes = None
        with self._publish_lock:
            segs, dev = self._cur_pair_locked()
            for i, s in enumerate(segs):
                if s is seg:
                    arrays, meta = dev[i]
                    pair = (segs,
                            dev[:i] + [(refresh_live(arrays, seg), meta)]
                            + dev[i + 1:])
                    # segments list unchanged → the stats cache (and its
                    # memo) stays valid; only the device pair re-publishes
                    if self._staging:
                        self._staged = pair
                    else:
                        self._published = pair
                    live_nbytes = int(arrays["live"].nbytes)
                    break
        if live_nbytes is not None:
            if _devseg.DELTA_PUBLISH:
                self._live_sigs[seg.seg_id] = _live_sig(seg)
            if _LEDGER.enabled:
                # only the liveness bitmap re-uploads
                _LEDGER.record("upload.corpus", "h2d", live_nbytes)
            cs = _CHURN.current()
            if cs is not None:
                cs.note_live_mask(live_nbytes)

    def update_segment(self, seg: Segment):
        """Adopt a possibly-replaced segment object with the same id
        (recovery/segment-replication installs clone_for_copy objects):
        shared immutable columns keep their device image, only the live
        mask re-uploads; a genuinely different segment re-uploads fully."""
        segs = (self._staged if self._staging else self._published)[0]
        for i, s in enumerate(segs):
            if s.seg_id != seg.seg_id:
                continue
            if s is seg or s.post_docs is seg.post_docs:
                if _devseg.DELTA_PUBLISH and s is seg:
                    # delta publish (ISSUE 16 tentpole d): the reader
                    # already holds this exact object — when the live
                    # mask is byte-identical to the last uploaded one,
                    # the refresh ships NOTHING for this segment (the
                    # legacy path re-uploads every segment's mask every
                    # refresh). The published pair and stats cache stay
                    # untouched: nothing changed.
                    sig = _live_sig(seg)
                    if self._live_sigs.get(seg.seg_id) == sig:
                        return
                live_nbytes = None
                with self._publish_lock:
                    segs, dev = self._cur_pair_locked()
                    for j, sj in enumerate(segs):
                        if sj.seg_id == seg.seg_id:
                            arrays, meta = dev[j]
                            self._set_pair_locked((
                                segs[:j] + [seg] + segs[j + 1:],
                                dev[:j]
                                + [(refresh_live(arrays, seg), meta)]
                                + dev[j + 1:]))
                            live_nbytes = int(arrays["live"].nbytes)
                            break
                if live_nbytes is not None:
                    if _devseg.DELTA_PUBLISH:
                        self._live_sigs[seg.seg_id] = _live_sig(seg)
                    if _LEDGER.enabled:
                        _LEDGER.record("upload.corpus", "h2d",
                                       live_nbytes)
                    cs = _CHURN.current()
                    if cs is not None:
                        cs.note_live_mask(live_nbytes)
            else:
                arrays, meta, xfer = _devseg.publish_segment(seg)
                nb = tree_nbytes(arrays)
                with self._publish_lock:
                    segs, dev = self._cur_pair_locked()
                    for j, sj in enumerate(segs):
                        if sj.seg_id == seg.seg_id:
                            self._set_pair_locked((
                                segs[:j] + [seg] + segs[j + 1:],
                                dev[:j] + [(arrays, meta)]
                                + dev[j + 1:]))
                            self._hold_image_locked(meta, nb)
                            break
                if _devseg.DELTA_PUBLISH:
                    self._live_sigs[seg.seg_id] = _live_sig(seg)
                if _LEDGER.enabled:
                    _LEDGER.record("upload.corpus", "h2d", xfer)
                fp = struct_fingerprint((meta.compile_key(),
                                         _shape_sig(arrays)))
                known = _CHURN.observe_shape(fp)
                if not known:
                    self._novel_shapes.append(fp)
                cs = _CHURN.current()
                if cs is not None:
                    cs.note_upload(seg.seg_id, xfer, known)
            return
        self.add_segment(seg)

    @property
    def num_docs(self) -> int:
        return sum(s.live_doc_count for s in self.segments)

    def stats(self) -> ShardStats:
        # cached while the segment list is stable: ShardStats carries the
        # per-term idf memo, so reuse across requests is the win (deletes
        # don't move doc_freq until merge, same as Lucene)
        return self.stats_snapshot()[0]

    def stats_snapshot(self) -> Tuple[ShardStats, List[Segment],
                                      List[Tuple[Dict,
                                                 DeviceSegmentMeta]]]:
        """The per-request anchor under concurrent publish: a
        (ShardStats, segments, device) triple that is mutually
        consistent — the stats (and its interned-plan memo) were built
        for exactly the returned segment list, and the device list is
        its pair. Retries if a refresh publishes mid-build (rare; the
        loop converges as soon as one read sees a stable pair)."""
        if getattr(self._stage_tls, "on", False):
            # barrier-publish replay thread: snapshot the STAGED pair —
            # the stats built here (memo carry + compiled bundles)
            # become the published cache at commit
            with self._publish_lock:
                pair = self._staged
                stats = self._staged_stats
            if pair is not None:
                if stats is None or stats.segments != pair[0]:
                    stats = self._build_stats(pair[0])
                    self._staged_stats = stats
                return stats, pair[0], pair[1]
        while True:
            pub = self._published
            stats = self._stats_cache
            if stats is None or stats.segments != pub[0]:
                stats = self._build_stats(pub[0])
                self._stats_cache = stats
            if self._published is pub:
                return stats, pub[0], pub[1]

    def _retire_stats_locked(self) -> None:
        """Invalidate the stats cache on publish; with memo carry on,
        stash the retiring stats so the next build can copy still-valid
        interned entries out of its memo. Caller holds _publish_lock."""
        if self.memo_carry and self._stats_cache is not None:
            self._carry_prev = self._stats_cache
        self._stats_cache = None

    def _build_stats(self, segments: List[Segment]) -> ShardStats:
        """Build the ShardStats for a published segment list. With memo
        carry ON (ISSUE 16 tentpole b) the retiring cache's still-valid
        interned entries copy into the fresh memo instead of dropping
        wholesale — see compile.carry_memo for the per-family rules.
        The carry copies into a FRESH RotatingMemo (never reuses the
        old object): an in-flight query holding the old snapshot keeps
        writing old-list-aligned bundles into the OLD memo, harmlessly."""
        stats = ShardStats(segments)
        stats.built_mapper_version = getattr(self.mapper, "version", 0)
        old = self._stats_cache
        if old is None:
            old = self._carry_prev
        if self.memo_carry and old is not None and \
                getattr(old, "built_mapper_version", None) == \
                stats.built_mapper_version:
            stats.carry_report = carry_memo(old, stats)
            self._carry_prev = None
        return stats

    def rebuild_stats(self) -> ShardStats:
        """Eagerly (re)build + cache the stats for the CURRENT published
        pair — called by the refreshing thread right after a publish so
        the carry pass runs OFF the serving path: serving threads find a
        warm cache instead of paying the rebuild under a query."""
        return self.stats_snapshot()[0]

    def take_novel_shapes(self) -> List[str]:
        """Drain the novel device-shape fingerprints uploads accumulated
        since the last take — the precompiler's per-publish trigger feed
        (ISSUE 16 tentpole a)."""
        shapes, self._novel_shapes = self._novel_shapes, []
        return shapes


class PinnedReader:
    """Point-in-time snapshot of a ShardReader: segments are immutable, so
    pinning is just holding references to the current segment list + device
    images (reference: ReaderContext / PitReaderContext keeping the Lucene
    searcher open across requests, search/internal/PitReaderContext.java)."""

    def __init__(self, reader: ShardReader):
        self.mapper = reader.mapper
        self.index_name = reader.index_name
        # one snapshot() read: a consistent pair even while a
        # concurrent refresh publishes
        segments, device = reader.snapshot()
        self.segments = list(segments)
        self.device = list(device)
        self._stats = ShardStats(self.segments)
        # the pinned images are the source reader's: so are their
        # resident lane -> bin vectors, for as long as it holds them
        self.with_lane_bins = reader.with_lane_bins

    @property
    def num_docs(self) -> int:
        return sum(s.live_doc_count for s in self.segments)

    def stats(self) -> ShardStats:
        return self._stats

    def snapshot(self):
        """A pinned reader IS a snapshot: the pair never changes."""
        return self.segments, self.device

    def stats_snapshot(self):
        return self._stats, self.segments, self.device


# ------------------------------------------------------------------ execution

_JIT_CACHE: Dict[Any, Any] = {}

# executable cache size for the device-memory stats: XLA does not expose
# per-executable HBM bytes portably, so this class reports counts (the
# raw backend bytes land in the `hbm` block when available)
_DEVMEM.add_provider(
    "compiled_executables",
    lambda: {"entries": len(_JIT_CACHE)})


# per-THREAD compile accounting + the first-call compile timer moved to
# telemetry/kernels.py (ISSUE 19) so the ops-layer jit sites (knn
# k-means, delta-publish expanders) share one census wrapper without an
# import cycle; the executor names stay as aliases — warmup.py and the
# ingest-serving tests import them from here
from opensearch_tpu.telemetry.kernels import (  # noqa: E402
    THREAD_COMPILES as _THREAD_COMPILES, jit_family,
    note_compile as _note_compile, offpath_compiles, stage as _stage,
    timed_first_call as _timed_first_call)

# the always-on span ring (telemetry/tracer.py, ISSUE 25): the envelope
# and its waves record completed spans from the clock reads the
# msearch.phase.* histograms already make
_SPANS = TELEMETRY.tracer.spans
# a `_search` served through the B=1 envelope observes the operator's
# search.* metrics as the general path does (controller.execute_search)
_SEARCH_QUERIES = TELEMETRY.metrics.counter("search.queries")
_SEARCH_TOOK = TELEMETRY.metrics.histogram("search.took_ms")
_SEARCH_PHASE_HISTS = {
    name: TELEMETRY.metrics.histogram(f"search.phase.{name}_ms")
    for name in ("parse", "query", "render")}
# query items dispatched through an envelope program that takes its page
# from a root k-NN clause's own k winners (`_page_from_clause`); beside
# search.knn_clause.exact / .ivf / .filtered (search/compile.py)
_KNN_PAGE_FROM_CLAUSE = TELEMETRY.metrics.counter(
    "search.knn_clause.page_from_clause")
# query items dispatched through an envelope program whose k-NN selection
# (ops/select.py `select_top_k`) reads block maxima (`_blocked_select`)
_KNN_BLOCKED_SELECT = TELEMETRY.metrics.counter(
    "search.knn_clause.blocked_select")
# hybrid sub-queries dispatched, by how the fused program selects each
# one's window (`_hybrid_window_route`): once a sub-query an item a shard
_HYBRID_WINDOWS = {
    route: TELEMETRY.metrics.counter(f"search.hybrid.windows.{route}")
    for route in ("from_clause", "blocked", "plain")}
# query items dispatched through the aggregating envelope program
# (`jit_agg_env`: build_batched_agg_query_phase), once an item whatever
# its segments
_AGG_ENV_QUERIES = TELEMETRY.metrics.counter("search.agg_env.queries")


def _derive_lane_bins(table: np.ndarray, col: Dict):
    """`table[val_ords]` over one segment's resident rank column, -1
    where the table says no bucket or the lane is padding: int32
    `[n_pad]` on the device. The gather the served program did a
    request, once a (segment image, field, bucketing); the table
    (`[u_pad]`) is uploaded for it and dropped."""
    key = ("lane_bins", table.shape, tuple(col["val_ords"].shape))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(lane_bins_row)     # module `jit_lane_bins_row`
        _JIT_CACHE[key] = fn  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
        # a compile on the serving thread counts as one
        # (search.xla_cache_miss); no census record: not a served
        # program, and it runs once a (segment image, field, bucketing)
        fn = _timed_first_call(fn)
    return fn(jnp.asarray(table), col["doc_ids"], col["val_ords"])


def _vector_leaf(plan: Plan) -> Optional[Plan]:
    """The first `knn` or `maxsim` leaf of a compiled plan tree, in
    pre-order, or None: it names the program's family and, for k-NN,
    its shape bucket."""
    if plan.kind in ("knn", "maxsim"):
        return plan
    for c in plan.children:
        leaf = _vector_leaf(c)
        if leaf is not None:
            return leaf
    return None


def _plan_family(plan: Plan, agg_plans=(), leaf=None) -> str:
    """Kernel-family label for one compiled plan tree (the census'
    vocabulary, telemetry/kernels.py): vector leaves win (their
    kernels dominate the program), then the agg envelope, then the
    dense BM25 kernel build_query_phase lowers to. `leaf` is the tree's
    `_vector_leaf` where the caller already has it."""
    if leaf is None:
        leaf = _vector_leaf(plan)
    if leaf is None:
        return "agg_env" if agg_plans else "bm25_dense"
    if leaf.kind == "knn":
        return "knn"
    comp = leaf.static[2] if len(leaf.static) > 2 else None
    return "maxsim_adc" if comp == "pq" else "maxsim"


def _layout_batch(layout) -> int:
    """Batch rows of a packed envelope layout (every stacked leaf shares
    the padded batch axis)."""
    for _off, shape, _dt in layout:
        if shape:
            return int(shape[0])
    return 0


def _env_shape(layout, k: int, meta, knn: Optional[Plan] = None,
               agg_bins: Optional[int] = None) -> str:
    """Shape-bucket string for an envelope executable: padded batch,
    top-k and the segment's padded doc axis — the axes the compile key
    buckets on. A `knn` family program names what its scan reads and
    selects instead, `d<d_pad>x<dims>k<k>` of its k-NN clause `knn`:
    the page's k says nothing of the clause's, and the vector width is
    the program's cost. An `agg_env` family program names its lanes and
    its bins, `d<d_pad>/bins<agg_bins>`: every bin of every partial
    array a query's row carries back (counts, and a metric's cnt, sum,
    min, max each), which with the lanes is what its reductions cost."""
    if knn is not None:
        return (f"b{_layout_batch(layout)}/d{meta.d_pad}"
                f"x{knn.inputs['query'].shape[-1]}k{knn.static[1]}")
    if agg_bins is not None:
        return f"b{_layout_batch(layout)}/d{meta.d_pad}/bins{agg_bins}"
    return f"b{_layout_batch(layout)}/k{k}/d{meta.d_pad}"


def _plan_cost(plan: Plan, meta, batch: int = 1):
    """Analytic (flops, bytes) fallback for the census when the backend
    exposes no cost model: the scan formulas (telemetry/scan.py) give
    the bytes the kernel touches; flops are estimated at 2 ops per f32
    lane (one multiply-add) — coarse, but roofline-stable, and marked
    `cost_source: analytic` so readers know the provenance."""
    from opensearch_tpu.telemetry.scan import (
        DENSE_LANE_BYTES, POSTING_BLOCK_BYTES, plan_scan_blocks,
        plan_scan_extra)
    per_row = (plan_scan_blocks(plan) * POSTING_BLOCK_BYTES
               + meta.d_pad * DENSE_LANE_BYTES + plan_scan_extra(plan))
    nbytes = float(per_row * max(1, batch))
    return nbytes / 4.0 * 2.0, nbytes

# msearch phase accounting (?profile analog for the batch path): per-batch
# milliseconds land in the always-on telemetry metrics registry as
# per-phase histograms — visible on _nodes/stats and
# tools/profile_host.py (replaces the old module-global accumulator)
MSEARCH_PHASE_NAMES = ("parse", "compile_group", "stack_pack_dispatch",
                       "device_get", "respond")
_PHASE_HISTS = {name: TELEMETRY.metrics.histogram(f"msearch.phase.{name}_ms")
                for name in MSEARCH_PHASE_NAMES}

# query-template interning (ISSUE 5): repeated-structure msearch batches
# skip parse+compile via the per-reader (template, literals) bundle memo.
# A/B parity tests (tests/test_template_interning.py) set the attribute;
# it is no serving configuration.
TEMPLATE_INTERNING = True
_BUNDLE_HITS = TELEMETRY.metrics.counter("msearch.template.bundle_hits")
_BUNDLE_MISSES = TELEMETRY.metrics.counter("msearch.template.bundle_misses")
_INTERN_FALLBACKS = TELEMETRY.metrics.counter("msearch.template.fallbacks")

# ------------------------------------------------------ wave-pipeline engine
#
# Overlapped multi-wave dispatch (ROADMAP item 1): a large msearch batch
# splits into power-of-two-bucketed waves so wave N+1's host work
# (intern/stack/pack/upload) and async dispatch run while wave N's
# device_get is in flight on a collector thread. The collect wall is
# the dispatch-sync, not byte volume (the ledger's count: one round
# trip, ~89 B a query), so what overlap can hide is host work. Wave
# sizes stay power-of-two buckets so the warmup registry's (plan-struct,
# shape-bucket, b_pad) signatures are reused across wave splits.

# tests set it; None = the auto policy below
FORCED_WAVES: Optional[int] = None

# below 2× this many batchable items a split cannot win: each extra wave
# is an extra device_get round trip, and the host work it could hide is
# O(items in the NEXT wave)
MSEARCH_MIN_WAVE_ITEMS = 128
MSEARCH_MAX_WAVES = 4
# bounded in-flight window (double buffering): at most this many waves
# dispatched-but-uncollected, so device memory holds at most two waves
# of input envelopes + result pages at any instant
MSEARCH_INFLIGHT_WINDOW = 2


# lazily probed once: overlap only pays where the collect wall is IDLE
# host time (an attached accelerator). On the CPU backend the "device"
# compute runs on the same cores as the host prepare, so pipelining
# just contends. None = not probed yet.
_OVERLAP_CAPABLE: Optional[bool] = None


def _overlap_capable() -> bool:
    global _OVERLAP_CAPABLE
    if _OVERLAP_CAPABLE is None:
        try:
            _OVERLAP_CAPABLE = jax.devices()[0].platform != "cpu"
        except Exception:  # except-ok: backend probe must never fail a search; unprobeable backends serve single-wave
            _OVERLAP_CAPABLE = False
    return _OVERLAP_CAPABLE


def _effective_waves(n_batchable: int) -> int:
    """Wave-count policy for an envelope of `n_batchable` items:
    FORCED_WAVES (tests) always wins; otherwise
    split only when every wave keeps MSEARCH_MIN_WAVE_ITEMS rows and
    the backend can actually overlap (see _overlap_capable)."""
    if FORCED_WAVES:
        return max(int(FORCED_WAVES), 1)
    if n_batchable < 2 * MSEARCH_MIN_WAVE_ITEMS or not _overlap_capable():
        return 1
    return min(MSEARCH_MAX_WAVES, n_batchable // MSEARCH_MIN_WAVE_ITEMS)


def _wave_sizes(n: int, n_waves: int) -> List[int]:
    """Split n items into power-of-two-bucketed wave sizes (the last
    wave takes the remainder; pad_bucket inside each wave's groups keeps
    its executables on reused shape buckets)."""
    if n_waves <= 1 or n <= 1:
        return [n]
    per = pad_bucket(-(-n // n_waves), minimum=1)
    sizes: List[int] = []
    left = n
    while left > 0:
        sizes.append(min(per, left))
        left -= per
    return sizes


def _release_wave_gauges(state: Optional[dict]) -> None:
    """Zero a wave state's `wave_buffer_bytes` marker and release the
    device-memory gauge. Idempotent (the marker is the guard), and the
    ONLY way any path releases it — finish halves at their fetch
    completion, _collect_wave's finally, and the pipeline's backstop
    all funnel here, so the release semantics live in one place."""
    if not state:
        return
    leaked = state.get("wave_buffer_bytes", 0)
    if leaked:
        state["wave_buffer_bytes"] = 0
        _DEVMEM.adjust("wave_buffers", -leaked)


class _StagingPool:
    """Double-buffered host staging for packed input envelopes.

    `jnp.asarray` on the CPU backend is ZERO-COPY (the device array
    aliases the host buffer) and on an accelerator an ASYNCHRONOUS
    host→device copy, so a staging buffer may only be reused
    once its wave's device_get has completed — the one point where, on
    either, the copy has been made and the dispatched program has
    provably finished reading its inputs. The
    pipeline acquires at pack time (main thread) and releases from the
    collector after the wave's collect (collector thread), hence the
    lock. Exact-size free lists: steady-state waves repeat identical
    envelope sizes, so after the first in-flight window fills, packing
    allocates nothing per wave. (True XLA buffer donation was measured
    unusable here: the int32 input envelope never shape/dtype-matches
    the f32 result rows, so donate_argnums degrades to a no-op with a
    per-dispatch warning — see README "Wave pipeline".)"""

    MAX_PER_SIZE = 4            # ≥ in-flight window, double-buffered
    MAX_BYTES = 64 << 20

    def __init__(self):
        self._lock = threading.Lock()
        self._free: Dict[int, List[np.ndarray]] = {}
        self._bytes = 0

    def acquire(self, n: int) -> np.ndarray:
        with self._lock:
            bufs = self._free.get(n)
            if bufs:
                buf = bufs.pop()
                self._bytes -= buf.nbytes
                return buf
        return np.empty(n, np.int32)

    def release(self, buf: np.ndarray) -> None:
        with self._lock:
            bufs = self._free.setdefault(int(buf.shape[0]), [])
            if len(bufs) < self.MAX_PER_SIZE and \
                    self._bytes + buf.nbytes <= self.MAX_BYTES:
                bufs.append(buf)
                self._bytes += buf.nbytes


class _EnvelopeSpan:
    """What one envelope carries for the always-on span ring: the
    request's trace and its own span id (the parent of its phase and
    wave spans), the trace ids a scheduler-coalesced envelope serves
    (per body, or None), its wave count, the two reads of its parse
    phase and the read its `took` came from."""

    __slots__ = ("trace", "span_id", "trace_ids", "waves", "parse", "end")

    def __init__(self, trace, span_id: int,
                 trace_ids: Optional[list] = None):
        self.trace = trace
        self.span_id = span_id
        self.trace_ids = trace_ids
        self.waves = 0
        self.parse = None
        self.end = 0.0


# the spans' attributes, built when the ring is exported (SpanRing
# keeps `(build, *arguments)`): the serving path stores the raw values

def _envelope_attrs(bodies: int, waves: int, trace_ids) -> dict:
    out = {"bodies": bodies, "waves": waves}
    if trace_ids is not None:
        out["trace_ids"] = sorted({t for t in trace_ids if t is not None})
    return out


def _wave_attrs(wave: int, trace_ids) -> dict:
    """What every span of a wave carries: its index in the envelope,
    and for a scheduler-coalesced wave the traces (requests) whose
    items it serves."""
    out = {"wave": wave}
    if trace_ids is not None:
        out["trace_ids"] = trace_ids
    return out


def _dispatch_attrs(wave: int, trace_ids, programs: int, nbytes: int,
                    infos) -> dict:
    """`dispatch`: programs enqueued, bytes uploaded, and the
    executables by their census records (telemetry/kernels.py
    ExecInfo). One program a wave is the common case (B=1, one group)
    and is named outright; more are listed in dispatch order."""
    out = _wave_attrs(wave, trace_ids)
    out.update(programs=programs, nbytes=nbytes)
    if infos:
        out.update(family=infos[0].family,
                   fingerprint=infos[0].fingerprint, shape=infos[0].shape)
        if len(infos) > 1:
            out["fingerprints"] = [i.fingerprint for i in infos]
    return out


def _hybrid_dispatch_attrs(wave: int, trace_ids, programs: int) -> dict:
    out = _wave_attrs(wave, trace_ids)
    out.update(family="hybrid_env", programs=programs)
    return out


def _note_hybrid_spans(marks: tuple, programs: int, nbytes: int, infos,
                       fetched) -> None:
    """A B=1 hybrid query phase's spans in the always-on ring, under the
    span open on this thread, from its clock reads `marks` (start, first
    upload, last jit call's return, fetch start, rows fetched):
    `hybrid.compile`, `dispatch` and `device_wait`, the last two named
    and paired (`wave`) as the envelope's are."""
    trace = _SPANS.current()
    if trace is None:
        return
    t_start, t_dispatch, t_dispatched, t_wait, t_got = marks
    fetched_bytes = sum(rows.nbytes for rows in fetched)
    wave = sum(1 for s in trace.spans
               if s[2] == "dispatch" and s[1] == trace.top)
    _SPANS.child("hybrid.compile", t_start, t_dispatch,
                 (_wave_attrs, wave, None))
    _SPANS.child("dispatch", t_dispatch, t_dispatched,
                 (_dispatch_attrs, wave, None, programs, nbytes, infos))
    _SPANS.child("device_wait", t_wait, t_got,
                 (_wait_attrs, wave, None, fetched_bytes, 0))


def _wait_attrs(wave: int, trace_ids, nbytes: int, programs: int) -> dict:
    """`device_wait`: bytes fetched, and whether a program of its own
    (concat_rows) ran inside it."""
    out = _wave_attrs(wave, trace_ids)
    out.update(nbytes=nbytes, programs=programs)
    return out


class _MsearchWave:
    """One wave of the msearch pipeline: its item indices, the payload
    the prepare half consumes, and the dispatch/collect bookkeeping the
    overlap attribution is computed from."""

    __slots__ = ("kind", "items", "payload", "state", "scope", "ph",
                 "raise_errors", "window", "prep_t0", "prep_t1",
                 "collect_t0", "collect_t1", "error", "index",
                 "timeline", "breaker_probe", "span")

    def __init__(self, kind: str, items: List[int], payload,
                 raise_errors: bool = False):
        self.kind = kind            # "plain" | "hybrid"
        self.items = items          # sub-request indices this wave owns
        self.payload = payload      # batchable entries / hybrid items
        self.state: Optional[dict] = None
        self.scope = None           # wave-local LedgerScope (or None)
        self.ph = dict.fromkeys(MSEARCH_PHASE_NAMES, 0.0)
        self.raise_errors = raise_errors
        self.window = None          # in-flight window semaphore slot
        self.prep_t0 = self.prep_t1 = 0.0
        self.collect_t0 = self.collect_t1 = 0.0
        self.error: Optional[Exception] = None
        self.index = 0              # envelope-local wave id (0-based)
        self.timeline = None        # request Timeline (or None) — rides
        # the wave record across the collector-thread boundary so the
        # collect event lands on the owning request's lifecycle
        self.breaker_probe = False  # this wave is the device-memory
        # breaker's single half-open probe (common/admission.py)
        self.span = None            # (the request's Trace, the envelope's
        # span id, the id this wave's `dispatch` drew, the wave's index,
        # the trace ids it serves or None): rides the wave across the
        # collector-thread boundary like `scope`, so a wave collected
        # there keeps its request's trace


class _TimelineFan:
    """Fan one wave's lifecycle events out to every owning request's
    timeline. When the wave scheduler (search/scheduler.py) packs
    sub-requests from DIFFERENT requests into one shared wave, the
    coalesce/dispatch/collect/overlap events must land on each
    request's own lifecycle — `co_batched` then counts CROSS-REQUEST
    siblings, the number the scheduler is judged by. Appends are
    GIL-atomic and each timeline is read only after its own request
    completes, the same contract the collector thread already rides."""

    __slots__ = ("timelines",)

    def __init__(self, timelines):
        self.timelines = timelines

    def event(self, name: str, **fields) -> None:
        for tl in self.timelines:
            tl.event(name, **fields)


def _distinct_timelines(timelines, items=None):
    """The identity-distinct non-None timelines of `timelines`
    (optionally restricted to positions `items`), insertion-ordered —
    one request's timeline appears once however many of its
    sub-requests share the wave."""
    seen: Dict[int, Any] = {}
    for i in (items if items is not None else range(len(timelines))):
        tl = timelines[i]
        if tl is not None and id(tl) not in seen:
            seen[id(tl)] = tl
    return list(seen.values())


class _WaveCollector:
    """Collector thread for the overlapped pipeline: pulls dispatched
    waves off the queue and runs their device_get + response assembly
    while the main thread prepares the next wave. The in-flight window
    is a semaphore acquired BEFORE the next wave's prepare
    (acquire_slot) and released when a wave's collect completes, so at
    most `window` waves are device-resident at any instant."""

    def __init__(self, collect_fn, window: int):
        self._collect = collect_fn
        # the window is enforced BEFORE prepare (acquire_slot), not at
        # submit: a wave is device-resident from its dispatch inside
        # prepare, so bounding the queue alone would let window+1 waves
        # of envelopes + result pages sit on the device
        self._window = threading.Semaphore(max(window, 1))
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, name="msearch-wave-collector", daemon=True)
        self._thread.start()

    def acquire_slot(self) -> threading.Semaphore:
        """Block until an in-flight slot frees (a prior wave's collect
        completed); the returned semaphore is released by that wave's
        _collect_wave finally."""
        self._window.acquire()
        return self._window

    def submit(self, wave: _MsearchWave) -> None:
        self._q.put(wave)

    def drain(self) -> None:
        """Flush and join — called on EVERY pipeline exit path, so a
        cancellation or mid-flight error still collects the dispatched
        waves and releases their buffers."""
        self._q.put(None)
        self._thread.join()

    def _loop(self) -> None:
        while True:
            wave = self._q.get()
            if wave is None:
                return
            # scope rides the wave record across the thread boundary;
            # the collect callback re-binds it (sync-lint's collector-
            # thread pattern) and attributes its own device_get region
            self._collect(wave)


def _base_response(took_ms: int, total: int, max_score, hits: list) -> dict:
    """The msearch envelope's response skeleton — shared by the batched
    respond path, the match-none short-circuit and the request-cache
    renderer so all three stay byte-identical."""
    return {
        "took": took_ms,
        "timed_out": False,
        "_shards": {"total": 1, "successful": 1, "skipped": 0,
                    "failed": 0},
        "hits": {"total": {"value": total, "relation": "eq"},
                 "max_score": max_score, "hits": hits},
    }


def _item_error(e: OpenSearchTpuError) -> dict:
    """Per-item msearch error object (reference:
    TransportMultiSearchAction wraps each failed sub-request instead of
    failing siblings)."""
    return {"error": e.to_xcontent(), "status": e.status}


def _timed_out_item(start: float) -> dict:
    """A sub-request the envelope's deadline expired before launching:
    rendered as a zero-hit partial response with timed_out: true (the
    reference's per-request timeout shape), never an error object —
    timeout is a budget decision, not a failure."""
    resp = _base_response(int((time.monotonic() - start) * 1000), 0,
                          None, [])
    resp["timed_out"] = True
    return resp


# ------------------------------------------------------- transfer accounting
#
# Channel decomposition of the device_get result layouts: bytes come from
# array nbytes (metadata — no device sync), padding from the difference
# against the actually-transferred buffer, so per-channel bytes always
# sum to the transferred total (tests/test_transfer_ledger.py pins the
# conservation property).

def _ledger_unbatched_collect(scope, fetched, ms: float) -> None:
    """One general-path collect: per segment (top_keys, top_scores,
    top_idx, total, agg_outs) tuples fetched in one round trip."""
    sort_b = score_b = id_b = tot_b = agg_b = 0
    for outs in fetched:
        top_keys, top_scores, top_idx, seg_total, agg_outs = outs
        sort_b += int(np.asarray(top_keys).nbytes)
        score_b += int(np.asarray(top_scores).nbytes)
        id_b += int(np.asarray(top_idx).nbytes)
        tot_b += int(np.asarray(seg_total).nbytes)
        if agg_outs:
            agg_b += sum(int(np.asarray(v).nbytes)
                         for v in jax.tree_util.tree_leaves(agg_outs))
    wave = _LEDGER.new_wave()
    for channel, b in (("sort_keys", sort_b), ("scores", score_b),
                       ("topk_ids", id_b), ("totals", tot_b),
                       ("agg_buffers", agg_b)):
        if b:
            _LEDGER.record(channel, "d2h", b, wave=wave, scope=scope)
    _LEDGER.note_device_get(
        ms, nbytes=sort_b + score_b + id_b + tot_b + agg_b, scope=scope)


def _ledger_page_collect(scope, page_np, agg_fetched, ms: float) -> None:
    """One result-page collect (RESULT_PAGE on): the packed int32 page
    plus the per-segment agg buffers, fetched together in EXACTLY one
    round trip — the whole wave lands in the `result_page` channel,
    byte-exact against the transferred total (the conservation
    invariant holds because the channel bytes ARE the fetched nbytes)."""
    nb = int(np.asarray(page_np).nbytes)
    nb += sum(int(np.asarray(v).nbytes)
              for v in jax.tree_util.tree_leaves(agg_fetched))
    wave = _LEDGER.new_wave()
    _LEDGER.record("result_page", "d2h", nb, wave=wave, scope=scope)
    _LEDGER.note_device_get(ms, nbytes=nb, scope=scope)


def _ledger_packed_rows(scope, pending, fetched, actual_bytes: int,
                        ms: float, round_trips: int) -> None:
    """One msearch-envelope wave: [B, 2k+1+W] packed rows per program —
    k scores, k ids, 1 total, W agg-partial floats per row. Real
    channels count only the group's REAL rows (len(idxs)); batch-pad
    rows and combined-fetch column padding both land in `padding` via
    the remainder, so channel bytes sum exactly to the transferred
    total while the decomposition reports payload, not pad."""
    score_b = id_b = tot_b = agg_b = pruned_b = 0
    for (idxs, _seg_i, k_seg, _out, _ol, bm), packed in zip(pending,
                                                            fetched):
        if packed is None:
            continue
        rows = min(len(idxs), packed.shape[0])
        width = packed.shape[1]
        score_b += rows * k_seg * 4
        id_b += rows * k_seg * 4
        tot_b += rows * 4
        if bm:
            # blockmax rows carry one trailing pruned-count lane
            pruned_b += rows * 4
            width -= 1
        agg_b += rows * max(width - 2 * k_seg - 1, 0) * 4
    wave = _LEDGER.new_wave()
    pad_b = max(actual_bytes
                - (score_b + id_b + tot_b + agg_b + pruned_b), 0)
    for channel, b in (("scores", score_b), ("topk_ids", id_b),
                       ("totals", tot_b), ("agg_buffers", agg_b),
                       ("pruned_counts", pruned_b),
                       ("padding", pad_b)):
        if b:
            _LEDGER.record(channel, "d2h", b, wave=wave,
                           round_trips=round_trips, scope=scope)
    _LEDGER.note_device_get(ms, nbytes=actual_bytes, scope=scope,
                            round_trips=round_trips)


def _ledger_hybrid_rows(scope, programs, ms: float) -> None:
    """One hybrid-envelope wave: per program (rows, real_rows, k_seg,
    n_sub) of [rows, n_sub·(2k+4)+1] fused rows — per-sub scores/ids
    plus the (count, min, max, sum_sq) bounds block and the union
    total. Batch-pad rows (rows > real_rows) go to the `padding`
    channel, same as the plain packed path, so the per-channel
    decomposition reports real payload, not pad."""
    score_b = id_b = bounds_b = tot_b = pad_b = 0
    for rows, real_rows, k_seg, n_sub in programs:
        score_b += real_rows * k_seg * n_sub * 4
        id_b += real_rows * k_seg * n_sub * 4
        bounds_b += real_rows * n_sub * 4 * 4
        tot_b += real_rows * 4
        pad_b += (rows - real_rows) * (n_sub * (2 * k_seg + 4) + 1) * 4
    wave = _LEDGER.new_wave()
    for channel, b in (("scores", score_b), ("topk_ids", id_b),
                       ("score_bounds", bounds_b), ("totals", tot_b),
                       ("padding", pad_b)):
        if b:
            _LEDGER.record(channel, "d2h", b, wave=wave, scope=scope)
    _LEDGER.note_device_get(
        ms, nbytes=score_b + id_b + bounds_b + tot_b + pad_b, scope=scope)


def _cache_get_isolated(rc, key):
    """Request-cache read with fault-site + transient-retry wrapping; a
    persistently failing cache degrades to a MISS (recompute), never a
    failed query. The disabled-injector path is the bare cache call —
    the in-memory cache itself has no transient failure modes."""
    if not faults.ENABLED:
        return rc.REQUEST_CACHE.get(key)

    def op():
        faults.fire("request_cache.get")
        return rc.REQUEST_CACHE.get(key)
    try:
        return retry.call_with_retry(op, label="request_cache.get")
    except Exception:   # except-ok: cache-IO isolation -- any failure class degrades to a MISS, never a failed query
        return rc.REQUEST_CACHE._MISS


def _cache_put_isolated(rc, key, value) -> None:
    """Request-cache write with the same wrapping; a failed put is
    dropped (the entry just isn't cached)."""
    if not faults.ENABLED:
        rc.REQUEST_CACHE.put(key, value)
        return

    def op():
        faults.fire("request_cache.put")
        rc.REQUEST_CACHE.put(key, value)
    try:
        retry.call_with_retry(op, label="request_cache.put")
    except Exception:   # except-ok: cache-IO isolation -- a failed put just drops the entry
        pass


# a single interned-plan bundle larger than this never enters the memo:
# its flattened inputs would crowd out a whole generation of normal-sized
# working-set entries for one outlier query shape
_BUNDLE_MEMO_MAX_ENTRY_BYTES = 16 << 20


def _bundle_nbytes(flats) -> int:
    """Approximate host bytes retained by a memoized bundle: the flattened
    per-segment input arrays dominate (plans/signatures are tuples)."""
    if not flats:
        return 0
    return sum(getattr(v, "nbytes", 0) for f in flats if f
               for d in f for v in d.values())


def _rendered_buckets(reduced: Dict[int, dict]) -> int:
    """Top-level buckets in a wave's reduced aggregations
    (`respond.reduce_aggs`' `buckets`)."""
    return sum(len(agg["buckets"]) for aggs in reduced.values()
               for agg in aggs.values()
               if isinstance(agg, dict) and "buckets" in agg)


def _timed_bundle(compiler, memo: str, build):
    """`build()`, a bundle compile, under its `compile.bundle` span in
    the always-on ring (`memo`: `miss`, or `extend` for a carried
    bundle's tail; `nbytes`: the flattened inputs it made): the open
    span while it runs, so that the compiler's `compile.text_clause`
    hangs below it, closed on every exit. Where the wave's compiler
    has no ring (no wave span: a direct library caller) or has
    recorded its `COMPILE_SPANS_A_WAVE`: `build()` alone."""
    ring = compiler.span_ring()
    if ring is None:
        return build()
    trace, sid, parent = ring.enter()
    t0 = time.monotonic()
    nbytes = 0
    try:
        bundle = build()
        nbytes = _bundle_nbytes(bundle[1])
        return bundle
    finally:
        trace.spans.append((sid, parent, "compile.bundle", t0,
                            time.monotonic(),
                            {"memo": memo, "nbytes": nbytes}))
        ring.leave(trace, parent)


def _item_error_untyped(e: Exception) -> dict:
    """Per-item wrapper for exceptions with no OpenSearchTpuError typing:
    reported as the 500-class failure it is (not relabeled 400 — a raw
    TypeError may just as well be an internal bug as a client error)."""
    return {"error": {"type": "exception",
                      "reason": f"{type(e).__name__}: {e}"},
            "status": 500}


def _run_item_isolated(responses, i: int, raise_item_errors: bool,
                       fn) -> None:
    """Execute one sub-request's work under the per-item failure contract
    (reference TransportMultiSearchAction wraps EVERY per-item exception,
    never the envelope): typed errors render with their own status,
    untyped ones honestly as a 500-class item; fn's non-None return value
    becomes the item's response. raise_item_errors (the B=1 _search
    delegation) propagates instead — error objects are an _msearch-only
    shape."""
    try:
        r = fn()
        if r is not None:
            responses[i] = r
    except OpenSearchTpuError as e:
        if raise_item_errors:
            raise
        responses[i] = _item_error(e)
    except Exception as e:  # except-ok: per-item isolation -- untyped failures render 500-class error items, never fail siblings
        if raise_item_errors:
            raise
        responses[i] = _item_error_untyped(e)


_request_cache_mod = None


def _request_cache():
    """Lazily bound indices.request_cache module: the indices package
    __init__ imports a chain that leads back here (index.shard ->
    executor), so a top-level import would be circular — and a fresh
    function-level import per msearch sub-request is pure sys.modules
    lookup cost on the hot parse loop."""
    global _request_cache_mod
    if _request_cache_mod is None:
        from opensearch_tpu.indices import request_cache
        _request_cache_mod = request_cache
    return _request_cache_mod


def _req_int(body: dict, key: str, default: int) -> int:
    try:
        return int(body.get(key, default))
    except (TypeError, ValueError):
        raise IllegalArgumentError(
            f"Failed to parse int parameter [{key}] with value "
            f"[{body.get(key)!r}]")


def _req_min_score(body: dict):
    raw = body.get("min_score")
    if raw is None:
        return NEG_INF
    try:
        return float(raw)
    except (TypeError, ValueError):
        raise IllegalArgumentError(
            f"Failed to parse float parameter [min_score] with value "
            f"[{raw!r}]")


def build_query_phase(plan: Plan, meta: DeviceSegmentMeta, k: int,
                      sort_mode: str, agg_plans=()):
    """The single-segment query phase as a pure jittable function — the TPU
    program that replaces one ContextIndexSearcher.searchLeaf pass
    (search/internal/ContextIndexSearcher.java:260). Exposed unjitted so the
    graft entry can hand it to the driver's compile check."""

    def run(seg, flat_inputs, sort_key_arr, min_score):
        cursor = [0]
        scores, matches = _eval_query(plan, seg, flat_inputs, cursor,
                                      bool(agg_plans))
        d_pad = seg["live"].shape[0]
        eligible, total = _eligible_total(matches, seg, meta.num_docs,
                                          scores, min_score)
        keys = scores if sort_mode == "score" else sort_key_arr
        k_eff = min(k, d_pad)
        with _stage("top_k"):
            masked = jnp.where(eligible, keys, NEG_INF)
            top_keys, top_idx = jax.lax.top_k(masked, k_eff)
            top_scores = scores[top_idx]
        agg_outs = []
        if agg_plans:
            with _stage("agg_bins"):
                eval_aggs(list(agg_plans), seg, flat_inputs, cursor,
                          eligible, agg_outs)
        return top_keys, top_scores, top_idx.astype(jnp.int32), total, agg_outs

    return run


# ---------------------------------------------------- packed input envelope
#
# Every jnp.asarray upload is its own host→device transfer, one per
# leaf. The envelope packs every stacked
# input leaf of a group into ONE int32 buffer host-side; the jitted program
# slices/bitcasts the leaves back out with a static layout, so a whole
# group costs exactly one host→device transfer regardless of leaf count.

def pack_leaves(leaves: List[np.ndarray], pool: Optional[_StagingPool] = None):
    """Concatenate i32/f32/bool leaves into one int32 buffer + layout.
    `pool` (the wave pipeline's staging pool) reuses a released buffer
    of the exact size instead of allocating — steady-state waves pack
    into recycled memory."""
    total = 0
    metas = []
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        metas.append((total, tuple(leaf.shape), str(leaf.dtype)))
        total += n
    buf = pool.acquire(max(total, 1)) if pool is not None \
        else np.empty(max(total, 1), np.int32)
    for leaf, (off, shape, dtype) in zip(leaves, metas):
        n = int(np.prod(shape)) if shape else 1
        flat = np.ascontiguousarray(leaf).reshape(-1)
        if leaf.dtype == np.float32:
            flat = flat.view(np.int32)
        elif leaf.dtype == np.bool_:
            flat = flat.astype(np.int32)
        elif leaf.dtype != np.int32:
            raise ValueError(f"unsupported envelope dtype [{leaf.dtype}]")
        buf[off:off + n] = flat
    return buf, tuple(metas)


def unpack_leaves(buf, layout):
    """Device-side inverse of pack_leaves (static layout → traced slices)."""
    out = []
    for off, shape, dtype in layout:
        n = int(np.prod(shape)) if shape else 1
        piece = jax.lax.slice(buf, (off,), (off + n,))
        if dtype == "float32":
            piece = jax.lax.bitcast_convert_type(piece, jnp.float32)
        elif dtype == "bool":
            piece = piece.astype(jnp.bool_)
        out.append(piece.reshape(shape))
    return out


def _fill_value(name: str, dtype) -> Any:
    from opensearch_tpu.parallel.distributed import _PAD_FILL
    return _PAD_FILL.get(name, False if dtype == np.bool_ else 0)


def stack_flat_inputs(flats: List[List[Dict[str, np.ndarray]]],
                      with_const: bool = False):
    """Fast batch-stack of per-query flat input trees: grows every leaf to
    the per-position max shape (same envelope semantics as
    parallel.distributed.pad_stack_trees) but via preallocated fills
    instead of per-query np.pad — the host-side hot path of msearch.

    with_const: leaves named in aggs.engine.CONST_INPUT_KEYS (content-
    hashed into the group signature, so identical across the batch) are
    NOT stacked — one copy is packed and the runner maps them with
    in_axes=None, keeping table lookups unbatched so the GEMM agg path's
    one-hot matrices stay shared across the query batch. Returns
    (stacked, treedef, axes) with axes the per-leaf vmap axis list."""
    from opensearch_tpu.search.aggs.engine import CONST_INPUT_KEYS
    b = len(flats)
    treedef = jax.tree_util.tree_structure(flats[0])
    names = [kp[-1].key if hasattr(kp[-1], "key") else ""
             for kp, _ in jax.tree_util.tree_flatten_with_path(flats[0])[0]]
    per_query = [jax.tree_util.tree_leaves(f) for f in flats]
    n_leaves = len(per_query[0])
    stacked = []
    axes: List[Optional[int]] = []
    for li in range(n_leaves):
        if with_const and names[li] in CONST_INPUT_KEYS:
            stacked.append(np.asarray(per_query[0][li]))  # sync-ok: host -- flattened plan inputs are host arrays pre-upload
            axes.append(None)
            continue
        arrs = [np.asarray(q[li]) for q in per_query]  # sync-ok: host -- flattened plan inputs are host arrays pre-upload
        a0 = arrs[0]
        shape = tuple(max(a.shape[d] for a in arrs)
                      for d in range(a0.ndim))
        if all(a.shape == shape for a in arrs):
            out = np.stack(arrs)
        else:
            out = np.full((b, *shape), _fill_value(names[li], a0.dtype),
                          dtype=a0.dtype)
            for qi, a in enumerate(arrs):
                out[(qi, *map(slice, a.shape))] = a
        stacked.append(out)
        axes.append(0)
    return stacked, treedef, axes


def _pack_row(top_scores, top_idx, total):
    """ONE int32 row [k | k | 1] (scores bitcast) so the host fetches a
    single array — each fetch is a synchronization with the device.

    The row is int32 and the FLOATS are the bitcast guests, never the
    other way round: an int32 doc ordinal or total viewed as f32 is a
    denormal, and a TPU flushes denormals to zero in whatever XLA lowers
    through float arithmetic — every id and total of a packed f32 row
    read 0 on a v5e. Integer lanes are never flushed, and a bitcast is
    pure data movement on every backend (the input envelope and the
    result page already travel this way round)."""
    with _stage("pack_row"):
        return jnp.concatenate([
            jax.lax.bitcast_convert_type(top_scores, jnp.int32),
            top_idx.astype(jnp.int32),
            total[None].astype(jnp.int32)])


def _eval_query(plan: Plan, seg, flat_inputs, cursor, aggregating: bool):
    """`_eval_plan` of a program's query. In an aggregating program (the
    agg envelope, the host loop's query phase with aggregations) it is
    the stage `filter_mask`, as in the SPMD program: the match mask the
    bins are counted under. A text clause inside keeps its own stages
    (the innermost scope names an op). A program without aggregations
    is left as it was."""
    if not aggregating:
        return _eval_plan(plan, seg, flat_inputs, cursor)
    with _stage("filter_mask"):
        return _eval_plan(plan, seg, flat_inputs, cursor)


def _topk_or_empty(eligible, scores, k_eff: int):
    """lax.top_k over the eligible docs' scores, except k=0 (size=0
    agg/count queries) skips the selection networks entirely — the
    dominant device cost for a hits-free query."""
    if k_eff == 0:
        return (jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32))
    with _stage("top_k"):
        return jax.lax.top_k(jnp.where(eligible, scores, NEG_INF), k_eff)


def _unpack_envelope(packed_buf, layout, treedef):
    """The packed input envelope back into its stacked leaves: (the
    batched flat inputs, the trailing min_score leaf)."""
    with _stage("unpack_envelope"):
        leaves = unpack_leaves(packed_buf, layout)
        return (jax.tree_util.tree_unflatten(treedef, leaves[:-1]),
                leaves[-1])


def _eligible_total(matches, seg, num_docs: int, scores, min_score):
    """The docs a query may return (matched, live, top-level, inside
    the segment, at or over min_score) and how many they are."""
    with _stage("eligible_total"):
        in_seg = jnp.arange(seg["live"].shape[0],
                            dtype=jnp.int32) < num_docs
        # root: only top-level rows are returnable hits — nested child
        # rows participate in scoring solely through the `nested`
        # plan's join (Queries.newNonNestedFilter analog)
        eligible = matches & seg["live"] & seg["root"] & in_seg \
            & (scores >= min_score)
        return eligible, jnp.sum(eligible.astype(jnp.int32))


# candidate-buffer kernel only pays off while the sorted buffer stays far
# below the dense [d_pad] width; above this lane count the dense
# scatter+top_k path wins (bitonic sort is O(N log^2 N))
CANDIDATE_MAX_LANES = 1 << 14

# the candidate-buffer kernel's exact-windowed segment sum needs the
# distinct-term count bounded; beyond this the dense kernel serves
CANDIDATE_MAX_TERMS = 16


def _candidate_kernel_fits(kind: str, n_terms: int, qb_lanes: int) -> bool:
    """THE candidate-vs-dense decision, shared by _envelope_runner
    (which kernel compiles) and _envelope_kernel (what the scan heat
    map records) so the telemetry's kernel-mix column can never drift
    from the kernel that actually dispatches."""
    return kind == "text" and n_terms <= CANDIDATE_MAX_TERMS \
        and 0 < qb_lanes <= CANDIDATE_MAX_LANES


def build_candidate_query_phase(plan: Plan, meta: DeviceSegmentMeta, k: int,
                                layout, treedef, bm: bool = False):
    """B text queries against one segment, scored in a COMPACT candidate
    buffer instead of a dense per-doc vector.

    The round-3 verdict's block-max/WAND analog: a text clause's matches
    are exactly the union of its terms' postings lanes, so instead of
    scatter-adding into a [d_pad]-wide score vector and top_k-ing 131K
    lanes per query (the round-3 kernel), the gathered [QB·128] lanes are
    sorted by doc id, duplicate docs are segment-summed with a
    cumsum-at-run-ends trick, and top-k runs over the small buffer. HBM
    traffic per query drops from O(d_pad) to O(QB·128).

    Correctness notes: BM25 partials are >= 0 (idf >= 0, boosts
    non-negative), which the monotone-cumsum run-total trick relies on;
    per-term postings list each doc once, so a doc's run length equals its
    distinct matched terms (min_hits / operator=and semantics); top_k on
    ties picks the lowest lane = lowest doc id, matching the dense
    kernel's doc-ascending tie-break."""

    constant = plan.static[0]
    n_terms = plan.static[1] if len(plan.static) > 1 else 1

    def one(seg, flat_inputs, min_score):
        my = flat_inputs[0]
        lane_real = my["ids"] >= 0                    # [QB]
        if bm:
            # block-max phase A (ISSUE 20): per-block upper bounds vs the
            # slice-derived competitive threshold. Non-competitive blocks
            # are redirected to the shared row 0 by the safe_ids gather
            # below, so they ship no postings; the mask is DATA — every
            # shape stays static (retrace-lint clean)
            keep, pruned = blockmax_keep_mask(
                seg, my, my["k1"], n_terms, k, min_score)
            lane_real = lane_real & keep
        else:
            pruned = jnp.int32(0)
        with _stage("postings_gather"):
            safe_ids = jnp.where(lane_real, my["ids"], 0)
            docs = seg["post_docs"][safe_ids]             # [QB, 128]
            tfs = seg["post_tf"][safe_ids]
            dl = posting_lengths(seg, safe_ids)
            valid = docs >= 0
        with _stage("bm25_score"):
            b = my["b"]
            k1 = my["k1"]
            denom = tfs + k1 * (1.0 - b + b * dl / my["avgdl"])
            partial = my["w"][:, None] * tfs * (k1 + 1.0) / denom
            real = valid & lane_real[:, None]

        n = docs.shape[0] * docs.shape[1]
        big = jnp.int32(2 ** 30)
        with _stage("candidate_sort"):
            doc_key = jnp.where(real, docs, big).reshape(n)
            part = jnp.where(real, partial, 0.0).reshape(n)
            hit = jnp.where(real, 1, 0).astype(jnp.int32).reshape(n)
            sdoc, spart, shit = jax.lax.sort([doc_key, part, hit],
                                             num_keys=1)
        with _stage("run_sum"):
            is_end = jnp.concatenate([sdoc[:-1] != sdoc[1:],
                                      jnp.ones((1,), bool)])
            # exact windowed segment-sum: a doc's lanes are adjacent
            # after the sort and number at most n_terms (each term lists
            # a doc once), so summing a fixed backward window at the
            # run's END lane is exact — no cumsum-difference
            # cancellation, and the left-to-right order of the (stable)
            # sort keeps float summation deterministic
            run_score = spart
            run_hits = shit
            for j in range(1, n_terms):
                prev_doc = jnp.concatenate(
                    [jnp.full((j,), -2, sdoc.dtype), sdoc[:-j]])
                same = prev_doc == sdoc
                prev_part = jnp.concatenate(
                    [jnp.zeros((j,), spart.dtype), spart[:-j]])
                prev_hit = jnp.concatenate(
                    [jnp.zeros((j,), shit.dtype), shit[:-j]])
                run_score = run_score + jnp.where(same, prev_part, 0.0)
                run_hits = run_hits + jnp.where(same, prev_hit, 0)
        with _stage("eligible_total"):
            matches = run_hits >= my["min_hits"]
            score = jnp.full(n, my["boost"]) if constant else run_score
            valid_end = is_end & (sdoc < big)
            safe_end_docs = jnp.where(valid_end, sdoc, 0)
            eligible = valid_end & matches & seg["live"][safe_end_docs] \
                & seg["root"][safe_end_docs] & (score >= min_score)
            total = jnp.sum(eligible.astype(jnp.int32))
        k_eff = min(k, n)
        with _stage("top_k"):
            masked = jnp.where(eligible, score, NEG_INF)
            top_scores, top_lane = jax.lax.top_k(masked, k_eff)
            top_docs = sdoc[top_lane]
        if k_eff < k:
            top_scores = jnp.concatenate(
                [top_scores, jnp.full(k - k_eff, NEG_INF)])
            top_docs = jnp.concatenate(
                [top_docs, jnp.zeros(k - k_eff, jnp.int32)])
        row = _pack_row(top_scores, top_docs, total)
        if bm:
            # phase-A popcount rides the SAME packed row the host already
            # fetches — pruned-block accounting costs no extra round trip
            row = jnp.concatenate([row, pruned[None].astype(jnp.int32)])
        return row

    def run(seg, packed_buf):
        batched_flat, min_scores = _unpack_envelope(packed_buf, layout,
                                                    treedef)
        return jax.vmap(one, in_axes=(None, 0, 0))(seg, batched_flat,
                                                   min_scores)

    return run


def _blockmax_admitted(plan, k: int) -> bool:
    """STATIC admission for the two-phase block-max kernel, shared by
    _envelope_runner (which kernel compiles) and the prepare/finish
    halves (whether a pruned-count lane exists in the packed row) so
    the row layout can never drift from the compiled program. A plan
    qualifies when it was compiled with the gate ON (it carries the
    phase-A `tid` input — the memo key includes the gate state), is a
    plain non-constant text clause on the candidate kernel, touches
    enough blocks to be worth a slice pass, and the slice can actually
    cover k (theta needs a k-th exact score)."""
    if plan is None or plan.kind != "text" or plan.static[0] \
            or "tid" not in plan.inputs:
        return False   # constant-score: no competitive threshold exists
    n_blocks = plan.inputs["ids"].shape[-1]
    return (n_blocks >= _bm25.BLOCKMAX_MIN_BLOCKS
            and 0 < k <= _bm25.BLOCKMAX_SLICE_BLOCKS * 128
            and _envelope_kernel(plan) == "candidate")


def _page_from_clause(plan: Plan) -> bool:
    """Whether build_batched_query_phase takes the page, or
    build_hybrid_query_phase a sub-query's window, from a k-NN clause's
    own k winners: the clause is the whole query or sub-query. Read off
    the plan's root alone, which the JIT key's plan signature holds."""
    return plan.kind == "knn"


def _blocked_select(plan: Plan, d_pad: int) -> bool:
    """Whether a `knn` or `maxsim` clause of the plan, at its root or
    under a parent, selects its k winners of `d_pad` lanes through block
    maxima (ops/select.py `blocked_select_width`, the rule
    `select_top_k` itself asks)."""
    if plan.kind in ("knn", "maxsim") and blocked_select_width(
            d_pad, min(int(plan.static[1]), d_pad)):
        return True
    return any(_blocked_select(c, d_pad) for c in plan.children)


def _winners_total(valid, idx, seg, num_docs: int, scores, min_score):
    """`_eligible_total` asked of a clause's k winners (each already
    live): the ones a query may return, and how many they are."""
    with _stage("eligible_total"):
        returnable = valid & seg["root"][idx] & (idx < num_docs) \
            & (scores >= min_score)
        return returnable, jnp.sum(returnable.astype(jnp.int32))


def build_batched_query_phase(plan: Plan, meta: DeviceSegmentMeta, k: int,
                              layout, treedef):
    """B same-shaped queries against one segment as ONE device program.

    The TPU answer to per-query launch latency: where the reference executes
    queries one at a time per shard (SearchService.executeQueryPhase), here a
    whole _msearch batch vmaps over a leading query axis — gathers, BM25 and
    top-k all batch cleanly, so one host↔device round trip serves B queries.
    Score-sorted, agg-free queries only (the common high-QPS shape).

    A plan whose root is a `knn` clause (`_page_from_clause`; filtered or
    not, exact or IVF) selects once: the page and the total come from the
    clause's k winners (`eval_knn_winners`, `knn_page`), no `[d_pad]`
    scores/matches/eligible vector is built and no second `top_k` runs
    over `d_pad` lanes. Every other plan, a `knn` clause under a
    `bool`/`boosting`/`function_score`/`nested` parent among them, is
    evaluated densely and selected by `_topk_or_empty`."""

    def one(seg, flat_inputs, min_score):
        k_eff = min(k, seg["live"].shape[0])
        if _page_from_clause(plan):
            scores, idx, valid = eval_knn_winners(plan, seg, flat_inputs,
                                                  [0])
            returnable, total = _winners_total(
                valid, idx, seg, meta.num_docs, scores, min_score)
            top_scores, top_idx = knn_page(scores, idx, returnable, k_eff)
            return _pack_row(top_scores, top_idx, total)
        cursor = [0]
        scores, matches = _eval_plan(plan, seg, flat_inputs, cursor)
        eligible, total = _eligible_total(matches, seg, meta.num_docs,
                                          scores, min_score)
        top_scores, top_idx = _topk_or_empty(eligible, scores, k_eff)
        return _pack_row(top_scores, top_idx, total)

    def run(seg, packed_buf):
        batched_flat, min_scores = _unpack_envelope(packed_buf, layout,
                                                    treedef)
        return jax.vmap(one, in_axes=(None, 0, 0))(seg, batched_flat,
                                                   min_scores)

    return run


def _flatten_agg_out(out: Dict[str, Any]) -> List[Any]:
    """Deterministic (sorted-key) leaf order for one eval_aggs output dict —
    the device-side packer and the host-side unpacker must agree."""
    return [out[k] for k in sorted(out)]


def build_batched_agg_query_phase(plan: Plan, meta: DeviceSegmentMeta,
                                  k: int, layout, treedef, axes, agg_plans):
    """B same-shaped queries WITH aggregations as ONE device program.

    Extends build_batched_query_phase with the agg collection pass
    (eval_aggs) per query row; every agg partial array rides the int32
    packed hit row (f32 partials bitcast, see _pack_row), so a whole
    group of agg queries still fetches as ONE [B, 2k+1+W] array = one
    transfer round trip (reference executes aggs per query per shard:
    search/aggregations/AggregationPhase.java preProcess/execute)."""

    def one(seg, flat_inputs, min_score):
        cursor = [0]
        scores, matches = _eval_query(plan, seg, flat_inputs, cursor, True)
        d_pad = seg["live"].shape[0]
        eligible, total = _eligible_total(matches, seg, meta.num_docs,
                                          scores, min_score)
        k_eff = min(k, d_pad)
        top_scores, top_idx = _topk_or_empty(eligible, scores, k_eff)
        agg_outs: List[dict] = []
        with _stage("agg_bins"):
            eval_aggs(list(agg_plans), seg, flat_inputs, cursor, eligible,
                      agg_outs, batch=_layout_batch(layout))
        pieces = [_pack_row(top_scores, top_idx, total)]
        with _stage("pack_row"):
            for out in agg_outs:
                for v in _flatten_agg_out(out):
                    v = v.reshape(-1)
                    pieces.append(
                        jax.lax.bitcast_convert_type(v, jnp.int32)
                        if v.dtype == jnp.float32 else v.astype(jnp.int32))
            return jnp.concatenate(pieces)

    def run(seg, packed_buf):
        batched_flat, min_scores = _unpack_envelope(packed_buf, layout,
                                                    treedef)
        axes_tree = jax.tree_util.tree_unflatten(treedef, list(axes[:-1]))
        return jax.vmap(one, in_axes=(None, axes_tree, 0))(
            seg, batched_flat, min_scores)

    return run


def _agg_out_layout(plan: Plan, meta: DeviceSegmentMeta, agg_plans,
                    arrays, example_flat, min_score_example):
    """Host-side layout of one query's agg partials: for each eval_aggs
    output dict, its sorted keys with shapes and dtypes. Computed by
    abstract evaluation (jax.eval_shape) — no device work."""

    def probe(seg, flat_inputs, min_score):
        cursor = [0]
        scores, matches = _eval_plan(plan, seg, flat_inputs, cursor)
        d_pad = seg["live"].shape[0]
        eligible = matches & seg["live"] & (scores >= min_score)
        agg_outs: List[dict] = []
        eval_aggs(list(agg_plans), seg, flat_inputs, cursor, eligible,
                  agg_outs)
        return agg_outs

    shapes = jax.eval_shape(probe, arrays, example_flat, min_score_example)
    out_layout = []
    width = 0
    for out in shapes:
        entry = []
        for key in sorted(out):
            s = out[key]
            n = int(np.prod(s.shape)) if s.shape else 1
            entry.append((key, tuple(s.shape), str(s.dtype)))
            width += n
        out_layout.append(tuple(entry))
    return tuple(out_layout), width


def _decode_agg_row(row: np.ndarray, out_layout) -> List[dict]:
    """Invert the device-side int32 packing for one query row (the agg
    tail of a [2k+1+W] packed row) back into eval_aggs-ordered output
    dicts."""
    outs = []
    off = 0
    for entry in out_layout:
        d = {}
        for key, shape, dtype in entry:
            n = int(np.prod(shape)) if shape else 1
            piece = row[off:off + n]
            off += n
            if dtype == "float32":
                arr = piece.view(np.float32)
            elif dtype == "bool":
                arr = piece.astype(np.bool_)
            elif dtype != "int32":
                arr = piece.astype(dtype)
            else:
                arr = piece
            d[key] = arr.reshape(shape)
        outs.append(d)
    return outs


def _agg_envelope_runner(plan_sig, plan: Plan, meta: DeviceSegmentMeta,
                         k: int, layout, treedef, axes, agg_sig, agg_plans,
                         arrays, example_flat):
    """Jitted group program for agg-bearing batches + the host layout of
    each row's agg tail. Always the dense kernel: eval_aggs consumes the
    dense eligible mask the candidate-buffer kernel never materializes."""
    key = ("aggenv", plan_sig, agg_sig, meta.compile_key(), k, layout,
           treedef, axes)
    hit = _JIT_CACHE.get(key)
    if hit is None:
        out_layout, width = _agg_out_layout(
            plan, meta, agg_plans, arrays, example_flat, np.float32(0))
        fn = jit_family(build_batched_agg_query_phase(
            plan, meta, k, layout, treedef, axes, agg_plans), "agg_env")
        _JIT_CACHE[key] = (fn, out_layout, width)  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
        wrapped = _timed_first_call(
            fn, family="agg_env",
            shape=_env_shape(layout, k, meta, agg_bins=width),
            key=key, cost=_plan_cost(plan, meta, _layout_batch(layout)))
        return (wrapped, out_layout, width)
    return hit


def concat_rows(outs):
    """Column-pad + row-concat all group outputs into ONE device array, so
    a whole msearch batch is fetched in a single transfer (each fetch is
    a synchronization with the device)."""
    width = max(o.shape[1] for o in outs)
    return jnp.concatenate(
        [jnp.pad(o, ((0, 0), (0, width - o.shape[1]))) for o in outs],
        axis=0)


# the XLA module is named for the function: jit_concat_rows
_concat_rows = jax.jit(concat_rows)


def unpack_batched_result(packed: np.ndarray, k_eff: int):
    """Inverse of the packed [B, 2k+1] row layout from
    build_batched_query_phase."""
    scores = packed[:, :k_eff].view(np.float32)
    idx = packed[:, k_eff:2 * k_eff]
    totals = packed[:, 2 * k_eff]
    return scores, idx, totals


def _envelope_runner(plan_sig, plan: Plan, meta: DeviceSegmentMeta, k: int,
                     layout, treedef):
    """Jitted group program over a packed input envelope: the candidate-
    buffer kernel for plain text clauses within the lane budget, the dense
    kernel otherwise."""
    # meta.compile_key() (seg_id excluded): a refreshed segment whose
    # shapes land in an already-compiled bucket REUSES the executable
    # instead of paying a per-segment XLA recompile — the churn
    # ledger's warmup_hit verdict is true by construction (ISSUE 13)
    key = ("env", plan_sig, meta.compile_key(), k, layout, treedef)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        qb128 = 0
        n_terms = plan.static[1] if plan.kind == "text" \
            and len(plan.static) > 1 else 1 << 30
        for off, shape, dtype in layout:
            if len(shape) == 2:         # first [B, QB] leaf
                qb128 = shape[1] * 128
                break
        cand = _candidate_kernel_fits(plan.kind, n_terms, qb128)
        # the family names the XLA module (jit_<family>) and the census
        # record alike
        leaf = None if cand else _vector_leaf(plan)
        fam = "bm25_candidate" if cand else _plan_family(plan, leaf=leaf)
        if cand:
            # blockmax admission is a pure function of facts already in
            # the JIT key: the plan's input tree (treedef gains tid/
            # bscale only when compiled with the gate on), the layout's
            # lane count, and k — no extra key component needed
            fn = jit_family(build_candidate_query_phase(
                plan, meta, k, layout, treedef,
                bm=_blockmax_admitted(plan, k)), fam)
        else:
            fn = jit_family(build_batched_query_phase(plan, meta, k,
                                                      layout, treedef),
                            fam)
        _JIT_CACHE[key] = fn  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
        return _timed_first_call(
            fn, family=fam, key=key,
            shape=_env_shape(layout, k, meta,
                             leaf if fam == "knn" else None),
            cost=_plan_cost(plan, meta, _layout_batch(layout)))
    return fn


def _envelope_kernel(plan: Plan) -> str:
    """The kernel class _envelope_runner picks for one item's plan —
    `candidate` (candidate-buffer kernel) or `dense` — via the SAME
    `_candidate_kernel_fits` predicate the runner compiles with, so
    the scan heat map's kernel mix matches what dispatches. The lane
    count comes from the plan's `ids` input, which IS the packed
    layout's [B, QB] leaf width (the compiler pre-buckets shapes)."""
    n_terms = plan.static[1] if plan.kind == "text" \
        and len(plan.static) > 1 else 1 << 30
    ids = plan.inputs.get("ids")
    qb128 = ids.shape[-1] * 128 if ids is not None else 0
    return "candidate" \
        if _candidate_kernel_fits(plan.kind, n_terms, qb128) else "dense"


def _scan_accumulate_item(device, plans, seg_rows, per_query) -> None:
    """Always-on scan accounting for ONE msearch item (ISSUE 14),
    accumulated LOCALLY (plain dict adds on the wave's own state — no
    lock, no estimator): per compiled segment plan, posting-block
    bytes from the plan statics and — only when the dense kernel runs
    — the O(d_pad) dense-lane bytes the candidate-buffer kernel exists
    to avoid. `SCAN.note_batch` lands the whole wave in one flush."""
    from opensearch_tpu.telemetry.scan import (
        DENSE_LANE_BYTES, POSTING_BLOCK_BYTES, plan_scan_blocks,
        plan_scan_extra)
    q_posting = q_dense = 0
    noted = False
    for plan, (_, meta) in zip(plans, device):
        if plan is None or plan.kind == "match_none":
            continue
        posting = plan_scan_blocks(plan) * POSTING_BLOCK_BYTES
        kernel = _envelope_kernel(plan)
        dense = 0 if kernel == "candidate" \
            else meta.d_pad * DENSE_LANE_BYTES
        # rank_vectors token-matrix / PQ-code bytes (maxsim kernels)
        # fold into the dense class — they are O(d_pad) HBM traffic
        dense += plan_scan_extra(plan)
        row = seg_rows.get(meta.seg_id)
        if row is None:
            row = seg_rows[meta.seg_id] = [0, 0, 0, {}]
        row[0] += 1
        row[1] += posting
        row[2] += dense
        row[3][kernel] = row[3].get(kernel, 0) + 1
        q_posting += posting
        q_dense += dense
        noted = True
    if noted:
        per_query.append((q_posting, q_dense))


def _runner(plan_sig, plan: Plan, meta: DeviceSegmentMeta, k: int, sort_mode: str,
            agg_plans=()):
    key = (plan_sig, meta.compile_key(), k, sort_mode,
           tuple(a.sig() for a in agg_plans))
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    fam = _plan_family(plan, agg_plans)
    fn = jit_family(build_query_phase(plan, meta, k, sort_mode, agg_plans),
                    fam)
    _JIT_CACHE[key] = fn  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
    return _timed_first_call(
        fn, family=fam,
        shape=f"k{k}/d{meta.d_pad}/{sort_mode}", key=key,
        cost=_plan_cost(plan, meta))


def _hybrid_window_route(plan: Plan, d_pad: int, k: int) -> str:
    """How build_hybrid_query_phase selects a sub-query's window of k of
    `d_pad` lanes, decided at trace time: `from_clause` where the
    sub-plan's root is a `knn` clause (`_page_from_clause`: the window
    is the clause's own k winners, `knn_page`); else over the sub-plan's
    dense pair by `select_top_k`, `blocked` where it reads block maxima
    (`blocked_select_width`) and `plain` where it runs one `top_k`."""
    if _page_from_clause(plan):
        return "from_clause"
    return "blocked" if blocked_select_width(d_pad, min(k, d_pad)) \
        else "plain"


def _note_hybrid_windows(dispatched, n_items: int) -> None:
    """Count each sub-query of `n_items` hybrid items once under its
    window's route (`_hybrid_window_route`) in the largest segment they
    were dispatched to; `dispatched` holds (d_pad, plans, k) a
    segment."""
    if not dispatched:
        return
    d_pad, plans, k = max(dispatched, key=lambda seg: seg[0])
    for plan in plans:
        _HYBRID_WINDOWS[_hybrid_window_route(plan, d_pad, k)].inc(n_items)


def _hybrid_union_total(union, winners):
    """How many docs any sub-query may return, each once: the dense
    sub-queries' eligible docs (`union`, `[d_pad]`, None where every
    sub-query took its window from a clause), then each from-clause
    sub-query's returnable winners that lie in none of them and in no
    earlier winner list, compared pairwise, k × k. `winners` holds
    (doc ordinals, returnable) a from-clause sub-query."""
    with _stage("eligible_total"):
        total = jnp.int32(0) if union is None \
            else jnp.sum(union.astype(jnp.int32))
        for j, (idx, returnable) in enumerate(winners):
            new = returnable if union is None \
                else returnable & ~union[idx]
            for idx_i, returnable_i in winners[:j]:
                new = new & ~jnp.any((idx[:, None] == idx_i[None, :])
                                     & returnable_i[None, :], axis=1)
            total = total + jnp.sum(new.astype(jnp.int32))
        return total


def build_hybrid_query_phase(plans, meta: DeviceSegmentMeta, k: int):
    """The FUSED hybrid query phase for one segment: every sub-query of a
    `hybrid` clause evaluates inside ONE jitted program (one plan-signature
    executable, one dispatch, one fetch) instead of N sequential searches.

    Per sub-query the program emits its own top-k channel PLUS the score
    bounds the normalization-processor needs at reduce time:
      [k scores | k doc ords | count | min | max | sum-of-squares]
    and one trailing union total (a doc matching any sub-query counts once).
    Bounds are computed ON DEVICE over the sub-query's selected top-k
    window — the exact candidate set that reaches the coordinator — so the
    merge can reconstruct GLOBAL min/max (min-of-mins / max-of-maxs) and
    the global L2 norm (sum of per-shard sums) without a second pass over
    candidate lists, mirroring the reference's per-shard TopDocs bounds
    (neural-search NormalizationProcessorWorkflow over CompoundTopDocs).

    The window is `top_k`'s over the sub-query's eligible docs, by the
    route `_hybrid_window_route` picks: a sub-query that is a `knn`
    clause takes it from the clause's own k winners (`eval_knn_winners`,
    `knn_page`), with no `[d_pad]` pair and no second selection; every
    other one selects it from its dense pair by `select_top_k`. The ords
    of a window's padding slots (past its count, never read) are the
    route's own."""

    n_sub = len(plans)

    def run(seg, flat_inputs, min_score):
        cursor = [0]
        d_pad = seg["live"].shape[0]
        in_seg = jnp.arange(d_pad, dtype=jnp.int32) < meta.num_docs
        base = seg["live"] & seg["root"] & in_seg
        union = None
        winners = []
        pieces = []
        k_eff = min(k, d_pad)
        for i in range(n_sub):
            if _hybrid_window_route(plans[i], d_pad, k_eff) \
                    == "from_clause":
                scores, idx, valid = eval_knn_winners(plans[i], seg,
                                                      flat_inputs, cursor)
                returnable, _ = _winners_total(valid, idx, seg,
                                               meta.num_docs, scores,
                                               min_score)
                winners.append((idx, returnable))
                top_scores, top_idx = knn_page(scores, idx, returnable,
                                               k_eff)
            else:
                scores, matches = _eval_plan(plans[i], seg, flat_inputs,
                                             cursor)
                eligible = matches & base & (scores >= min_score)
                union = eligible if union is None else union | eligible
                top_scores, top_idx, _ = select_top_k(scores, eligible,
                                                      k_eff)
            valid = top_scores > NEG_INF
            cnt = jnp.sum(valid.astype(jnp.int32))
            mn = jnp.min(jnp.where(valid, top_scores, jnp.inf))
            mx = jnp.max(jnp.where(valid, top_scores, -jnp.inf))
            vs = jnp.where(valid, top_scores, 0.0)
            ssq = jnp.sum(vs * vs)
            # an int32 row with the floats bitcast in (see _pack_row)
            pieces.append(jnp.concatenate([
                jax.lax.bitcast_convert_type(top_scores, jnp.int32),
                top_idx.astype(jnp.int32), cnt[None],
                jax.lax.bitcast_convert_type(
                    jnp.stack([mn, mx, ssq]), jnp.int32)]))
        pieces.append(_hybrid_union_total(union, winners)[None])
        return jnp.concatenate(pieces)

    return run


def build_batched_hybrid_query_phase(plans, meta: DeviceSegmentMeta,
                                     k: int, layout, treedef):
    """B same-shaped hybrid queries against one segment as ONE device
    program: the fused multi-sub-query phase vmapped over the msearch
    envelope's packed batch axis — a whole dashboard of hybrid queries
    costs one upload, one program, one fetch."""
    one = build_hybrid_query_phase(plans, meta, k)

    def run(seg, packed_buf):
        batched_flat, min_scores = _unpack_envelope(packed_buf, layout,
                                                    treedef)
        return jax.vmap(one, in_axes=(None, 0, 0))(seg, batched_flat,
                                                   min_scores)

    return run


def _batched_hybrid_runner(plans, meta: DeviceSegmentMeta, k: int,
                           layout, treedef):
    key = ("hybenv", tuple(p.sig() for p in plans), meta.compile_key(),
           k, layout, treedef)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jit_family(build_batched_hybrid_query_phase(
            plans, meta, k, layout, treedef), "hybrid_env")
        _JIT_CACHE[key] = fn  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
        cost = [_plan_cost(p, meta, _layout_batch(layout))
                for p in plans]
        return _timed_first_call(
            fn, family="hybrid_env",
            shape=_hybrid_shape(layout, k, meta, plans), key=key,
            cost=(sum(c[0] for c in cost), sum(c[1] for c in cost)))
    return fn


def _vector_width(plan: Plan) -> int:
    """The width of the vectors a `knn` clause of the plan scans, at its
    root or under a parent; 0 where it has none."""
    if plan.kind == "knn":
        return int(plan.inputs["query"].shape[-1])
    return max((_vector_width(c) for c in plan.children), default=0)


def _hybrid_shape(layout, k: int, meta, plans) -> str:
    """Shape string of a `hybrid_env` executable: the padded batch, the
    segment's padded doc axis, each sub-query's window k, how many
    sub-queries, and the width of the vectors a `knn` sub-query scans
    (`dim0`: none) — with the text clauses' lanes, what a run costs."""
    dims = max((_vector_width(p) for p in plans), default=0)
    return (f"b{_layout_batch(layout)}/d{meta.d_pad}/k{k}"
            f"/sub{len(plans)}/dim{dims}")


def _decode_hybrid_row(row: np.ndarray, k_seg: int, n_sub: int):
    """Invert one segment's fused hybrid row: per-sub (scores, ords,
    count, min, max, sum_sq) channels + the trailing union total."""
    out = []
    off = 0
    for _ in range(n_sub):
        scores = row[off:off + k_seg].view(np.float32)
        ords = row[off + k_seg:off + 2 * k_seg]
        off += 2 * k_seg
        cnt = int(row[off])
        mn, mx, ssq = row[off + 1:off + 4].view(np.float32).tolist()  # sync-ok: host -- the fetched row is already a host array
        off += 4
        out.append((scores, ords, cnt, mn, mx, ssq))
    total = int(row[off])
    return out, total


# body keys the batched hybrid envelope fully renders (weights/techniques
# come from the pipeline spec, not the body)
_HYBRID_BATCHABLE_KEYS = frozenset({"query", "size", "from", "min_score",
                                    "_source", "track_total_hits"})


def _hybrid_msearch_batchable(body: dict) -> bool:
    return (_contains_hybrid(body.get("query"))
            and set(body) <= _HYBRID_BATCHABLE_KEYS)


class HybridShardResult:
    """One shard's fused hybrid query phase output: per-sub-query candidate
    lists + per-sub-query (min, max, sum_sq, count) bounds + union total."""
    __slots__ = ("per_sub", "bounds", "total")

    def __init__(self, per_sub, bounds, total):
        self.per_sub = per_sub      # [sub][(score, seg_i, ord), ...]
        self.bounds = bounds        # [sub](min, max, sum_sq, count)
        self.total = total


def _empty_hybrid_result(n_sub: int) -> HybridShardResult:
    return HybridShardResult(
        [[] for _ in range(n_sub)],
        [[float("inf"), float("-inf"), 0.0, 0] for _ in range(n_sub)], 0)


def _accumulate_hybrid_row(result: HybridShardResult, row: np.ndarray,
                           seg_i: int, k_seg: int, n_sub: int) -> None:
    channels, total = _decode_hybrid_row(row, k_seg, n_sub)
    for i, (scores, ords, cnt, mn, mx, ssq) in enumerate(channels):
        # top_k is score-desc with padding last: the first cnt lanes are
        # exactly the valid candidates
        for s, o in zip(scores[:cnt], ords[:cnt]):
            result.per_sub[i].append((float(s), seg_i, int(o)))
        if cnt:
            b = result.bounds[i]
            b[0] = min(b[0], mn)
            b[1] = max(b[1], mx)
            b[2] += ssq
            b[3] += cnt
    result.total += total


def _build_sort_key(arrays, primary_sort) -> jnp.ndarray:
    """Dense per-doc f32 key for the device's per-segment top-k selection
    (segment-local value ranks; higher sorts first; missing → MISSING_KEY)."""
    d_pad = arrays["live"].shape[0]
    if primary_sort is None:
        return jnp.zeros(d_pad, jnp.float32)
    field, order = primary_sort
    col = arrays["numeric"].get(field)
    if col is not None:
        if order == "asc":
            key = -col["min_rank"].astype(jnp.float32)
        else:
            key = col["max_rank"].astype(jnp.float32)
        return jnp.where(col["exists"], key, MISSING_KEY)
    col = arrays["ordinal"].get(field)
    if col is not None:
        pair_valid = col["doc_ids"] >= 0
        idx = jnp.where(pair_valid, col["doc_ids"], d_pad)
        if order == "asc":
            dense = jnp.full(d_pad, 2 ** 30, jnp.int32).at[idx].min(
                jnp.where(pair_valid, col["ords"], 2 ** 30), mode="drop")
            key = -dense.astype(jnp.float32)
        else:
            dense = jnp.full(d_pad, -1, jnp.int32).at[idx].max(
                jnp.where(pair_valid, col["ords"], -1), mode="drop")
            key = dense.astype(jnp.float32)
        return jnp.where(col["exists"], key, MISSING_KEY)
    return jnp.full(d_pad, MISSING_KEY, jnp.float32)


# ------------------------------------------------------ result page (ISSUE 17)
#
# The single-round-trip result page: a SECOND jitted program per wave
# that (a) re-keys every segment's per-segment winners with cross-
# segment-comparable decoded values and lax.top_k's them into ONE
# global candidate page, (b) gathers the winners' sort-key ranks inside
# the same program (the host's exact-value re-scan disappears — decode
# is an O(1) unique[rank] lookup per winner), and (c) gathers each
# fused docvalue field's rank + exists lane for the winners, so the
# fetch phase's per-hit column reads disappear too. Everything lands in
# one packed int32 buffer (f32 lanes bitcast, the pack_leaves idiom)
# fetched together with the agg partials in ONE device_get.

def _page_sort_mode(body: dict, sort_specs, mapper):
    """Static page admission: ("score",) / ("field", name, order) when
    the request's result assembly can ride the on-device merge, None for
    the legacy host merge. Collapse/rescore post-process the candidate
    POOL and need the full per-segment over-fetch (the page's global cut
    would under-fill them — same reason search/spmd.py excludes them);
    multi-key and keyword sorts keep the host path (ordinal ranks are
    not comparable across segments)."""
    if body.get("collapse") or body.get("rescore"):
        return None
    if len(sort_specs) != 1:
        return None
    field, order = sort_specs[0]
    if field == "_score":
        return ("score",)
    ft = mapper.get_field(field)
    if ft is None or not (ft.is_numeric or ft.is_date or ft.is_bool):
        return None
    return ("field", field, order)


def _page_dv_fields(body: dict, mapper) -> tuple:
    """The docvalue_fields specs a result page can fuse: numeric-typed
    fields (decode is rank -> host unique[], exact f64 — dates included,
    unlike the f32-compared SORT key). Keyword fields keep the host
    dictionary scan; per-SEGMENT multi-valued columns fall back in
    _page_segment_admit."""
    out = []
    for spec in body.get("docvalue_fields") or []:
        field = spec["field"] if isinstance(spec, dict) else spec
        ft = mapper.get_field(field)
        if ft is not None and (ft.is_numeric or ft.is_date or ft.is_bool) \
                and field not in out:
            out.append(field)
    return tuple(out)


def _page_segment_admit(seg, arrays, meta, mode, dv_fields):
    """Per-segment page admission + the device/host column refs one
    segment contributes. None disqualifies the whole request (a sort
    column whose values are not exactly f32-representable — selection
    on device would diverge from the host's exact keys). Per dv field:
    `col` (device gather + host unique[] decode), `absent` (no column —
    decode to no-values), or `host` (multi-valued: the fetch phase's
    host scan, with its own per-leaf round-trip accounting)."""
    out = {"d_pad": meta.d_pad, "sort_col": None, "sort_host": None,
           "dv_state": {}}
    if mode[0] == "field":
        field = mode[1]
        host = seg.numeric_dv.get(field)
        if host is not None and not f32_sortable(host):
            return None
        out["sort_col"] = arrays["numeric"].get(field)
        out["sort_host"] = host
    for f in dv_fields:
        host = seg.numeric_dv.get(f)
        dev = arrays["numeric"].get(f)
        if host is None and f not in seg.ordinal_dv:
            out["dv_state"][f] = ("absent", None, None)
        elif host is not None and dev is not None and single_valued(host):
            out["dv_state"][f] = ("col", dev, host)
        else:
            out["dv_state"][f] = ("host", None, None)
    return out


def _page_merger(sig, mode, k_page: int, stride: int, seg_statics,
                 dv_fields):
    """The cached jitted page-merge program (one executable per layout
    signature, the same _JIT_CACHE + compile-event discipline as
    _runner). Takes every segment's (keys, scores, idx, total) plus the
    device column refs and returns ONE packed int32 page."""
    fn = _JIT_CACHE.get(sig)
    if fn is not None:
        return fn
    field_mode = mode[0] == "field"
    order = mode[2] if field_mode else None

    def run(rows):
        keys, scores, gids = [], [], []
        sranks, sexists = [], []
        dv_lanes = {f: ([], []) for f in dv_fields}
        for pos, ((k_i, d_pad, _has_sort, dv_states), row) in enumerate(
                zip(seg_statics, rows)):
            ti = row["idx"]
            valid = row["keys"] != NEG_INF
            if field_mode:
                # re-key this segment's winners with decoded VALUES:
                # per-segment selection by rank is order-correct inside
                # the segment, but ranks are not comparable across
                # segments — the value key is (ops/topk.py)
                col = row.get("sort_col")
                vkey = value_merge_key(col, order, d_pad)
                keys.append(jnp.where(valid, vkey[ti], NEG_INF))
                if col is None:
                    sranks.append(jnp.zeros(ti.shape[0], jnp.int32))
                    sexists.append(jnp.zeros(ti.shape[0], jnp.int32))
                else:
                    ra = col["min_rank"] if order == "asc" \
                        else col["max_rank"]
                    sranks.append(ra[ti])
                    sexists.append(col["exists"][ti].astype(jnp.int32))
            else:
                keys.append(row["keys"])
            scores.append(row["scores"])
            gids.append(jnp.int32(pos * stride) + ti)
            for f, state in zip(dv_fields, dv_states):
                r_l, e_l = dv_lanes[f]
                if state == "col":
                    col = row["dv"][f]
                    r_l.append(col["min_rank"][ti])
                    e_l.append(col["exists"][ti].astype(jnp.int32))
                else:
                    r_l.append(jnp.zeros(ti.shape[0], jnp.int32))
                    e_l.append(jnp.zeros(ti.shape[0], jnp.int32))
        mk, mi = jax.lax.top_k(jnp.concatenate(keys), k_page)
        parts = [jax.lax.bitcast_convert_type(mk, jnp.int32),
                 jax.lax.bitcast_convert_type(
                     jnp.concatenate(scores)[mi], jnp.int32),
                 jnp.concatenate(gids)[mi]]
        if field_mode:
            parts.append(jnp.concatenate(sranks)[mi])
            parts.append(jnp.concatenate(sexists)[mi])
        for f in dv_fields:
            r_l, e_l = dv_lanes[f]
            parts.append(jnp.concatenate(r_l)[mi])
            parts.append(jnp.concatenate(e_l)[mi])
        parts.append(jnp.stack([row["total"] for row in rows])
                     .astype(jnp.int32).reshape(-1))
        return jnp.concatenate(parts)

    fn = jit_family(run, "page_merger")
    _JIT_CACHE[sig] = fn  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
    return _timed_first_call(
        fn, family="page_merger",
        shape=f"k{k_page}/s{stride}/n{len(seg_statics)}", key=sig)


class _Candidate:
    __slots__ = ("score", "seg_i", "ord", "sort_values", "shard_i",
                 "collapse_value", "dv_page")

    def __init__(self, score, seg_i, ord_, sort_values, shard_i=0):
        self.score = score
        self.seg_i = seg_i
        self.ord = ord_
        self.sort_values = sort_values  # list parallel to sort specs; None = missing
        self.shard_i = shard_i          # coordinator-side shard index
        # result-page prefetch (ISSUE 17): {field: [raw values]} decoded
        # from the fused docvalue lanes; None = no page rode this
        # candidate (fetch falls back to the per-leaf host scan)
        self.dv_page = None


def _compare_candidates(specs):
    """Multi-key comparator with missing-last semantics (reference default).
    Final tie-break (shard, segment, doc) asc — mergeTopDocs order
    (action/search/SearchPhaseController.java:228)."""
    def cmp(a: _Candidate, b: _Candidate) -> int:
        for i, (field, order) in enumerate(specs):
            va, vb = a.sort_values[i], b.sort_values[i]
            if va is None and vb is None:
                continue
            if va is None:
                return 1   # missing sorts last
            if vb is None:
                return -1
            if va != vb:
                lt = va < vb
                if order == "desc":
                    lt = not lt
                return -1 if lt else 1
        if a.shard_i != b.shard_i:
            return -1 if a.shard_i < b.shard_i else 1
        if a.seg_i != b.seg_i:
            return -1 if a.seg_i < b.seg_i else 1
        return -1 if a.ord < b.ord else 1
    return functools.cmp_to_key(cmp)


# request keys the batched envelope path fully renders; anything else
# (highlight, collapse, rescore, ...) takes the general path
_BATCHABLE_KEYS = frozenset({"query", "size", "from", "min_score", "sort",
                             "_source", "aggs", "aggregations",
                             "_request_cache"})


def _contains_hybrid(query_spec) -> bool:
    """Top-level hybrid clause detection on the RAW body (pre-parse): the
    batched envelope and the general host loop both hand hybrid off to the
    fused hybrid query phase (searchpipeline/hybrid.py drives it)."""
    return isinstance(query_spec, dict) and "hybrid" in query_spec


def _contains_inner_hits(obj) -> bool:
    if isinstance(obj, dict):
        return "inner_hits" in obj or any(_contains_inner_hits(v)
                                          for v in obj.values())
    if isinstance(obj, list):
        return any(_contains_inner_hits(v) for v in obj)
    return False


def _msearch_batchable(body: dict) -> bool:
    return (set(body) <= _BATCHABLE_KEYS
            and body.get("sort") in (None, "_score", ["_score"])
            # inner_hits need the full fetch sub-phase pipeline, which
            # the batched envelope's _hit_dict does not run
            and not _contains_inner_hits(body.get("query"))
            # hybrid executes through its own fused multi-sub-query
            # program with per-sub-query score channels — the envelope's
            # single (scores, matches) row can't carry them
            and not _contains_hybrid(body.get("query")))


class SearchExecutor:
    """Executes a parsed search request against one shard (query + fetch)."""

    def __init__(self, reader: ShardReader):
        self.reader = reader
        # index.max_result_window (set by the owning IndexService; the
        # default matches the reference)
        self.max_result_window = 10000
        # index.requests.cache.enable (likewise; indices/request_cache.py
        # `admits` is the one reader)
        self.request_cache_enabled = True
        # wave-pipeline staging: recycled host envelope buffers, released
        # only after the owning wave's collect (zero-copy-safe reuse)
        self._staging = _StagingPool()

    def search(self, body: Optional[dict] = None,
               _direct: bool = False) -> dict:
        from opensearch_tpu.search.controller import execute_search
        body = body or {}
        if not _direct and _msearch_batchable(body):
            # single searches share the batched envelope kernel (B=1): one
            # program, one upload, and bit-identical scores with _msearch;
            # errors raise (the per-item error wrapping is _msearch-only)
            return self.multi_search(
                [body], _raise_item_errors=True)["responses"][0]
        return execute_search([self], body)

    def execute_query_phase(self, body: dict, k: int,
                            extra_filter: Optional[dict] = None,
                            stats_override=None, trace=None,
                            ledger_scope=None):
        """Per-shard query phase (SearchService.executeQueryPhase analog):
        returns (candidates, per-segment decoded agg partials, total hits)
        for the coordinator to merge. `k` = from+size requested globally.
        `extra_filter` is an alias filter applied as a non-scoring clause
        (reference: QueryShardContext filter from AliasFilter). `trace`
        (a telemetry Span or None) collects device-dispatch attribution:
        compile/dispatch/collect ns, bytes_to_device, XLA compile events.

        size=0 requests are served through the shard request cache
        (IndicesRequestCache analog — indices/request_cache.py); the key
        includes the segment identities, so refreshes/deletes miss."""
        body = body or {}
        # DFS requests never cache (the reference excludes
        # dfs_query_then_fetch from IndicesRequestCache): the global stats
        # live outside the shard's own segments, so a per-shard key can't
        # see them change
        if body.get("search_type") == "dfs_query_then_fetch" \
                or "_dfs" in body:
            return self._query_phase_uncached(body, k, extra_filter,
                                              stats_override, trace,
                                              ledger_scope)
        rc = _request_cache()
        if rc.admits(body, self.request_cache_enabled):
            base = rc.cache_key(self.reader.segments, body, k,
                                extra_filter)
            key = ("shard", base) if base is not None else None
            if key is not None:
                hit = _cache_get_isolated(rc, key)
                if hit is not rc.REQUEST_CACHE._MISS:
                    if trace is not None:
                        trace.set_attribute("request_cache", "hit")
                    cts, decoded, total = hit
                    return ([_Candidate(s, g, o, sv)
                             for s, g, o, sv in cts], decoded, total)
                if trace is not None:
                    trace.set_attribute("request_cache", "miss")
                cands, decoded, total = self._query_phase_uncached(
                    body, k, extra_filter, stats_override, trace,
                    ledger_scope)
                # store candidates as plain tuples: callers mutate
                # _Candidate.shard_i, which must not leak between hits
                _cache_put_isolated(
                    rc, key, ([(c.score, c.seg_i, c.ord, c.sort_values)
                               for c in cands], decoded, total))
                return cands, decoded, total
        return self._query_phase_uncached(body, k, extra_filter,
                                          stats_override, trace,
                                          ledger_scope)

    def _query_phase_uncached(self, body: dict, k: int,
                              extra_filter: Optional[dict] = None,
                              stats_override=None, trace=None,
                              ledger_scope=None):
        node = dsl.parse_query(body.get("query"))
        if extra_filter is not None:
            node = dsl.BoolQuery(must=[node],
                                 filter=[dsl.parse_query(extra_filter)])
        slice_spec = body.get("slice")
        if slice_spec is not None:
            sid = int(slice_spec.get("id", 0))
            smax = int(slice_spec.get("max", 0))
            if smax < 2:
                raise IllegalArgumentError("[slice] max must be >= 2")
            if not 0 <= sid < smax:
                raise IllegalArgumentError(
                    f"[slice] id must be in [0, {smax})")
            node = dsl.BoolQuery(must=[node],
                                 filter=[dsl.SliceQuery(id=sid, max=smax)])
        min_score = float(body["min_score"]) if body.get("min_score") is not None \
            else NEG_INF

        sort_specs = _parse_sort(body.get("sort"))
        score_sorted = sort_specs[0][0] == "_score"
        primary = None if score_sorted else sort_specs[0]

        # DFS query-then-fetch: score with the coordinator-merged global
        # statistics instead of shard-local ones (StaticStats)
        if stats_override is not None:
            stats = stats_override
            segments, device = self.reader.snapshot()
        else:
            # one consistent (stats, segments, device) anchor: a
            # concurrent refresh publishing mid-request must not let
            # this request pair segment i with another segment's arrays
            stats, segments, device = self.reader.stats_snapshot()
        compiler = Compiler(self.reader.mapper, stats)
        agg_nodes = parse_aggs(body.get("aggs") or body.get("aggregations"))
        from opensearch_tpu.search.aggs.parse import PIPELINE_TYPES
        device_agg_nodes = [n for n in agg_nodes
                            if n.type not in PIPELINE_TYPES]
        k_fetch = min(k + 128, 1 << 16)  # over-fetch for ties & cross-seg merge

        # single-round-trip result page (RESULT_PAGE, ISSUE 17): static
        # admission here, per-segment admission in the dispatch loop;
        # page_rows collapses to None the moment any segment (or later
        # the gid-packing range) disqualifies — the legacy host merge is
        # always the fallback and stays byte-identical when gated off
        page_mode = _page_sort_mode(body, sort_specs, self.reader.mapper) \
            if RESULT_PAGE else None
        page_dv = _page_dv_fields(body, self.reader.mapper) \
            if page_mode is not None else ()
        page_rows = [] if page_mode is not None else None

        # phase 1: dispatch every segment's program without forcing — jax
        # dispatch is async, so device work overlaps; phase 2 collects ALL
        # results in ONE device_get (one synchronization in total)
        rec = trace is not None and getattr(trace, "recording", False)
        # per-shard transfer accounting (None = ledger off AND request not
        # traced/profiled — the zero-overhead path)
        scope = _LEDGER.scope(trace)
        if rec:
            # request-scoped compile attribution via the thread-local
            # accumulator (_note_compile) — global-counter deltas would
            # charge this span with CONCURRENT requests' compiles
            _THREAD_COMPILES.active = True
            _THREAD_COMPILES.count = 0
            _THREAD_COMPILES.ms = 0.0
            plan_compile_ns = dispatch_ns = 0
        launched = []
        from opensearch_tpu.telemetry.scan import (
            DENSE_LANE_BYTES, POSTING_BLOCK_BYTES, SCAN,
            plan_scan_blocks, plan_scan_extra)
        scan_shard = str(getattr(self.reader, "shard_id", 0))
        q_posting = q_dense = 0
        from opensearch_tpu.indices.query_cache import FilterCacheContext
        for seg_i, (seg, (arrays, meta)) in enumerate(
                zip(segments, device)):
            if seg.num_docs == 0:
                continue
            if rec:
                t0 = time.perf_counter_ns()
            compiler.filter_ctx = FilterCacheContext(seg, arrays)
            plan = compiler.compile(node, seg, meta)
            compiler.filter_ctx = None
            agg_plans = compile_aggs(device_agg_nodes, self.reader.mapper, seg,
                                     meta, compiler) if agg_nodes else []
            note_bin_sources(agg_plans)
            if rec:
                plan_compile_ns += time.perf_counter_ns() - t0
            # always-on scan accounting (telemetry/scan.py, ISSUE 14):
            # this path runs the DENSE kernel (build_query_phase) —
            # posting blocks gathered per the plan statics plus the
            # O(d_pad) dense lanes, attributed per (shard, segment)
            posting = plan_scan_blocks(plan) * POSTING_BLOCK_BYTES
            dense = meta.d_pad * DENSE_LANE_BYTES + plan_scan_extra(plan)
            SCAN.note_segment(self.reader.index_name, scan_shard,
                              meta.seg_id, posting, dense, "dense")
            q_posting += posting
            q_dense += dense
            if page_rows is not None:
                prow = _page_segment_admit(seg, arrays, meta, page_mode,
                                           page_dv)
                if prow is None:
                    page_rows = None
                else:
                    page_rows.append(prow)
            sort_key = _build_sort_key(arrays, primary)
            fn = _runner(plan.sig(), plan, meta,
                         min(k_fetch, pad_bucket(max(seg.num_docs, 1))),
                         "score" if score_sorted else "field",
                         tuple(agg_plans))
            flat = plan.flatten_inputs([])
            for ap in agg_plans:
                ap.flatten_inputs(flat)
            if scope is not None:
                _LEDGER.record(
                    "upload.literals", "h2d",
                    sum(int(np.asarray(v).nbytes)
                        for d in flat for v in d.values()),
                    scope=scope)
            if rec:
                t0 = time.perf_counter_ns()
            flat = jax.tree_util.tree_map(jnp.asarray, flat)
            seg_in = self.reader.with_lane_bins(arrays, meta, agg_plans)

            def _dispatch(fn=fn, arrays=seg_in, flat=flat,
                          sort_key=sort_key):
                # fault site + bounded transient retry around the device
                # call: a transient dispatch blip costs a retry, not the
                # shard (the jitted fn is pure — re-dispatch is safe)
                if faults.ENABLED:
                    faults.fire("query.dispatch")
                return fn(arrays, flat, sort_key, jnp.float32(min_score))
            launched.append((seg_i, seg, agg_plans,
                             retry.call_with_retry(
                                 _dispatch, label="query.dispatch",
                                 trace=trace)))
            if rec:
                dispatch_ns += time.perf_counter_ns() - t0

        if launched:
            SCAN.note_query(q_posting, q_dense)
            ins = _INSIGHTS.gate()
            if ins is not None:
                # the per-request scan join (ISSUE 15): the SAME bytes
                # the heat map just counted, accumulated thread-locally
                # for the controller's per-shape note at request end
                ins.add_scan(q_posting, q_dense)

        page_args = None
        if page_rows is not None and launched:
            page_args = self._page_build(launched, page_rows, page_mode,
                                         page_dv, k_fetch, body)

        def _collect():
            if faults.ENABLED:
                faults.fire("fetch.gather")
            if page_args is not None:
                # dispatch the page merger, then fetch the packed page
                # TOGETHER with the agg partials: one device_get, one
                # round trip for the wave's entire result assembly
                fn, rows_arg, _lay = page_args
                return jax.device_get(
                    (fn(rows_arg), [o[3][4] for o in launched]))
            return jax.device_get([out for _, _, _, out in launched])

        t0c = time.monotonic() if scope is not None else 0.0
        with _LEDGER.attributed(scope):
            if rec:
                try:
                    with trace.child("device_collect",
                                     segments=len(launched)):
                        fetched = retry.call_with_retry(
                            _collect, label="fetch.gather", trace=trace)
                finally:
                    _THREAD_COMPILES.active = False
            else:
                fetched = retry.call_with_retry(_collect,
                                                label="fetch.gather")
        if scope is not None:
            if page_args is not None:
                _ledger_page_collect(scope, fetched[0], fetched[1],
                                     (time.monotonic() - t0c) * 1000)
            else:
                _ledger_unbatched_collect(scope, fetched,
                                          (time.monotonic() - t0c) * 1000)
            if rec:
                xla_compiles = _THREAD_COMPILES.count
                trace.set_attribute("plan_compile_ns", plan_compile_ns)
                trace.set_attribute("device_dispatch_ns", dispatch_ns)
                trace.set_attribute("bytes_to_device", scope.h2d_bytes)
                trace.set_attribute("bytes_fetched", scope.d2h_bytes)
                trace.set_attribute("transfers", scope.to_list())
                trace.set_attribute("compiled", xla_compiles > 0)
                if xla_compiles:
                    trace.set_attribute("xla_compiles", xla_compiles)
                    trace.set_attribute("compile_ms",
                                        round(_THREAD_COMPILES.ms, 3))

        def _absorb():
            # absorb runs LAST: the legacy path's re-key round trip
            # (below) must reach the caller's request scope too
            if scope is not None and ledger_scope is not None \
                    and ledger_scope is not scope:
                ledger_scope.absorb(scope)

        if page_args is not None:
            out = self._decode_page(fetched, page_args, launched,
                                    agg_nodes)
            _absorb()
            return out

        candidates: List[_Candidate] = []
        per_segment_decoded = []
        total = 0
        t0r = time.monotonic() if scope is not None else 0.0
        for (seg_i, seg, agg_plans, _), outs in zip(launched, fetched):
            top_keys, top_scores, top_idx, seg_total, agg_outs = outs
            if agg_nodes:
                per_segment_decoded.append(decode_outputs(agg_plans, agg_outs))
            total += int(seg_total)
            for key_val, score, ord_ in zip(top_keys, top_scores, top_idx):
                if key_val == NEG_INF:
                    continue  # ineligible / padding
                sort_values = [
                    float(score) if f == "_score" else _sort_value(seg, f, o, int(ord_))
                    for f, o in sort_specs]
                candidates.append(_Candidate(float(score), seg_i, int(ord_),
                                             sort_values))
        if scope is not None and primary is not None and candidates:
            # round-trip attribution fix (ISSUE 17 satellite 1): the
            # exact-value re-key above reads the sort column once per
            # winner — served by the host mirror here (zero wire bytes,
            # so byte conservation against the measured device_get
            # holds) but a full gather round trip on a remote device.
            # The result page (RESULT_PAGE) extracts these keys inside
            # the merge program and never pays it.
            _LEDGER.note_round_trip("sort_keys",
                                    (time.monotonic() - t0r) * 1000,
                                    scope=scope)
        _absorb()
        return candidates, per_segment_decoded, total

    def _page_build(self, launched, page_rows, page_mode, page_dv,
                    k_fetch: int, body: dict):
        """Assemble the page merger's (jitted fn, device args, layout)
        for one wave, or None when the gid packing cannot cover the
        launched segments in int32 (the legacy collect takes over)."""
        stride = max(r["d_pad"] for r in page_rows)
        if len(launched) * stride >= (1 << 31):
            return None
        seg_statics, rows_arg = [], []
        lanes = 0
        for (seg_i, seg, agg_plans, out), prow in zip(launched, page_rows):
            top_keys, top_scores, top_idx = out[0], out[1], out[2]
            k_i = int(top_keys.shape[0])
            lanes += k_i
            dv_states = tuple(prow["dv_state"][f][0] for f in page_dv)
            seg_statics.append((k_i, prow["d_pad"],
                                prow["sort_col"] is not None, dv_states))
            arg = {"keys": top_keys, "scores": top_scores, "idx": top_idx,
                   "total": out[3]}
            if prow["sort_col"] is not None:
                arg["sort_col"] = prow["sort_col"]
            dv_cols = {f: prow["dv_state"][f][1] for f in page_dv
                       if prow["dv_state"][f][0] == "col"}
            if dv_cols:
                arg["dv"] = dv_cols
            rows_arg.append(arg)
        k_page = min(k_fetch, lanes)
        mode_sig = page_mode if page_mode[0] == "score" \
            else (page_mode[0], page_mode[1], page_mode[2])
        sig = ("page", mode_sig, k_page, stride, tuple(seg_statics),
               page_dv)
        fn = _page_merger(sig, page_mode, k_page, stride,
                          tuple(seg_statics), page_dv)
        # page-shaped executables enter the warmup registry: a node
        # restart (search/warmup.py warm_all) or a publish-triggered
        # precompile replay (Precompiler) re-runs the body and — with
        # the node's RESULT_PAGE gate on — reproduces exactly this
        # merger executable off the serving path
        from opensearch_tpu.search.warmup import WARMUP
        WARMUP.record(self.reader.index_name, body, 1, sig)
        lay = {"mode": page_mode, "k_page": k_page, "stride": stride,
               "dv_fields": page_dv, "rows_meta": page_rows}
        return fn, rows_arg, lay

    def _decode_page(self, fetched, page_args, launched, agg_nodes):
        """Host decode of one packed result page: candidates with exact
        sort values (rank -> host unique[], f64 — no f32 precision ever
        reaches a response) and the fused docvalue prefetch attached per
        candidate, plus per-segment totals and decoded agg partials."""
        packed, agg_fetched = fetched
        _fn, _rows, lay = page_args
        # already host-resident: the one device_get in _collect() moved
        # (and _ledger_page_collect accounted) every byte of the page
        buf = np.asarray(packed)  # sync-ok: result_page
        k_page, stride = lay["k_page"], lay["stride"]
        off = 0

        def take(n):
            nonlocal off
            part = buf[off:off + n]
            off += n
            return part

        mk = take(k_page).view(np.float32)
        msc = take(k_page).view(np.float32)
        mg = take(k_page)
        field_mode = lay["mode"][0] == "field"
        srank = sexists = None
        if field_mode:
            field, order = lay["mode"][1], lay["mode"][2]
            srank, sexists = take(k_page), take(k_page)
        dv_cols = [(f, take(k_page), take(k_page))
                   for f in lay["dv_fields"]]
        totals = take(len(launched))
        total = int(totals.sum())
        per_segment_decoded = []
        if agg_nodes:
            for (_seg_i, _seg, agg_plans, _), agg_outs in zip(
                    launched, agg_fetched):
                per_segment_decoded.append(
                    decode_outputs(agg_plans, agg_outs))
        candidates: List[_Candidate] = []
        for j in range(k_page):
            if mk[j] == NEG_INF:
                continue  # ineligible / padding
            pos, ord_ = divmod(int(mg[j]), stride)
            seg_i, seg = launched[pos][0], launched[pos][1]
            score = float(msc[j])
            if field_mode:
                if sexists[j]:
                    # exact f64 decode (host unique[]): the f32 merge key
                    # selected, the host table answers — same contract as
                    # _sort_value's vals.min()/max()
                    host = lay["rows_meta"][pos]["sort_host"]
                    v = float(host.unique[int(srank[j])])
                    sv = [int(v) if v.is_integer() else v]
                else:
                    sv = [None]
            else:
                sv = [score]
            cand = _Candidate(score, seg_i, ord_, sv)
            if dv_cols:
                prow = lay["rows_meta"][pos]
                dvm = {}
                for f, ranks, exists in dv_cols:
                    state, _dev, host = prow["dv_state"][f]
                    if state == "host":
                        continue  # fetch-phase host scan (own accounting)
                    if state == "col" and exists[j]:
                        dvm[f] = [float(host.unique[int(ranks[j])])]
                    else:
                        dvm[f] = []
                cand.dv_page = dvm
            candidates.append(cand)
        return candidates, per_segment_decoded, total

    def execute_hybrid_query_phase(self, body: dict, k: int,
                                   extra_filter: Optional[dict] = None,
                                   ledger_scope=None
                                   ) -> "HybridShardResult":
        """Per-shard fused hybrid query phase: ALL sub-queries of the
        hybrid clause run as ONE device program per segment (dispatched
        async across segments, collected with one device_get), returning
        per-sub-query candidates + score bounds for the coordinator's
        normalization merge (searchpipeline/hybrid.py). `ledger_scope`
        (telemetry/ledger.py) accumulates this shard's transfer
        attribution for the caller's span / slow log.

        In the always-on span ring, under the span open on this thread
        (`rest.search`), from five clock reads: `hybrid.compile` (parse,
        both sub-queries' plans, flatten, stack and pack, up to the
        first upload), `dispatch` (the first upload to the last jit
        call's return: `family` hybrid_env, `fingerprint`, `shape`,
        `programs`, `nbytes`) and `device_wait` (the blocking
        `device_get` of the rows); `wave` counts the shards this
        request dispatched for before, as the SPMD route's does."""
        t_start = time.monotonic()
        node = dsl.parse_query(body.get("query"))
        if not isinstance(node, dsl.HybridQuery):
            raise IllegalArgumentError(
                "execute_hybrid_query_phase requires a top-level [hybrid] "
                "query")
        min_score = float(body["min_score"]) \
            if body.get("min_score") is not None else NEG_INF
        n_sub = len(node.queries)
        sub_nodes: List[dsl.QueryNode] = []
        for sub in node.queries:
            if extra_filter is not None:
                sub = dsl.BoolQuery(must=[sub],
                                    filter=[dsl.parse_query(extra_filter)])
            sub_nodes.append(sub)
        stats, segments, device = self.reader.stats_snapshot()
        compiler = Compiler(self.reader.mapper, stats)
        # per-sub-query candidate window = from+size, the reference's
        # per-shard TopDocs size for hybrid sub-queries (no tie overfetch:
        # no cursor path rides hybrid, and the window depth directly sets
        # both the top_k cost and the normalization pool)
        k_fetch = min(k, 1 << 16)

        from opensearch_tpu.indices.query_cache import FilterCacheContext
        from opensearch_tpu.search.warmup import WARMUP
        scope = ledger_scope if ledger_scope is not None \
            else _LEDGER.scope()
        launched = []
        dispatched = []
        infos: List[Any] = []
        sent_bytes = 0
        t_dispatch = 0.0
        struct_parts: List[Any] = []
        shape_parts: List[Any] = []
        for seg_i, (seg, (arrays, meta)) in enumerate(
                zip(segments, device)):
            if seg.num_docs == 0:
                struct_parts.append(None)
                shape_parts.append(None)
                continue
            compiler.filter_ctx = FilterCacheContext(seg, arrays)
            plans = [compiler.compile(q, seg, meta) for q in sub_nodes]
            compiler.filter_ctx = None
            k_seg = min(k_fetch, pad_bucket(max(seg.num_docs, 1)))
            flat: List[Dict[str, np.ndarray]] = []
            for p in plans:
                p.flatten_inputs(flat)
            struct_parts.append(tuple(p.sig() for p in plans))
            shape_parts.append(tuple((k2, v.shape, v.dtype.num)
                                     for d in flat for k2, v in d.items()))
            # the B=1 envelope program: the SAME executable family as the
            # batched _msearch hybrid path (identical layout/treedef), so
            # single searches and batches share warmed executables
            stacked, treedef, _axes = stack_flat_inputs([flat])
            stacked.append(np.asarray([min_score], dtype=np.float32))
            buf, layout = pack_leaves(stacked)
            fn = _batched_hybrid_runner(plans, meta, k_seg, layout,
                                        treedef)

            def _dispatch(fn=fn, arrays=arrays, buf=buf):
                if faults.ENABLED:
                    faults.fire("query.dispatch")
                return fn(arrays, jnp.asarray(buf))
            if not launched:
                t_dispatch = time.monotonic()
            launched.append((seg_i, k_seg, retry.call_with_retry(
                _dispatch, label="query.dispatch")))
            dispatched.append((meta.d_pad, plans, k_seg))
            sent_bytes += buf.nbytes
            info = getattr(fn, "exec_info", None)
            if info is not None:
                infos.append(info)
            if scope is not None:
                # after the dispatch: a failed one must not count h2d
                # bytes that never crossed
                _LEDGER.record("upload.literals", "h2d", buf.nbytes,
                               scope=scope)
        _note_hybrid_windows(dispatched, 1)
        if extra_filter is None:
            # register the fused executable's (plan-struct, shape-bucket)
            # signature so index-open / node-start warmup AOT-compiles the
            # hybrid program off the query path — replaying the recorded
            # body through multi_search reproduces exactly this B=1 group
            # (alias-filtered variants are skipped: the recorded body
            # alone cannot reproduce their plans)
            WARMUP.record(self.reader.index_name, body, 1,
                          ("hybenv", tuple(struct_parts),
                           tuple(shape_parts), k_fetch, 1))

        result = _empty_hybrid_result(n_sub)
        if launched:
            t_dispatched = time.monotonic()

            def _collect():
                if faults.ENABLED:
                    faults.fire("fetch.gather")
                return jax.device_get([out for _, _, out in launched])
            t0c = time.monotonic()
            with _LEDGER.attributed(scope):
                fetched = retry.call_with_retry(_collect,
                                                label="fetch.gather")
            t_got = time.monotonic()
            if scope is not None:
                _ledger_hybrid_rows(
                    scope, [(1, 1, k_seg, n_sub)
                            for _seg_i, k_seg, _ in launched],
                    (t_got - t0c) * 1000)
            _note_hybrid_spans(
                (t_start, t_dispatch, t_dispatched, t0c, t_got),
                len(launched), sent_bytes, infos, fetched)
            for (seg_i, k_seg, _), rows in zip(launched, fetched):
                _accumulate_hybrid_row(result, np.asarray(rows)[0], seg_i,
                                       k_seg, n_sub)
        result.bounds = [tuple(b) for b in result.bounds]
        return result

    def _hit_dict(self, seg_i: int, ord_: int, score: Optional[float],
                  body: dict, segments=None) -> dict:
        """One search hit (fetch phase for a single doc) — shared by search()
        and multi_search(). `segments` is the query phase's snapshot
        list: under a concurrent refresh, `seg_i` must resolve against
        the list the candidates were produced over, not today's."""
        seg = (segments if segments is not None
               else self.reader.segments)[seg_i]
        hit = {"_index": self.reader.index_name,
               "_id": seg.doc_ids[ord_],
               "_score": score}
        src = _filter_source(seg.sources[ord_], body.get("_source", True))
        if src is not None:
            hit["_source"] = src
        return hit

    def multi_search(self, bodies: List[dict],
                     _bypass_request_cache: bool = False,
                     _raise_item_errors: bool = False,
                     task=None, deadline: Optional[float] = None,
                     trace=None,
                     phase_times: Optional[dict] = None,
                     waves: Optional[int] = None,
                     timelines: Optional[list] = None,
                     tenants: Optional[list] = None,
                     trace_ids: Optional[list] = None) -> dict:
        """_msearch: execute many search bodies, batching same-shaped
        score-sorted queries into single vmapped device programs per segment
        (reference: action/search/TransportMultiSearchAction fans bodies out
        concurrently; here concurrency is a batch axis on the MXU/VPU).

        A malformed sub-request (negative/non-numeric size/from/min_score,
        unparseable query, too-deep pagination) renders as a PER-ITEM
        error object — siblings execute normally, matching the
        reference's per-item failure contract.

        _bypass_request_cache: executable warmup replays must reach the
        device even when an identical body was just served (search/warmup
        — a cache hit would compile nothing).
        _raise_item_errors: the B=1 delegation from search() wants the
        exception, not an error item.
        task / deadline: cancellation + timeout checkpoints at wave
        boundaries — cancellation kills the whole envelope (the task IS
        the msearch request, reference TransportMultiSearchAction task)
        after draining in-flight waves, a passed deadline stops
        launching new waves and renders the unlaunched items as
        zero-hit `timed_out: true` partials while already-dispatched
        waves' results survive.
        waves: explicit wave count for the overlapped pipeline (None =
        the _effective_waves policy; warmup replays pass 1 so the
        recorded (plan-struct, shape-bucket, b_pad) signatures
        reproduce exactly).
        trace / phase_times: the envelope's transfer attribution —
        bytes_to_device/bytes_fetched/transfers land on the span when it
        records, device_get/bytes_fetched in phase_times for the
        caller's slow log (both only when the ledger or tracing is on;
        see telemetry/ledger.py's no-op discipline).

        Request lifecycle (telemetry/lifecycle.py): when the flight
        recorder is on and no timeline is bound (direct callers —
        bench, warmup, tests), this wrapper owns one for the envelope
        and completes it on EVERY exit, error paths included (a
        cancelled/faulted envelope must still be capture-eligible);
        REST/controller-owned requests pass straight through to the
        impl, which rides the bound timeline.
        timelines: per-body request timelines from the wave scheduler's
        batch-of-batches entry (search/scheduler.py) — wave events fan
        out to each owning request's lifecycle and the envelope itself
        owns NO timeline (the foreign requests' own wrappers complete
        theirs).
        tenants: per-body tenant ids from the scheduler (aligned with
        `timelines`) — the insights recorder's per-shape tenant
        breakdown reads them per item on coalesced waves; inline paths
        ride the thread-local binding instead.
        trace_ids: per-body span-ring trace ids from the scheduler
        (aligned with `bodies`): a coalesced envelope runs on the
        scheduler's thread under a trace of its own and lists, on its
        `envelope` and `dispatch` spans, the traces it serves."""
        if timelines is not None or not _FLIGHT.enabled \
                or _FLIGHT.current() is not None:
            return self._multi_search_impl(
                bodies, _bypass_request_cache, _raise_item_errors, task,
                deadline, trace, phase_times, waves, timelines, tenants,
                trace_ids)
        tl = _FLIGHT.timeline()
        if tl is None:      # disabled race: behave as the gate said
            return self._multi_search_impl(
                bodies, _bypass_request_cache, _raise_item_errors, task,
                deadline, trace, phase_times, waves, tenants=tenants)
        tl.event("admit")
        prev = _FLIGHT.bind(tl)
        status = "error"
        try:
            res = self._multi_search_impl(
                bodies, _bypass_request_cache, _raise_item_errors, task,
                deadline, trace, phase_times, waves, tenants=tenants)
            status = "ok"
            return res
        finally:
            _FLIGHT.unbind(prev)
            tl.event("respond")
            _FLIGHT.complete(tl, status=status, span=trace)

    def _multi_search_impl(self, bodies: List[dict],
                           _bypass_request_cache: bool = False,
                           _raise_item_errors: bool = False,
                           task=None, deadline: Optional[float] = None,
                           trace=None,
                           phase_times: Optional[dict] = None,
                           waves: Optional[int] = None,
                           timelines: Optional[list] = None,
                           tenants: Optional[list] = None,
                           trace_ids: Optional[list] = None) -> dict:
        """The envelope under its span: `envelope` in the always-on ring
        runs from the envelope's `start` to its return (the read `took`
        is computed from), closes on every exit with `envelope.parse`
        beside it, and is the parent of the wave spans recorded below
        it."""
        ring_trace, sid, parent = _SPANS.enter()
        env = _EnvelopeSpan(ring_trace, sid, trace_ids)
        start = time.monotonic()
        try:
            return self._envelope(
                bodies, start, env, _bypass_request_cache,
                _raise_item_errors, task, deadline, trace, phase_times,
                waves, timelines, tenants)
        finally:
            ring_trace.spans.append((
                sid, parent, "envelope", start,
                env.end or time.monotonic(),
                (_envelope_attrs, len(bodies), env.waves, trace_ids)))
            if env.parse is not None:
                ring_trace.spans.append((sid + 1, sid, "envelope.parse",
                                         env.parse[0], env.parse[1], None))
            _SPANS.leave(ring_trace, parent)

    def _envelope(self, bodies: List[dict], start: float,
                  env: "_EnvelopeSpan", _bypass_request_cache: bool,
                  _raise_item_errors: bool, task,
                  deadline: Optional[float], trace,
                  phase_times: Optional[dict], waves: Optional[int],
                  timelines: Optional[list],
                  tenants: Optional[list]) -> dict:
        TELEMETRY.metrics.counter("msearch.requests").inc()
        TELEMETRY.metrics.counter("msearch.bodies").inc(len(bodies))
        scope = _LEDGER.scope(trace)
        # the request's lifecycle timeline, bound by whoever owns it
        # (REST / controller / the multi_search wrapper above).
        # Disabled: one attribute load + branch.
        tl = _FLIGHT.current() if _FLIGHT.enabled else None
        if tl is not None:
            tl.route()      # arrive→envelope-entry gap becomes `route`
        # scheduler-coalesced envelopes carry the owning requests' own
        # timelines instead: each request's pre-envelope gap (admission
        # glue minus its recorded queue_wait) becomes ITS `route`
        fan_tls = _distinct_timelines(timelines) if timelines else None
        if fan_tls:
            for _ftl in fan_tls:
                _ftl.route()
        if task is not None:
            task.check_cancelled()
        ph = dict.fromkeys(MSEARCH_PHASE_NAMES, 0.0)
        _t = time.monotonic()
        responses: List[Optional[dict]] = [None] * len(bodies)

        resp_cache_keys: Dict[int, Any] = {}
        batchable: List[Tuple[int, dict, Any, int, int, float]] = []
        hybrid_items: List[Tuple[int, dict]] = []
        for i, body in enumerate(bodies):
            if task is not None and i % 16 == 0:
                # general-path items execute inline here, so the parse
                # loop is itself a sequence of safe points
                task.check_cancelled()
            if deadline is not None and time.monotonic() > deadline:
                responses[i] = _timed_out_item(start)
                continue
            _run_item_isolated(
                responses, i, _raise_item_errors,
                lambda: self._msearch_parse_one(
                    i, body or {}, responses, batchable, hybrid_items,
                    resp_cache_keys, _bypass_request_cache, start,
                    # the per-item tenant rides into the cache-hit note:
                    # on a scheduler-coalesced envelope this loop runs
                    # on the scheduler thread, where the REST layer's
                    # thread-local binding never reached
                    tenant=tenants[i] if tenants is not None else None))

        _t1 = time.monotonic()
        ph["parse"] += _t1 - _t
        env.parse = (_t, _t1)
        # Overlapped multi-wave dispatch: the batchable list splits into
        # power-of-two-bucketed waves; wave N+1's host work and async
        # dispatch run while wave N's device_get is in flight on the
        # collector thread (bounded in-flight window). Hybrid items ride
        # the same engine as their own wave, and a single-wave envelope
        # (B=1, small batches) degenerates to the inline flow — no
        # thread.
        wave_list: List[_MsearchWave] = []
        if hybrid_items:
            wave_list.append(_MsearchWave(
                "hybrid", [i for i, _b in hybrid_items], hybrid_items,
                raise_errors=_raise_item_errors))
        if batchable:
            n_waves = _effective_waves(len(batchable)) if waves is None \
                else max(int(waves), 1)
            off = 0
            for size in _wave_sizes(len(batchable), n_waves):
                chunk = batchable[off:off + size]
                off += size
                wave_list.append(_MsearchWave(
                    "plain", [e[0] for e in chunk], chunk,
                    raise_errors=_raise_item_errors))
        env.waves = len(wave_list)
        if wave_list:
            # mixed hybrid+plain envelopes have >1 waves structurally;
            # whether they OVERLAP still follows the wave-count policy
            # (explicit waves>1 / FORCED_WAVES win, else the backend
            # probe) — on the unforced CPU backend they run
            # inline-sequentially, exactly the old flow
            explicit = waves if waves is not None else FORCED_WAVES
            allow_pipeline = (int(explicit) > 1 if explicit is not None
                              else _overlap_capable())
            self._run_wave_pipeline(
                wave_list, responses, start, ph, task=task,
                deadline=deadline, scope=scope,
                resp_cache_keys=resp_cache_keys,
                allow_pipeline=allow_pipeline, timeline=tl,
                item_timelines=timelines, item_tenants=tenants, env=env)
        # parse always runs; the wave phases only get a sample when a
        # batched wave actually executed — otherwise every all-general or
        # all-hybrid envelope would log spurious 0-ms device_get/respond
        # samples and drag the telemetry percentiles toward zero
        _PHASE_HISTS["parse"].observe(ph["parse"] * 1000)
        if batchable:
            for name, sec in ph.items():
                if name != "parse":
                    _PHASE_HISTS[name].observe(sec * 1000)
        TELEMETRY.metrics.histogram("msearch.batch_ms").observe(
            (time.monotonic() - start) * 1000)
        if scope is not None:
            # the envelope's transfer attribution (the shared
            # LedgerScope.publish contract): fixes the spuriously-zero
            # bytes_to_device on envelope/hybrid-served spans (the old
            # accounting lived only in the general path's single-branch
            # sum)
            scope.publish(trace, phase_times)
        if tl is not None:
            # the envelope's phase decomposition lands on the request's
            # lifecycle (parse/compile_group/stack_pack_dispatch/
            # device_get/respond are disjoint, so a captured slow
            # envelope explains its own took — tools/tail_report.py).
            # `coordinate` is the controller's `render` catch-all
            # analog: everything inside the envelope the five phase
            # timers don't bracket (wave splitting, scope/gauge
            # bookkeeping, collector handoff) — without it a slow
            # envelope under GIL contention leaves its glue time
            # unattributed. max(0): pipelined waves' phases overlap
            # wall-clock, so their sum can exceed the envelope wall.
            ph_ms = {name: sec * 1000.0 for name, sec in ph.items()}
            glue = (time.monotonic() - start) * 1000.0 \
                - sum(ph_ms.values())
            if glue > 0:
                ph_ms["coordinate"] = glue
            tl.merge_phases(ph_ms)
            tl.mark_ready()
        if fan_tls:
            # each coalesced request WAITED for the whole shared
            # envelope, so the envelope's phase decomposition explains
            # each request's wall: merge it into every owner (their own
            # threads mark_ready/complete after demux)
            ph_ms = {name: sec * 1000.0 for name, sec in ph.items()}
            for _ftl in fan_tls:
                _ftl.merge_phases(ph_ms)
        env.end = time.monotonic()
        if _raise_item_errors and len(bodies) == 1:
            # a `_search` served through the B=1 envelope (the
            # delegation from search() and the controller, which is
            # what asks for raised errors) counts in the operator's
            # search.* metrics as the general path's does
            # (controller.execute_search), from the reads above: plan
            # compile and the device round trip are its query phase,
            # response assembly its render. `_msearch` batches do not.
            _SEARCH_QUERIES.inc()
            _SEARCH_TOOK.observe((env.end - start) * 1000.0)
            hists = _SEARCH_PHASE_HISTS
            hists["parse"].observe(ph["parse"] * 1000.0)
            hists["query"].observe(
                (ph["compile_group"] + ph["stack_pack_dispatch"]
                 + ph["device_get"]) * 1000.0)
            hists["render"].observe(ph["respond"] * 1000.0)
        return {"took": int((env.end - start) * 1000),
                "responses": responses}

    def _run_wave_pipeline(self, wave_list: List[_MsearchWave], responses,
                           start: float, ph: dict, task=None,
                           deadline: Optional[float] = None, scope=None,
                           resp_cache_keys: Optional[dict] = None,
                           allow_pipeline: bool = True,
                           timeline=None,
                           item_timelines: Optional[list] = None,
                           item_tenants: Optional[list] = None,
                           env: Optional[_EnvelopeSpan] = None) -> None:
        """Drive the wave engine: prepare + async-dispatch each wave on
        THIS thread, collect on the collector thread (bounded in-flight
        window), and merge per-wave phase times, ledger scopes and
        overlap attribution once everything drained.

        The PR 6 checkpoints live at the wave boundaries: a cancellation
        raises here after in-flight waves drain (their buffers release,
        the device-memory gauge returns to baseline); a passed deadline
        renders the unlaunched waves' items as zero-hit timed-out
        partials while dispatched waves still finish and their results
        survive. len(wave_list) == 1 is the degenerate W=1 pipeline —
        fully inline, no thread — which the B=1 single-search delegation
        and hybrid-only envelopes ride. `allow_pipeline` carries the
        wave-count policy's verdict: a mixed hybrid+plain envelope has
        >1 waves structurally, but must still run inline-sequentially
        where the policy says overlap cannot pay (the CPU backend,
        unforced)."""
        pipelined = len(wave_list) > 1 and allow_pipeline
        collector = _WaveCollector(
            lambda w: self._collect_wave(w, responses, start),
            MSEARCH_INFLIGHT_WINDOW) if pipelined else None
        dispatched: List[_MsearchWave] = []
        try:
            for wave_idx, wave in enumerate(wave_list):
                wave.index = wave_idx
                wave.timeline = timeline
                if timeline is None and item_timelines is not None:
                    # scheduler-coalesced wave: fan its events out to
                    # every owning request's timeline (one per request,
                    # however many of its items share the wave)
                    fanned = _distinct_timelines(item_timelines,
                                                 wave.items)
                    if fanned:
                        wave.timeline = _TimelineFan(fanned)
                if task is not None:
                    task.check_cancelled()
                if deadline is not None and time.monotonic() > deadline:
                    for i in wave.items:
                        if responses[i] is None:
                            responses[i] = _timed_out_item(start)
                    continue
                breaker = WAVE_BREAKER.gate()
                if breaker is not None:
                    # device-memory breaker (common/admission.py): a
                    # node whose in-flight wave buffers are over budget
                    # sheds this WAVE as per-item 429s through the PR 6
                    # per-item machinery — never a 5xx. Checked BEFORE
                    # prepare so a shed wave allocates nothing; the
                    # half-open probe's collect outcome reports back in
                    # the merge loop below.
                    berr, wave.breaker_probe = breaker.pre_wave(
                        _DEVMEM.live_bytes("wave_buffers"))
                    if berr is not None:
                        if wave.raise_errors:
                            raise berr
                        item = _item_error(berr)
                        for i in wave.items:
                            if responses[i] is None:
                                responses[i] = dict(item)
                        continue
                if wave.timeline is not None:
                    # coalesce: which wave this request's items ride and
                    # with how many co-batched siblings — fanned to
                    # every owning request on a scheduler-coalesced
                    # wave, where co_batched counts CROSS-REQUEST
                    # companions
                    wave.timeline.event("coalesce", wave=wave_idx,
                                        co_batched=len(wave.items),
                                        kind=wave.kind)
                if collector is not None:
                    # bounded in-flight window: block until a slot frees
                    # BEFORE compiling/dispatching the next wave
                    wave.window = collector.acquire_slot()
                wave.scope = LedgerScope() if scope is not None else None
                if env is not None:
                    # a scheduler-coalesced wave lists the traces
                    # (requests) whose items it carries
                    wave.span = (
                        env.trace, env.span_id, next(_SPANS.ids), wave_idx,
                        None if env.trace_ids is None else sorted(
                            {env.trace_ids[i] for i in wave.items
                             if env.trace_ids[i] is not None}))
                wave.prep_t0 = time.monotonic()
                if wave.kind == "hybrid":
                    wave.state = self._msearch_hybrid_prepare(
                        wave.payload, responses, start,
                        wave.raise_errors, scope=wave.scope)
                else:
                    wave.state = self._msearch_prepare(
                        wave.payload, responses, start, wave.ph,
                        wave.raise_errors, deadline=deadline,
                        scope=wave.scope, span=wave.span)
                    wave.state["resp_cache_keys"] = resp_cache_keys or {}
                wave.prep_t1 = time.monotonic()
                if wave.kind == "hybrid" and wave.span is not None:
                    # the hybrid halves keep no phase clock of their
                    # own: the wave's prepare is its `dispatch` (pack
                    # included), its collect its `device_wait`
                    # (response assembly included)
                    _trace, _eid, _base, _idx, _tids = wave.span
                    _trace.spans.append((
                        _base, _eid, "dispatch", wave.prep_t0,
                        wave.prep_t1,
                        (_hybrid_dispatch_attrs, _idx, _tids,
                         len(wave.state["pending"]))))
                # the in-flight gauges rise HERE (not inside prepare) so
                # an exception out of prepare can never strand them; the
                # collect path and the finally below are the two release
                # points — no exit path leaks
                _DEVMEM.adjust("wave_buffers",
                               wave.state.get("wave_buffer_bytes", 0))
                _LEDGER.note_wave_inflight(+1)
                if wave.timeline is not None:
                    wave.timeline.event("dispatch", wave=wave_idx,
                                        inflight=_LEDGER
                                        .inflight_waves())
                dispatched.append(wave)
                if collector is None:
                    if task is not None:
                        task.check_cancelled()
                    self._collect_wave(wave, responses, start)
                else:
                    collector.submit(wave)
        finally:
            if collector is not None:
                collector.drain()
            # backstop for waves whose collect never ran or died before
            # its release points (e.g. the inline path's pre-collect
            # cancellation checkpoint fired between dispatch and
            # collect): after drain() every submitted wave has been
            # collected, so an unset collect_t1 means THIS wave still
            # owns its inflight-gauge slot and its buffers
            for wave in dispatched:
                _release_wave_gauges(wave.state)
                if not wave.collect_t1:
                    _LEDGER.note_wave_inflight(-1)
            # device-memory breaker probe verdicts — in the finally so
            # no exit path (cancellation, raised wave error, crashed
            # prepare) can strand the breaker half-open with a probe
            # outstanding: a clean collect closes it, anything else
            # re-opens it
            _dispatched_ids = {id(w) for w in dispatched}
            for w in wave_list:
                if w.breaker_probe:
                    WAVE_BREAKER.on_result(
                        id(w) in _dispatched_ids and w.error is None
                        and bool(w.collect_t1))
        # merge per-wave accounting on this thread (single writer):
        # phase times sum, wave scopes absorb into the request scope,
        # and each wave's measured overlap — its prepare/dispatch time
        # that ran while an earlier wave's device_get was in flight —
        # lands in the ledger as a first-class number
        collects: List[Tuple[float, float]] = []
        pipeline_error: Optional[Exception] = None
        for wave in dispatched:
            for name, sec in wave.ph.items():
                ph[name] += sec
            if wave.scope is not None:
                wave.scope.waves += 1
            if pipelined and collects:
                # this wave's prepare/dispatch time during which an
                # earlier wave's device_get was in flight — the
                # pipeline's measured win (first wave has nothing to
                # overlap with, so it records no event)
                overlap_s = sum(
                    max(0.0, min(c1, wave.prep_t1)
                        - max(c0, wave.prep_t0))
                    for c0, c1 in collects)
                _LEDGER.note_overlap(overlap_s * 1000.0,
                                     scope=wave.scope)
                if wave.timeline is not None:
                    # per-wave overlap as a lifecycle event: what
                    # tools/trace_report.py's pipeline table reads
                    wave.timeline.event("overlap", wave=wave.index,
                                        ms=round(overlap_s * 1000.0, 3))
            if wave.collect_t1:
                collects.append((wave.collect_t0, wave.collect_t1))
            if wave.scope is not None and scope is not None:
                scope.absorb(wave.scope)
            if wave.error is not None and wave.raise_errors \
                    and pipeline_error is None:
                pipeline_error = wave.error
        if pipeline_error is not None:
            raise pipeline_error
        self._note_wave_insights(dispatched, responses, timeline,
                                 item_timelines, item_tenants)

    def _note_wave_insights(self, dispatched: List[_MsearchWave],
                            responses, timeline,
                            item_timelines: Optional[list],
                            item_tenants: Optional[list]) -> None:
        """Per-item insights notes + timeline shape annotation at wave
        merge (ISSUE 15): runs on the dispatching thread AFTER the
        collector drained, so every wave's responses, phase walls and
        ledger scope are final (single writer — no lock beyond the
        recorder's own). Shared wave costs split across the wave's live
        grouped items exactly as the scheduler's `device_share_ms`
        split: the device_get wall divides evenly, ledger byte/round-
        trip integers divide with the remainder landing on the first
        live item so per-shape totals conserve EXACTLY against the
        global ledger. Scan bytes were attributed per item at prepare
        (including items a mid-envelope deadline later expired — the
        heat map counted their compile-time scan, so the per-shape join
        must too). Runs only when prepare built shape meta: insights or
        flight recorder enabled."""
        ins = _INSIGHTS.gate()
        for wave in dispatched:
            meta = (wave.state or {}).get("insights")
            if not meta:
                continue
            dead = (wave.state or {}).get("dead") or set()
            co = len(wave.items)
            live = [i for i in meta
                    if meta[i]["grouped"] and i not in dead
                    and isinstance(responses[i], dict)
                    and "error" not in responses[i]]
            if not live:
                # every grouped item errored or deadline-expired, but
                # the wave's uploads may already have crossed (the
                # ledger counted them): split over ALL grouped items so
                # the per-shape byte totals still conserve exactly
                # against the global ledger
                live = [i for i in sorted(meta) if meta[i]["grouped"]]
            n_live = len(live)
            live_set = set(live)
            # the wave's shared device wall: the finish half's measured
            # device_get (seconds in wave.ph), the ledger's attributed
            # wall for hybrid waves, else the collect duration
            dev_ms = wave.ph.get("device_get", 0.0) * 1000.0
            if not dev_ms and wave.scope is not None:
                dev_ms = wave.scope.device_get_ms
            if not dev_ms and wave.collect_t1:
                dev_ms = (wave.collect_t1 - wave.collect_t0) * 1000.0
            h2d = wave.scope.h2d_bytes if wave.scope is not None else 0
            d2h = wave.scope.d2h_bytes if wave.scope is not None else 0
            rts = wave.scope.round_trips if wave.scope is not None else 0
            dev_share = dev_ms / n_live if n_live else 0.0
            h2d_q, h2d_r = divmod(h2d, n_live) if n_live else (0, 0)
            d2h_q, d2h_r = divmod(d2h, n_live) if n_live else (0, 0)
            rt_q, rt_r = divmod(rts, n_live) if n_live else (0, 0)
            rem_pending = n_live > 0
            for i in sorted(meta):
                m = meta[i]
                resp = responses[i]
                if not isinstance(resp, dict):
                    continue        # never answered (catastrophic wave)
                in_split = i in live_set
                eh, ed, er = (h2d_q, d2h_q, rt_q) if in_split \
                    else (0, 0, 0)
                if in_split and rem_pending:
                    eh, ed, er = eh + h2d_r, ed + d2h_r, er + rt_r
                    rem_pending = False
                tl_i = item_timelines[i] \
                    if item_timelines is not None else timeline
                if tl_i is not None and \
                        getattr(tl_i, "shape", "") is None:
                    # the tail-capture shape annotation ("which shape
                    # owns the p99" — tools/tail_report.py): first
                    # resolved item wins for a multi-item envelope's
                    # single owned timeline; scheduler-coalesced waves
                    # stamp each owner with its OWN item's shape
                    tl_i.shape = m["label"]
                if ins is None:
                    continue
                status = "error" if "error" in resp else "ok"
                item_dev = dev_share if in_split else 0.0
                ins.note(
                    m["label"], kind=m["kind"],
                    took_ms=float(resp.get("took", 0))
                    if status == "ok" else 0.0,
                    device_ms=item_dev,
                    posting_bytes=m["posting"],
                    dense_bytes=m["dense"],
                    pruned_bytes=m.get("pruned", 0),
                    h2d_bytes=eh, d2h_bytes=ed, round_trips=er,
                    co_batched=co,
                    # warm=None (hybrid) = no bundle verdict exists:
                    # count neither compiled nor warm
                    compiled=m["warm"] is False,
                    warm_hit=bool(m["warm"]),
                    status=status,
                    tenant=item_tenants[i]
                    if item_tenants is not None
                    else ins.current_tenant())

    def _collect_wave(self, wave: _MsearchWave, responses,
                      start: float) -> None:
        """Wave half 2, on the collector thread (or inline for W=1):
        device_get + response assembly. `wave.scope` is the LedgerScope
        handed across the queue/thread boundary — the finish halves
        open their own LEDGER.attributed regions on THIS thread, so the
        sanitizer contract holds with the collector active. An escaping
        exception is captured per wave: the owning wave's unanswered
        items render as error objects, sibling waves are untouched."""
        scope = wave.scope
        wave.collect_t0 = time.monotonic()
        try:
            if wave.kind == "hybrid":
                self._msearch_hybrid_finish(wave.state, responses, start,
                                            scope=scope)
            else:
                self._msearch_finish(wave.state, responses, start,
                                     wave.ph, scope=scope)
        except Exception as e:  # except-ok: per-wave isolation -- a collect failure downgrades only this wave's items, never siblings or the envelope
            wave.error = e
        finally:
            wave.collect_t1 = time.monotonic()
            if wave.kind == "hybrid" and wave.span is not None:
                _trace, _eid, _base, _idx, _tids = wave.span
                _trace.spans.append((
                    _base + 3, _eid, "device_wait", wave.collect_t0,
                    wave.collect_t1, (_wave_attrs, _idx, _tids)))
            if wave.timeline is not None:
                # collect lands on the owning request's lifecycle from
                # THIS thread (appends are GIL-atomic; the timeline is
                # only read after the pipeline drains)
                wave.timeline.event(
                    "collect", wave=wave.index,
                    ms=round((wave.collect_t1 - wave.collect_t0) * 1000,
                             3),
                    device_get_ms=round(wave.scope.device_get_ms, 3)
                    if wave.scope is not None else None)
            state = wave.state or {}
            _release_wave_gauges(state)
            # collect done ⇒ the device program finished reading its
            # (zero-copy-aliased) input envelope: staging is reusable
            for buf in state.pop("staging", ()):
                self._staging.release(buf)
            _LEDGER.note_wave_inflight(-1)
            if wave.window is not None:
                wave.window.release()
        if wave.error is not None and not wave.raise_errors:
            err = _item_error(wave.error) \
                if isinstance(wave.error, OpenSearchTpuError) \
                else _item_error_untyped(wave.error)
            for i in wave.items:
                if responses[i] is None:
                    responses[i] = dict(err)

    def _msearch_parse_one(self, i: int, body: dict, responses, batchable,
                           hybrid_items, resp_cache_keys,
                           bypass_request_cache: bool,
                           start: float,
                           tenant: Optional[str] = None) -> None:
        """One sub-request of the parse loop: route to the general path /
        hybrid envelope / request cache, or intern + validate it into the
        batchable list. Raises OpenSearchTpuError for malformed items —
        multi_search converts that to a per-item error object."""
        if not _msearch_batchable(body):
            if _hybrid_msearch_batchable(body):
                # hybrid bodies batch through their own envelope: one
                # vmapped fused multi-sub-query program per
                # (plan-struct, shape) group
                hybrid_items.append((i, body))
            else:
                responses[i] = self.search(body, _direct=True)
            return
        # template interning: structural signature + stripped literals
        # (dsl.intern_query); None = a shape only the full parser handles
        tpl = dsl.intern_query(body.get("query")) if TEMPLATE_INTERNING \
            else None
        rc = _request_cache()
        if not bypass_request_cache and rc.admits(
                body, self.request_cache_enabled,
                query_now_safe=tpl is not None):
            # shard request cache at QUERY-PHASE granularity: the
            # cached value is (total, decoded partials, agg nodes) —
            # live objects the renderers only read — and the response
            # is rebuilt per hit, so caller mutations of a returned
            # response can't leak back in (the old design serialized
            # the whole response to JSON for that guarantee, which
            # cost a full dumps per MISS on the respond hot path).
            # A refresh/delete rotates segment uids/live counts out
            # of the key
            base = rc.cache_key(self.reader.segments, body, 0, None,
                                query_key=tpl.key if tpl is not None
                                else None)
            if base is not None:
                key = ("msearch", base)
                hit = _cache_get_isolated(rc, key)
                if hit is not rc.REQUEST_CACHE._MISS:
                    responses[i] = self._render_cached_msearch(hit, start)
                    ins = _INSIGHTS.gate()
                    if ins is not None:
                        # a cache-served sub-request is still a
                        # completed request of its shape: count it
                        # (zero device/scan bytes — the scan counters
                        # don't see cache hits either, so per-shape
                        # totals stay byte-exact vs the heat map)
                        label, kind = _item_shape(tpl, body)
                        ins.note(label, kind=kind,
                                 took_ms=float(
                                     responses[i].get("took", 0)),
                                 cached=True,
                                 tenant=tenant if tenant is not None
                                 else ins.current_tenant())
                    return
                resp_cache_keys[i] = key
        if tpl is None:
            _INTERN_FALLBACKS.inc()
            try:
                node: Any = dsl.parse_query(body.get("query"))
            except OpenSearchTpuError:
                raise
            except Exception:  # except-ok: per-item isolation -- the general path renders the proper error object for this item
                # surface the error uniformly via the general path
                responses[i] = self.search(body, _direct=True)
                return
        else:
            node = tpl
        size = _req_int(body, "size", 10)
        from_ = _req_int(body, "from", 0)
        if size < 0 or from_ < 0:
            raise IllegalArgumentError(
                "[from] parameter cannot be negative" if from_ < 0
                else "[size] parameter cannot be negative")
        if from_ + size > self.max_result_window:
            raise IllegalArgumentError(
                f"Result window is too large, from + size must be "
                f"less than or equal to: [{self.max_result_window}] "
                f"but was [{from_ + size}]. See the scroll api for a "
                f"more efficient way to request large data sets. This "
                f"limit can be set by changing the "
                f"[index.max_result_window] index level setting.")
        min_score = _req_min_score(body)
        batchable.append((i, body, node, size, from_, min_score))

    def _msearch_hybrid_prepare(self, items: List[Tuple[int, dict]],
                                responses, start: float,
                                raise_item_errors: bool = False,
                                scope=None) -> dict:
        """Hybrid wave half 1 (compile + group + stack + pack +
        DISPATCH, async): same-structure hybrid bodies become ONE
        vmapped fused program per (plan-struct, shape, k) group per
        segment — per-query launch cost amortizes exactly like the
        plain msearch envelope. Returns the state
        _msearch_hybrid_finish consumes. Responses use the DEFAULT
        normalization spec (pipeline-specific specs ride the REST path,
        where _run_search executes per query with the resolved
        processor chain)."""
        from opensearch_tpu.searchpipeline import hybrid as hyb
        # one consistent anchor for the hybrid wave (see _msearch_prepare)
        stats, segments, device = self.reader.stats_snapshot()
        compiler = Compiler(self.reader.mapper, stats)
        prepared: Dict[int, tuple] = {}
        groups: Dict[Any, List[int]] = {}
        # per-item shape meta (ISSUE 15): hybrid bodies are never
        # internable, so their shape class is the structural hash
        ins_items: Optional[Dict[int, dict]] = {} \
            if (_INSIGHTS.enabled or _FLIGHT.enabled) else None
        for i, body in items:
            try:
                min_score = _req_min_score(body)
                node = dsl.parse_query(body.get("query"))
                n_sub = len(node.queries)
                _s, _f, k = hyb.validate_hybrid_request(
                    body, n_sub, hyb.DEFAULT_SPEC, [self])
                k_fetch = min(k, 1 << 16)  # same window as the 1-query path
                plans_per_seg: List[Optional[list]] = []
                flats_per_seg: List[Optional[list]] = []
                for seg, (arrays, meta) in zip(segments, device):
                    if seg.num_docs == 0:
                        plans_per_seg.append(None)
                        flats_per_seg.append(None)
                        continue
                    plans = [compiler.compile(q, seg, meta)
                             for q in node.queries]
                    flat: List[Dict[str, np.ndarray]] = []
                    for p in plans:
                        p.flatten_inputs(flat)
                    plans_per_seg.append(plans)
                    flats_per_seg.append(flat)
            except OpenSearchTpuError as e:
                # already a well-typed request error (bad min_score,
                # invalid hybrid spec): render per item directly
                if raise_item_errors:
                    raise
                responses[i] = _item_error(e)
                continue
            except Exception:  # except-ok: per-item isolation -- a malformed hybrid body fails through the general path's renderer, not siblings
                # surface errors through the general path's renderer —
                # per item, so a malformed hybrid body can't fail siblings
                _run_item_isolated(responses, i, raise_item_errors,
                                   lambda: self.search(body, _direct=True))
                continue
            prepared[i] = (body, n_sub, min_score, plans_per_seg,
                           flats_per_seg)
            if ins_items is not None:
                from opensearch_tpu.telemetry.insights import \
                    structural_shape
                # warm=None: the hybrid path has no per-item bundle
                # memo, so a warm-vs-compiled verdict would be a guess —
                # the note pass counts NEITHER rather than reporting
                # compiled=True for every warm repeat
                ins_items[i] = {
                    "label": structural_shape(body.get("query")),
                    "kind": "hash", "posting": 0, "dense": 0,
                    "grouped": True, "warm": None, "interned": False}
            struct = tuple(
                tuple(p.sig() for p in plans) if plans is not None
                else None for plans in plans_per_seg)
            shape_sig = tuple(
                None if f is None else tuple(
                    (k2, v.shape, v.dtype.num)
                    for d in f for k2, v in d.items())
                for f in flats_per_seg)
            groups.setdefault((struct, shape_sig, k_fetch), []).append(i)

        from opensearch_tpu.search.warmup import WARMUP
        pending = []
        dead: set = set()
        staging: List[np.ndarray] = []
        wave_buffer_bytes = 0
        for (struct, shape_sig, k_fetch), idxs in groups.items():
            b_pad = pad_bucket(len(idxs), minimum=1)
            pad_rows = b_pad - len(idxs)
            WARMUP.record(self.reader.index_name, prepared[idxs[0]][0],
                          b_pad, ("hybenv", struct, shape_sig, k_fetch,
                                  b_pad))
            min_scores = np.asarray(
                [prepared[i][2] for i in idxs] + [np.inf] * pad_rows,
                dtype=np.float32)
            dispatched = []
            for seg_i, (seg, (arrays, meta)) in enumerate(
                    zip(segments, device)):
                if seg.num_docs == 0:
                    continue
                group_flats = [prepared[i][4][seg_i] for i in idxs]
                group_flats += [group_flats[0]] * pad_rows
                stacked, treedef, axes = stack_flat_inputs(group_flats)
                stacked.append(min_scores)
                buf, layout = pack_leaves(stacked, pool=self._staging)
                k_seg = min(k_fetch, pad_bucket(max(seg.num_docs, 1)))
                plans0 = prepared[idxs[0]][3][seg_i]
                try:
                    fn = _batched_hybrid_runner(plans0, meta, k_seg,
                                                layout, treedef)

                    def _dispatch(fn=fn, arrays=arrays, buf=buf):
                        if faults.ENABLED:
                            faults.fire("query.dispatch")
                        return fn(arrays, jnp.asarray(buf))
                    out = retry.call_with_retry(_dispatch,
                                                label="msearch.dispatch")
                except Exception as e:  # except-ok: per-item isolation -- a failed hybrid group dispatch downgrades its items to error objects
                    if raise_item_errors:
                        raise
                    err = _item_error(e) \
                        if isinstance(e, OpenSearchTpuError) \
                        else _item_error_untyped(e)
                    for i in idxs:
                        responses[i] = dict(err)
                        dead.add(i)
                    break
                if scope is not None:
                    # after the dispatch: a failed one must not count
                    # h2d bytes that never crossed
                    _LEDGER.record("upload.literals", "h2d", buf.nbytes,
                                   scope=scope)
                staging.append(buf)
                wave_buffer_bytes += buf.nbytes
                pending.append((idxs, seg_i, k_seg, len(plans0), out))
                dispatched.append((meta.d_pad, plans0, k_seg))
            if not dead.issuperset(idxs):
                _note_hybrid_windows(dispatched, len(idxs))
        return {"prepared": prepared, "pending": pending, "dead": dead,
                "raise_item_errors": raise_item_errors,
                "staging": staging,
                "insights": ins_items,
                "wave_buffer_bytes": wave_buffer_bytes}

    def _msearch_hybrid_finish(self, state: dict, responses,
                               start: float, scope=None) -> None:
        """Hybrid wave half 2: ONE device_get for the wave's fused
        rows (run on the collector thread when the pipeline overlaps),
        then accumulate per-sub-query channels and render through the
        normalization merge."""
        prepared, pending, dead = (state["prepared"], state["pending"],
                                   state["dead"])
        raise_item_errors = state["raise_item_errors"]
        results = {i: _empty_hybrid_result(prepared[i][1])
                   for i in prepared}
        if pending:
            def _collect():
                if faults.ENABLED:
                    faults.fire("fetch.gather")
                return jax.device_get(
                    [packed for _, _, _, _, packed in pending])
            t0c = time.monotonic() if scope is not None else 0.0
            try:
                with _LEDGER.attributed(scope):
                    fetched = retry.call_with_retry(_collect,
                                                    label="fetch.gather")
                if scope is not None:
                    _ledger_hybrid_rows(
                        scope,
                        [(packed.shape[0], len(idxs), k_seg, n_sub)
                         for idxs, _s, k_seg, n_sub, packed in pending],
                        (time.monotonic() - t0c) * 1000)
            except Exception as e:  # except-ok: per-item isolation -- any device-fault class downgrades the wave's items to error objects, never the envelope
                if raise_item_errors:
                    raise
                err = _item_error(e) if isinstance(e, OpenSearchTpuError) \
                    else _item_error_untyped(e)
                for idxs, _s, _k, _n, _p in pending:
                    for i in idxs:
                        responses[i] = dict(err)
                        dead.add(i)
                fetched = []
                pending = []
            _release_wave_gauges(state)
            for (idxs, seg_i, k_seg, n_sub, _), packed in zip(pending,
                                                              fetched):
                packed = np.asarray(packed)
                for row_i, i in enumerate(idxs):
                    _accumulate_hybrid_row(results[i], packed[row_i],
                                           seg_i, k_seg, n_sub)
        _release_wave_gauges(state)
        from opensearch_tpu.searchpipeline import hybrid as hyb
        for i, result in results.items():
            if i in dead:
                continue
            body, n_sub = prepared[i][0], prepared[i][1]
            result.bounds = [tuple(b) for b in result.bounds]
            combined, _ = hyb.merge_hybrid([result], hyb.DEFAULT_SPEC,
                                           n_sub)
            responses[i] = hyb.render_hybrid([self], body, [result],
                                             combined, start)

    def _compile_msearch_bundle(self, compiler: Compiler, stats, tpl,
                                node, body: dict, agg_spec,
                                agg_json: Optional[str] = None,
                                snapshot=None,
                                force_full: bool = False) -> tuple:
        """Compile ONE sub-request's per-segment plans + flattened inputs
        + grouping signatures. When `tpl` (a dsl.QueryTemplate) is given,
        plans bind through the (template, segment) skeleton cache
        (Compiler.compile_interned); the returned bundle is what the
        per-(template, literals) memo stores, so a repeated body skips
        this function entirely."""
        from opensearch_tpu.parallel.distributed import plan_struct
        from opensearch_tpu.search.aggs.parse import PIPELINE_TYPES
        agg_nodes = parse_aggs(agg_spec)
        device_agg_nodes = [n for n in agg_nodes
                            if n.type not in PIPELINE_TYPES]
        # agg plans are (agg spec, segment)-static — memoized on the
        # reader stats like compiled text plans, so a dashboard workload
        # of repeated agg shapes skips the per-query bucket-table
        # recomputation (the Weight-cache analog)
        if agg_nodes and agg_json is None:
            agg_json = json.dumps(agg_spec, sort_keys=True, default=str)
        plans: List[Optional[Plan]] = []
        agg_plans_per_seg: List[list] = []
        segments, device = (snapshot if snapshot is not None
                            else self.reader.snapshot())
        for seg, (arrays, meta) in zip(segments, device):
            if seg.num_docs == 0:
                plans.append(None)
                agg_plans_per_seg.append([])
                continue
            plan = None
            if tpl is not None:
                plan = compiler.compile_interned(tpl, seg, meta)
            if plan is None:
                if node is None:
                    node = dsl.parse_query(body.get("query"))
                plan = compiler.compile(node, seg, meta)
            plans.append(plan)
            if not agg_nodes:
                agg_plans_per_seg.append([])
                continue
            memo_key = ("aggc", seg.uid, agg_json)
            aplans = stats.memo.get(memo_key)
            if aplans is None:
                aplans = compile_aggs(device_agg_nodes, self.reader.mapper,
                                      seg, meta, compiler)
                stats.memo[memo_key] = aplans
            agg_plans_per_seg.append(aplans)
        all_none = all(p is None or p.kind == "match_none" for p in plans)
        if all_none and not force_full:
            # force_full (the _PartialBundle tail-extension path) needs
            # real struct/flats even for an all-none tail slice — the
            # short-circuit form cannot concatenate positionally
            return (plans, None, None, None, None, agg_plans_per_seg,
                    agg_nodes, True)
        struct = tuple(plan_struct(p) if p is not None else None
                       for p in plans)
        flats: List[Optional[list]] = []
        for p, aplans in zip(plans, agg_plans_per_seg):
            if p is None:
                flats.append(None)
                continue
            flat = p.flatten_inputs([])
            for ap in aplans:
                ap.flatten_inputs(flat)
            flats.append(flat)
        shape_sig = tuple(
            None if f is None else tuple(
                (k2, v.shape, v.dtype.num)
                for d in f for k2, v in d.items())
            for f in flats)
        agg_sig = tuple(tuple(ap.sig() for ap in aplans)
                        for aplans in agg_plans_per_seg) \
            if agg_nodes else None
        return (plans, flats, struct, shape_sig, agg_sig,
                agg_plans_per_seg, agg_nodes, all_none)

    def _extend_msearch_bundle(self, compiler: Compiler, stats, tpl,
                               body: dict, agg_spec,
                               agg_json: Optional[str],
                               partial: _PartialBundle,
                               snapshot) -> tuple:
        """Complete a carried _PartialBundle (pure-append publish,
        ISSUE 16 tentpole b): compile ONLY the appended tail segments
        and concatenate the per-segment positional lists — a warm query
        after a 32-doc refresh pays one tail-segment compile instead of
        a whole-bundle rebuild. Returns the full 8-tuple for this
        snapshot's segment list."""
        segments, device = snapshot
        n = partial.n_segs
        (plans, flats, struct, shape_sig, agg_sig, agg_plans,
         agg_nodes, _all_none) = partial.bundle
        if n >= len(segments):
            return partial.bundle
        tail = self._compile_msearch_bundle(
            compiler, stats, tpl, None, body, agg_spec, agg_json,
            snapshot=(segments[n:], device[n:]), force_full=True)
        (t_plans, t_flats, t_struct, t_shape, t_agg_sig, t_aggs,
         _t_nodes, _t_all_none) = tail
        return (plans + t_plans, flats + t_flats, struct + t_struct,
                shape_sig + t_shape,
                (agg_sig + t_agg_sig) if agg_sig is not None else None,
                agg_plans + t_aggs, agg_nodes, False)

    def _msearch_prepare(self, batchable, responses, start, ph,
                         raise_item_errors: bool = False,
                         deadline: Optional[float] = None, scope=None,
                         span=None):
        """Wave half 1: compile + group + stack + pack + DISPATCH (async).
        Returns the state _msearch_finish consumes.

        `span` is the wave's `_MsearchWave.span` for the always-on
        ring: the phase boundaries below become
        `envelope.compile_group`, `envelope.pack` (up to the first
        upload) and `dispatch` (first upload to the return of the last
        jit call: enqueue, not execution), and it rides the returned
        state to the finish half.

        Template interning makes this phase O(unique (template, literals)
        pairs): interned bodies memoize their whole compiled bundle
        (plans, flattened inputs, grouping signatures) on the reader
        stats, so a warm repeated batch reduces to one memo lookup per
        query — zero plan compiles, zero DSL walks.

        Grouping is by plan STRUCTURE + per-segment input SHAPES: shapes
        are already power-of-two bucketed by the compiler, so shape-keyed
        groups stay few while making each group's stack a plain np.stack
        (no padding growth) and its kernel choice (candidate vs dense)
        uniform — one packed upload + one device program per group. The
        shape signature uses dtype.num (numpy's dtype.__str__ is slow on
        this path) and relies on deterministic dict insertion order."""
        _t = time.monotonic()
        groups: Dict[Any, List[int]] = {}
        # always-on scan accounting (telemetry/scan.py, ISSUE 14):
        # per-wave LOCAL accumulators, flushed in ONE note_batch call
        # below — the disabled-lock discipline the <2% gate demands
        _scan_rows: Dict[Any, list] = {}
        _scan_per_query: List = []
        # per-item STATIC posting bytes, kept for the finish half's
        # pruned-overlay flush (effective = static - pruned per query)
        _scan_posting_by_i: Dict[int, int] = {}
        # per-item shape meta (ISSUE 15): shape id + scan bytes + bundle
        # verdict, read back by the wave-merge note pass. Built when the
        # insights recorder wants cost rows OR the flight recorder wants
        # the shape annotation on captured timelines; both gates off =
        # one attribute load + branch, nothing allocates.
        ins_items: Optional[Dict[int, dict]] = {} \
            if (_INSIGHTS.enabled or _FLIGHT.enabled) else None
        compiled: Dict[int, List[Optional[Plan]]] = {}
        flats_by_i: Dict[int, List[Optional[list]]] = {}
        agg_by_i: Dict[int, List[list]] = {}      # i -> per-seg AggPlans
        agg_nodes_by_i: Dict[int, list] = {}      # i -> parsed AggNodes
        # one consistent anchor for the whole wave (prepare -> dispatch
        # -> finish): a concurrent refresh publishing mid-wave must not
        # re-pair seg_i between the compiled flats and the device arrays
        stats, segments, device = self.reader.stats_snapshot()
        # a traced wave's compiler records `compile.text_clause` under
        # the `compile.bundle` open when it plans one
        compiler = Compiler(self.reader.mapper, stats,
                            spans=_SPANS if span is not None else None)
        mapper_version = getattr(self.reader.mapper, "version", 0)

        def _general_fallback(i, body):
            # an agg/query shape the batch program can't express (or a
            # user error): the general path raises it properly — rendered
            # per item so one bad body can't fail siblings
            _run_item_isolated(responses, i, raise_item_errors,
                               lambda: self.search(body, _direct=True))

        # `envelope.compile_group` is the open span of the compile loop
        # (the ring's own enter / leave): what the loop records names it
        # as parent, `compile.bundle` for a body the bundle memo does not
        # hold, under that the compiler's `compile.text_clause`, and
        # `compile.scan_note`; it closes on every exit, with the read
        # `ph["compile_group"]` ends on
        cg = _SPANS.enter() if span is not None else None
        try:
            for entry in batchable:
                i, body, node, size, from_, min_score = entry
                tpl = node if isinstance(node, dsl.QueryTemplate) else None
                agg_spec = body.get("aggs") or body.get("aggregations")
                bundle = bkey = agg_json = None
                if tpl is not None:
                    try:
                        agg_json = (json.dumps(agg_spec, sort_keys=True,
                                               default=str) if agg_spec
                                    else None)
                    except Exception:  # except-ok: per-item isolation -- e.g. mixed-type agg keys; the general path owns the typed error
                        # e.g. mixed-type agg keys breaking sort_keys: the
                        # general path owns the proper error, per item
                        _general_fallback(i, body)
                        continue
                    # gate in the key: bundles hold compiled plans, and a
                    # blockmax flip changes plan inputs (tid/bscale) — a
                    # stale-gate bundle would prune (or not) the wrong way
                    bkey = ("qenv", mapper_version, tpl.sig, tpl.literals,
                            agg_json, _bm25.BLOCKMAX)
                    bundle = stats.memo.get(bkey)
                    if isinstance(bundle, _PartialBundle):
                        # pure-append carry (ISSUE 16): compile only the
                        # appended tail segments, re-store the completed
                        # bundle (two threads racing here duplicate one
                        # tail compile, harmlessly — last store wins)
                        try:
                            bundle = _timed_bundle(
                                compiler, "extend",
                                lambda: self._extend_msearch_bundle(
                                    compiler, stats, tpl, body, agg_spec,
                                    agg_json, bundle, (segments, device)))
                        except Exception:  # except-ok: per-item isolation -- tail-compile failure falls back to the general path per item
                            _general_fallback(i, body)
                            continue
                        cost = _bundle_nbytes(bundle[1])
                        if cost <= _BUNDLE_MEMO_MAX_ENTRY_BYTES:
                            stats.memo.set(bkey, bundle, cost=cost)
                bundle_hit = bundle is not None
                if bundle is None:
                    if tpl is not None:
                        _BUNDLE_MISSES.inc()
                    try:
                        bundle = _timed_bundle(
                            compiler, "miss",
                            lambda: self._compile_msearch_bundle(
                                compiler, stats, tpl,
                                None if tpl is not None else node, body,
                                agg_spec, agg_json,
                                snapshot=(segments, device)))
                    except Exception:  # except-ok: per-item isolation -- compile failure falls back to the general path per item
                        _general_fallback(i, body)
                        continue
                    if bkey is not None:
                        # bundles hold flattened device inputs — charge their
                        # bytes against the memo's byte budget, and keep
                        # outliers (a single huge high-cardinality filter)
                        # out entirely rather than letting one entry evict a
                        # whole generation's working set
                        cost = _bundle_nbytes(bundle[1])
                        if cost <= _BUNDLE_MEMO_MAX_ENTRY_BYTES:
                            stats.memo.set(bkey, bundle, cost=cost)
                else:
                    _BUNDLE_HITS.inc()
                (plans, flats, struct, shape_sig, agg_sig, agg_plans_per_seg,
                 agg_nodes, all_none) = bundle
                # no tie overfetch needed: per-segment top-k by score with
                # doc-asc tie-break (lax.top_k picks the lowest index) merges
                # to the exact global page for score-sorted queries; size=0
                # (agg/count-only) requests skip hit selection entirely
                k = 0 if from_ + size == 0 else max(from_ + size, 10)
                if all_none:
                    if agg_nodes:
                        # empty-match WITH aggs still owes fully-shaped empty
                        # agg structures — the general path builds those
                        _general_fallback(i, body)
                    else:
                        # no term matched any segment: answer host-side, zero
                        # device work (the can-match pre-filter analog)
                        responses[i] = _base_response(
                            int((time.monotonic() - start) * 1000), 0, None,
                            [])
                        if ins_items is not None:
                            label, kind = _item_shape(node, body)
                            ins_items[i] = {
                                "label": label, "kind": kind, "posting": 0,
                                "dense": 0, "grouped": False,
                                "warm": bundle_hit,
                                "interned": tpl is not None}
                    continue
                compiled[i] = plans
                flats_by_i[i] = flats
                if agg_nodes:
                    agg_by_i[i] = agg_plans_per_seg
                    agg_nodes_by_i[i] = agg_nodes
                groups.setdefault((struct, agg_sig, shape_sig,
                                   min(k, 1 << 16)), []).append(i)
                # per-item posting/dense bytes from the compiled plans —
                # the kernel split mirrors _envelope_runner's decision
                # (candidate-buffer for plain text clauses within the lane
                # budget, dense otherwise), so the heat map's kernel mix
                # reflects what actually dispatches. One attribute read
                # per warm (memoized) plan, no per-lane work, no lock.
                n_scan0 = len(_scan_per_query)
                _scan_accumulate_item(device, plans, _scan_rows,
                                      _scan_per_query)
                _scan_posting_by_i[i] = _scan_per_query[-1][0] \
                    if len(_scan_per_query) > n_scan0 else 0
                if ins_items is not None:
                    # the per-item scan join (ISSUE 15): the SAME tuple the
                    # always-on heat map just accumulated, so per-shape
                    # totals conserve byte-exactly against telemetry.scan
                    sp, sd = _scan_per_query[-1] \
                        if len(_scan_per_query) > n_scan0 else (0, 0)
                    label, kind = _item_shape(node, body)
                    ins_items[i] = {"label": label, "kind": kind,
                                    "posting": sp, "dense": sd,
                                    "grouped": True, "warm": bundle_hit,
                                    "interned": tpl is not None}

            from opensearch_tpu.telemetry.scan import SCAN
            _t_scan = time.monotonic()
            SCAN.note_batch(self.reader.index_name,
                            str(getattr(self.reader, "shard_id", 0)),
                            _scan_rows, _scan_per_query)
            if cg is not None:
                _SPANS.child("compile.scan_note", _t_scan,
                             time.monotonic())
        finally:
            _t_pack = time.monotonic()
            if cg is not None:
                cg[0].spans.append((
                    cg[1], cg[2], "envelope.compile_group", _t, _t_pack,
                    (_wave_attrs, span[3], span[4])))
                _SPANS.leave(cg[0], cg[2])
        entry_by_i = {e[0]: e for e in batchable}
        ph["compile_group"] += _t_pack - _t
        # `dispatch` in the ring: its id is the open span for the loop
        # below, so a compile that a first call pays lands under it
        # (`xla.compile`, telemetry/kernels.py)
        _t_dispatch = 0.0       # the first upload of the wave
        span_programs = span_nbytes = 0
        span_infos = []         # the executables' census records
        if span is not None:
            span_top = span[0].top
            span[0].top = span[2]
        from opensearch_tpu.parallel.distributed import plan_struct
        # dispatch every group × segment program without blocking — jax
        # dispatch is async, so device work and transfers overlap.
        # The batch axis is padded to a power-of-two bucket (dummy rows
        # get min_score=+inf, matching nothing) so executables are reused
        # across varying msearch batch sizes.
        from opensearch_tpu.search.warmup import WARMUP
        pending = []
        wave_buffer_bytes = 0   # in-flight packed uploads, released by
        # _msearch_finish once the wave's results are fetched
        staging: List[np.ndarray] = []  # pooled envelope buffers, back
        # to the pool once this wave's collect completes (zero-copy-safe)
        dead: set = set()       # items already answered (error/timeout):
        # _msearch_finish must not overwrite their responses
        for (struct, agg_sig, shape_sig, k_fetch), idxs in groups.items():
            if deadline is not None and time.monotonic() > deadline:
                # budget spent between waves: unlaunched groups render as
                # zero-hit timed-out partials, launched ones still finish
                for i in idxs:
                    if responses[i] is None:
                        responses[i] = _timed_out_item(start)
                    dead.add(i)
                continue
            b_pad = pad_bucket(len(idxs), minimum=1)
            pad_rows = b_pad - len(idxs)
            # register this (plan-struct, shape-bucket) combination so an
            # index-open / node-start warmup can AOT-compile its
            # executable off the query path (a representative body replayed
            # b_pad times reproduces exactly this group program)
            WARMUP.record(self.reader.index_name, entry_by_i[idxs[0]][1],
                          b_pad, (struct, agg_sig, shape_sig, k_fetch,
                                  b_pad))
            min_scores = np.asarray(
                [entry_by_i[i][5] for i in idxs]
                + [np.inf] * pad_rows, dtype=np.float32)
            from_clause = False     # a program of this group pages from
            # its k-NN clause's winners (counted once an item, below)
            blocked = False         # ... or selects them by block maxima
            for seg_i, (seg, (arrays, meta)) in enumerate(
                    zip(segments, device)):
                if seg.num_docs == 0:
                    continue
                group_flats = [flats_by_i[i][seg_i] for i in idxs]
                group_flats += [group_flats[0]] * pad_rows
                stacked, treedef, axes = stack_flat_inputs(
                    group_flats, with_const=agg_sig is not None)
                stacked.append(min_scores)
                axes.append(0)
                buf, layout = pack_leaves(stacked, pool=self._staging)
                k_seg = min(k_fetch, pad_bucket(max(seg.num_docs, 1)))
                plan0 = compiled[idxs[0]][seg_i]
                try:
                    if agg_sig is not None:
                        # one vector a level serves the whole group:
                        # its items agree on `agg_sig`, so on the keys
                        arrays = self.reader.with_lane_bins(
                            arrays, meta, agg_by_i[idxs[0]][seg_i])
                        fn, out_layout, agg_w = _agg_envelope_runner(
                            plan_struct(plan0), plan0, meta, k_seg,
                            layout, treedef, tuple(axes), agg_sig[seg_i],
                            agg_by_i[idxs[0]][seg_i], arrays,
                            group_flats[0])
                    else:
                        fn = _envelope_runner(plan_struct(plan0), plan0,
                                              meta, k_seg, layout,
                                              treedef)
                        out_layout = None

                    def _dispatch(fn=fn, arrays=arrays, buf=buf):
                        if faults.ENABLED:
                            faults.fire("query.dispatch")
                        return fn(arrays, jnp.asarray(buf))
                    if not _t_dispatch:
                        _t_dispatch = time.monotonic()
                    out = retry.call_with_retry(_dispatch,
                                                label="msearch.dispatch")
                except Exception as e:  # except-ok: per-item isolation -- a runtime device fault downgrades only this group's items
                    # a runtime device fault downgrades ONLY this group's
                    # items to per-item error objects (extending the
                    # malformed-item machinery to runtime faults) — the
                    # envelope and sibling groups are untouched
                    if raise_item_errors:
                        raise
                    err = _item_error(e) \
                        if isinstance(e, OpenSearchTpuError) \
                        else _item_error_untyped(e)
                    for i in idxs:
                        responses[i] = dict(err)
                        dead.add(i)
                    break       # no point dispatching more segments
                if scope is not None:
                    # record AFTER the dispatch succeeded: a failed
                    # dispatch must not count h2d bytes that never
                    # crossed (conservation). Const agg tables
                    # (in_axes=None leaves) are a distinct channel: one
                    # copy serves the whole batch, so their bytes scale
                    # with groups, not with B.
                    const_b = sum(int(a.nbytes)
                                  for a, ax in zip(stacked, axes)
                                  if ax is None) \
                        if agg_sig is not None else 0
                    if const_b:
                        _LEDGER.record("upload.agg_constants", "h2d",
                                       const_b, scope=scope)
                    _LEDGER.record("upload.literals", "h2d",
                                   buf.nbytes - const_b, scope=scope)
                # the in-flight gauge is ALWAYS fed (an int add here; the
                # device-memory classes are live like corpus_columns,
                # not ledger-gated) but NOT adjusted here: multi_search
                # raises it once from the returned total, so an
                # exception out of this loop can never strand bytes
                wave_buffer_bytes += buf.nbytes
                staging.append(buf)
                if span is not None:
                    span_programs += 1
                    span_nbytes += buf.nbytes
                    info = getattr(fn, "exec_info", None)
                    if info is not None:
                        span_infos.append(info)
                # bm: whether this program's packed rows carry the extra
                # pruned-count lane — MUST mirror _envelope_runner's
                # admission (same predicate on the same plan/k)
                pending.append((idxs, seg_i, k_seg, out, out_layout,
                                agg_sig is None
                                and _blockmax_admitted(plan0, k_seg)))
                from_clause |= agg_sig is None and _page_from_clause(plan0)
                blocked |= _blocked_select(plan0, meta.d_pad)
                if agg_sig is not None:
                    note_bin_sources(agg_by_i[idxs[0]][seg_i], len(idxs))
            if from_clause:
                _KNN_PAGE_FROM_CLAUSE.inc(len(idxs))
            if blocked:
                _KNN_BLOCKED_SELECT.inc(len(idxs))
            if agg_sig is not None and not dead.issuperset(idxs):
                _AGG_ENV_QUERIES.inc(len(idxs))
        _t_end = time.monotonic()
        ph["stack_pack_dispatch"] += _t_end - _t_pack
        if span is not None:
            # the prepare half's spans; the finish half's two take the
            # ids after these (`did` + 3, + 4)
            trace, eid, did, wave_idx, wave_tids = span
            trace.top = span_top
            attrs = (_wave_attrs, wave_idx, wave_tids)
            spans = [(did + 2, eid, "envelope.pack", _t_pack,
                      _t_dispatch or _t_end, attrs)]
            if _t_dispatch:
                spans.append((did, eid, "dispatch", _t_dispatch, _t_end,
                              (_dispatch_attrs, wave_idx, wave_tids,
                               span_programs, span_nbytes, span_infos)))
            trace.spans.extend(spans)
        return {"groups": groups, "entry_by_i": entry_by_i,
                "span": span,
                "pending": pending, "agg_by_i": agg_by_i,
                "agg_nodes_by_i": agg_nodes_by_i, "dead": dead,
                "raise_item_errors": raise_item_errors,
                "staging": staging,
                "wave_buffer_bytes": wave_buffer_bytes,
                # per-item shape meta for the insights note pass
                "insights": ins_items,
                "scan_posting": _scan_posting_by_i,
                # the wave's (segments, device) anchor: finish resolves
                # seg_i hits against THIS list, never a later publish
                "segments": segments}

    def _msearch_finish(self, state, responses, start, ph, scope=None):
        """Wave half 2: ONE device_get for the wave's outputs (concatenated
        on device = one transfer round trip), then COLUMNAR response
        assembly: per query the hit page is sliced from the fetched
        [B, k] score/ord arrays and converted once (`.tolist()` — Python
        floats/ints in bulk instead of a np-scalar cast per hit), doc ids
        and sources resolve through hoisted per-segment lists, and every
        response shares the `_base_response` skeleton. Replaces the
        per-query per-hit `_hit_dict` call chain that dominated the old
        respond phase."""
        _t = time.monotonic()
        groups, entry_by_i, pending = (state["groups"], state["entry_by_i"],
                                       state["pending"])
        agg_by_i = state.get("agg_by_i") or {}
        agg_nodes_by_i = state.get("agg_nodes_by_i") or {}
        dead = state.get("dead") or set()
        grouped = [i for idxs in groups.values() for i in idxs]
        per_query_segs: Dict[int, List[Tuple[int, np.ndarray, np.ndarray]]] = \
            {i: [] for i in grouped}
        per_query_total: Dict[int, int] = {i: 0 for i in grouped}
        per_query_decoded: Dict[int, list] = {i: [] for i in agg_by_i}
        if not pending:
            _release_wave_gauges(state)
            return

        # [actually transferred d2h bytes, round trips] — filled by the
        # fetch closures so the ledger attributes REAL buffer sizes
        # (combined-fetch padding included) and true round-trip counts
        fetch_stats = [0, 0]

        def _fetch_all():
            if faults.ENABLED:
                faults.fire("fetch.gather")
            if len(pending) > 1:
                combined = np.asarray(jax.device_get(_concat_rows(
                    tuple(p[3] for p in pending))))
                fetch_stats[0] = combined.nbytes
                fetch_stats[1] = 1
                out = []
                row = 0
                for p in pending:
                    rows, width = p[3].shape
                    out.append(combined[row:row + rows, :width])
                    row += rows
                return out
            out = jax.device_get([p[3] for p in pending])
            fetch_stats[0] = sum(int(np.asarray(a).nbytes) for a in out)
            fetch_stats[1] = 1
            return out

        with _LEDGER.attributed(scope):
            try:
                fetched = retry.call_with_retry(_fetch_all,
                                                label="fetch.gather")
            except Exception:   # except-ok: combined-gather isolation -- any failure class degrades to per-program fetches below
                # the combined gather failed as a unit: fall back to one
                # fetch per dispatched program, so a single bad program
                # downgrades only ITS items to error objects
                fetched = []
                fetch_stats[0] = fetch_stats[1] = 0
                for idxs, _seg_i, _k_seg, packed, _ol, _bm in pending:
                    def _one(packed=packed):
                        if faults.ENABLED:
                            faults.fire("fetch.gather")
                        return np.asarray(jax.device_get(packed))
                    try:
                        got = retry.call_with_retry(_one,
                                                    label="fetch.gather")
                        fetched.append(got)
                        fetch_stats[0] += got.nbytes
                        fetch_stats[1] += 1
                    except Exception as e:  # except-ok: per-item isolation -- a bad program downgrades only ITS items to error objects
                        fetched.append(None)
                        err = _item_error(e) \
                            if isinstance(e, OpenSearchTpuError) \
                            else _item_error_untyped(e)
                        for i in idxs:
                            responses[i] = dict(err)
                            dead.add(i)
        _t_got = time.monotonic()
        collect_s = _t_got - _t
        ph["device_get"] += collect_s
        span = state.get("span")
        if span is not None:
            # the blocking fetch of the wave's rows, and whether a
            # program of its own (concat_rows) ran inside it
            span[0].spans.append((
                span[2] + 3, span[1], "device_wait", _t, _t_got,
                (_wait_attrs, span[3], span[4], fetch_stats[0],
                 int(len(pending) > 1))))
        _t = _t_got
        # `respond`'s children in the ring, each from two clock reads:
        # `respond.unpack` and `respond.decode_aggs` a fetched program,
        # then one `respond.reduce_aggs` and one `respond.render` a
        # wave. This half may run on the collector's thread, so they
        # are put under `respond`'s id (`span[2] + 4`) here, not under
        # a thread's open span
        kids = [] if span is not None else None
        _release_wave_gauges(state)
        if scope is not None:
            _ledger_packed_rows(scope, pending, fetched, fetch_stats[0],
                                collect_s * 1000, max(fetch_stats[1], 1))
        # block-max pruning overlay (ISSUE 20): phase-A popcounts decoded
        # from the packed rows' trailing lane, flushed once per wave
        per_query_pruned: Dict[int, int] = {}
        seg_pruned_bytes: Dict[str, int] = {}
        bm_items: set = set()
        wave_segments = state.get("segments")
        for (idxs, seg_i, k_seg, _, out_layout, bm), packed in zip(pending,
                                                                   fetched):
            if packed is None:
                continue            # this program's items are dead
            _t_unpack = time.monotonic()
            packed = np.asarray(packed)
            scores_b, idx_b, total_b = unpack_batched_result(
                packed[:, :2 * k_seg + 1], k_seg)
            totals = total_b.tolist()
            if bm:
                from opensearch_tpu.telemetry.scan import \
                    POSTING_BLOCK_BYTES
                pruned_rows = packed[:, 2 * k_seg + 1].tolist()
                seg_total = 0
                for row, i in enumerate(idxs):
                    blocks = int(pruned_rows[row])
                    per_query_pruned[i] = per_query_pruned.get(i, 0) \
                        + blocks * POSTING_BLOCK_BYTES
                    seg_total += blocks
                    bm_items.add(i)
                if wave_segments is not None and seg_total:
                    sid = wave_segments[seg_i].seg_id
                    seg_pruned_bytes[sid] = seg_pruned_bytes.get(sid, 0) \
                        + seg_total * POSTING_BLOCK_BYTES
            for row, i in enumerate(idxs):
                per_query_total[i] += totals[row]
                per_query_segs[i].append((seg_i, scores_b[row], idx_b[row]))
            _t_decode = time.monotonic()
            if kids is not None:
                kids.append(("respond.unpack", _t_unpack, _t_decode, None))
            if out_layout is not None:
                for row, i in enumerate(idxs):
                    outs = _decode_agg_row(packed[row, 2 * k_seg + 1:],
                                           out_layout)
                    per_query_decoded[i].append(
                        decode_outputs(agg_by_i[i][seg_i], outs))
                if kids is not None:
                    # `buckets`: the bins of every partial array a row
                    # carries back (the `bins` of the dispatch's shape)
                    kids.append(("respond.decode_aggs", _t_decode,
                                 time.monotonic(),
                                 {"buckets": int(packed.shape[1])
                                  - 2 * k_seg - 1}))
        if bm_items:
            from opensearch_tpu.telemetry.scan import SCAN
            scan_posting = state.get("scan_posting") or {}
            ins_meta = state.get("insights")
            pq = []
            for i in sorted(bm_items):
                pruned = per_query_pruned.get(i, 0)
                pq.append((scan_posting.get(i, 0), pruned))
                if ins_meta is not None and i in ins_meta:
                    # ride the existing insights join so the per-shape
                    # effective bytes conserve against telemetry.scan
                    ins_meta[i]["pruned"] = pruned
            SCAN.note_pruned_batch(
                self.reader.index_name,
                str(getattr(self.reader, "shard_id", 0)),
                seg_pruned_bytes, pq)

        took_ms = int((time.monotonic() - start) * 1000)
        segments = state.get("segments")
        if segments is None:
            segments = self.reader.segments
        index_name = self.reader.index_name
        resp_cache_keys = state.get("resp_cache_keys", {})
        # the wave's aggregations first, then its pages: two intervals
        reduced: Dict[int, dict] = {}
        if agg_by_i:
            from opensearch_tpu.search.aggs.pipeline import apply_pipelines
            _t_reduce = time.monotonic()
            raise_item_errors = state.get("raise_item_errors", False)

            def _reduce_item(i):
                aggregations = reduce_aggs(per_query_decoded[i])
                apply_pipelines(agg_nodes_by_i[i], aggregations)
                reduced[i] = aggregations

            for i in agg_by_i:
                if i in dead:
                    continue        # already answered (error/timeout item)
                # per item: one body's reduce failing answers that item
                # with its error object, and its siblings keep theirs
                _run_item_isolated(responses, i, raise_item_errors,
                                   lambda: _reduce_item(i))
                if i not in reduced:
                    dead.add(i)
            if kids is not None:
                kids.append(("respond.reduce_aggs", _t_reduce,
                             time.monotonic(),
                             {"buckets": _rendered_buckets(reduced)}))
        _t_render = time.monotonic()
        for i, seg_results in per_query_segs.items():
            if i in dead:
                continue        # already answered (error/timeout item)
            entry = entry_by_i[i]
            body, size, from_ = entry[1], entry[3], entry[4]
            page_segs: Optional[list] = None
            if seg_results:
                if len(seg_results) == 1:
                    # the device's top_k is already score-desc with
                    # doc-asc tie-break (candidate lanes are doc-sorted;
                    # ties pick the lowest lane) and padding (NEG_INF)
                    # sorts last — the single-segment page is a slice of
                    # the valid prefix
                    one_seg_i, scores, ords = seg_results[0]
                    n_valid = int((scores > NEG_INF).sum())
                    hi = min(from_ + size, n_valid)
                    page_scores = scores[from_:hi].tolist()
                    page_ords = ords[from_:hi].tolist()
                    max_score = float(scores[0]) if n_valid else None
                else:
                    all_scores = np.concatenate(
                        [s for _, s, _ in seg_results])
                    all_ords = np.concatenate(
                        [o for _, _, o in seg_results])
                    all_segs = np.concatenate(
                        [np.full(len(s), si, np.int32)
                         for si, s, _ in seg_results])
                    valid = all_scores > NEG_INF
                    all_scores, all_ords, all_segs = (
                        all_scores[valid], all_ords[valid],
                        all_segs[valid])
                    # score desc, seg asc, doc asc — mergeTopDocs order
                    order = np.lexsort((all_ords, all_segs, -all_scores))
                    page = order[from_:from_ + size]
                    page_scores = all_scores[page].tolist()
                    page_ords = all_ords[page].tolist()
                    page_segs = all_segs[page].tolist()
                    max_score = float(all_scores.max()) \
                        if len(all_scores) else None
            else:
                page_scores = page_ords = []
                max_score = None
            source_spec = body.get("_source", True)
            if source_spec is True or source_spec is None:
                hits = []
                if page_segs is None:
                    if page_ords:
                        seg = segments[one_seg_i]
                        ids, srcs = seg.doc_ids, seg.sources
                        for o, s in zip(page_ords, page_scores):
                            h = {"_index": index_name, "_id": ids[o],
                                 "_score": s}
                            src = srcs[o]
                            if src is not None:
                                h["_source"] = src
                            hits.append(h)
                else:
                    for g, o, s in zip(page_segs, page_ords, page_scores):
                        seg = segments[g]
                        h = {"_index": index_name, "_id": seg.doc_ids[o],
                             "_score": s}
                        src = seg.sources[o]
                        if src is not None:
                            h["_source"] = src
                        hits.append(h)
            else:
                # filtered _source: the general per-hit fetch path
                segs_for_page = page_segs if page_segs is not None \
                    else [one_seg_i] * len(page_ords)
                hits = [self._hit_dict(g, o, s, body, segments=segments)
                        for g, o, s in zip(segs_for_page, page_ords,
                                           page_scores)]
            responses[i] = _base_response(took_ms, per_query_total[i],
                                          max_score, hits)
            if per_query_pruned.get(i):
                # pruned blocks never reach the hit-count scatter, so the
                # total is a lower bound — same "gte" semantics Lucene
                # BMW reports under track_total_hits. The top-k page
                # itself stays byte-identical (rank-exact pruning).
                responses[i]["hits"]["total"]["relation"] = "gte"
            if i in reduced:
                responses[i]["aggregations"] = reduced[i]
            key = resp_cache_keys.get(i)
            if key is not None:
                # cached at query-phase granularity (totals + decoded agg
                # partials); the response dict handed to the caller is
                # NOT stored — _render_cached_msearch rebuilds one per hit
                _cache_put_isolated(
                    _request_cache(), key,
                    (per_query_total[i], per_query_decoded.get(i),
                     agg_nodes_by_i.get(i)))
        _t_end = time.monotonic()
        ph["respond"] += _t_end - _t
        if span is not None:
            kids.append(("respond.render", _t_render, _t_end, None))
            span[0].spans.append((
                span[2] + 4, span[1], "respond", _t, _t_end,
                (_wave_attrs, span[3], span[4])))
            span[0].spans.extend(
                (next(_SPANS.ids), span[2] + 4, name, t0, t1, attrs)
                for name, t0, t1, attrs in kids)

    def _render_cached_msearch(self, cached, start: float) -> dict:
        """Build a fresh response from a cached (total, decoded partials,
        agg nodes) entry — size=0 only (the cacheable() gate), so there is
        no hits page to rebuild."""
        total, decoded, agg_nodes = cached
        resp = _base_response(int((time.monotonic() - start) * 1000),
                              total, None, [])
        if decoded is not None and agg_nodes is not None:
            from opensearch_tpu.search.aggs.pipeline import apply_pipelines
            aggregations = reduce_aggs(decoded)
            apply_pipelines(agg_nodes, aggregations)
            resp["aggregations"] = aggregations
        return resp

    def count(self, body: Optional[dict] = None) -> int:
        body = dict(body or {})
        body["size"] = 0
        body.pop("from", None)
        return self.search(body)["hits"]["total"]["value"]


def _parse_sort(sort_body) -> List[Tuple[str, str]]:
    """Normalize the sort body to [(field | '_score', order), ...].
    Default (None / empty / '_score') is score-descending."""
    if sort_body is None:
        return [("_score", "desc")]
    specs = sort_body if isinstance(sort_body, list) else [sort_body]
    out: List[Tuple[str, str]] = []
    for spec in specs:
        if isinstance(spec, str):
            if spec == "_score":
                out.append(("_score", "desc"))
            elif spec == "_doc":
                continue  # doc order is the built-in final tie-break
            else:
                out.append((spec, "asc"))
        elif isinstance(spec, dict):
            field, opts = next(iter(spec.items()))
            if field == "_score":
                order = opts.get("order", "desc") if isinstance(opts, dict) \
                    else str(opts)
                out.append(("_score", order))
            else:
                order = opts.get("order", "asc") if isinstance(opts, dict) \
                    else str(opts)
                out.append((field, order))
    if not out:
        return [("_score", "desc")]
    return out


def _sort_value(seg: Segment, field: str, order: str, ord_: int):
    """Real (host, exact) sort value for the cross-segment merge + response."""
    col = seg.numeric_dv.get(field)
    if col is not None:
        vals = col.values[col.doc_ids == ord_]
        if len(vals) == 0:
            return None
        v = float(vals.min() if order == "asc" else vals.max())
        return int(v) if v.is_integer() else v
    ocol = seg.ordinal_dv.get(field)
    if ocol is not None:
        ords = ocol.ords[ocol.doc_ids == ord_]
        if len(ords) == 0:
            return None
        o = int(ords.min() if order == "asc" else ords.max())
        return ocol.dictionary[o]
    return None


def _filter_source(source: Optional[dict], source_spec) -> Optional[dict]:
    """_source filtering per the reference's FetchSourceContext: an include
    pattern selects its whole subtree; excludes override includes."""
    if source is None or source_spec is True or source_spec is None:
        return source
    if source_spec is False:
        return None
    import fnmatch as _fn

    if isinstance(source_spec, str):
        includes, excludes = [source_spec], []
    elif isinstance(source_spec, list):
        includes, excludes = list(source_spec), []
    elif isinstance(source_spec, dict):
        includes = source_spec.get("includes", source_spec.get("include", []))
        excludes = source_spec.get("excludes", source_spec.get("exclude", []))
        if isinstance(includes, str):
            includes = [includes]
        if isinstance(excludes, str):
            excludes = [excludes]
    else:
        return source

    def matches_any(path: str, patterns) -> bool:
        # a pattern matches the leaf itself or any ancestor object path
        parts = path.split(".")
        prefixes = [".".join(parts[:i + 1]) for i in range(len(parts))]
        return any(_fn.fnmatchcase(prefix, p)
                   for prefix in prefixes for p in patterns)

    def walk(obj, path=""):
        if not isinstance(obj, dict):
            return obj
        out = {}
        for k, v in obj.items():
            full = f"{path}{k}"
            if isinstance(v, dict):
                sub = walk(v, f"{full}.")
                if sub:
                    out[k] = sub
                continue
            if matches_any(full, includes) if includes else True:
                if not matches_any(full, excludes):
                    out[k] = v
        return out

    return walk(source)
