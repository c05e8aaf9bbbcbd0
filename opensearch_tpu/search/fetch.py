"""Fetch-phase subphases: per-hit enrichment after the device query phase.

Re-design of the reference FetchPhase (search/fetch/FetchPhase.java:106) and
its sub-phases (search/fetch/subphase/): _source filtering, docvalue_fields,
highlighting (highlight/), and explain (ExplainPhase → Lucene
Explanation via BM25Similarity.explain). All of this is host-side work over
the hit page only — the device program already picked the top docs.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from opensearch_tpu.common.errors import IllegalArgumentError
from opensearch_tpu.index.segment import Segment, smallfloat_byte4_to_int
from opensearch_tpu.search import dsl
from opensearch_tpu.telemetry import TELEMETRY

DEFAULT_K1 = 1.2
DEFAULT_B = 0.75


# ----------------------------------------------------------- term extraction

def collect_field_terms(node, mapper) -> Dict[str, List[str]]:
    """Walk a parsed query tree collecting the analyzed terms per field —
    what the reference gets from Query.visit(QueryVisitor) for highlighting."""
    out: Dict[str, List[str]] = {}

    def add(field: str, terms: List[str]):
        if field:
            out.setdefault(field, []).extend(t for t in terms if t)

    def analyze(field: str, text: Any) -> List[str]:
        ft = mapper.get_field(field)
        if ft is None or text is None:
            return []
        if ft.is_text:
            analyzer = mapper.analysis.get(ft.search_analyzer or ft.analyzer)
            return [t for t, _ in analyzer.analyze(str(text))]
        return [str(text)]

    def walk(n):
        if n is None:
            return
        if isinstance(n, dsl.BoolQuery):
            for child in list(n.must) + list(n.should) + list(n.filter):
                walk(child)  # must_not terms don't highlight
            return
        if isinstance(n, (dsl.ConstantScoreQuery,)):
            walk(n.filter)
            return
        if isinstance(n, dsl.DisMaxQuery):
            for child in n.queries:
                walk(child)
            return
        if isinstance(n, dsl.BoostingQuery):
            walk(n.positive)
            return
        if isinstance(n, (dsl.MatchQuery, dsl.MatchPhraseQuery,
                          dsl.MatchBoolPrefixQuery)):
            add(n.field, analyze(n.field, n.query))
            return
        if isinstance(n, dsl.MultiMatchQuery):
            for f in mapper.expand_field_patterns(list(n.fields)):
                f = f.split("^")[0]
                add(f, analyze(f, n.query))
            return
        if isinstance(n, dsl.TermQuery):
            add(n.field, [str(n.value)])
            return
        if isinstance(n, dsl.TermsQuery):
            add(n.field, [str(v) for v in n.values])
            return
        if isinstance(n, dsl.PrefixQuery):
            # trailing-* marker: highlight_text prefix-matches these
            add(n.field, [str(n.value) + "*"])
            return
        if isinstance(n, dsl.FuzzyQuery):
            add(n.field, [str(n.value)])
            return
        if isinstance(n, (dsl.QueryStringQuery, dsl.SimpleQueryStringQuery)):
            # best effort: bare terms against default/explicit fields
            fields = [f.split("^")[0] for f in (n.fields or [])]
            if getattr(n, "default_field", None):
                fields.append(n.default_field)
            text = re.sub(r'[+\-()"~*?:\\]|AND|OR|NOT', " ", n.query)
            for token in text.split():
                if ":" in token:
                    f, v = token.split(":", 1)
                    add(f, analyze(f, v))
                else:
                    for f in fields:
                        add(f, analyze(f, token))
            return
        # leaf without highlightable terms (range/exists/knn/...)

    walk(node)
    return {f: list(dict.fromkeys(ts)) for f, ts in out.items()}


# -------------------------------------------------------------- highlighting

_TOKEN_RE = re.compile(r"\w+", re.UNICODE)


def highlight_text(text: str, terms: List[str], pre: str, post: str,
                   fragment_size: int, number_of_fragments: int,
                   analyzer) -> List[str]:
    """Unified-highlighter analog: analyze the stored text, mark offsets of
    matching terms, cut fragments around matches."""
    term_set = {t for t in terms if not t.endswith("*")}
    prefixes = tuple(t[:-1] for t in terms if t.endswith("*") and len(t) > 1)
    matches: List[Tuple[int, int]] = []
    for m in _TOKEN_RE.finditer(text):
        raw = m.group(0)
        analyzed = analyzer.analyze(raw) if analyzer else [(raw.lower(), 0)]
        if any(t in term_set or (prefixes and t.startswith(prefixes))
               for t, _ in analyzed):
            matches.append((m.start(), m.end()))
    if not matches:
        return []
    if number_of_fragments == 0:
        # whole-field highlighting
        return [_mark(text, matches, pre, post)]
    fragments: List[str] = []
    used_until = -1
    for start, end in matches:
        if start < used_until:
            continue
        frag_start = max(0, start - max(0, (fragment_size - (end - start)) // 2))
        # snap to a word boundary
        while frag_start > 0 and text[frag_start - 1].isalnum():
            frag_start -= 1
        frag_end = min(len(text), frag_start + fragment_size)
        while frag_end < len(text) and text[frag_end - 1].isalnum() \
                and not text[frag_end].isspace():
            frag_end += 1
        used_until = frag_end
        inside = [(s, e) for s, e in matches if s >= frag_start and e <= frag_end]
        fragments.append(_mark(text[frag_start:frag_end],
                               [(s - frag_start, e - frag_start)
                                for s, e in inside], pre, post))
        if len(fragments) >= number_of_fragments:
            break
    return fragments


def _mark(text: str, spans: List[Tuple[int, int]], pre: str, post: str) -> str:
    out = []
    last = 0
    for s, e in spans:
        out.append(text[last:s])
        out.append(pre + text[s:e] + post)
        last = e
    out.append(text[last:])
    return "".join(out)


def build_highlights(source: Optional[dict], hl_body: dict, field_terms,
                     mapper) -> dict:
    TELEMETRY.metrics.counter("fetch.highlight_hits").inc()
    if not source:
        return {}
    pre = (hl_body.get("pre_tags") or ["<em>"])[0]
    post = (hl_body.get("post_tags") or ["</em>"])[0]
    out = {}
    for field_spec, spec in (hl_body.get("fields") or {}).items():
        spec = spec or {}
        # wildcard highlight fields expand to the fields the query
        # actually matched (the reference's HighlightPhase field
        # resolution over wildcard patterns)
        if "*" in field_spec:
            import fnmatch as _fn
            targets = [f for f in field_terms
                       if _fn.fnmatchcase(f, field_spec)]
        else:
            targets = [field_spec]
        for field in targets:
            _highlight_one(source, field, spec, hl_body, field_terms,
                           mapper, pre, post, out)
    return out


def _highlight_one(source, field, spec, hl_body, field_terms, mapper,
                   pre, post, out):
        hq = spec.get("highlight_query") or hl_body.get("highlight_query")
        if hq is not None:
            # highlight with a DIFFERENT query's terms (the reference's
            # highlight_query override, HighlightBuilder#highlightQuery)
            try:
                field_terms = collect_field_terms(dsl.parse_query(hq),
                                                  mapper)
            except Exception:   # except-ok: highlighting is best-effort -- an unparseable highlight_query just yields no fragments
                field_terms = {}
        terms = field_terms.get(field, [])
        if not terms:
            return
        value = _source_value(source, field)
        if value is None and "." in field:
            # multi-fields (text.fvh) read their parent's source value
            value = _source_value(source, field.rsplit(".", 1)[0])
        if value is None:
            return
        ft = mapper.get_field(field)
        analyzer = None
        if ft is not None and ft.is_text:
            analyzer = mapper.analysis.get(ft.search_analyzer or ft.analyzer)
        frags = highlight_text(
            str(value), terms,
            pre=(spec.get("pre_tags") or [pre])[0],
            post=(spec.get("post_tags") or [post])[0],
            fragment_size=int(spec.get("fragment_size",
                                       hl_body.get("fragment_size", 100))),
            number_of_fragments=int(spec.get(
                "number_of_fragments",
                hl_body.get("number_of_fragments", 5))),
            analyzer=analyzer)
        if frags:
            out[field] = frags


def _source_value(source: dict, path: str):
    cur: Any = source
    for part in path.split("."):
        if isinstance(cur, dict) and part in cur:
            cur = cur[part]
        else:
            return None
    if isinstance(cur, list):
        return " ".join(str(v) for v in cur)
    return cur


# ------------------------------------------------------------------- explain

def explain_hit(seg: Segment, ord_: int, node, mapper, stats,
                score: float) -> dict:
    """BM25 explanation tree for one hit — mirrors the shape of Lucene's
    BM25Similarity.explain (weight(...) / idf / tf breakdown) for the term
    clauses; compound/other queries get a summary node."""
    details = []
    field_terms = collect_field_terms(node, mapper)
    for field, terms in field_terms.items():
        ft = mapper.get_field(field)
        if ft is None or not ft.is_text:
            continue
        norms = seg.norms.get(field)
        dl = float(smallfloat_byte4_to_int(int(norms[ord_]))) \
            if norms is not None else 1.0
        avgdl = stats.avgdl(field)
        doc_count, _ = stats.field_stats(field)
        for term in terms:
            tf = _term_freq(seg, field, term, ord_)
            if tf <= 0:
                continue
            df = stats.df(field, term)
            idf_v = math.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))
            tf_factor = (tf * (DEFAULT_K1 + 1.0)
                         / (tf + DEFAULT_K1 * (1.0 - DEFAULT_B
                                               + DEFAULT_B * dl / avgdl)))
            details.append({
                "value": idf_v * tf_factor,
                "description": f"weight({field}:{term} in {ord_}) "
                               f"[BM25Similarity], result of:",
                "details": [
                    {"value": idf_v,
                     "description": f"idf, computed as log(1 + (N - n + 0.5) "
                                    f"/ (n + 0.5)) from n={df}, N={doc_count}",
                     "details": []},
                    {"value": tf_factor,
                     "description": f"tf, computed as freq * (k1 + 1) / "
                                    f"(freq + k1 * (1 - b + b * dl / avgdl)) "
                                    f"from freq={tf}, k1={DEFAULT_K1}, "
                                    f"b={DEFAULT_B}, dl={dl}, avgdl={avgdl}",
                     "details": []},
                ],
            })
    return {"value": score,
            "description": "sum of:" if details else "score(...), computed "
            "by the TPU query phase",
            "details": details}


def _term_freq(seg: Segment, field: str, term: str, ord_: int) -> float:
    meta = seg.get_term(field, term)
    if meta is None:
        return 0.0
    blocks = slice(meta.start_block, meta.start_block + meta.num_blocks)
    docs = seg.post_docs[blocks].reshape(-1)
    tfs = seg.post_tf[blocks].reshape(-1)
    hit = np.nonzero(docs == ord_)[0]
    return float(tfs[hit[0]]) if len(hit) else 0.0


# ----------------------------------------------------------- field retrieval

def _format_numeric_dv(vals, ft) -> list:
    """Response formatting for numeric docvalues — shared by the host
    column scan below and the result page's fused gather (the prefetched
    branch), so the two paths can never drift on types."""
    if ft is not None and ft.is_date:
        from opensearch_tpu.index.mapper import format_date_millis
        return [format_date_millis(int(v)) for v in vals]
    if ft is not None and (ft.is_numeric and ft.type in
                           ("integer", "long", "short", "byte")):
        return [int(v) for v in vals]
    return [float(v) for v in vals]


def docvalue_fields(seg: Segment, ord_: int, specs: List[Any],
                    mapper, prefetched: Optional[dict] = None) -> dict:
    """`prefetched`: the result page's fused docvalue gather for this hit
    ({field: [raw values]}, empty list = field missing on the doc) —
    those fields skip the per-leaf column scan below; fields the page
    could not fuse (multi-valued, keyword) fall through to it."""
    import time
    out = {}
    ledger = TELEMETRY.ledger
    scope = ledger.current()
    accounting = ledger.enabled or scope is not None
    for spec in specs or []:
        field = spec["field"] if isinstance(spec, dict) else spec
        if prefetched is not None and field in prefetched:
            vals = prefetched[field]
            if vals:
                out[field] = _format_numeric_dv(vals, mapper.get_field(field))
            continue
        t0 = time.monotonic() if accounting else 0.0
        col = seg.numeric_dv.get(field)
        if col is not None:
            mask = col.doc_ids == ord_
            vals = col.values[mask]
            if accounting:
                # per-leaf round-trip attribution (ISSUE 17 satellite 1):
                # this host-mirror scan stands in for a device column
                # fetch — one round trip per leaf on a remote device,
                # zero wire bytes here (byte conservation stays exact)
                ledger.note_round_trip(
                    "docvalues", (time.monotonic() - t0) * 1000,
                    scope=scope)
            if len(vals):
                out[field] = _format_numeric_dv(vals,
                                                mapper.get_field(field))
            continue
        ocol = seg.ordinal_dv.get(field)
        if ocol is not None:
            mask = ocol.doc_ids == ord_
            ords = ocol.ords[mask]
            if accounting:
                ledger.note_round_trip(
                    "docvalues", (time.monotonic() - t0) * 1000,
                    scope=scope)
            if len(ords):
                out[field] = [ocol.dictionary[o] for o in ords]
    return out


# --------------------------------------------------------------- inner hits
#
# Nested inner_hits (index/query/InnerHitBuilder + fetch/subphase/
# InnerHitsPhase): for each page hit, return the CHILD rows that matched
# the nested query, scored and paged. The child-level plan (the nested
# query's inner query compiled WITHOUT the root join) is evaluated once
# per (segment, query) on device; per-hit work is then a host-side slice
# of that dense result over the root's own child rows.

_INNER_JIT: Dict[Any, Any] = {}


def _eval_child_scores(plan, arrays):
    import time

    import jax
    import jax.numpy as jnp

    from opensearch_tpu.search.plan_eval import _eval_plan
    sig = ("inner_hits", plan.sig())
    fn = _INNER_JIT.get(sig)
    if fn is None:
        def inner_hits(seg, flat, _plan=plan):  # names jit_inner_hits
            cursor = [0]
            return _eval_plan(_plan, seg, flat, cursor)
        fn = _INNER_JIT[sig] = jax.jit(inner_hits)  # shared-state-ok: benign double-jit race; dict slot write is GIL-atomic
    host_flat = plan.flatten_inputs([])
    ledger = TELEMETRY.ledger
    # scope: the request's LedgerScope, bound ambiently by the
    # controller's fetch phase — a traced/profiled request accounts
    # here even with the node-wide ledger off
    scope = ledger.current()
    accounting = ledger.enabled or scope is not None
    if accounting:
        ledger.record("upload.literals", "h2d",
                      sum(int(np.asarray(v).nbytes)
                          for d in host_flat for v in d.values()),
                      scope=scope)
    flat = jax.tree_util.tree_map(jnp.asarray, host_flat)
    t0 = time.monotonic() if accounting else 0.0
    # self-attributing region: the single-node controller binds ambient
    # around its fetch phase, but the cluster-distributed fetch
    # (cluster/service.py _on_shard_fetch) reaches here without it — the
    # sync site owns its own attribution marker so every caller is
    # covered (the sanitizer caught exactly this gap on the transport
    # path)
    with ledger.attributed(scope):
        scores, matches = jax.device_get(fn(arrays, flat))
        scores, matches = np.asarray(scores), np.asarray(matches)
    if accounting:
        # the fetch phase's one device gather (dense child scores/masks
        # for inner_hits) — the `docvalues` channel of the ledger
        nb = scores.nbytes + matches.nbytes
        ledger.record("docvalues", "d2h", nb, wave=ledger.new_wave(),
                      scope=scope)
        ledger.note_device_get((time.monotonic() - t0) * 1000, nbytes=nb,
                               scope=scope)
    return scores, matches


def collect_inner_hit_specs(node) -> List[Any]:
    """Every nested/has_child/has_parent query carrying an inner_hits
    spec in the tree."""
    from dataclasses import fields as dc_fields
    out: List[Any] = []

    def walk(n):
        if isinstance(n, (dsl.NestedQuery, dsl.HasChildQuery,
                          dsl.HasParentQuery)) and \
                n.inner_hits is not None:
            out.append(n)
        for f in dc_fields(n):
            sub = getattr(n, f.name, None)
            if isinstance(sub, dsl.QueryNode):
                walk(sub)
            elif isinstance(sub, (list, tuple)):
                for s in sub:
                    if isinstance(s, dsl.QueryNode):
                        walk(s)

    if node is not None:
        walk(node)
    names = [(n.inner_hits or {}).get(
        "name", n.path if isinstance(n, dsl.NestedQuery) else n.type)
        for n in out]
    for name in names:
        if names.count(name) > 1:
            raise IllegalArgumentError(
                f"[inner_hits] already contains an entry for key [{name}]")
    return out


def build_inner_hits(ex, seg_i: int, root_ord: int, nested_nodes,
                     cache: Dict) -> Dict[str, dict]:
    """inner_hits sections for one page hit. `cache` memoizes the per-
    (segment, nested node) child evaluation across the page's hits."""
    TELEMETRY.metrics.counter("fetch.inner_hits").inc()
    from opensearch_tpu.search.compile import Compiler
    seg = ex.reader.segments[seg_i]
    arrays, meta = ex.reader.device[seg_i]
    out: Dict[str, dict] = {}
    for node in nested_nodes:
        if isinstance(node, (dsl.HasChildQuery, dsl.HasParentQuery)):
            _join_inner_hits(ex, seg, seg_i, root_ord, node, cache, out)
            continue
        spec = node.inner_hits or {}
        name = spec.get("name", node.path)
        # every REQUESTED section appears, even with zero matching
        # children (the reference returns an empty hits array, not a
        # missing key — clients index hit["inner_hits"][name] directly)
        empty = {"hits": {"total": {"value": 0, "relation": "eq"},
                          "max_score": None, "hits": []}}
        try:
            pord = seg.nested_paths.index(node.path)
        except ValueError:
            out[name] = empty           # segment has no rows on this path
            continue
        key = (seg.uid, repr(node.query))   # repr = stable fingerprint
        got = cache.get(key)
        if got is None:
            compiler = Compiler(ex.reader.mapper, ex.reader.stats())
            plan = compiler.compile(node.query, seg, meta)
            if len(cache) > 256:
                cache.clear()
            got = cache[key] = _eval_child_scores(plan, arrays)
        scores, matches = got
        rows = np.nonzero((seg.parent_ptr == root_ord)
                          & (seg.path_ords == pord) & seg.live)[0]
        hit_rows = rows[matches[rows]] if len(rows) else rows
        if not len(hit_rows):
            out[name] = empty
            continue
        # offsets index the parent's source array in row order
        offset_of = {int(r): i for i, r in enumerate(rows)}
        order = np.argsort(-scores[hit_rows], kind="stable")
        size = int(spec.get("size", 3))
        from_ = int(spec.get("from", 0))
        page = [int(hit_rows[j]) for j in order][from_:from_ + size]
        src_parent = _source_value_raw(seg.sources[root_ord], node.path)
        hits = []
        for r in page:
            off = offset_of[r]
            child_src = (src_parent[off]
                         if isinstance(src_parent, list)
                         and off < len(src_parent) else src_parent)
            hits.append({
                "_index": ex.reader.index_name,
                "_id": seg.doc_ids[root_ord],
                "_nested": {"field": node.path, "offset": off},
                "_score": float(scores[r]),
                "_source": child_src,
            })
        out[name] = {"hits": {
            "total": {"value": int(len(hit_rows)), "relation": "eq"},
            "max_score": float(scores[hit_rows].max()),
            "hits": hits,
        }}
    return out


def _source_value_raw(source, path: str):
    """Navigate dotted paths WITHOUT flattening lists (inner hits need the
    raw nested array to index by offset)."""
    cur = source
    for part in path.split("."):
        if isinstance(cur, dict) and part in cur:
            cur = cur[part]
        else:
            return None
    return cur


def _join_inner_hits(ex, seg, seg_i: int, root_ord: int, node, cache,
                     out: Dict[str, dict]):
    """has_child/has_parent inner_hits (parent-join InnerHitContextBuilder):
    children/parents are ROOT documents related through the join field's
    hidden parent-id column, joined host-side across the shard's segments
    (the reference joins via global ordinals)."""
    from opensearch_tpu.search.compile import Compiler
    spec = node.inner_hits or {}
    name = spec.get("name", node.type)
    empty = {"hits": {"total": {"value": 0, "relation": "eq"},
                      "max_score": None, "hits": []}}
    ckey = ("join_ctx", ex.reader.index_name)
    ctx = cache.get(ckey)
    if ctx is None:
        compiler = Compiler(ex.reader.mapper, ex.reader.stats())
        info = compiler._join_info()
        ctx = cache[ckey] = {"compiler": compiler, "info": info}
    compiler, info = ctx["compiler"], ctx["info"]
    if info is None:
        out[name] = empty
        return
    join, _relations = info

    def seg_ctx(s):
        key = ("join_cols", s.uid)
        got = cache.get(key)
        if got is None:
            got = cache[key] = compiler._join_columns(s, join)
        return got

    def match_mask(s):
        key = ("join_match", s.uid, repr(node.query))
        got = cache.get(key)
        if got is None:
            got = cache[key] = compiler._host_match(s, node.query)
        return got

    def children_by_parent():
        """parent_id → [(segment, ord)] of matching live children —
        computed ONCE per (shard, query) and reused across the page."""
        key = ("join_children", repr(node.query), node.type)
        got = cache.get(key)
        if got is None:
            got = {}
            for s in ex.reader.segments:
                rel, par = seg_ctx(s)
                mask = match_mask(s)
                cand = np.nonzero(mask & s.live[:s.num_docs])[0] \
                    if len(mask) else []
                for d in cand:
                    d = int(d)
                    if rel[d] == node.type and par[d] is not None:
                        got.setdefault(par[d], []).append((s, d))
            cache[key] = got
        return got

    doc_id = seg.doc_ids[root_ord]
    hits = []
    total = 0
    if isinstance(node, dsl.HasChildQuery):
        # this hit is the PARENT: gather its matching children
        size = int(spec.get("size", 3))
        from_ = int(spec.get("from", 0))
        kids = children_by_parent().get(doc_id, [])
        total = len(kids)
        hits = [{"_index": ex.reader.index_name, "_id": s2.doc_ids[d],
                 "_score": 1.0, "_source": s2.sources[d]}
                for s2, d in kids[from_:from_ + size]]
    else:
        # this hit is the CHILD: resolve its single parent
        size = int(spec.get("size", 3))
        from_ = int(spec.get("from", 0))
        rel, par = seg_ctx(seg)
        parent_id = par[root_ord]
        if parent_id is not None:
            for s in ex.reader.segments:
                srel, _ = seg_ctx(s)
                ord_ = s.ord_of(parent_id)
                if ord_ is not None and srel[ord_] == node.type \
                        and match_mask(s)[ord_]:
                    total = 1
                    hits = [{"_index": ex.reader.index_name,
                             "_id": parent_id, "_score": 1.0,
                             "_source": s.sources[ord_]}]
                    break
        hits = hits[from_:from_ + size]   # paging applies here too
    if not total:
        out[name] = empty
        return
    out[name] = {"hits": {"total": {"value": total, "relation": "eq"},
                          "max_score": 1.0, "hits": hits}}
