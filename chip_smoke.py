#!/usr/bin/env python3
"""Chip smoke: the served search path, once, on the accelerator.

One process. Starts the node the way `python -m opensearch_tpu` does
(`launcher.start_node`), talks to it over the real socket, loads four
indices at sizes a user would call real, sends a few requests of every
served shape and compares every page with a plain numpy oracle computed
from the host-side data the smoke generated — never with another device
path. Prints two JSON lines on standard output, the report and then, last,
the verdict `{"ok": ..., "device": {"platform", "kind", "count"}}` with
exactly those keys; exits 0 only if every phase passed on a TPU. Each
failed phase is named, with its traceback, on standard error. Where jax
finds no TPU it prints nothing on standard output and exits non-zero.

    python3 chip_smoke.py                      # on the chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --dry-run   # tiny, to debug

It never selects a platform itself: it runs on what jax gives it and
refuses anything but a TPU, the one exception being the explicit dry run
above (the caller pinned the CPU AND asked for it), which shrinks every
size and says `"dry_run": true, "platform": "cpu"`.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import sys
import time
import traceback
from collections import Counter

import numpy as np

RTOL = 1e-5
# exact kNN scores 1/(1+d²) with d² from the engine's documented
# ||x||² - 2x·q + ||q||² expansion: f32 cancels ~4,400 down to d² ~ 200
# here, which puts 1e-5 at the formula's own noise floor (1.0e-5 seen on
# the CPU backend). A bf16-rounded matmul would be off by ~1e-1.
KNN_RTOL = 1e-4
K1, B = 1.2, 0.75
WEEK_MS = 7 * 86400_000
TS_BASE = 1700000000000
# MS MARCO passage v1's passage count (BASELINE config 1)
MSMARCO_PASSAGES = 8_841_823
# utils/demo.py fast-corpus parameters: bursty tf, spread doc lengths
FAST = dict(vocab_size=20000, avg_len=60, materialize_terms=64,
            burst_tf=30, burst_window=256, doc_len_cv=0.5)


T0 = time.monotonic()


def log(msg: str) -> None:
    sys.stderr.write(f"[chip_smoke +{time.monotonic() - T0:7.1f}s] {msg}\n")
    sys.stderr.flush()


class SmokeFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ client

class Client:
    """The node's REST surface over its real socket."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=900)

    def call(self, method: str, path: str, body=None, ndjson=False):
        data, headers = None, {}
        if body is not None:
            if ndjson:
                data = ("\n".join(json.dumps(x) for x in body)
                        + "\n").encode()
                headers["Content-Type"] = "application/x-ndjson"
            else:
                data = json.dumps(body).encode()
                headers["Content-Type"] = "application/json"
        self.conn.request(method, path, body=data, headers=headers)
        resp = self.conn.getresponse()
        raw = resp.read()
        out = json.loads(raw)
        require(resp.status == 200,
                f"{method} {path} -> {resp.status}: {str(out)[:400]}")
        return out

    def search(self, index: str, body: dict) -> dict:
        out = self.call("POST", f"/{index}/_search", body)
        check_clean(out, f"{index}/_search")
        return out

    def msearch(self, index: str, bodies: list) -> list:
        lines = []
        for b in bodies:
            lines.append({"index": index})
            lines.append(b)
        out = self.call("POST", "/_msearch", lines, ndjson=True)
        responses = out["responses"]
        require(len(responses) == len(bodies),
                f"_msearch answered {len(responses)} of {len(bodies)}")
        for i, r in enumerate(responses):
            check_clean(r, f"{index}/_msearch[{i}]")
        return responses

    def node_stats(self) -> dict:
        out = self.call("GET", "/_nodes/stats")
        return next(iter(out["nodes"].values()))

    def close(self):
        self.conn.close()


def check_clean(resp: dict, what: str) -> None:
    """A response that carries an error item, a failed shard or a timeout
    is a failure here, whatever status the envelope had."""
    require("error" not in resp, f"{what}: error item {str(resp)[:400]}")
    require(resp.get("timed_out") is False, f"{what}: timed_out")
    require(resp["_shards"]["failed"] == 0,
            f"{what}: _shards.failed={resp['_shards']['failed']}")


# ----------------------------------------------------------------- oracles

class TextShard:
    """One shard's text side as plain arrays: per term (doc ords, tf),
    per doc the decoded (SmallFloat-quantized) length, and the
    collection statistics BM25 reads."""

    def __init__(self, postings: dict, dl: np.ndarray, doc_count: int,
                 sum_ttf: int, doc_ids):
        self.postings = postings
        self.dl = dl
        self.doc_count = doc_count
        self.avgdl = sum_ttf / max(doc_count, 1)
        self.doc_ids = doc_ids

    @classmethod
    def from_segment(cls, seg, field: str = "body"):
        """From the host-side arrays a fast corpus was GENERATED as
        (post_docs/post_tf/norms are the generator's output, not a
        device read-back)."""
        from opensearch_tpu.index.segment import LENGTH_TABLE
        postings = {}
        for (f, term), tm in seg.term_dict.items():
            if f != field:
                continue
            blocks = slice(tm.start_block, tm.start_block + tm.num_blocks)
            docs = seg.post_docs[blocks].ravel()
            tfs = seg.post_tf[blocks].ravel()
            valid = docs >= 0
            postings[term] = (docs[valid].astype(np.int64),
                              tfs[valid].astype(np.float64))
        st = seg.field_stats[field]
        dl = LENGTH_TABLE[seg.norms[field]].astype(np.float64)
        return cls(postings, dl, st.doc_count, st.sum_total_term_freq,
                   seg.doc_ids)

    @classmethod
    def from_docs(cls, docs: list, ids: list):
        """From raw documents — independent of the engine's write path
        (parse -> engine -> seal), which this therefore checks too."""
        from opensearch_tpu.index.segment import (LENGTH_TABLE,
                                                  smallfloat_int_to_byte4)
        # synth_docs' tokens are all "wNNNNN": 6 bytes and a separator,
        # so the whole corpus parses as one [tokens, 7] byte matrix
        blob = np.frombuffer(
            (" ".join(d["body"] for d in docs) + " ").encode("ascii"),
            dtype=np.uint8).reshape(-1, 7)
        require(bool(np.all(blob[:, 0] == ord("w"))
                     and np.all(blob[:, 6] == ord(" "))),
                "synth_docs token format changed")
        term_ids = (blob[:, 1:6].astype(np.int64) - ord("0")) \
            @ 10 ** np.arange(4, -1, -1)
        lens = np.array([(len(d["body"]) + 1) // 7 for d in docs],
                        dtype=np.int64)
        flat_doc = np.repeat(np.arange(len(docs), dtype=np.int64), lens)
        upair, tf = np.unique(term_ids * len(docs) + flat_doc,
                              return_counts=True)
        t_of, d_of = upair // len(docs), upair % len(docs)
        vocab = np.unique(t_of)
        starts = np.searchsorted(t_of, vocab)
        ends = np.append(starts[1:], len(t_of))
        postings = {
            f"w{t:05d}": (d_of[a:b], tf[a:b].astype(np.float64))
            for t, a, b in zip(vocab.tolist(), starts.tolist(),
                               ends.tolist())}
        norm = np.array([smallfloat_int_to_byte4(int(n)) for n in lens],
                        dtype=np.uint8)
        dl = LENGTH_TABLE[norm].astype(np.float64)
        return cls(postings, dl, len(docs), int(lens.sum()), ids)

    def match(self, text: str):
        """`match` (operator OR) -> (ords, scores) of every matching doc."""
        docs_l, s_l = [], []
        for term, mult in Counter(text.split()).items():
            p = self.postings.get(term)
            if p is None:
                continue
            docs, tf = p
            df = len(docs)
            idf = math.log(1.0 + (self.doc_count - df + 0.5) / (df + 0.5))
            norm = K1 * (1.0 - B + B * self.dl[docs] / self.avgdl)
            docs_l.append(docs)
            s_l.append(mult * idf * tf * (K1 + 1.0) / (tf + norm))
        if not docs_l:
            return np.zeros(0, np.int64), np.zeros(0)
        uniq, inv = np.unique(np.concatenate(docs_l), return_inverse=True)
        return uniq, np.bincount(inv, weights=np.concatenate(s_l))


class Ranking:
    """An oracle's full ranking: every matching doc of every shard, best
    first — key desc, then shard asc, then doc ord asc, the engine's
    documented order (SearchPhaseController.mergeTopDocs)."""

    def __init__(self, per_shard):
        """per_shard: [(doc_ids, ords, keys)], one entry a shard."""
        self.doc_ids = [ids for ids, _, _ in per_shard]
        self.shard = np.concatenate([np.full(len(o), i, dtype=np.int64)
                                     for i, (_, o, _) in
                                     enumerate(per_shard)])
        self.ords = np.concatenate([o for _, o, _ in per_shard])
        self.keys = np.concatenate([np.asarray(k, dtype=np.float64)
                                    for _, _, k in per_shard])
        self.order = np.lexsort((self.ords, self.shard, -self.keys))
        self._table = None

    def __len__(self):
        return len(self.keys)

    def _id(self, j):
        return self.doc_ids[self.shard[j]][self.ords[j]]

    def top(self, k: int):
        idx = self.order[:k]
        return [self._id(j) for j in idx], self.keys[idx]

    def key_of(self, doc_id):
        if self._table is None:
            self._table = {self._id(j): self.keys[j]
                           for j in range(len(self.keys))}
        return self._table.get(doc_id)


def check_page(what: str, hits: list, ranking: Ranking, k: int,
               exact_order: bool = False, rtol: float = RTOL) -> None:
    """Hit ids equal the oracle's, except permutations among hits whose
    oracle scores are within `rtol` (`exact_order` forbids even those:
    the pages made only of ties, where the order IS the tie-break);
    scores within `rtol`."""
    want_ids, want_scores = ranking.top(k)
    require(len(hits) == len(want_ids),
            f"{what}: {len(hits)} hits, oracle has {len(want_ids)}")
    got_ids = [h["_id"] for h in hits]
    require(len(set(got_ids)) == len(got_ids), f"{what}: duplicate hits")
    for i, (h, wid, ws) in enumerate(zip(hits, want_ids, want_scores)):
        gs = h["_score"]
        require(gs is not None and math.isfinite(gs),
                f"{what}: hit {i} score {gs}")
        require(math.isclose(gs, ws, rel_tol=rtol, abs_tol=1e-12),
                f"{what}: hit {i} score {gs!r} != oracle {ws!r}")
        if h["_id"] == wid:
            continue
        require(not exact_order,
                f"{what}: hit {i} is {h['_id']}, the tie-break order "
                f"wants {wid} (got {got_ids}, want {want_ids})")
        s = ranking.key_of(h["_id"])
        require(s is not None and math.isclose(s, ws, rel_tol=rtol),
                f"{what}: hit {i} is {h['_id']} (oracle score {s}), "
                f"oracle wants {wid} ({ws})")


def check_total(what: str, resp: dict, want: int) -> None:
    t = resp["hits"]["total"]
    require(t == {"value": int(want), "relation": "eq"},
            f"{what}: total {t}, oracle {want}")


def terms_buckets(tag_ords, tags: list, size: int):
    """terms agg oracle: (key, count) by count desc then key asc."""
    counts = np.bincount(tag_ords, minlength=len(tags))
    rows = sorted(((tags[i], int(c)) for i, c in enumerate(counts) if c),
                  key=lambda r: (-r[1], r[0]))
    return rows[:size], sum(c for _, c in rows[size:])


def check_terms(what: str, agg: dict, tag_ords, tags, size: int) -> None:
    want, other = terms_buckets(tag_ords, tags, size)
    got = [(b["key"], b["doc_count"]) for b in agg["buckets"]]
    require(got == want, f"{what}: buckets {got} != oracle {want}")
    require(agg["sum_other_doc_count"] == other,
            f"{what}: sum_other_doc_count {agg['sum_other_doc_count']} "
            f"!= {other}")


def check_metric(what: str, agg: dict, values, avg: bool) -> None:
    """sum / avg of integer values. The engine accumulates metric sums in
    f32: exact while the sum is (< 2^24), to RTOL beyond (3.5e-6 seen on
    the CPU backend at 75K docs)."""
    require(len(values) > 0, f"{what}: weak request, no docs")
    total = float(np.sum(values))
    want = total / len(values) if avg else total
    got = agg["value"]
    require(got == want if total < 2 ** 24
            else math.isclose(got, want, rel_tol=RTOL),
            f"{what}: {got!r} != oracle {want!r} over {len(values)} docs")


def check_date_histogram(what: str, agg: dict, ts) -> None:
    keys, counts = np.unique((ts // WEEK_MS) * WEEK_MS, return_counts=True)
    want = dict(zip(keys.tolist(), counts.tolist()))
    if len(keys):       # min_doc_count=0: empty weeks inside the range
        for k in range(int(keys[0]), int(keys[-1]) + 1, WEEK_MS):
            want.setdefault(k, 0)
    got = {b["key"]: b["doc_count"] for b in agg["buckets"]}
    require(got == want, f"{what}: buckets {got} != oracle {want}")


# ------------------------------------------------------------------- smoke

class Smoke:
    def __init__(self, args, jax):
        self.args = args
        self.jax = jax
        self.seed = args.seed
        self.phases = []            # [{"phase", "ok", "wall_s", ...}]
        self.extra = {}
        self.resident = {}          # index -> corpus bytes on the device
        self.reduced = []
        self.node = self.client = self.server = None

    # -------------------------------------------------------------- phases

    def phase(self, name: str, fn) -> bool:
        """Run one phase; a failure is recorded, printed, and makes the
        whole run exit non-zero — later phases still run so one chip call
        reports everything that is broken."""
        t0 = time.monotonic()
        rec = {"phase": name, "ok": False}
        self.phases.append(rec)
        try:
            info = fn()
            rec["ok"] = True
            if info:
                rec.update(info)
        except Exception as e:   # recorded; the run still exits non-zero
            rec["error"] = f"{type(e).__name__}: {e}"[:600]
            log(f"PHASE FAILED [{name}]: {rec['error']}")
            traceback.print_exc(file=sys.stderr)
        rec["wall_s"] = round(time.monotonic() - t0, 3)
        log(f"phase {name}: {'ok' if rec['ok'] else 'FAILED'} "
            f"({rec['wall_s']}s)")
        return rec["ok"]

    def corpus_bytes(self) -> int:
        cls = self.client.node_stats()["telemetry"]["device_memory"][
            "classes"]
        return cls.get("corpus_columns", {}).get("live_bytes", 0)

    def install(self, index: str, settings: dict, mapping: dict,
                segments: list) -> None:
        """A pre-built sealed segment per shard, installed the way
        __graft_entry__ does (engine.install_segments + _sync_reader =
        the upload)."""
        before = self.corpus_bytes()
        self.client.call("PUT", f"/{index}", {"settings": settings,
                                              "mappings": mapping})
        svc = self.node.indices.get(index)
        require(len(svc.shards) == len(segments),
                f"{index}: {len(svc.shards)} shards, "
                f"{len(segments)} segments")
        for shard, seg in zip(svc.shards, segments):
            shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                          local_checkpoint=seg.num_docs)
            shard._sync_reader()
        self.resident[index] = self.corpus_bytes() - before

    # -------------------------------------------------------------- start

    def start(self):
        from opensearch_tpu.launcher import start_node
        # REST admission's default lets 100 searches in at once and
        # answers the rest of an _msearch with per-item 429s; a
        # deployment that sends 1,024-item batches raises it
        self.node, self.server = start_node({
            "http.port": 0, "node.name": "chip-smoke",
            "search.backpressure.max_concurrent": max(
                100, self.args.msearch)})
        self.client = Client(self.server.port)
        info = next(iter(self.client.call("GET", "/_nodes")["nodes"]
                         .values()))["tpu"]
        dev = self.jax.devices()[0]
        want = {"platform": dev.platform, "device_kind": dev.device_kind,
                "count": len(self.jax.devices())}
        require(info == want, f"GET /_nodes reports {info}, jax {want}")
        require(self.args.dry_run or info["platform"] == "tpu",
                f"GET /_nodes does not report the TPU: {info}")
        return {"nodes_tpu": info}

    # ------------------------------------------------------------- served

    def served_load(self):
        from opensearch_tpu.utils.demo import DEMO_MAPPING, synth_docs
        n = self.args.served_docs
        docs = synth_docs(n, vocab_size=20000, avg_len=60, seed=self.seed)
        ids = [f"d{i}" for i in range(n)]
        before = self.corpus_bytes()
        self.client.call("PUT", "/served", {
            "settings": {"number_of_shards": 1}, "mappings": DEMO_MAPPING})
        t0 = time.monotonic()
        for lo in range(0, n, 5000):
            lines = []
            for i in range(lo, min(lo + 5000, n)):
                lines.append({"index": {"_id": ids[i]}})
                lines.append(docs[i])
            out = self.client.call("POST", "/served/_bulk", lines,
                                   ndjson=True)
            require(out["errors"] is False, "_bulk reported errors")
        self.client.call("POST", "/served/_refresh")
        bulk_s = time.monotonic() - t0
        count = self.client.call("GET", "/served/_count")["count"]
        require(count == n, f"served holds {count} docs, sent {n}")
        self.resident["served"] = self.corpus_bytes() - before
        tags = sorted({d["tag"] for d in docs})
        self.served = {
            "text": TextShard.from_docs(docs, ids), "ids": ids,
            "tags": tags,
            "tag": np.array([tags.index(d["tag"]) for d in docs]),
            "views": np.array([d["views"] for d in docs], dtype=np.int64),
            "ts": np.array([d["ts"] for d in docs], dtype=np.int64)}
        return {"docs": n, "bulk_s": round(bulk_s, 2)}

    def _match_page(self, what, index, shard: TextShard, text, size=10):
        resp = self.client.search(index, {
            "query": {"match": {"body": text}}, "size": size,
            "_source": False})
        self._check_match(what, resp, shard, text, size)

    def _check_match(self, what, resp, shard, text, size):
        ords, scores = shard.match(text)
        check_total(what, resp, len(ords))
        check_page(what, resp["hits"]["hits"],
                   Ranking([(shard.doc_ids, ords, scores)]), size)

    def served_match(self):
        from opensearch_tpu.utils.demo import query_terms
        qs = query_terms(4, 20000, seed=self.seed + 1, terms_per_query=2)
        for q in qs:
            self._match_page(f"served match [{q}]", "served",
                             self.served["text"], q)
        return {"queries": len(qs)}

    def served_msearch(self):
        """The request that takes the automatic multi-wave pipeline off
        the CPU backend; the transfer ledger (the node's own
        instrument) says how many waves ran."""
        from opensearch_tpu.utils.demo import query_terms
        n = self.args.msearch
        qs = query_terms(n, 20000, seed=7, terms_per_query=2)
        bodies = [{"query": {"match": {"body": q}}, "size": 10,
                   "_source": False} for q in qs]
        self.client.call("POST", "/_telemetry/transfers/_enable")
        self.client.call("POST", "/_telemetry/transfers/_clear")
        responses = self.client.msearch("served", bodies)
        ledger = self.client.call("GET", "/_telemetry/transfers")[
            "transfers"]
        self.client.call("POST", "/_telemetry/transfers/_disable")
        for q, r in zip(qs, responses):
            self._check_match(f"served msearch [{q}]", r,
                              self.served["text"], q, 10)
        waves = ledger["waves"]
        require(waves >= 1, f"the ledger saw {waves} waves")
        if not self.args.dry_run and n >= 256:
            require(waves > 1, f"msearch of {n} ran {waves} wave off-CPU")
        pipe = ledger.get("pipeline", {})
        return {"items": n, "waves": waves,
                "max_inflight_waves": pipe.get("max_inflight_waves"),
                "device_round_trips": ledger["device_get"]["calls"]}

    def served_terms_agg(self):
        """BASELINE config 2's shape."""
        s = self.served
        text, lo = "w00400 w00460", 2500
        resp = self.client.search("served", {
            "size": 5, "_source": False,
            "query": {"bool": {
                "must": [{"match": {"body": text}}],
                "filter": [{"range": {"views": {"gte": lo}}}]}},
            "aggs": {"by_tag": {"terms": {"field": "tag", "size": 20}},
                     "avg_views": {"avg": {"field": "views"}},
                     "sum_views": {"sum": {"field": "views"}}}})
        ords, scores = s["text"].match(text)
        keep = s["views"][ords] >= lo
        ords, scores = ords[keep], scores[keep]
        require(len(ords) > 20, f"weak request: {len(ords)} matches")
        check_total("terms_agg", resp, len(ords))
        check_page("terms_agg page", resp["hits"]["hits"],
                   Ranking([(s["ids"], ords, scores)]), 5)
        aggs = resp["aggregations"]
        check_terms("terms(tag)", aggs["by_tag"], s["tag"][ords],
                    s["tags"], 20)
        check_metric("avg(views)", aggs["avg_views"], s["views"][ords],
                     avg=True)
        check_metric("sum(views)", aggs["sum_views"], s["views"][ords],
                     avg=False)
        # the same aggregations over wide filters, B=1 (sums far past
        # 2^24) and as ONE _msearch of 8 (per-bucket sums below it, so
        # held exact): a batch reduces through a real [B,n]x[n,bins]
        # matmul — the shape a TPU rounds to bf16 at default precision —
        # where a single request's matvec is exact either way
        def body(gte):
            return {"size": 0,
                    "query": {"range": {"views": {"gte": gte}}},
                    "aggs": {"by_tag": {"terms": {"field": "tag",
                                                  "size": 20},
                                        "aggs": {"avg_v": {"avg": {
                                            "field": "views"}}}},
                             "avg_views": {"avg": {"field": "views"}}}}

        bounds = [9000 + 90 * i for i in range(8)]
        responses = [self.client.search("served", body(lo))] \
            + self.client.msearch("served", [body(g) for g in bounds])
        for gte, resp in zip([lo] + bounds, responses):
            keep = s["views"] >= gte
            check_total(f"terms_agg gte {gte}", resp, int(keep.sum()))
            aggs = resp["aggregations"]
            check_terms(f"terms(tag) gte {gte}", aggs["by_tag"],
                        s["tag"][keep], s["tags"], 20)
            check_metric(f"avg(views) gte {gte}", aggs["avg_views"],
                         s["views"][keep], avg=True)
            for b in aggs["by_tag"]["buckets"]:
                in_b = keep & (s["tag"] == s["tags"].index(b["key"]))
                check_metric(f"avg(views) gte {gte} in {b['key']}",
                             b["avg_v"], s["views"][in_b], avg=True)
        return {"matched": len(ords), "batched_agg_bodies": len(bounds)}

    def served_date_histogram(self):
        """BASELINE config 3's shape."""
        s = self.served
        hi = TS_BASE + 60 * 86400_000 + 12345
        resp = self.client.search("served", {
            "size": 0,
            "query": {"range": {"ts": {"lt": hi}}},
            "aggs": {"per_week": {"date_histogram": {
                "field": "ts", "fixed_interval": "7d"}},
                "uniq": {"cardinality": {"field": "tag"}}}})
        keep = s["ts"] < hi
        check_total("date_histogram", resp, int(keep.sum()))
        aggs = resp["aggregations"]
        check_date_histogram("date_histogram(ts,7d)", aggs["per_week"],
                             s["ts"][keep])
        want = len(np.unique(s["tag"][keep]))
        require(aggs["uniq"]["value"] == want,
                f"cardinality(tag) {aggs['uniq']['value']} != {want}")
        return {"matched": int(keep.sum())}

    def served_sorted_and_ties(self):
        s = self.served
        n = len(s["ids"])
        resp = self.client.search("served", {
            "size": 10, "_source": False,
            "query": {"match_all": {}},
            "sort": [{"views": {"order": "desc"}}],
            "docvalue_fields": ["views", "tag"]})
        check_total("sorted", resp, n)
        order = np.lexsort((np.arange(n), -s["views"]))[:10]
        got = [(h["_id"], h["sort"], h["fields"]["views"],
                h["fields"]["tag"]) for h in resp["hits"]["hits"]]
        want = [(s["ids"][o], [int(s["views"][o])], [int(s["views"][o])],
                 [s["tags"][s["tag"][o]]]) for o in order]
        require(got == want, f"sorted page {got} != oracle {want}")
        # constant score: every hit ties, the order IS the tie-break
        lo = 5000
        resp = self.client.search("served", {
            "size": 10, "_source": False,
            "query": {"bool": {"filter": [
                {"range": {"views": {"gte": lo}}}]}}})
        keep = np.nonzero(s["views"] >= lo)[0]
        check_total("constant-score", resp, len(keep))
        check_page("constant-score page", resp["hits"]["hits"],
                   Ranking([(s["ids"], keep, np.zeros(len(keep)))]), 10,
                   exact_order=True)
        return {"ties_on_page": 10}

    # -------------------------------------------------------------- scale

    def scale_load(self):
        from opensearch_tpu.utils.demo import (DEMO_MAPPING,
                                               build_shards_fast)
        n = self.args.scale_docs
        t0 = time.monotonic()
        _, segments, terms = build_shards_fast(n, n_shards=1,
                                               seed=self.seed, **FAST)
        build_s = time.monotonic() - t0
        self.install("scale", {"number_of_shards": 1}, DEMO_MAPPING,
                     segments)
        self.scale = {"text": TextShard.from_segment(segments[0]),
                      "terms": terms, "seg": segments[0]}
        return {"docs": n, "build_s": round(build_s, 2)}

    def scale_match(self):
        """One query on each side of the candidate/dense decision
        (executor.CANDIDATE_MAX_LANES); the always-on scan counters say
        which kernel served it."""
        from opensearch_tpu.search.executor import CANDIDATE_MAX_LANES
        seg, terms = self.scale["seg"], self.scale["terms"]

        def lanes(q):
            return 128 * sum(seg.get_term("body", t).num_blocks
                             for t in q.split())

        def kernels():
            shards = self.client.node_stats()["telemetry"]["scan"][
                "shards"]
            return dict(shards.get("scale[0]", {}).get("kernels", {}))

        small = f"{terms[-1]} {terms[-2]}"      # the band's rarest terms
        big = f"{terms[0]} {terms[1]}"          # its most frequent
        out = {"candidate_max_lanes": CANDIDATE_MAX_LANES}
        for kind, q in (("candidate", small), ("dense", big)):
            k0 = kernels()
            self._match_page(f"scale match [{q}]", "scale",
                             self.scale["text"], q)
            k1 = kernels()
            ran = [k for k in k1 if k1[k] != k0.get(k, 0)]
            fits = lanes(q) <= CANDIDATE_MAX_LANES
            out[f"{kind}_query"] = {"lanes": lanes(q), "kernel": ran}
            require(ran == ["candidate" if fits else "dense"],
                    f"[{q}] with {lanes(q)} lanes ran {ran}")
            if not self.args.dry_run:
                require(fits == (kind == "candidate"),
                        f"[{q}] has {lanes(q)} lanes: not a {kind} query "
                        f"at this corpus size")
        # E.4: what one dense query holds on the device, and what bounds
        # a batch of them — nothing in the envelope does
        from opensearch_tpu.index.segment import pad_bucket
        d_pad = pad_bucket(seg.num_docs)
        out["dense_kernel"] = {
            "d_pad": d_pad, "score_bytes_per_query": 4 * d_pad,
            "batch_bounded_by": None}
        return out

    # ------------------------------------------------------------ vectors

    def vectors_load(self):
        """A clustered 128-d generator (SIFT-shaped), as one sealed
        segment."""
        from opensearch_tpu.index.segment import Segment, VectorColumn
        n, dims = self.args.vector_docs, 128
        rng = np.random.RandomState(self.seed + 11)
        centers = rng.randn(256, dims).astype(np.float32) * 4
        vectors = centers[rng.randint(0, 256, size=n)] \
            + rng.randn(n, dims).astype(np.float32)
        ids = [f"v{i}" for i in range(n)]
        seg = Segment("v0", n, ids, [None] * n, {},
                      np.full((1, 128), -1, dtype=np.int32),
                      np.zeros((1, 128), dtype=np.float32), {}, {}, {}, {},
                      {"vec": VectorColumn(vectors, np.ones(n, dtype=bool))})
        self.install("vectors", {"number_of_shards": 1}, {"properties": {
            "vec": {"type": "knn_vector", "dimension": dims,
                    "method": {"space_type": "l2"}}}}, [seg])
        self.vectors = {"x": vectors.astype(np.float64), "ids": ids,
                        "centers": centers, "rng": rng}
        return {"docs": n, "dims": dims}

    def _knn_check(self, what, resp, q):
        x = self.vectors["x"]
        d2 = ((x - q.astype(np.float64)) ** 2).sum(axis=1)
        check_page(what, resp["hits"]["hits"],
                   Ranking([(self.vectors["ids"], np.arange(len(x)),
                             1.0 / (1.0 + d2))]), 10, rtol=KNN_RTOL)

    def vectors_knn(self):
        v = self.vectors
        n_q = 2 + self.args.knn_msearch
        qs = (v["centers"][v["rng"].randint(0, 256, size=n_q)]
              + v["rng"].randn(n_q, 128).astype(np.float32))
        bodies = [{"query": {"knn": {"vec": {"vector": q.tolist(),
                                             "k": 10}}},
                   "size": 10, "_source": False} for q in qs]
        for q, b in zip(qs[:2], bodies[:2]):
            self._knn_check("knn B=1", self.client.search("vectors", b), q)
        responses = self.client.msearch("vectors", bodies[2:])
        for i, (q, r) in enumerate(zip(qs[2:], responses)):
            self._knn_check(f"knn msearch[{i}]", r, q)
        return {"b1": 2, "msearch": len(responses)}

    # ------------------------------------------------------------ sharded

    def sharded_load(self):
        from opensearch_tpu.utils.demo import (DEMO_MAPPING,
                                               build_shards_fast)
        n, n_shards = self.args.sharded_docs, 8
        _, segments, terms = build_shards_fast(
            n, n_shards=n_shards, seed=self.seed + 3, columns=True, **FAST)
        self.install("sharded", {"number_of_shards": n_shards},
                     DEMO_MAPPING, segments)
        shards = []
        for seg in segments:
            col = seg.ordinal_dv["tag"]
            shards.append({
                "text": TextShard.from_segment(seg), "ids": seg.doc_ids,
                "tags": col.dictionary, "tag": col.ords,
                "views": seg.numeric_dv["views"].values.astype(np.int64),
                "ts": seg.numeric_dv["ts"].values.astype(np.int64)})
        self.sharded = {"shards": shards, "terms": terms}
        return {"docs": n, "shards": n_shards}

    def sharded_bodies(self):
        """__graft_entry__'s five bodies (the SPMD program), each page
        against the oracle. Shard-local statistics: every shard scores
        with its own idf and avgdl, as the engine's default
        query_then_fetch does."""
        sh, terms = self.sharded["shards"], self.sharded["terms"]
        spmd0 = self.client.node_stats()["telemetry"]["metrics"][
            "counters"].get("search.spmd_queries", 0)
        out = {}

        # 1. bool: must match + should term, terms(tag) + avg(views)
        text, tag = f"{terms[5]} {terms[9]}", "cat1"
        resp = self.client.search("sharded", {
            "size": 8, "_source": False,
            "query": {"bool": {"must": [{"match": {"body": text}}],
                               "should": [{"term": {"tag": tag}}]}},
            "aggs": {"by_tag": {"terms": {"field": "tag"}},
                     "v": {"avg": {"field": "views"}}}})
        per, m_tags, m_views = [], [], []
        for s in sh:
            ords, scores = s["text"].match(text)
            t_i = s["tags"].index(tag)
            df = int((s["tag"] == t_i).sum())
            n = len(s["ids"])
            idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
            per.append((s["ids"], ords,
                        scores + idf * (s["tag"][ords] == t_i)))
            m_tags.append(s["tag"][ords])
            m_views.append(s["views"][ords])
        ranking = Ranking(per)
        check_total("sharded bool", resp, len(ranking))
        check_page("sharded bool page", resp["hits"]["hits"], ranking, 8)
        check_terms("sharded terms(tag)", resp["aggregations"]["by_tag"],
                    np.concatenate(m_tags), sh[0]["tags"], 10)
        check_metric("sharded avg(views)", resp["aggregations"]["v"],
                     np.concatenate(m_views), avg=True)
        out["bool_total"] = len(ranking)

        # 2. plain match
        text = f"{terms[20]} {terms[41]}"
        resp = self.client.search("sharded", {
            "size": 12, "_source": False,
            "query": {"match": {"body": text}}})
        ranking = Ranking([(s["ids"], *s["text"].match(text)) for s in sh])
        check_total("sharded match", resp, len(ranking))
        check_page("sharded match page", resp["hits"]["hits"], ranking, 12)

        # 3. size:0 range + date_histogram
        resp = self.client.search("sharded", {
            "size": 0,
            "query": {"range": {"views": {"gte": 5000}}},
            "aggs": {"per_week": {"date_histogram": {
                "field": "ts", "fixed_interval": "7d"}}}})
        ts = np.concatenate([s["ts"][s["views"] >= 5000] for s in sh])
        check_total("sharded range", resp, len(ts))
        check_date_histogram("sharded date_histogram",
                             resp["aggregations"]["per_week"], ts)

        # 4. match_all sorted by views desc: at this size the whole page
        # shares the top value, so its order is the cross-shard tie-break
        resp = self.client.search("sharded", {
            "size": 10, "_source": False,
            "query": {"match_all": {}},
            "sort": [{"views": {"order": "desc"}}]})
        ranking = Ranking([(s["ids"], np.arange(len(s["ids"])), s["views"])
                           for s in sh])
        check_total("sharded sorted", resp, len(ranking))
        got = [(h["_id"], h["sort"]) for h in resp["hits"]["hits"]]
        want = [(i, [int(k)]) for i, k in zip(*ranking.top(10))]
        require(got == want, f"sharded sorted page {got} != {want}")

        # 5. constant score: all ties, order = (shard, doc)
        resp = self.client.search("sharded", {
            "size": 10, "_source": False,
            "query": {"bool": {"filter": [
                {"range": {"views": {"gte": 5000}}}]}}})
        per = []
        for s in sh:
            o = np.nonzero(s["views"] >= 5000)[0]
            per.append((s["ids"], o, np.zeros(len(o))))
        ranking = Ranking(per)
        check_total("sharded constant-score", resp, len(ranking))
        check_page("sharded constant-score page", resp["hits"]["hits"],
                   ranking, 10, exact_order=True)

        stats = self.client.node_stats()["telemetry"]
        n_spmd = stats["metrics"]["counters"].get(
            "search.spmd_queries", 0) - spmd0
        require(n_spmd == 5, f"{n_spmd} of 5 bodies took the SPMD program")
        image = stats["device_memory"]["classes"].get("spmd_shard_sets", {})
        out["spmd_image"] = image
        n_dev = len(self.jax.devices())
        mesh = min(8, n_dev)
        on = {d: b for d, b in image.get("by_device", {}).items() if b > 0}
        require(len(on) == mesh,
                f"spmd_shard_sets image on {len(on)} devices, mesh has "
                f"{mesh}: {image}")
        if n_dev >= 4 and not self.args.dry_run:
            # not everything on the first chip: what jax itself reports
            # in use on each device, beside the node's own accounting
            # (the CPU backend's virtual devices report no memory stats)
            in_use = [d.memory_stats()["bytes_in_use"]
                      for d in self.jax.devices()[:mesh]]
            share = image["live_bytes"] // mesh
            require(all(b >= share for b in in_use),
                    f"per-device bytes_in_use {in_use} below the image's "
                    f"per-device share {share}")
            out["bytes_in_use_per_device"] = in_use
        return out

    # -------------------------------------------------------------- checks

    def norm_decode(self):
        """The kernels build a posting's length from its norm byte by
        assembling an f32 from its fields (ops/bm25.py posting_lengths).
        Whether that equals Lucene's LENGTH_TABLE for all 256 bytes, bit
        for bit, is a property of the device: checked here, on it."""
        import jax.numpy as jnp
        from opensearch_tpu.index.segment import LENGTH_TABLE
        from opensearch_tpu.ops.bm25 import posting_lengths
        seg = {"post_norm": jnp.arange(256, dtype=jnp.uint8).reshape(2, 128)}
        got = np.asarray(self.jax.jit(posting_lengths)(
            seg, jnp.arange(2, dtype=jnp.int32))).ravel()
        wrong = np.flatnonzero(got.view(np.int32)
                               != LENGTH_TABLE.view(np.int32))
        require(wrong.size == 0,
                f"norm bytes {wrong[:8].tolist()} decode to "
                f"{got[wrong[:8]].tolist()}, LENGTH_TABLE has "
                f"{LENGTH_TABLE[wrong[:8]].tolist()}")
        return {"bytes_checked": 256}

    def node_checks(self):
        """What the product's fault tolerance would otherwise absorb."""
        n = self.client.node_stats()
        counters = n["telemetry"]["metrics"]["counters"]
        require(counters.get("search.retries", 0) == 0,
                f"search.retries = {counters.get('search.retries')}")
        require(n["search_warmup"]["warmup_errors"] == 0,
                f"warmup_errors = {n['search_warmup']['warmup_errors']}")
        kern = n["telemetry"]["kernels"]
        fams = kern["families"]
        for fam in ("bm25_candidate", "bm25_dense", "agg_env", "knn"):
            require(fams.get(fam, {}).get("compiles", 0) > 0,
                    f"kernel family {fam} never compiled here: "
                    f"{sorted(fams)}")
        hbm = n["telemetry"]["device_memory"]["hbm"]
        if not self.args.dry_run:
            require(hbm and "peak_bytes_in_use" in hbm
                    and "bytes_in_use" in hbm,
                    f"_nodes/stats reports no HBM stats: {hbm}")
            require(kern["peak_flops"] and kern["peak_bw"],
                    f"no roofline peaks for this device_kind: {kern}")
        self.extra.update({
            "compile_cache_dir": n["search_warmup"]["compile_cache_dir"],
            "native_tokenizer": n["analysis"]["native_tokenizer"],
            "hbm": hbm,
            "kernel_compile_s": {f: round(r["compile_ms"] / 1000, 2)
                                 for f, r in fams.items()},
            "kernel_executables": kern["census"]["entries"]})
        return {"families": sorted(fams)}

    def stop(self):
        if self.client is not None:
            self.client.close()
        if self.server is not None:
            self.server.close()


# -------------------------------------------------------------------- main

# flag -> (default, --dry-run default)
SIZES = {"served_docs": (100_000, 2000),
         "scale_docs": (MSMARCO_PASSAGES, 40_000),
         "vector_docs": (100_000, 2000),
         "sharded_docs": (1_000_000, 16_000),
         "msearch": (1024, 64),
         "knn_msearch": (64, 8)}


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--dry-run", action="store_true",
                   help="tiny default sizes on the CPU backend; needs "
                        "JAX_PLATFORMS=cpu set by the caller")
    for flag, (full, _) in SIZES.items():
        p.add_argument("--" + flag.replace("_", "-"), type=int,
                       default=None, help=f"default {full:,}")
    args = p.parse_args(argv)
    for flag, (full, tiny) in SIZES.items():
        if getattr(args, flag) is None:
            setattr(args, flag, tiny if args.dry_run else full)
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if args.dry_run:
        if os.environ.get("JAX_PLATFORMS") != "cpu" or dev.platform != "cpu":
            sys.stderr.write("chip_smoke: --dry-run is the CPU debug run; "
                             "set JAX_PLATFORMS=cpu yourself\n")
            return 2
    elif dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: jax found no TPU (platform={dev.platform}, "
            f"device_kind={dev.device_kind}); refusing to run. The CPU "
            f"debug run is JAX_PLATFORMS=cpu python3 chip_smoke.py "
            f"--dry-run\n")
        return 1

    import opensearch_tpu  # noqa: F401 -- alone in a directory: fail here

    # every backend compile (or cache retrieval) of the process, from
    # jax's own monitoring events
    compile_events = {"n": 0, "secs": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_events["n"] += 1
            compile_events["secs"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compile_events["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {device}; sizes served={args.served_docs} "
        f"scale={args.scale_docs} vectors={args.vector_docs} "
        f"sharded={args.sharded_docs}")
    smoke = Smoke(args, jax)
    try:
        if smoke.phase("start_node", smoke.start):
            for name in ("served_load", "served_match", "served_msearch",
                         "served_terms_agg", "served_date_histogram",
                         "served_sorted_and_ties", "scale_load",
                         "scale_match", "vectors_load", "vectors_knn",
                         "sharded_load", "sharded_bodies", "norm_decode",
                         "node_checks"):
                smoke.phase(name, getattr(smoke, name))
    finally:
        smoke.stop()

    import jaxlib
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        from importlib.metadata import version
        versions["libtpu"] = version("libtpu")
    except Exception:   # not installed where there is no TPU runtime
        versions["libtpu"] = None
    reduced = [
        "scale: vocabulary materialised to 64 mid-band terms, no "
        "doc-values columns",
        "vectors: 100,000 x 128-d (SIFT-1M has 1,000,000)",
        "sharded: 64 mid-band terms materialised"]
    for flag, (full, _) in SIZES.items():
        if getattr(args, flag) != full:
            reduced.append(f"{flag}: {getattr(args, flag)} "
                           f"(default {full})")
    failed = [p["phase"] for p in smoke.phases if not p["ok"]]
    report = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": device["count"],
        "dry_run": args.dry_run,
        "versions": versions,
        "seed": args.seed,
        "wall_s": round(time.monotonic() - T0, 1),
        "compile": {"executables": compile_events["n"],
                    "seconds": round(compile_events["secs"], 2),
                    "persistent_cache_hits": compile_events["cache_hits"]},
        "peak_hbm_bytes": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()],
        "resident_corpus_bytes": smoke.resident,
        "reduced": reduced,
        **smoke.extra,
        "phases": smoke.phases,
        "failed": failed,
    }
    # two lines on standard output: the report, then -- last, and with
    # exactly these keys -- the verdict the driver parses
    print(json.dumps(report))
    print(json.dumps({"ok": not failed, "device": device}))
    sys.stdout.flush()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
