"""A text clause's per-lane vectors are written a term at a time (ISSUE 38).

`Compiler._text_clause` fills `ids`, `w` (and `tid` under block-max) with
one array store a term's run of posting blocks. These tests hold those
vectors to the plain double loop (a term, a block) that built them before,
array for array, and a `match` / `multi_match` served through them to the
numpy BM25 oracle (`reference_impl.RefField`), so a lane written into
another term's run fails here and not only in the benchmark.
"""

import random

import numpy as np
import pytest

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import BLOCK, SegmentBuilder, pad_bucket
from opensearch_tpu.ops import bm25 as _bm25
from opensearch_tpu.ops import device_segment as devseg
from opensearch_tpu.search.compile import Compiler, ShardStats
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.telemetry import TELEMETRY

from reference_impl import RefField

N_DOCS = 1024
# term -> the docs that hold it: 8, 4, 2, 1, 1, 1 posting blocks
WIDE = {"b8": N_DOCS, "b4": 512, "b2": 256, "b1a": 100, "b1b": 60, "b1c": 30}


@pytest.fixture(scope="module")
def corpus():
    """Doc i holds `u<i>` (1,024 terms of one block each) and every term of
    WIDE whose count it is under."""
    mapper = MapperService({"properties": {"body": {"type": "text"}}})
    builder = SegmentBuilder(mapper)
    for i in range(N_DOCS):
        words = [f"u{i}"] + [t for t, n in WIDE.items() if i < n]
        builder.add(mapper.parse_document(f"d{i}", {"body": " ".join(words)}))
    seg = builder.seal()
    for term, n in WIDE.items():
        assert seg.get_term("body", term).num_blocks == -(-n // BLOCK)
    _, meta = devseg.upload_segment(seg)
    return mapper, seg, meta


def _loop_vectors(seg, field, weighted_terms):
    """The reference: one append a block of every term the segment holds."""
    ids, ws, tids = [], [], []
    for t_i, (term, w) in enumerate(weighted_terms):
        tm = seg.get_term(field, term)
        if tm is None:
            continue
        for blk_i in range(tm.start_block, tm.start_block + tm.num_blocks):
            ids.append(blk_i)
            ws.append(w)
            tids.append(t_i)
    pad = pad_bucket(max(len(ids), 1), minimum=8) - len(ids)
    return (np.asarray(ids + [-1] * pad, dtype=np.int32),
            np.asarray(ws + [0.0] * pad, dtype=np.float32),
            np.asarray(tids + [0] * pad, dtype=np.int32), len(ids))


# name -> (weighted terms, min_hits, constant, qb, score_only)
CASES = {
    "one-term-one-block": ([("b1a", 1.5)], 1, False, 8, True),
    "one-under-a-bucket": (                                 # 15 blocks
        [("b8", 0.1), ("b4", 0.7), ("b2", 1.4), ("b1a", 2.3)],
        1, False, 16, True),
    "on-a-bucket": (                                        # 16 blocks
        [("b1b", 2.8), ("b8", 0.1), ("b4", 0.7), ("b2", 1.4), ("b1a", 2.3)],
        1, False, 16, True),
    "one-over-a-bucket": (                                  # 17 blocks
        [("b1b", 2.8), ("b8", 0.1), ("b1c", 3.5), ("b4", 0.7), ("b2", 1.4),
         ("b1a", 2.3)], 1, False, 32, True),
    "eight-terms": (
        [("u7", 6.9), ("b2", 1.4), ("u8", 6.9), ("b8", 0.1), ("b1c", 3.5),
         ("u1023", 6.9), ("b4", 0.7), ("b1a", 2.3)], 2, False, 32, False),
    "first-term-missing": (
        [("nope", 0.0), ("b4", 0.7), ("b2", 1.4)], 1, False, 8, True),
    "middle-term-missing": (
        [("b4", 0.7), ("nope", 0.0), ("b2", 1.4)], 1, False, 8, True),
    "last-term-missing": (
        [("b4", 0.7), ("b2", 1.4), ("nope", 0.0)], 1, False, 8, True),
    "every-term-missing": (
        [("nope", 0.0), ("nada", 0.0)], 1, False, 8, True),
    "zero-weight": (
        [("b4", 0.7), ("b2", 0.0), ("b1a", 2.3)], 1, False, 8, False),
    "repeated-term": (
        [("b2", 1.4), ("b1a", 2.3), ("b2", 0.6)], 1, False, 8, True),
    "1024-one-block-terms": (
        [(f"u{i}", 6.9 + i / 4096) for i in range(N_DOCS)],
        1, False, 1024, True),
    "constant": ([("b4", 1.0), ("b1a", 1.0)], 1, True, 8, False),
}


@pytest.mark.parametrize("blockmax", [False, True],
                         ids=["plain", "blockmax"])
@pytest.mark.parametrize("case", list(CASES))
def test_lane_vectors_equal_the_loops(corpus, monkeypatch, case, blockmax):
    mapper, seg, meta = corpus
    weighted, min_hits, constant, qb, score_only = CASES[case]
    monkeypatch.setattr(_bm25, "BLOCKMAX", blockmax)
    plan = Compiler(mapper, ShardStats([seg]))._text_clause(
        seg, meta, "body", weighted, min_hits, 1.0, constant=constant)
    ids, ws, tids, total = _loop_vectors(seg, "body", weighted)
    assert ids.shape == (qb,)
    want = {"ids": ids, "w": ws}
    if blockmax:
        want["tid"] = tids
    for name, vector in want.items():
        got = plan.inputs[name]
        assert got.dtype == vector.dtype and got.shape == vector.shape, name
        assert np.array_equal(got, vector), name
    scalars = {"avgdl": np.float32, "b": np.float32, "k1": np.float32,
               "min_hits": np.int32, "boost": np.float32}
    if blockmax:
        scalars["bscale"] = np.float32
    assert set(plan.inputs) == set(want) | set(scalars)
    for name, dtype in scalars.items():
        assert plan.inputs[name].dtype == dtype, name
        assert plan.inputs[name].shape == (), name
    assert int(plan.inputs["min_hits"]) == min_hits
    assert plan.scan_blocks == total and type(plan.scan_blocks) is int
    assert plan.kind == "text"
    assert plan.static == (constant, len(weighted), score_only)


def _text_counters():
    counters = TELEMETRY.metrics.to_dict()["counters"]
    return sum(counters.get(f"search.text_clause.{k}", 0)
               for k in ("score_only", "counted"))


@pytest.mark.parametrize("case", ["one-over-a-bucket", "constant"])
def test_a_second_call_returns_the_memos_plan(corpus, case):
    mapper, seg, meta = corpus
    weighted, min_hits, constant, _, _ = CASES[case]
    comp = Compiler(mapper, ShardStats([seg]))
    before = _text_counters()
    first = comp._text_clause(seg, meta, "body", weighted, min_hits, 1.0,
                              constant=constant)
    assert _text_counters() == before + 1
    second = comp._text_clause(seg, meta, "body", list(weighted), min_hits,
                               1.0, constant=constant)
    assert _text_counters() == before + 2
    assert second is first
    assert all(second.inputs[k] is first.inputs[k] for k in first.inputs)


# ---------------------------------------------------------- end to end

VOCAB = [f"w{i}" for i in range(12)]
# the share of docs that hold each word: runs of 1 to 5 posting blocks
SHARE = [0.95, 0.7, 0.5, 0.33, 0.25, 0.2, 0.15, 0.1, 0.06, 0.03, 0.02, 0.01]
K = 10


@pytest.fixture(scope="module")
def two_fields():
    rng = random.Random(38)
    mapper = MapperService({"properties": {
        "title": {"type": "text"}, "body": {"type": "text"}}})
    builder = SegmentBuilder(mapper)
    docs = {"title": [], "body": []}
    for i in range(600):
        source = {}
        for field, longest in (("title", 3), ("body", 9)):
            words = [w for w, p in zip(VOCAB, SHARE) if rng.random() < p]
            words += rng.choices(words or VOCAB, k=rng.randrange(longest))
            rng.shuffle(words)
            docs[field].append(words)
            source[field] = " ".join(words)
        builder.add(mapper.parse_document(f"d{i}", source))
    seg = builder.seal()
    assert seg.get_term("body", "w1").num_blocks >= 3
    return (SearchExecutor(ShardReader(mapper, [seg])),
            {field: RefField(d) for field, d in docs.items()})


@pytest.mark.parametrize("kind,text", [
    ("match", "w1 w4 w9"), ("match", "w3 w3 w11 w0"),
    ("multi_match", "w2 w5 w10"), ("multi_match", "w8 w1")])
def test_a_served_page_equals_the_host_scorers(two_fields, kind, text):
    executor, refs = two_fields
    terms = text.split()
    if kind == "match":
        query = {"match": {"body": text}}
        expected = refs["body"].match_scores(terms)
    else:                               # best_fields: the better field's
        query = {"multi_match": {"query": text,
                                 "fields": ["title", "body"]}}
        expected = np.maximum(refs["title"].match_scores(terms),
                              refs["body"].match_scores(terms))
    order = sorted(range(len(expected)), key=lambda i: (-expected[i], i))
    want = [(f"d{i}", expected[i]) for i in order if expected[i] > 0][:K]
    for resp in (executor.multi_search([{"query": query, "size": K}])
                 ["responses"][0],
                 executor.search({"query": query, "size": K}, _direct=True)):
        got = [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
        assert [g[0] for g in got] == [w[0] for w in want]
        for (_, got_score), (_, want_score) in zip(got, want):
            assert got_score == pytest.approx(want_score, rel=1e-4)
        assert resp["hits"]["total"]["value"] == np.count_nonzero(expected)
