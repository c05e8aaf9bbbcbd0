"""One scatter-add a dense text clause, not two, where no term count is
needed (ISSUE 30).

A text clause whose matches are "any term touched the doc" (`min_hits` 1,
not constant-score) and whose every partial is provably a positive normal
float32 plans `score_only` (`Plan.static[2]`): the dense kernel
(`ops/bm25.py score_text_clause`) then builds no hit-count scatter and
reads `matches = scores > 0`. These tests hold that program to the counted
one (the same clause with the flag off) bit for bit, the planner to the
cases that must keep the count, and the two counters to `_nodes/stats`.
"""

import dataclasses
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import LENGTH_TABLE, SegmentBuilder
from opensearch_tpu.node import Node
from opensearch_tpu.ops import device_segment as devseg
from opensearch_tpu.parallel.distributed import plan_struct
from opensearch_tpu.search import compile as _compile
from opensearch_tpu.search import dsl
from opensearch_tpu.search import executor as _executor
from opensearch_tpu.search.compile import (Compiler, ShardStats,
                                           struct_fingerprint,
                                           text_clause_score_only)
from opensearch_tpu.search.executor import SearchExecutor, ShardReader
from opensearch_tpu.search.plan_eval import _eval_plan
from opensearch_tpu.telemetry import TELEMETRY

MAPPING = {"properties": {
    "body": {"type": "text"}, "tag": {"type": "keyword"},
    "comments": {"type": "nested",
                 "properties": {"text": {"type": "text"}}}}}
N_DOCS = 17000      # `alpha` fills 133 posting blocks: past the candidate
K = 10              # kernel's 16,384 lanes, so the envelope runs the dense


@pytest.fixture(scope="module")
def corpus():
    """Every doc holds `alpha`; one in a thousand carries two nested child
    rows (non-root docs whose own text field holds the same words); one in
    thirteen is deleted after the seal."""
    rng = random.Random(30)
    mapper = MapperService(MAPPING)
    builder = SegmentBuilder(mapper)
    rare = [f"r{i}" for i in range(50)]
    for i in range(N_DOCS):
        words = ["alpha"] + [w for w in ("beta", "gamma")
                             if rng.random() < 0.5]
        words += rng.choices(rare, k=rng.choice([1, 3, 9, 30]))
        doc = {"body": " ".join(words), "tag": rng.choice(["red", "blue"])}
        if i % 1000 == 7:
            doc["comments"] = [{"text": "alpha beta"}, {"text": "gamma"}]
        builder.add(mapper.parse_document(f"d{i}", doc))
    seg = builder.seal()
    for d in range(0, N_DOCS, 13):
        seg.delete(f"d{d}")
    assert not seg.root.all() and not seg.live.all()
    image, meta = devseg.upload_segment(seg)
    return mapper, seg, image, meta


def _plan(corpus, query):
    mapper, seg, _, meta = corpus
    comp = Compiler(mapper, ShardStats([seg]))
    return comp.compile(dsl.parse_query(query), seg, meta)


def _text_nodes(plan):
    found = [plan] if plan.kind == "text" else []
    for c in plan.children:
        found += _text_nodes(c)
    return found


def _counted(plan):
    """The same plan with every text clause's flag off: the parent's
    program."""
    static = plan.static[:2] + (False,) if plan.kind == "text" \
        else plan.static
    return dataclasses.replace(
        plan, static=static, children=[_counted(c) for c in plan.children])


def _device_inputs(plan):
    return [{k: jnp.asarray(v) for k, v in d.items()}
            for d in plan.flatten_inputs([])]


def _page(corpus, plan):
    """(scores, matches) of the plan's evaluation and the dense query
    phase's page, as numpy."""
    _, _, image, meta = corpus
    flat = _device_inputs(plan)
    scores, matches = jax.jit(
        lambda seg, flat: _eval_plan(plan, seg, flat, [0]))(image, flat)
    phase = jax.jit(_executor.build_query_phase(plan, meta, K, "score"))
    keys, top_scores, idx, total, _ = phase(
        image, flat, jnp.zeros(meta.d_pad, jnp.float32),
        jnp.float32(-np.inf))
    return tuple(np.asarray(x) for x in
                 (scores, matches, keys, top_scores, idx, total))


def _scatters_into_d_pad(corpus, plan):
    _, _, image, meta = corpus
    phase = jax.jit(_executor.build_query_phase(plan, meta, K, "score"))
    text = phase.lower(image, _device_inputs(plan),
                       jnp.zeros(meta.d_pad, jnp.float32),
                       jnp.float32(-np.inf)).as_text()
    # a scatter's type follows its update region: `}) : (...) -> tensor<..>`
    results = re.findall(
        r'"stablehlo\.scatter".*?\}\) : \([^)]*\) -> tensor<(\d+)x',
        text, flags=re.DOTALL)
    return results.count(str(meta.d_pad))


SAME = {
    "match": {"match": {"body": "alpha beta"}},
    "match-rare": {"match": {"body": "r3 alpha gamma r7"}},
    "match-boosted": {"match": {"body": {"query": "beta gamma",
                                         "boost": 2.5}}},
    "term-keyword": {"term": {"tag": "red"}},       # norm-less: b = 0
    "match-child-rows": {"match": {"comments.text": "alpha gamma"}},
    "bool-of-matches": {"bool": {
        "must": [{"match": {"body": "alpha"}}],
        "should": [{"match": {"body": "beta r1"}}],
        "must_not": [{"term": {"tag": "blue"}}]}},
    "nested-match": {"nested": {"path": "comments", "score_mode": "sum",
                                "query": {"match": {
                                    "comments.text": "beta gamma"}}}},
}

COUNTED = {
    "operator-and": {"match": {"body": {"query": "alpha beta",
                                        "operator": "and"}}},
    "minimum-should-match-2": {"match": {"body": {
        "query": "alpha beta gamma", "minimum_should_match": 2}}},
    "boost-0": {"match": {"body": {"query": "alpha beta", "boost": 0}}},
    # a normal float32, and so is idf x boost; the bound is not
    "boost-too-small": {"match": {"body": {"query": "alpha beta",
                                           "boost": 1e-25}}},
    "terms-constant-score": {"terms": {"tag": ["red", "blue"]}},
    "constant_score": {"constant_score": {
        "filter": {"terms": {"tag": ["red"]}}, "boost": 1.5}},
}


@pytest.mark.parametrize("case", [
    *(f"same:{name}" for name in SAME),
    *(f"counted:{name}" for name in COUNTED),
    "static-and-fingerprint", "hlo-one-scatter-not-two", "bound",
    "zero-weight"])
def test_text_clause_scatter(corpus, case):
    _, seg, _, meta = corpus
    kind, _, name = case.partition(":")

    if kind == "same":
        # the score_only program and the counted one: identical scores
        # (bitwise), matches, top-10 and total
        plan = _plan(corpus, SAME[name])
        texts = _text_nodes(plan)
        assert texts and all(t.static[2] is True for t in texts)
        got, want = _page(corpus, plan), _page(corpus, _counted(plan))
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
        scores, matches, _, top_scores, idx, total = got
        assert matches.any()
        if name == "match-child-rows":
            # only non-root rows match: none is returnable
            assert not seg.root[np.flatnonzero(matches)].any()
            assert int(total) == 0
        elif name != "nested-match":
            # deleted docs match too and the page leaves them out
            dead = np.flatnonzero(matches[:seg.num_docs]
                                  & ~seg.live[:seg.num_docs])
            assert len(dead) and int(total) < int(matches.sum())
            assert seg.live[idx].all() and seg.root[idx].all()
            assert np.array_equal(scores[idx], top_scores)
            assert (scores[matches] > 0).all()
        return

    if kind == "counted":
        texts = _text_nodes(_plan(corpus, COUNTED[name]))
        assert texts and all(t.static[2] is False for t in texts)
        return

    if case == "static-and-fingerprint":
        on = _plan(corpus, {"match": {"body": "alpha beta"}})
        off = _plan(corpus, COUNTED["operator-and"])
        assert on.static == (False, 2, True)
        assert off.static == (False, 2, False)
        # the same input shapes: the flag alone tells the programs apart
        assert on.sig()[2:] == off.sig()[2:] and on.sig() != off.sig()
        assert struct_fingerprint(plan_struct(on)) \
            != struct_fingerprint(plan_struct(off))
        assert struct_fingerprint(plan_struct(_counted(on))) \
            == struct_fingerprint(plan_struct(off))
        return

    if case == "hlo-one-scatter-not-two":
        plan = _plan(corpus, {"match": {"body": "alpha beta"}})
        assert plan.inputs["ids"].shape[-1] * 128 \
            > _executor.CANDIDATE_MAX_LANES        # the dense kernel's
        assert _scatters_into_d_pad(corpus, plan) == 1
        assert _scatters_into_d_pad(corpus, _counted(plan)) == 2
        return

    if case == "zero-weight":
        # idf is 0.0 for a term the shard lacks: the flag is what a row
        # that holds the term plans. Under a posting of this segment (the
        # statistics are older than it) a weight of 0.0 keeps the count
        mapper, _, _, _ = corpus
        comp = Compiler(mapper, ShardStats([seg]))

        def flag(weighted):
            return comp._text_clause(seg, meta, "body", weighted, 1, 1.0,
                                     constant=False).static[2]
        assert flag([("alpha", 0.4), ("no-such-term", 0.0)]) is True
        assert flag([("no-such-term", 0.0)]) is True
        assert flag([("alpha", 0.4), ("beta", 0.0)]) is False
        return

    assert case == "bound"
    dl_max = float(LENGTH_TABLE[255])
    floor = _compile.SCORE_ONLY_MIN_PARTIAL
    assert float(np.finfo(np.float32).tiny) * 2 ** 20 <= floor
    ok = dict(min_hits=1, constant=False, boost=1.0, k1=1.2, b=0.75,
              avgdl=56.0)
    assert text_clause_score_only([2.0, 0.3], **ok)
    assert text_clause_score_only([1e-9], **{**ok, "avgdl": 1.0})
    assert text_clause_score_only([0.3], **{**ok, "min_hits": 0})
    assert text_clause_score_only([1e-30], **{**ok, "b": 0.0})
    # idf 0.0: a term the shard does not hold carries no posting, so a
    # row that lacks it plans what a row that holds it plans
    assert text_clause_score_only([2.0, 0.0], **ok)
    assert text_clause_score_only([0.0, 0.0], **ok)
    assert text_clause_score_only([], **ok)
    for weights, change in [
            ([0.0, 0.0], {"boost": 0.0}), ([2.0], {"boost": -1.0}),
            ([2.0, -1.0], {}), ([float("nan")], {}), ([float("inf")], {}),
            ([1e-46], {"b": 0.0}),          # 0 as float32
            ([2.0], {"min_hits": 2}), ([2.0], {"constant": True}),
            ([2.0], {"k1": -1.0}), ([2.0], {"b": 1.5}),
            ([2.0], {"avgdl": 0.0})]:
        assert not text_clause_score_only(weights, **{**ok, **change})
    # the bound is the one the docstring states, at its edge
    w = 1.001 * floor * (1 + 1.2 * (0.25 + 0.75 * dl_max / 56.0)) / 2.2
    assert text_clause_score_only([w], **ok)
    assert not text_clause_score_only([w / 1.01], **ok)


def test_the_envelope_serves_the_same_page_from_either_program(
        corpus, monkeypatch):
    """Through `multi_search` (the B=1 envelope, the dense kernel by the
    clause's size): ids in order, scores bit for bit and `hits.total` of
    the score_only program equal the counted program's."""
    mapper, seg, _, _ = corpus
    body = {"query": {"match": {"body": "alpha beta r3"}}, "size": K}
    before = _executor._envelope_kernel
    kernels = []
    monkeypatch.setattr(
        _executor, "_envelope_kernel",
        lambda plan: kernels.append(before(plan)) or kernels[-1])
    got = SearchExecutor(ShardReader(mapper, [seg])).multi_search(
        [body])["responses"][0]
    monkeypatch.setattr(_compile, "text_clause_score_only",
                        lambda *a, **kw: False)
    want = SearchExecutor(ShardReader(mapper, [seg])).multi_search(
        [body])["responses"][0]
    assert kernels and set(kernels) == {"dense"}
    assert got["hits"]["total"] == want["hits"]["total"]
    assert got["hits"]["total"]["value"] == int(seg.live[seg.root].sum())
    assert [(h["_id"], h["_score"]) for h in got["hits"]["hits"]] \
        == [(h["_id"], h["_score"]) for h in want["hits"]["hits"]]
    assert len(got["hits"]["hits"]) == K


def _text_counters():
    return {k: v for k, v in
            TELEMETRY.metrics.to_dict()["counters"].items()
            if k.startswith("search.text_clause.")}


def test_the_counters_move_and_show_under_nodes_stats():
    node = Node()
    node.request("PUT", "/notes", {"mappings": {"properties": {
        "body": {"type": "text"}}}})
    for i, text in enumerate(["alpha beta", "alpha", "beta gamma"]):
        node.request("PUT", f"/notes/_doc/{i}", {"body": text})
    node.request("POST", "/notes/_refresh")

    def search(match):
        before = _text_counters()
        out = node.request("POST", "/notes/_search",
                           {"query": {"match": {"body": match}}})
        after = _text_counters()
        return out["hits"]["total"]["value"], {
            k.rsplit(".", 1)[1]: after[k] - before[k] for k in after}

    assert search("alpha beta") == (3, {"score_only": 1, "counted": 0})
    assert search({"query": "alpha beta", "operator": "and"}) \
        == (1, {"score_only": 0, "counted": 1})
    # other literals through the same template: the clause memo is missed
    assert search("gamma alpha") == (3, {"score_only": 1, "counted": 0})
    stats = next(iter(node.request("GET", "/_nodes/stats")["nodes"]
                      .values()))["telemetry"]["metrics"]["counters"]
    assert stats["search.text_clause.score_only"] \
        == _text_counters()["search.text_clause.score_only"] >= 2
    assert stats["search.text_clause.counted"] >= 1
