"""The `http_logs-4chip` configuration and its cell off the chip (ISSUE
28): the panel the node serves through `launcher.start_node` on a mesh
of 4 virtual devices equals the builder's plain reference at dry-run
size on three seeds, by the SPMD program and with no fallback; a
window start never repeats and never leaves the span, at the dry-run
size and at the cell's own; the reference is plain numpy in blocks; and
the control of `correct`: the reference's own sums put in the program's
place in bfloat16 have to come out NOT correct. No timing is asserted.
"""

import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import oracle                # noqa: E402
from benchmark import run as bench_run      # noqa: E402

FILES = bench_run.Files(REPO)
CELL = "http_logs-4chip-dashboards"
CONFIG = FILES.config("http_logs-4chip")
BUILDER = FILES.builder(CONFIG)
TRAFFIC = FILES.traffic("dashboards-closed-4")
SEEDS = [5, 2147483659, 3000000019]


@pytest.fixture(scope="module")
def corpora():
    return {seed: BUILDER.build(CONFIG, seed, True) for seed in SEEDS}


def panels(corpus, seed, n):
    return corpus.draw(TRAFFIC["query"], TRAFFIC["classes"] * n, seed)


# ---------------------------------------------------- served = reference

@pytest.mark.parametrize("seed", SEEDS)
def test_the_served_panel_equals_the_reference_on_a_mesh_of_four(seed):
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        [f for f in env.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
        + ["--xla_force_host_platform_device_count=4"])
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", "1", "--dry-run"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4     # and the mesh-width check held
    compared = line["compared"]
    assert compared["pages_differing"]["value"] == 0
    assert compared["pages_judged"]["value"] == 12
    gap = compared["score_rel_err_max"]     # here: the widest sum gap
    assert 0 <= gap["value"] <= gap["limit"] == 1e-6
    m = line["metrics"]
    assert m["spmd_fallbacks_in_window"]["value"] == 0
    assert m["compiles_in_window"]["value"] == 0
    assert m["spmd_resident_gb"]["value"] > 0
    for name in ("http_self_ms.dash", "rest_self_ms.dash",
                 "spmd_plan_ms.dash", "enqueue_ms.dash",
                 "device_wait_ms.dash", "spmd_reduce_ms.dash",
                 "respond_ms.dash"):
        assert m[name]["value"] > 0, name
    # no device plane on the CPU: no device metric, under any name
    assert not {"device_ms_per_query.dash", "agg_bins_ms.dash",
                "agg_binned_roofline", "device_busy_skew.dash"} & set(m)


# --------------------------------------------------------------- traffic

def check_starts(corpus, queries, window_s):
    starts = [q.start_s for q in queries]
    assert len(set(starts)) == len(starts)              # never repeats
    for s in starts:
        assert (s - corpus.t0) % 60 == 0                # a whole minute
        assert corpus.t0 <= s                           # inside the span
        assert s + window_s <= corpus.t0 + corpus.span_s


def test_window_starts_never_repeat_and_never_leave_the_span(corpora):
    window_s = TRAFFIC["query"]["window_days"] * 86400
    for seed, corpus in corpora.items():
        qs = panels(corpus, seed, 3000)
        check_starts(corpus, qs, window_s)
        assert [q.start_s for q in panels(corpus, seed, 40)] \
            == [q.start_s for q in panels(corpus, seed, 40)]
    a, b = (panels(corpora[s], s, 40) for s in SEEDS[:2])
    assert [q.start_s for q in a] != [q.start_s for q in b]
    # more panels than the span has starts: an error, not a repeat
    with pytest.raises(RuntimeError):
        panels(corpora[SEEDS[0]], 1, 5000)


def test_full_size_windows_by_arithmetic_alone():
    """The cell's own size without its 67M rows: the draw reads the
    span and nothing else of the corpus."""
    corpus = BUILDER.Corpus.__new__(BUILDER.Corpus)
    n, per_day = CONFIG["documents"], CONFIG["docs_per_day"]
    corpus.t0 = 893894400
    corpus.span_s = int(np.ceil(n / per_day * 86400.0))
    corpus.statuses = sorted(int(s) for s in CONFIG["status_shares"])
    assert n == 8 * (1 << 23) and CONFIG["shards"] == 8
    assert corpus.span_s < 1 << 21      # distinct seconds a row: 2^21
    assert 23.8 < corpus.span_s / 86400 < 24.0
    need = int(TRAFFIC["provision_per_s"] * 51) + 64
    qs = corpus.draw(TRAFFIC["query"], TRAFFIC["classes"] * need,
                     2147483659)
    check_starts(corpus, qs, 7 * 86400)
    assert {q.work["hour_buckets"] for q in qs} == {169}
    assert {q.work["status_values"] for q in qs} == {8}
    body = json.loads(corpus.payload(qs[0]))
    assert body["size"] == 0 and body["track_total_hits"] is True
    r = body["query"]["range"]["@timestamp"]
    assert set(r) == {"gte", "lt"}
    agg = body["aggs"]["by_hour"]
    assert agg["date_histogram"] == {"field": "@timestamp",
                                     "calendar_interval": "hour"}
    assert agg["aggs"]["by_status"]["terms"] == {"field": "status"}
    assert agg["aggs"]["by_status"]["aggs"]["bytes"] \
        == {"sum": {"field": "size"}}


def test_every_shard_is_one_full_row_of_identity_columns(corpora):
    from opensearch_tpu.index.segment import ident_pairs, pad_bucket
    corpus = corpora[SEEDS[0]]
    n = CONFIG["dry_run"]["documents"]
    assert len(corpus.segments) == 8
    ids = set()
    for seg in corpus.segments:
        assert seg.num_docs == n // 8 == pad_bucket(seg.num_docs)
        assert set(seg.numeric_dv) == {"@timestamp", "status", "size",
                                       "clientip"}
        for col in seg.numeric_dv.values():
            assert ident_pairs(col) and col.exists.all()
            assert np.array_equal(col.unique[col.value_ords], col.values)
            assert (np.diff(col.unique) > 0).all()
        ts = seg.numeric_dv["@timestamp"].values
        assert (np.diff(ts) >= 0).all() and ts[0] >= corpus.t0 * 1000
        assert seg.ordinal_dv == {} and seg.term_dict == {}
        ids.add(seg.doc_ids[0])
        assert seg.ord_of(seg.doc_ids[seg.num_docs - 1]) == seg.num_docs - 1
    assert len(ids) == 8
    # all rows together are the reference's columns
    all_ts = np.sort(np.concatenate(
        [s.numeric_dv["@timestamp"].values for s in corpus.segments]))
    assert np.array_equal(
        all_ts, (corpus.ts.astype(np.int64) + corpus.t0) * 1000.0)
    sizes = np.concatenate([s.numeric_dv["size"].values
                            for s in corpus.segments])
    assert sizes.sum() == corpus.size.sum(dtype=np.int64)
    assert sizes.max() < 1 << 23
    is_304 = corpus.status_code == corpus.statuses.index(304)
    assert is_304.any() and not corpus.size[is_304].any()


# ------------------------------------------------------------- reference

def test_the_reference_is_plain_numpy_in_blocks():
    for fn in (BUILDER.hour_status_keys, BUILDER.reference_panel,
               BUILDER.expected_buckets):
        assert "opensearch_tpu" not in inspect.getsource(fn)
    rng = np.random.default_rng(3)
    n = 20000
    ts = np.sort(rng.integers(0, 40 * 3600, n)).astype(np.int32)
    code = rng.integers(0, 3, n).astype(np.uint8)
    size = rng.integers(0, 1 << 23, n).astype(np.int32)
    lo, hi = 5 * 3600 + 120, 29 * 3600 + 120
    keys = BUILDER.hour_status_keys(ts, code, 3)
    assert keys.dtype == np.int32 and keys is not ts
    h0, counts, sums, total = BUILDER.reference_panel(
        ts, keys, size, lo, hi, 3, block=777)
    inside = (ts >= lo) & (ts < hi)
    assert total == int(inside.sum()) and h0 == 5
    assert counts.shape == sums.shape == (25, 3)
    assert sums.dtype == np.float64
    for h in (0, 7, 24):
        for c in range(3):
            sel = inside & (ts // 3600 == h0 + h) & (code == c)
            assert counts[h, c] == sel.sum()
            assert sums[h, c] == float(size[sel].astype(np.int64).sum())
    rows = BUILDER.expected_buckets(h0, counts, sums, [200, 304, 404])
    assert [key for key, _, _ in rows] \
        == [(h0 + h) * 3600000 for h in range(25)]
    for _, n_docs, inner in rows:
        assert n_docs == sum(c for _, c, _ in inner)
        assert [(-c, st) for st, c, _ in inner] \
            == sorted((-c, st) for st, c, _ in inner)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_passes_and_its_bfloat16_control_fails(corpora, seed):
    corpus = corpora[seed]
    queries = panels(corpus, seed, 6)
    # the reference in the program's place, as float32 serves it: correct
    seen = {}
    sound = [(q, corpus.reference_response(
        q, lambda s: s.astype(np.float32).astype(np.float64)))
        for q in queries]
    assert corpus.judge(sound, seen) == []
    assert seen["sums_compared"] > 6 * 169 * 3
    lower = seen["score_rel_err_max"]
    assert lower <= 2.0 ** -24 < seen["score_rel_err_limit"] == 1e-6
    # the control: the same sums in bfloat16. Every page has to differ,
    # and the widest gap reads far over the limit
    seen = {}
    control = [(q, corpus.reference_response(q, oracle.lower_precision))
               for q in queries]
    bad = corpus.judge(control, seen)
    assert len(bad) == len(queries)
    assert all("relative gap" in b for b in bad)
    upper = seen["score_rel_err_max"]
    assert upper > 1000 * 1e-6 and upper > 3 * max(lower, 1e-12)


def test_a_wrong_count_a_missing_hour_and_a_failed_shard_are_caught(corpora):
    corpus = corpora[SEEDS[0]]
    (q,) = panels(corpus, 9, 1)

    def judged(change):
        resp = corpus.reference_response(q)
        change(resp)
        return corpus.judge([(q, resp)])
    buckets = "aggregations", "by_hour", "buckets"

    def at(resp):
        return resp[buckets[0]][buckets[1]][buckets[2]]
    assert judged(lambda r: None) == []
    assert judged(lambda r: at(r)[3].__setitem__(
        "doc_count", at(r)[3]["doc_count"] + 1))
    assert judged(lambda r: at(r).pop(5))
    assert judged(lambda r: at(r)[0]["by_status"]["buckets"].reverse())
    assert judged(lambda r: r["hits"]["total"].__setitem__("value", 1))
    assert judged(lambda r: r["_shards"].__setitem__("failed", 1))
    assert judged(lambda r: r.__setitem__("timed_out", True))
    assert judged(lambda r: r.pop("aggregations"))
