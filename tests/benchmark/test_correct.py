"""How `correct` is decided, held to its two ends off the chip: the
control (the plain reference itself, put in the program's place and
computed in bfloat16, the nearest precision below the float32 both
configurations state) has to come out NOT correct, and a run whose timed
path is broken underneath (an answer altered where it is produced) has
to print `correct` false, with the number that caught it beside its
limit. Dry-run sizes; the same control at the cells' own sizes ran on
the chip's machine (PERF.md, section 2).
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import oracle                # noqa: E402
from benchmark import run as bench_run      # noqa: E402

FILES = bench_run.Files(REPO)
CELL = "msmarco-natural-closed"


def corpus_and_queries(seed: int, n: int = 6):
    run = bench_run.Run(FILES, bench_run.parse_args(
        ["--workload", CELL, "--seed", str(seed), "--dry-run"]))
    run.corpus = FILES.builder(run.config).build(run.config, seed, True)
    warm, window = run.draw()
    return run.corpus, [reqs[0] for reqs in (warm + window)[:n]]


def reference_page(corpus, query, scores_as=lambda s: s) -> dict:
    """The response the plain reference itself would serve: its top k
    by (score desc, doc asc), scores in the precision `scores_as`
    leaves them in."""
    ords, scores = corpus.match(query.text)
    served = scores_as(scores)
    top, top_scores = oracle.top_ords(served, ords, corpus.k)
    return {"timed_out": False, "_shards": {"failed": 0},
            "hits": {"total": {"value": len(ords), "relation": "eq"},
                     "hits": [{"_id": f"p{int(o)}", "_score": float(s)}
                              for o, s in zip(top, top_scores)]}}


def test_lower_precision_rounds_to_bfloat16():
    x = np.array([1.0, 1.00390625, 1.001, 3.14159274, 12.5, 1e-3])
    got = oracle.lower_precision(x)
    assert got[0] == 1.0 and got[1] == 1.0      # ties to even
    assert got[4] == 12.5
    assert np.all(np.abs(got - x) <= np.abs(x) * 2.0 ** -8)
    assert abs(got[2] - 1.001) > 1e-5           # 8 bits of mantissa


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_the_reference_passes_and_its_bfloat16_control_fails(seed):
    corpus, queries = corpus_and_queries(seed)
    # the reference in the program's place, as float32 serves it: correct
    seen = {}
    sound = [(q, reference_page(
        corpus, q, lambda s: s.astype(np.float32).astype(np.float64)))
        for q in queries]
    assert corpus.judge(sound, seen) == []
    assert seen["hits_compared"] == len(queries) * corpus.k
    lower = seen["score_rel_err_max"]
    assert lower <= 2.0 ** -24 < seen["score_rel_err_limit"] == oracle.RTOL
    # the control: the same reference in bfloat16. Every page has to
    # differ, and the widest score gap reads far over the limit
    seen = {}
    control = [(q, reference_page(corpus, q, oracle.lower_precision))
               for q in queries]
    bad = corpus.judge(control, seen)
    assert len(bad) == len(queries)
    upper = seen["score_rel_err_max"]
    assert upper > 30 * oracle.RTOL and upper > 3 * max(lower, 1e-12)


def test_a_run_whose_answers_are_altered_prints_correct_false(
        monkeypatch, capfd):
    """The rest of a run, the harness's look for a chip skipped
    (`--dry-run`), with the timed path broken underneath: the node
    serves every page with its best hit's score raised by a thousandth.
    `correct` comes out false, `failed` stays 0 (the pages are well
    formed), and the number that caught it stands beside its limit."""
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    start = bench_run.Run.start_node

    def start_broken(self):
        start(self)
        handle = self.node.handle

        def altered(method, path, *a, **kw):
            resp = handle(method, path, *a, **kw)
            if path.endswith("/_search") and isinstance(resp.body, dict) \
                    and resp.body.get("hits", {}).get("hits"):
                resp.body["hits"]["hits"][0]["_score"] *= 1.001
            return resp
        self.node.handle = altered

    monkeypatch.setattr(bench_run.Run, "start_node", start_broken)
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483693",
                         "--seconds", "2", "--trace", "0", "--dry-run"])
    out, err = capfd.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] > 0
    compared = line["compared"]
    assert list(line)[-1] == "compared"
    assert compared["pages_differing"]["value"] \
        == compared["pages_judged"]["value"] > 0
    gap = compared["score_rel_err_max"]
    assert gap["value"] == pytest.approx(1e-3, rel=1e-2)
    assert gap["value"] > 30 * gap["limit"]
    # the same numbers are the last lines of standard error
    last = [ln for ln in err.strip().splitlines()][-len(compared):]
    assert all(ln.startswith("[compared] ") for ln in last)
    assert any("score_rel_err_max" in ln and "<= 1e-05" in ln for ln in last)
