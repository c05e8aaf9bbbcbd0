"""The comparison that decides `correct`: a served page against a plain
numpy ranking. Copied from chip_smoke.py (`check_page`, `check_total`,
`check_clean`, the tolerances and their reasons) and kept here so that no
later PR can change the yardstick; the only adaptation is that a ranking
is a dense score vector over doc ordinals (8.8M matches do not fit a
dict of ids), with `NaN` marking a doc that does not match.

Order: score descending, then doc ordinal ascending — the engine's
documented tie-break (lowest doc first).
"""

from __future__ import annotations

import math

import numpy as np

# BM25 pages: f32 accumulation of up to a dozen partials against the
# oracle's f64 (chip_smoke.RTOL; 1e-5 held on the chip in PR 21)
RTOL = 1e-5
# exact kNN scores 1/(1+d2) with d2 from the engine's documented
# ||x||2 - 2x.q + ||q||2 expansion: f32 cancels ~4,400 down to d2 ~ 200
# here, which puts 1e-5 at the formula's own noise floor (1.0e-5 seen on
# the CPU backend). A bf16-rounded matmul would be off by ~1e-1, so this
# still fails a lower precision than the configuration states
# (chip_smoke.KNN_RTOL).
KNN_RTOL = 1e-4


class Mismatch(AssertionError):
    """A served response differs from the oracle's."""


def require(cond, msg: str) -> None:
    if not cond:
        raise Mismatch(msg)


def check_clean(resp: dict, what: str) -> None:
    """A response that carries an error item, a failed shard or a timeout
    is a failure, whatever status the envelope had."""
    require("error" not in resp, f"{what}: error item {str(resp)[:300]}")
    require(resp.get("timed_out") is False, f"{what}: timed_out")
    require(resp["_shards"]["failed"] == 0,
            f"{what}: _shards.failed={resp['_shards']['failed']}")


def check_total(what: str, resp: dict, want: int) -> None:
    t = resp["hits"]["total"]
    require(t == {"value": int(want), "relation": "eq"},
            f"{what}: total {t}, oracle {want}")


def lower_precision(scores: np.ndarray) -> np.ndarray:
    """The control of `correct`: the reference's scores in the nearest
    precision below the float32 both configurations state, bfloat16
    (round to nearest even on the top 16 bits of the float32). A page
    ranked and scored so has to FAIL `check_page`; the tests beside the
    benchmark hold it to that (tests/benchmark/test_correct.py)."""
    bits = np.asarray(scores, dtype=np.float32).view(np.uint32)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32).astype(np.float64)


def top_ords(scores: np.ndarray, ords: np.ndarray, k: int):
    """The best k of (ords, scores): score desc, then ordinal asc."""
    if len(ords) > 4 * k:
        # everything that can reach the page: scores >= the k-th best
        kth = np.partition(scores, len(scores) - k)[len(scores) - k]
        keep = scores >= kth
        ords, scores = ords[keep], scores[keep]
    order = np.lexsort((ords, -scores))[:k]
    return ords[order], scores[order]


def check_page(what: str, hits: list, ords: np.ndarray, scores: np.ndarray,
               score_of, ord_of_id, k: int, rtol: float = RTOL,
               seen: dict = None) -> None:
    """Hit ids equal the oracle's in order, except permutations among hits
    whose ORACLE scores are within `rtol` of each other (f32 cannot
    order what f64 separates by less); every score within `rtol`.

    `ords`/`scores`: every matching doc; `score_of(ord)`: the oracle's
    score of one doc or None; `ord_of_id(_id)`: the doc's ordinal.
    `seen`, where given, keeps what was compared for the run's last
    lines: `score_rel_err_max`, the widest relative gap of a served
    score from the oracle's at its rank, beside its limit `rtol`, and
    `hits_compared`."""
    want_ords, want_scores = top_ords(scores, ords, k)
    if seen is not None:
        seen["score_rel_err_limit"] = rtol
        seen.setdefault("score_rel_err_max", 0.0)
        seen.setdefault("hits_compared", 0)
        for h, ws in zip(hits, want_scores):
            gs = h["_score"]
            if gs is not None and math.isfinite(gs):
                seen["hits_compared"] += 1
                seen["score_rel_err_max"] = max(
                    seen["score_rel_err_max"],
                    abs(gs - float(ws)) / max(abs(gs), abs(float(ws)),
                                              1e-300))
    require(len(hits) == len(want_ords),
            f"{what}: {len(hits)} hits, oracle has {len(want_ords)}")
    got = [ord_of_id(h["_id"]) for h in hits]
    require(len(set(got)) == len(got), f"{what}: duplicate hits")
    for i, (h, g, wo, ws) in enumerate(zip(hits, got, want_ords,
                                           want_scores)):
        gs = h["_score"]
        require(gs is not None and math.isfinite(gs),
                f"{what}: hit {i} score {gs}")
        require(math.isclose(gs, ws, rel_tol=rtol, abs_tol=1e-12),
                f"{what}: hit {i} score {gs!r} != oracle {ws!r}")
        if g == wo:
            continue
        s = score_of(g)
        require(s is not None and math.isclose(s, ws, rel_tol=rtol),
                f"{what}: hit {i} is doc {g} (oracle score {s}), oracle "
                f"wants doc {int(wo)} ({ws})")
