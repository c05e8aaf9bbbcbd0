"""Device-side evaluation of compiled query plans.

Shared by the search executor (search/executor.py) and the aggregation engine
(search/aggs/engine.py — filter/filters aggs embed query plans). The traced
structure is static per plan signature; only the numpy inputs vary.
"""

from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp

from opensearch_tpu.common.errors import QueryShardError
from opensearch_tpu.ops.topk import NEG_INF
from opensearch_tpu.ops.bm25 import (
    ordinal_terms_match, range_match_on_ranks, score_text_clause)
from opensearch_tpu.ops.knn import (
    exact_knn_scores, ivf_knn_scores, knn_match_topk)
from opensearch_tpu.ops.select import select_top_k
from opensearch_tpu.search.compile import Plan

def _identity(score_mode: str) -> float:
    return 1.0 if score_mode in ("multiply",) else 0.0


def _haversine_m(lat1, lon1, lat2, lon2):
    """Great-circle distance in meters (Lucene SloppyMath.haversinMeters
    analog, exact formula)."""
    rad = jnp.pi / 180.0
    dlat = (lat2 - lat1) * rad
    dlon = (lon2 - lon1) * rad
    a = jnp.sin(dlat / 2.0) ** 2 + \
        jnp.cos(lat1 * rad) * jnp.cos(lat2 * rad) * jnp.sin(dlon / 2.0) ** 2
    return 6371008.7714 * 2.0 * jnp.arcsin(jnp.sqrt(jnp.minimum(a, 1.0)))


def _apply_modifier(value, modifier: str):
    if modifier in ("none", None, ""):
        return value
    if modifier == "log":
        return jnp.log10(value)
    if modifier == "log1p":
        return jnp.log10(value + 1.0)
    if modifier == "log2p":
        return jnp.log10(value + 2.0)
    if modifier == "ln":
        return jnp.log(value)
    if modifier == "ln1p":
        return jnp.log1p(value)
    if modifier == "ln2p":
        return jnp.log(value + 2.0)
    if modifier == "square":
        return value * value
    if modifier == "sqrt":
        return jnp.sqrt(value)
    if modifier == "reciprocal":
        return 1.0 / value
    raise QueryShardError(f"Unknown modifier [{modifier}]")


def dense_numeric(seg: Dict, field: str, d_pad: int, missing: float = 0.0):
    """Materialize a per-doc dense value column from the (doc, value) pair
    arrays: first (smallest) value per doc, `missing` where absent. Shared
    by script_score / function_score / distance_feature / geo kernels."""
    col = seg["numeric"][field]
    valid = col["doc_ids"] >= 0
    idx = jnp.where(valid, col["doc_ids"], d_pad)
    dense = jnp.full(d_pad + 1, jnp.inf, jnp.float32) \
        .at[idx].min(jnp.where(valid, col["values_f32"], jnp.inf))
    value = jnp.where(jnp.isfinite(dense[:d_pad]), dense[:d_pad], missing)
    counts = jnp.zeros(d_pad + 1, jnp.int32) \
        .at[idx].add(valid.astype(jnp.int32))[:d_pad]
    return value, col["exists"], counts


def _knn_scores(plan: Plan, seg: Dict, inputs: List[Dict],
                cursor: List[int], my: Dict):
    """A `knn` clause's dense scores in its space and the docs its
    selection may pick: present, live, passing the clause's `filter`
    child and, on an IVF field, among the probed candidates."""
    field, _k, space, method, nprobe = plan.static
    col = seg["vector"][field]
    eligible = col["exists"] & seg["live"]
    if plan.children:
        _, fmatches = _eval_plan(plan.children[0], seg, inputs, cursor)
        eligible = eligible & fmatches
    if method == "ivf":
        scores, cand = ivf_knn_scores(
            col["ivf_packed_vecs"], col["ivf_packed_ids"],
            col["ivf_centroids"], col["ivf_block_centroid"],
            seg["live"].shape[0], my["query"], space, nprobe)
        return scores, eligible & cand
    return exact_knn_scores(col["vectors"], my["query"], space), eligible


def eval_knn_winners(plan: Plan, seg: Dict, inputs: List[Dict],
                     cursor: List[int]):
    """A plan whose root is a `knn` clause, as the clause's k winners
    alone: (boosted scores, doc ordinals, valid), in the clause's order
    (raw score descending, ties by lowest doc). No `[d_pad]` pair is
    built: the caller takes its page from these k (ops/knn.py
    `knn_page`). `cursor` walks `inputs` as `_eval_plan`'s does."""
    my = inputs[cursor[0]]  # the root's own inputs; its filter's follow
    cursor[0] += 1
    scores, eligible = _knn_scores(plan, seg, inputs, cursor, my)
    top_vals, top_idx, valid = select_top_k(scores, eligible,
                                            plan.static[1])
    return top_vals * my["boost"], top_idx, valid


def _eval_plan(plan: Plan, seg: Dict, inputs: List[Dict], cursor: List[int]):
    my = inputs[cursor[0]]
    cursor[0] += 1
    d_pad = seg["live"].shape[0]
    kind = plan.kind

    if kind == "match_all":
        return (jnp.full(d_pad, my["boost"], jnp.float32),
                jnp.ones(d_pad, jnp.bool_))

    if kind == "match_none":
        return (jnp.zeros(d_pad, jnp.float32), jnp.zeros(d_pad, jnp.bool_))

    if kind == "text":
        constant, _, score_only = plan.static
        scores, matches = score_text_clause(seg, my, my["k1"],
                                            score_only=score_only)
        if constant:
            scores = jnp.where(matches, my["boost"], 0.0)
        else:
            scores = jnp.where(matches, scores, 0.0)
        return scores, matches

    if kind == "precomputed":
        return my["scores"], my["matches"]

    if kind == "nested":
        # block-join (ToParentBlockJoinQuery analog): evaluate the inner
        # plan over nested child rows, scatter the verdict up to each
        # child's root row, combine child scores by score_mode
        score_mode = plan.static[0]
        child_scores, child_matches = _eval_plan(plan.children[0], seg,
                                                 inputs, cursor)
        path_ok = (seg["nested_path"] == my["path_ord"]) \
            & (my["path_ord"] >= 0)
        sel = child_matches & path_ok & seg["live"]
        idx = jnp.where(sel, seg["parent_ptr"], d_pad)
        pmatch = jnp.zeros(d_pad, jnp.bool_).at[idx].max(sel, mode="drop")
        if score_mode == "none":
            # reference ScoreMode.None: matches contribute score 0
            return jnp.zeros(d_pad, jnp.float32), pmatch
        csel = jnp.where(sel, child_scores, 0.0)
        psum = jnp.zeros(d_pad, jnp.float32).at[idx].add(csel, mode="drop")
        if score_mode == "sum":
            combined = psum
        elif score_mode == "avg":
            cnt = jnp.zeros(d_pad, jnp.float32).at[idx].add(
                sel.astype(jnp.float32), mode="drop")
            combined = psum / jnp.maximum(cnt, 1.0)
        elif score_mode == "max":
            combined = jnp.full(d_pad, NEG_INF, jnp.float32).at[idx].max(
                jnp.where(sel, child_scores, NEG_INF), mode="drop")
        else:   # min
            combined = jnp.full(d_pad, -NEG_INF, jnp.float32).at[idx].min(
                jnp.where(sel, child_scores, -NEG_INF), mode="drop")
        return jnp.where(pmatch, combined * my["boost"], 0.0), pmatch

    if kind == "num_terms":
        col = seg["numeric"][plan.static[0]]
        ident = plan.static[1] if len(plan.static) > 1 else False
        matches = ordinal_terms_match(col["doc_ids"], col["val_ords"],
                                      my["mask"], d_pad, ident)
        return jnp.where(matches, my["boost"], 0.0), matches

    if kind == "range_num":
        col = seg["numeric"][plan.static[0]]
        ident = plan.static[1] if len(plan.static) > 1 else False
        matches = range_match_on_ranks(col["doc_ids"], col["val_ords"],
                                       my["lo"], my["hi"], d_pad, ident)
        return jnp.where(matches, my["boost"], 0.0), matches

    if kind == "range_ord":
        col = seg["ordinal"][plan.static[0]]
        ident = plan.static[1] if len(plan.static) > 1 else False
        matches = range_match_on_ranks(col["doc_ids"], col["ords"],
                                       my["lo"], my["hi"], d_pad, ident)
        return jnp.where(matches, my["boost"], 0.0), matches

    if kind == "exists":
        ctype, key = plan.static
        if ctype == "numeric":
            matches = seg["numeric"][key]["exists"]
        elif ctype == "ordinal":
            matches = seg["ordinal"][key]["exists"]
        elif ctype == "vector":
            matches = seg["vector"][key]["exists"]
        elif ctype == "rank_vectors":
            matches = seg["rank_vectors"][key]["exists"]
        else:  # norms row
            matches = seg["norms"][key] > 0
        return jnp.where(matches, my["boost"], 0.0), matches

    if kind == "knn":
        scores, eligible = _knn_scores(plan, seg, inputs, cursor, my)
        scores, matches = knn_match_topk(scores, eligible, plan.static[1])
        return scores * my["boost"], matches

    if kind == "maxsim":
        from opensearch_tpu.ops.maxsim import (
            exact_maxsim_scores, maxsim_match_topk, pq_maxsim_scores)
        field, k, compression = plan.static
        col = seg["rank_vectors"][field]
        eligible = col["exists"] & seg["live"]
        if plan.children:
            _, fmatches = _eval_plan(plan.children[0], seg, inputs, cursor)
            eligible = eligible & fmatches
        if compression == "pq":
            scores = pq_maxsim_scores(col["codes"], col["codebook"],
                                      col["token_count"], my["query"],
                                      my["qmask"])
        else:
            scores = exact_maxsim_scores(col["tokens"], col["token_count"],
                                         my["query"], my["qmask"])
        scores, matches = maxsim_match_topk(scores, eligible, k)
        return scores * my["boost"], matches

    if kind == "bool":
        n_must, n_filter, n_should, n_must_not = plan.static
        child_results = [_eval_plan(c, seg, inputs, cursor) for c in plan.children]
        must = child_results[:n_must]
        filt = child_results[n_must:n_must + n_filter]
        should = child_results[n_must + n_filter:n_must + n_filter + n_should]
        must_not = child_results[n_must + n_filter + n_should:]
        matches = jnp.ones(d_pad, jnp.bool_)
        scores = jnp.zeros(d_pad, jnp.float32)
        for s, m in must:
            matches &= m
            scores += s
        for _, m in filt:
            matches &= m
        if should:
            should_count = jnp.zeros(d_pad, jnp.int32)
            for s, m in should:
                should_count += m.astype(jnp.int32)
                scores += s
            matches &= should_count >= my["msm"]
        for _, m in must_not:
            matches &= ~m
        scores = jnp.where(matches, scores * my["boost"], 0.0)
        return scores, matches

    if kind == "const_score":
        _, m = _eval_plan(plan.children[0], seg, inputs, cursor)
        return jnp.where(m, my["boost"], 0.0), m

    if kind == "dis_max":
        child_results = [_eval_plan(c, seg, inputs, cursor) for c in plan.children]
        matches = jnp.zeros(d_pad, jnp.bool_)
        best = jnp.zeros(d_pad, jnp.float32)
        total = jnp.zeros(d_pad, jnp.float32)
        for s, m in child_results:
            matches |= m
            best = jnp.maximum(best, s)
            total += s
        scores = best + my["tie"] * (total - best)
        return jnp.where(matches, scores * my["boost"], 0.0), matches

    if kind == "script_score":
        from opensearch_tpu.script.painless import compile_score_script
        source, pkeys, static_params = plan.static
        script = compile_score_script(source)
        child_s, child_m = _eval_plan(plan.children[0], seg, inputs, cursor)
        columns = {f: dense_numeric(seg, f, d_pad)
                   for f in script.fields}
        params = {k: my[f"p_{k}"] for k in pkeys}
        params.update(dict(static_params))
        new_scores = script(columns, child_s, params)
        scores = jnp.where(child_m,
                           jnp.asarray(new_scores, jnp.float32) * my["boost"],
                           0.0)
        return scores, child_m

    if kind == "function_score":
        score_mode, boost_mode, fn_specs = plan.static
        cursor_children = plan.children
        child_s, child_m = _eval_plan(cursor_children[0], seg, inputs,
                                      cursor)
        fn_values = []       # (value array, applies mask)
        child_idx = 1
        for i, spec in enumerate(fn_specs):
            fkind = spec[0]
            has_filter = spec[-1]
            if has_filter:
                _, fmask = _eval_plan(cursor_children[child_idx], seg,
                                      inputs, cursor)
                child_idx += 1
            else:
                fmask = jnp.ones(d_pad, jnp.bool_)
            if fkind == "weight_only":
                value = jnp.full(d_pad, my[f"f{i}_weight"], jnp.float32)
            elif fkind == "fvf":
                _, field, modifier = spec[0], spec[1], spec[2]
                if field is None:  # field has no values in this segment
                    value = jnp.full(d_pad, my[f"f{i}_missing"], jnp.float32)
                else:
                    value, exists, _ = dense_numeric(seg, field, d_pad)
                    value = jnp.where(exists, value, my[f"f{i}_missing"])
                value = _apply_modifier(value * my[f"f{i}_factor"], modifier)
            elif fkind == "random":
                seed = spec[1]
                ords = jnp.arange(d_pad, dtype=jnp.uint32)
                h = (ords * jnp.uint32(2654435761)
                     + jnp.uint32(seed & 0xFFFFFFFF))
                h = h ^ (h >> 16)
                h = h * jnp.uint32(2246822519)
                h = h ^ (h >> 13)
                value = (h % jnp.uint32(1 << 24)).astype(jnp.float32) \
                    / float(1 << 24)
            elif fkind == "script":
                from opensearch_tpu.script.painless import (
                    compile_score_script)
                source, pkeys, static_params = spec[1], spec[2], spec[3]
                script = compile_score_script(source)
                columns = {f: dense_numeric(seg, f, d_pad)
                           for f in script.fields}
                params = {k: my[f"f{i}_p_{k}"] for k in pkeys}
                params.update(dict(static_params))
                value = jnp.asarray(script(columns, child_s, params),
                                    jnp.float32)
            elif fkind == "decay":
                decay_kind, field = spec[1], spec[2]
                if field is None:  # no values in this segment: no decay
                    fn_values.append((jnp.ones(d_pad, jnp.float32), fmask))
                    continue
                value_col, exists, _ = dense_numeric(seg, field, d_pad)
                dist = jnp.maximum(
                    jnp.abs(value_col - my[f"f{i}_origin"])
                    - my[f"f{i}_offset"], 0.0)
                scale, decay = my[f"f{i}_scale"], my[f"f{i}_decay"]
                if decay_kind == "gauss":
                    sigma2 = -(scale ** 2) / (2.0 * jnp.log(decay))
                    value = jnp.exp(-(dist ** 2) / (2.0 * sigma2))
                elif decay_kind == "exp":
                    lam = jnp.log(decay) / scale
                    value = jnp.exp(lam * dist)
                else:  # linear
                    s = scale / (1.0 - decay)
                    value = jnp.maximum((s - dist) / s, 0.0)
                value = jnp.where(exists, value, 1.0)
            else:
                raise QueryShardError(
                    f"unknown score function [{fkind}]")
            if fkind != "weight_only" and f"f{i}_weight" in my:
                value = value * my[f"f{i}_weight"]
            fn_values.append((value, fmask))

        if fn_values:
            applied = [jnp.where(m, v, jnp.nan) for v, m in fn_values]
            stacked = jnp.stack([jnp.where(jnp.isnan(a),
                                           _identity(score_mode), a)
                                 for a in applied])
            any_applies = jnp.stack([m for _, m in fn_values]).any(axis=0)
            if score_mode == "multiply":
                combined = jnp.prod(stacked, axis=0)
            elif score_mode == "sum":
                combined = jnp.sum(stacked, axis=0)
            elif score_mode == "avg":
                n_applied = jnp.maximum(jnp.stack(
                    [m.astype(jnp.float32) for _, m in fn_values]
                ).sum(axis=0), 1.0)
                combined = jnp.sum(stacked, axis=0) / n_applied
            elif score_mode == "max":
                combined = jnp.max(jnp.stack(
                    [jnp.where(m, v, -jnp.inf) for v, m in fn_values]),
                    axis=0)
                combined = jnp.where(any_applies, combined, 1.0)
            elif score_mode == "min":
                combined = jnp.min(jnp.stack(
                    [jnp.where(m, v, jnp.inf) for v, m in fn_values]),
                    axis=0)
                combined = jnp.where(any_applies, combined, 1.0)
            elif score_mode == "first":
                combined = jnp.full(d_pad, jnp.nan, jnp.float32)
                for v, m in reversed(fn_values):
                    combined = jnp.where(m, v, combined)
                combined = jnp.where(jnp.isnan(combined), 1.0, combined)
            else:
                raise QueryShardError(
                    f"illegal score_mode [{score_mode}]")
            combined = jnp.where(any_applies, combined, 1.0)
            combined = jnp.minimum(combined, my["max_boost"])
        else:
            combined = jnp.ones(d_pad, jnp.float32)

        if boost_mode == "multiply":
            scores = child_s * combined
        elif boost_mode == "replace":
            scores = combined
        elif boost_mode == "sum":
            scores = child_s + combined
        elif boost_mode == "avg":
            scores = (child_s + combined) / 2.0
        elif boost_mode == "max":
            scores = jnp.maximum(child_s, combined)
        elif boost_mode == "min":
            scores = jnp.minimum(child_s, combined)
        else:
            raise QueryShardError(f"illegal boost_mode [{boost_mode}]")
        matches = child_m
        if "min_score" in my:
            matches = matches & (scores >= my["min_score"])
        return jnp.where(matches, scores * my["boost"], 0.0), matches

    if kind == "terms_set":
        field_msm = plan.static[0]
        child_results = [_eval_plan(c, seg, inputs, cursor)
                         for c in plan.children]
        hits = jnp.zeros(d_pad, jnp.int32)
        scores = jnp.zeros(d_pad, jnp.float32)
        for s, m in child_results:
            hits += m.astype(jnp.int32)
            scores += s
        if field_msm is not None:
            msm, msm_exists, _ = dense_numeric(seg, field_msm, d_pad)
            msm = msm.astype(jnp.int32)
            # docs without the msm field never match (CoveringQuery skips
            # docs where the LongValuesSource has no value); and a doc may
            # require MORE matches than the query has terms — then it
            # simply cannot match (no clamping down)
            matches = msm_exists & (hits >= jnp.maximum(msm, 1))
        else:
            matches = hits >= jnp.maximum(my["msm"], 1)
        return jnp.where(matches, scores * my["boost"], 0.0), matches

    if kind == "distance_feature":
        field = plan.static[0]
        value, exists, _ = dense_numeric(seg, field, d_pad)
        dist = jnp.abs(value - my["origin"])
        scores = my["boost"] * my["pivot"] / (my["pivot"] + dist)
        return jnp.where(exists, scores, 0.0), exists

    if kind == "distance_feature_geo":
        field = plan.static[0]
        lat, exists, _ = dense_numeric(seg, f"{field}.lat", d_pad)
        lon, _, _ = dense_numeric(seg, f"{field}.lon", d_pad)
        dist = _haversine_m(lat, lon, my["lat"], my["lon"])
        scores = my["boost"] * my["pivot"] / (my["pivot"] + dist)
        return jnp.where(exists, scores, 0.0), exists

    if kind == "rank_feature":
        field, function = plan.static
        value, exists, _ = dense_numeric(seg, field, d_pad)
        value = jnp.maximum(value, 0.0)
        if function == "saturation":
            s = value / (value + my["pivot"])
        elif function == "log":
            s = jnp.log(my["scaling_factor"] + value)
        elif function == "sigmoid":
            vp = value ** my["exponent"]
            s = vp / (vp + my["pivot"] ** my["exponent"])
        else:  # linear
            s = value
        return jnp.where(exists, s * my["boost"], 0.0), exists

    if kind == "geo_distance":
        field = plan.static[0]
        lat, exists, _ = dense_numeric(seg, f"{field}.lat", d_pad)
        lon, _, _ = dense_numeric(seg, f"{field}.lon", d_pad)
        dist = _haversine_m(lat, lon, my["lat"], my["lon"])
        matches = exists & (dist <= my["dist"])
        return jnp.where(matches, my["boost"], 0.0), matches

    if kind == "geo_bbox":
        field = plan.static[0]
        lat, exists, _ = dense_numeric(seg, f"{field}.lat", d_pad)
        lon, _, _ = dense_numeric(seg, f"{field}.lon", d_pad)
        in_lat = (lat <= my["top"]) & (lat >= my["bottom"])
        # dateline-crossing box: left > right wraps
        in_lon = jnp.where(my["left"] <= my["right"],
                           (lon >= my["left"]) & (lon <= my["right"]),
                           (lon >= my["left"]) | (lon <= my["right"]))
        matches = exists & in_lat & in_lon
        return jnp.where(matches, my["boost"], 0.0), matches

    if kind == "boosting":
        pos_s, pos_m = _eval_plan(plan.children[0], seg, inputs, cursor)
        neg_s, neg_m = _eval_plan(plan.children[1], seg, inputs, cursor)
        scores = pos_s * jnp.where(neg_m, my["nb"], 1.0)
        return jnp.where(pos_m, scores * my["boost"], 0.0), pos_m

    raise QueryShardError(f"unknown plan kind [{kind}]")
