"""Names on the device (ISSUE 25): every program of the served path is
an XLA module named for its kernel family (`jit_bm25_dense`, never
`jit_run`), every stage of the fixed vocabulary shows in the programs
that have it, and the executable census maps an optimized program's HLO
instructions (what a TPU trace names its events by) to stages.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.node import Node
from opensearch_tpu.telemetry import TELEMETRY
from opensearch_tpu.telemetry.kernels import (KERNEL_FAMILIES, STAGES,
                                              fingerprint, hlo_scopes,
                                              jit_family, stage,
                                              timed_first_call)

KERNELS = TELEMETRY.kernels
DIMS = 4
MODULE = re.compile(r"module @(\w+)")


def module_name(lowered) -> str:
    return MODULE.search(lowered.as_text()).group(1)


@contextlib.contextmanager
def compile_cache_at(path):
    """jax's persistent compilation cache at `path` (empty: whatever is
    compiled inside is compiled here, not loaded from an older run)."""
    from jax._src import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One node, one request of every family a default node serves; the
    census then holds each executable with what it takes to lower it
    again."""
    KERNELS.clear()
    node = Node()
    with compile_cache_at(tmp_path_factory.mktemp("compile_cache")):
        yield from _serve_every_family(node)
    KERNELS.clear()


def _serve_every_family(node):
    node.request("PUT", "/names", {"mappings": {"properties": {
        "t": {"type": "text"}, "title": {"type": "text"},
        "n": {"type": "integer"},
        "vec": {"type": "knn_vector", "dimension": DIMS}}}})
    for i in range(60):
        node.request("PUT", f"/names/_doc/{i}", {
            "t": f"hello world w{i % 7} x{i % 13}",
            "title": ["red dog", "blue fox", "red cat"][i % 3], "n": i,
            "vec": [i % 5, i % 3, 1.0, 0.5]})
    node.request("POST", "/names/_refresh")
    ex = node.indices.get("names").shards[0].executor
    match = {"query": {"match": {"t": "hello w3"}}}
    assert node.request("POST", "/names/_search", match)["_status"] == 200
    # a field sort leaves the envelope: the general query phase
    assert node.request("POST", "/names/_search", {
        **match, "sort": [{"n": "asc"}]})["_status"] == 200
    # a bool of two clauses is past the candidate kernel: dense, batched
    assert node.request("POST", "/names/_search", {"query": {"bool": {
        "should": [{"match": {"t": "hello"}},
                   {"term": {"t": "w1"}}]}}})["_status"] == 200
    assert node.request("POST", "/names/_search", {"query": {"knn": {
        "vec": {"vector": [1, 1, 1, 1], "k": 3}}}})["_status"] == 200
    hybrid = {"query": {"hybrid": {"queries": [
        {"match": {"title": "red dog"}},
        {"knn": {"vec": {"vector": [0.5, 0.2, 0.8, 0.1], "k": 5}}}]}}}
    aggs = {"size": 3, "query": {"match": {"t": "w2"}},
            "aggs": {"by_n": {"max": {"field": "n"}}}}
    res = ex.multi_search([dict(hybrid), dict(hybrid), dict(aggs),
                           dict(aggs), dict(match), dict(match)])
    assert all("hits" in r for r in res["responses"])
    records = {r["fingerprint"]: r for r in
               KERNELS.snapshot()["census"]["executables"]}
    programs = []       # (family, shape, lowered)
    for fp, (fn, structs) in KERNELS._lowerable.items():
        programs.append((records[fp]["family"], records[fp]["shape"],
                         fn.lower(*structs)))
    yield programs


def lowered_of(programs, family, batched=None):
    for fam, shape, lowered in programs:
        if fam == family and (batched is None
                              or shape.startswith("b") == batched):
            return lowered
    raise AssertionError(f"no [{family}] program among "
                         f"{[(f, s) for f, s, _ in programs]}")


# ------------------------------------------------------------- programs

@pytest.mark.parametrize("family", KERNEL_FAMILIES)
def test_jit_family_names_the_module_for_the_family(family):
    def run(x):
        return x + 1

    fn = jit_family(run, family)
    assert module_name(fn.lower(jnp.zeros(4))) == f"jit_{family}"
    assert float(fn(jnp.zeros(4))[0]) == 1.0


SERVED = ("bm25_candidate", "bm25_dense", "knn", "hybrid_env", "agg_env")


@pytest.mark.parametrize("family", SERVED)
def test_a_served_program_lowers_to_its_familys_module(served, family):
    assert module_name(lowered_of(served, family)) == f"jit_{family}"


def test_no_served_program_is_called_jit_run(served):
    names = {module_name(low) for _, _, low in served}
    assert names == {f"jit_{fam}" for fam, _, _ in served}
    assert "jit_run" not in names and len(served) >= len(SERVED)


def test_expand_and_concat_rows_are_named_too():
    from opensearch_tpu.ops.device_segment import _expand_fn
    from opensearch_tpu.search import executor
    fn = _expand_fn((3, 2), (8, 2), 0, "int32")
    out = fn(jnp.ones((3, 2), jnp.int32))
    assert out.shape == (8, 2)
    rec = KERNELS.snapshot()["census"]["executables"][-1]
    assert rec["family"] == "expand"
    raw, structs = KERNELS._lowerable[rec["fingerprint"]]
    assert module_name(raw.lower(*structs)) == "jit_expand"
    rows = [jnp.zeros((2, 5), jnp.int32), jnp.zeros((1, 3), jnp.int32)]
    assert module_name(executor._concat_rows.lower(rows)) \
        == "jit_concat_rows"


def test_the_dispatch_span_reads_the_executables_own_record(served):
    from opensearch_tpu.telemetry.kernels import timed_first_call
    fn = jit_family(lambda x: x * 2, "other")
    first = timed_first_call(fn, family="other", shape="t1", key=("t", 1))
    for f in (first, fn):
        info = f.exec_info
        assert (info.family, info.shape) == ("other", "t1")
        assert re.fullmatch(r"[0-9a-f]{8}", info.fingerprint)


# --------------------------------------------------------------- stages

TEXT = {"postings_gather", "bm25_score", "eligible_total", "top_k",
        "pack_row", "unpack_envelope"}
STAGES_OF = {
    "bm25_dense": TEXT | {"scatter"},
    "bm25_candidate": TEXT | {"candidate_sort", "run_sum"},
    "knn": {"distance", "top_k", "pack_row", "unpack_envelope",
            "eligible_total"},
}


@pytest.mark.parametrize("family", sorted(STAGES_OF))
def test_each_stage_shows_in_the_lowered_program(served, family):
    text = lowered_of(served, family, batched=True).as_text(debug_info=True)
    found = {st for st in STAGES
             if re.search(rf"[/(]{st}[/)]", text)}
    assert found == STAGES_OF[family]


def test_the_general_query_phase_carries_the_dense_stages(served):
    text = lowered_of(served, "bm25_dense", batched=False).as_text(
        debug_info=True)
    for st in ("postings_gather", "bm25_score", "scatter",
               "eligible_total", "top_k"):
        assert re.search(rf"[/(]{st}[/)]", text), st


def test_stage_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError):
        stage("gather")
    assert "blockmax_mask" in STAGES and len(set(STAGES)) == len(STAGES)


def test_blockmax_mask_is_a_stage_where_it_is_compiled_in():
    from opensearch_tpu.ops import bm25
    seg = {"post_bound": jnp.ones(4), "post_docs": jnp.zeros((4, 128),
                                                             jnp.int32),
           "post_tf": jnp.ones((4, 128)),
           "post_norm": jnp.zeros((4, 128), jnp.uint8),
           "live": jnp.ones(256, bool), "root": jnp.ones(256, bool)}
    blk = {"ids": jnp.arange(4, dtype=jnp.int32),
           "tid": jnp.zeros(4, jnp.int32), "bscale": jnp.ones(4),
           "w": jnp.ones(4), "b": jnp.float32(0.75),
           "avgdl": jnp.float32(8.0),
           "min_hits": jnp.int32(1)}

    def run(seg, blk):
        return bm25.blockmax_keep_mask(seg, blk, 1.2, 1, 2)

    try:
        text = jax.jit(run).lower(seg, blk).as_text(debug_info=True)
    except Exception as e:      # the kernel's inputs moved: say so
        pytest.fail(f"blockmax_keep_mask did not lower: {e}")
    assert "blockmax_mask" in text


# -------------------------------------------------------- the scope map

def test_the_scope_map_of_a_small_compiled_program_names_its_fusions():
    def one(x, idx):
        with stage("postings_gather"):
            g = x[idx] * 2.0
        with stage("scatter"):
            acc = jnp.zeros(x.shape[0], jnp.float32).at[idx].add(g)
        with stage("top_k"):
            return jax.lax.top_k(acc, 4)[0]

    def run(x, idxs):
        return jax.vmap(one, in_axes=(None, 0))(x, idxs)

    fn = jit_family(run, "bm25_dense")
    text = fn.lower(jnp.arange(1024, dtype=jnp.float32),
                    jnp.zeros((2, 64), jnp.int32)).compile().as_text()
    assert text.startswith("HloModule jit_bm25_dense")
    scopes = hlo_scopes(text)
    # `~stage`: inferred from the op's neighbours (rule 3)
    assert {st.lstrip("~") for st in scopes.values()} \
        == {"postings_gather", "scatter", "top_k"}
    fusions = [name for name in scopes if "fusion" in name]
    assert fusions, scopes
    entry = text[text.index("ENTRY"):]
    named = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([\w.\-]+) = .*fusion\(", entry, re.M)]
    assert named and all(n in scopes for n in named), (named, scopes)


HAND_MADE = """HloModule jit_bm25_dense, is_scheduled=true

%region_0.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="scatter-add"}
  %b = f32[] parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.15 = f32[] add(%a, %b), metadata={op_name="jit(bm25_dense)/vmap(scatter)/add" stack_frame_id=45}
}

%fused_computation.5 (p0: f32[8], p1: s32[8], p2: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %p2 = f32[8]{0} parameter(2)
  ROOT %scatter.8 = f32[8]{0} scatter(%p0, %p1, %p2), indices_are_sorted=true, to_apply=%region_0.5
}

%compare (x: s32[], y: s32[]) -> pred[] {
  %x = s32[] parameter(0)
  %y = s32[] parameter(1)
  ROOT %compare.11 = pred[] compare(%x, %y), direction=LT
}

%fused_computation.2 (q0: f32[256], q1: s32[8]) -> f32[8] {
  %q0 = f32[256]{0} parameter(0)
  %q1 = s32[8]{0} parameter(1)
  ROOT %gather.3 = f32[8]{0} gather(%q0, %q1), metadata={op_name="jit(bm25_dense)/vmap(postings_gather)/gather" stack_frame_id=20}
}

ENTRY %main.1 (table: f32[256], ids: s32[8], vals: f32[8]) -> f32[8] {
  %table = f32[256]{0} parameter(0), metadata={op_name="seg['length_table']"}
  %ids = s32[8]{0} parameter(1)
  %vals = f32[8]{0} parameter(2)
  %copy-start = (f32[256]{0}, f32[256]{0}, u32[]) copy-start(%table)
  %copy-done = f32[256]{0} copy-done(%copy-start)
  %fusion.2 = f32[8]{0} fusion(%copy-done, %ids), kind=kCustom, calls=%fused_computation.2, metadata={op_name="jit(bm25_dense)/vmap(postings_gather)/gather" stack_frame_id=20}, backend_config={"x":["%not_an_operand"]}
  %where.1 = f32[8]{0} multiply(%fusion.2, %vals), metadata={op_name="jit(bm25_dense)/vmap(top_k)/jit(_where)/select_n;jit(bm25_dense)/vmap(pack_row)/mul"}
  %sort.3 = (s32[8]{0}, f32[8]{0}) sort(%ids, %vals), dimensions={0}, to_apply=%compare
  %get-tuple-element.10 = s32[8]{0} get-tuple-element(%sort.3), index=0
  %get-tuple-element.11 = f32[8]{0} get-tuple-element(%sort.3), index=1
  %constant.19 = f32[] constant(0)
  %broadcast.1 = f32[8]{0} broadcast(%constant.19), dimensions={}
  %fusion.5 = f32[8]{0} fusion(%broadcast.1, %get-tuple-element.10, %get-tuple-element.11), kind=kCustom, calls=%fused_computation.5
  %reshape.14 = f32[1,8]{1,0} reshape(%where.1)
  %custom-call = (f32[1,4]{1,0}, s32[1,4]{1,0}) custom-call(%reshape.14), custom_call_target="TopK", called_computations={%compare}
  %get-tuple-element.6 = f32[1,4]{1,0} get-tuple-element(%custom-call), index=0
  %mix = f32[8]{0} add(%fusion.5, %where.1)
  %aside = f32[8]{0} add(%mix, %vals), metadata={op_name="jit(bm25_dense)/vmap(eligible_total)/and"}
  ROOT %out = f32[8]{0} add(%mix, %aside), metadata={op_name="jit(bm25_dense)/vmap(pack_row)/concatenate"}
}
"""


def test_the_three_rules_of_the_scope_map_on_hand_made_hlo():
    scopes = hlo_scopes(HAND_MADE)
    # 1. its own op_name; the innermost scope; the first of a merged pair
    assert scopes["fusion.2"] == "postings_gather"
    assert scopes["where.1"] == "top_k"
    # 2. the root of the computation it runs, through a fusion's body
    #    into the combiner a scatter applies (the TPU's scatter
    #    expansion keeps no metadata on the scatter itself)
    assert scopes["scatter.8"] == "scatter"
    assert scopes["fusion.5"] == "scatter"
    # 3a. forwards: what a `lax.top_k` rewrite leaves behind the masked
    #     scores takes their stage, not its reader's (pack_row)
    #     and says that it was inferred
    assert scopes["reshape.14"] == "~top_k"
    assert scopes["custom-call"] == "~top_k"
    assert scopes["get-tuple-element.6"] == "~top_k"
    # 3b. backwards: the sort the expansion puts before its scatter, the
    #     constant it fills with, the prefetch of a gather's table
    assert scopes["sort.3"] == "~scatter"
    assert scopes["get-tuple-element.10"] == "~scatter"
    assert scopes["broadcast.1"] == scopes["constant.19"] == "~scatter"
    assert scopes["copy-start"] == scopes["copy-done"] \
        == "~postings_gather"
    # fed by two stages and read by two: left out; attribute
    # references are no operands
    assert "mix" not in scopes and "ids" not in scopes
    assert "not_an_operand" not in scopes
    assert {st.lstrip("~") for st in scopes.values()} <= set(STAGES)


def test_census_scopes_are_built_on_demand_and_kept(served):
    before = dict(KERNELS._scopes)
    snap = KERNELS.snapshot(scopes=True)
    execs = snap["census"]["executables"]
    assert execs and all(isinstance(e["scopes"], dict) for e in execs)
    # compiled here or served an entry this process compiled: none is
    # refused
    assert not [e for e in execs if "_error" in e["scopes"]]
    dense = next(e for e in execs if e["family"] == "bm25_dense"
                 and e["shape"].startswith("b"))
    assert "_error" not in dense["scopes"]
    assert {"postings_gather", "scatter", "top_k"} \
        <= {st.lstrip("~") for st in dense["scopes"].values()}
    assert set(KERNELS._scopes) >= set(before)
    kept = {fp: id(m) for fp, m in KERNELS._scopes.items()}
    KERNELS.snapshot(scopes=True)
    assert {fp: id(m) for fp, m in KERNELS._scopes.items()} == kept
    plain = KERNELS.snapshot()["census"]["executables"]
    assert all("scopes" not in e for e in plain)
    np.testing.assert_equal(len(plain), len(execs))


# ------------------------------------------ a map that would be stale

def staged(mask_stage):
    """One computation whatever `mask_stage` is: only the stage its
    mask lies in differs, which the compile cache's key leaves out."""
    def run(x, idx):
        with stage("postings_gather"):
            g = x[idx] * 2.0
        with stage(mask_stage):
            masked = jnp.where(g > 1.0, g, 0.0)
        with stage("top_k"):
            return jax.lax.top_k(masked, 4)[0]
    return run


ARGS = (np.arange(1024, dtype=np.float32), np.arange(64, dtype=np.int32))
LAYOUT_KEY = ("layout-test", 1024, 64)


def first_call(mask_stage, key=LAYOUT_KEY, fresh=True):
    """A fresh process' first call of the program, as far as jax can
    tell: nothing compiled is kept in memory. Its census record."""
    jax.clear_caches()
    if fresh:
        KERNELS.clear()
    fn = timed_first_call(jit_family(staged(mask_stage), "bm25_dense"),
                          family="bm25_dense", shape="t", key=key)
    fn(*ARGS)
    return KERNELS.snapshot()["census"]["executables"][-1]


def layout_files(cache_dir):
    return sorted((cache_dir / "stage_layouts").iterdir())


def test_the_compiler_of_an_executable_leaves_its_stage_layout(tmp_path):
    with compile_cache_at(tmp_path):
        rec = first_call("eligible_total")
        assert rec["from_cache"] is False
        scopes = KERNELS.scopes()[fingerprint(LAYOUT_KEY)]
        assert "eligible_total" in {s.lstrip("~") for s in scopes.values()}
        (path,) = layout_files(tmp_path)
        assert path.name.startswith("bm25_dense-")
        assert len(path.read_text()) == 40
        # another process of the same source loads it and reads the
        # same stages
        rec = first_call("eligible_total")
        assert rec["from_cache"] is True
        assert KERNELS.scopes()[fingerprint(LAYOUT_KEY)] == scopes
        # another plan of this process that lowers to the same
        # computation is served the same entry (the six classes of the
        # benchmark's dense cell are two computations): the layout is
        # the computation's, whatever the census fingerprint
        other = ("layout-test", "another plan")
        rec = first_call("eligible_total", key=other, fresh=False)
        assert rec["from_cache"] is True
        assert KERNELS.scopes()[fingerprint(other)] == scopes
        assert len(layout_files(tmp_path)) == 1
    KERNELS.clear()


def test_a_cached_executable_of_another_stage_layout_gives_no_map(
        tmp_path):
    fp = fingerprint(LAYOUT_KEY)
    with compile_cache_at(tmp_path):
        assert first_call("eligible_total")["from_cache"] is False
        # the mask moved into `top_k`: the same computation, so the
        # cache serves the executable compiled before the move, whose
        # metadata still says `eligible_total`
        assert first_call("top_k")["from_cache"] is True
        stale = KERNELS.scopes()[fp]
        assert set(stale) == {"_error"}
        assert "another stage layout" in stale["_error"]
        # an error is not kept: with the layout file gone the next
        # demand says that instead
        os.remove(*layout_files(tmp_path))
        unknown = KERNELS.scopes()[fp]
        assert "keeps no stage layout" in unknown["_error"]
        # and the metric's reader sees no map at all for it
        execs = KERNELS.snapshot(scopes=True)["census"]["executables"]
        assert "_error" in execs[0]["scopes"]
    KERNELS.clear()
