"""The benchmark harness off the chip: every cell that BENCHMARK.json or
the fuller copy beside this file names runs end to end at `--dry-run`
sizes on the CPU backend and prints the contract's last line;
configurations, mixes and metrics are found by name, and so are the
cells these tests run: a cell added as entries and new files is tested
with no edit here; the classes a cell sends do not depend on the seed;
BENCHMARK.json keeps to its own rules, `chips` 1 or 4 among them.
No timing is asserted: a CPU run gives correctness and counts only.
"""

import collections
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import loadgen               # noqa: E402
from benchmark import run as bench_run      # noqa: E402

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
# BENCHMARK.json holds the cells proven on the chip. The cells whose files
# are in the tree but whose runs are still owed (PERF.md, Open questions)
# are exercised here through this fuller copy, as a later PR will add them:
# entries only, no file edited.
FULL = json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCHMARK.full.json")))
CELLS = [w["name"] for w in FULL["workloads"]]
# every cell that either file names, once, with the file that names it:
# the fuller copy's cells run from a copy of the tree that holds it, a
# cell only BENCHMARK.json names (as a later PR adds one) from the repo
EVERY_CELL = [("full", c) for c in CELLS] \
    + [("committed", w["name"]) for w in BENCH["workloads"]
       if w["name"] not in CELLS]
EACH_CELL = pytest.mark.parametrize("where,cell", EVERY_CELL,
                                    ids=[c for _, c in EVERY_CELL])


def with_a_four_chip_cell(bench: dict) -> dict:
    """A copy with a second cell appended as the next `model_config` PR
    appends one: `chips` 4, reporting the closed-loop median."""
    bench = json.loads(json.dumps(bench))
    bench["workloads"].append({
        "name": "four-chip-cell", "config": bench["workloads"][0]["config"],
        "traffic": "rare-msearch-256", "chips": 4,
        "why": "what exists only across chips: the SPMD program on a "
               "mesh of 4"})
    for m in bench["end_to_end"]:
        if m["name"] == "closed_search_p50_ms":
            m["workloads"].append("four-chip-cell")
    return bench


BOTH = pytest.mark.parametrize(
    "bench", [BENCH, FULL, with_a_four_chip_cell(BENCH)],
    ids=["committed", "full", "committed-and-a-four-chip-cell"])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def full_root(tmp_path_factory):
    """A copy of the benchmark with the fuller BENCHMARK.json."""
    root = str(tmp_path_factory.mktemp("full"))
    copy_benchmark(root)
    json.dump(FULL, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(scope="module")
def files(full_root):
    return bench_run.Files(full_root)


# the program comes from this tree, or from wherever the caller's
# PYTHONPATH already finds it (a copy of the benchmark alone, tested from
# the repo)
WITH_REPO = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [REPO] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
              if p]))


def run_cell(*flags, cwd=REPO, env=None, chips=1):
    assert os.environ["JAX_PLATFORMS"] == "cpu"     # conftest pinned it
    script = os.path.join(cwd, "benchmark", "run.py")
    if chips > 1:
        # a cell of several chips has to find a mesh of that width: as
        # many virtual CPU devices as the cell asks for chips
        env = dict(os.environ if env is None else env)
        env["XLA_FLAGS"] = " ".join(
            [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
            + [f"--xla_force_host_platform_device_count={chips}"])
    return subprocess.run([sys.executable, script, *flags], cwd=cwd,
                          capture_output=True, text=True, timeout=600,
                          env=env)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def names_of(cell: str, group: str, bench=FULL) -> set:
    return {m["name"] for m in bench[group]
            if cell in m.get("workloads", [cell])}


def chips_of(cell: str, bench: dict) -> int:
    return next(w["chips"] for w in bench["workloads"] if w["name"] == cell)


# ------------------------------------------------------- every cell runs

@pytest.fixture(scope="module")
def roots(full_root):
    """The root a cell runs from and the file that names it there."""
    return {"full": (full_root, FULL), "committed": (REPO, BENCH)}


@EACH_CELL
def test_cell_prints_the_contract_line(where, cell, roots):
    root, bench = roots[where]
    out = last_line(run_cell("--workload", cell, "--seed", "3000000019",
                             "--seconds", "2", "--trace", "0", "--dry-run",
                             cwd=root, env=WITH_REPO,
                             chips=chips_of(cell, bench)))
    assert set(out) == RESULT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["device"]) == DEVICE_KEYS
    assert out["device"]["platform"] == "cpu"
    # what `correct` rests on comes last in the line, each number beside
    # its limit
    assert list(out)[-1] == "compared"
    for c in out["compared"].values():
        assert set(c) == {"value", "limit", "holds"}
        assert c["value"] <= c["limit"] if c["holds"] == "at_most" \
            else c["value"] >= c["limit"]
    assert out["compared"]["pages_differing"]["value"] == 0
    assert 0 <= out["compared"]["score_rel_err_max"]["value"] \
        <= out["compared"]["score_rel_err_max"]["limit"] <= 1e-4
    assert set(out["metrics"]) == names_of(cell, "end_to_end", bench)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    for name, m in out["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert m["value"] > 0


@EACH_CELL
def test_traced_run_prints_per_layer_metrics(where, cell, roots):
    root, bench = roots[where]
    out = last_line(run_cell("--workload", cell, "--seed", "7",
                             "--seconds", "3", "--trace", "1", "--dry-run",
                             cwd=root, env=WITH_REPO,
                             chips=chips_of(cell, bench)))
    assert set(out) == RESULT_KEYS | {"breakdown"}
    assert out["correct"] is True
    assert set(out["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # a reader that finds nothing to read (no device plane on the CPU)
    # leaves its metric out; no other name may appear
    assert set(out["metrics"]) <= names_of(cell, "per_layer", bench)
    assert {"compiles_in_window", "warmup_s", "install_upload_s",
            "resident_corpus_gb"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    # nothing ran on a device here: no device time a query, not 0
    assert not [n for n in out["metrics"] if n.startswith("device_ms")]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_committed_cell_runs_from_the_repo_itself(cell):
    out = last_line(run_cell("--workload", cell, "--seed", "11",
                             "--seconds", "2", "--trace", "0", "--dry-run",
                             chips=chips_of(cell, BENCH)))
    assert out["correct"] is True
    assert set(out["metrics"]) == names_of(cell, "end_to_end", BENCH)


def test_refuses_to_run_without_a_tpu():
    proc = run_cell("--workload", BENCH["workloads"][0]["name"], "--seed",
                    "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "Refusing to run" in proc.stderr
    assert proc.stdout.strip() == ""


def copy_benchmark(dst: str) -> None:
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(dst, path),
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_fails_alone_in_a_directory(tmp_path):
    copy_benchmark(str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = run_cell("--workload", BENCH["workloads"][0]["name"], "--seed",
                    "1", "--seconds", "1", "--trace", "0", "--dry-run",
                    cwd=str(tmp_path), env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ---------------------------------------------------------- found by name

def digest(root: str) -> dict:
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def add_a_small_knn_cell(root: str, bench: dict) -> dict:
    """A cell as a later PR adds one: a configuration file, a traffic
    file and entries, no file edited. Returns the new BENCHMARK."""
    bdir = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(bdir, "configs", "sift-1m.json")))
    cfg["name"], cfg["dry_run"] = "sift-small", {"vectors": 500}
    json.dump(cfg, open(os.path.join(bdir, "configs", "sift-small.json"),
                        "w"))
    mix = json.load(open(os.path.join(bdir, "traffic", "knn-open.json")))
    mix["name"] = "knn-closed-2"
    mix["dry_run"] = {"loop": "closed", "clients": 2,
                      "provision_per_s": 400, "judge": {"sample": 10}}
    json.dump(mix, open(os.path.join(bdir, "traffic", "knn-closed-2.json"),
                        "w"))
    bench = json.loads(json.dumps(bench))
    bench["configs"].append({
        "name": "sift-small", "source": "test",
        "file": "benchmark/configs/sift-small.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "sift-small.closed", "config": "sift-small",
        "traffic": "knn-closed-2", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "closed_search_p50_ms":
            m["workloads"].append("sift-small.closed")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return bench


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later PR adds a configuration, a traffic mix and a per-layer
    metric as files of their own plus entries in BENCHMARK.json, and
    edits no file that is there."""
    root = str(tmp_path)
    copy_benchmark(root)
    bdir = os.path.join(root, "benchmark")
    before = digest(bdir)

    bench = add_a_small_knn_cell(root, FULL)
    json.dump({"reader": "count_requests", "params": {"scale": 1}},
              open(os.path.join(bdir, "metrics", "requests_counted.json"),
                   "w"))
    with open(os.path.join(bdir, "metrics", "readers",
                           "count_requests.py"), "w") as f:
        f.write("def read(run, params):\n"
                "    return len(run.samples) * params['scale']\n")
    bench["per_layer"].append({
        "name": "requests_counted", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "closed_search_p50_ms",
        "workloads": ["sift-small.closed"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    env = WITH_REPO
    e2e = last_line(run_cell(
        "--workload", "sift-small.closed", "--seed", "5", "--seconds", "2",
        "--trace", "0", "--dry-run", cwd=root, env=env))
    assert e2e["correct"] is True
    assert set(e2e["metrics"]) == {"closed_search_p50_ms", "setup_s"}
    traced = last_line(run_cell(
        "--workload", "sift-small.closed", "--seed", "5", "--seconds", "2",
        "--trace", "1", "--dry-run", cwd=root, env=env))
    assert traced["metrics"]["requests_counted"]["value"] > 0
    after = digest(bdir)
    assert {k: after[k] for k in before} == before     # nothing edited
    assert len(after) == len(before) + 4


def test_a_cell_added_to_benchmark_json_is_dry_run_with_no_edit_here(
        tmp_path):
    """The per-cell tests of this file find their cells in
    BENCHMARK.json: in a copy of the benchmark where a PR has appended
    a cell with its files, this file, unedited, dry-runs the new cell
    from the root that names it."""
    root = str(tmp_path)
    copy_benchmark(root)
    before = digest(root)
    add_a_small_knn_cell(root, BENCH)
    after = digest(root)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} \
        == {k: before[k] for k in before if k != "BENCHMARK.json"}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", "sift-small.closed",
         os.path.join(root, "tests", "benchmark", "test_benchmark.py")],
        cwd=root, capture_output=True, text=True, timeout=900,
        env=WITH_REPO)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    ran = re.findall(r"(\d+) passed", proc.stdout)
    # the contract line, the traced run, and the run from the root itself
    assert ran and int(ran[-1]) == 3, proc.stdout[-2000:]


def test_a_four_chip_cell_that_ran_on_no_mesh_of_four_gets_no_result(
        tmp_path):
    """`chips` is not a label: the committed one-chip index under a
    cell that asks for 4 chips never takes the SPMD program, the node
    reports a mesh of 0 after the window, and the run prints no result.
    Read from what the node reports, `mesh_width`."""
    stats = {"telemetry": {"device_memory": {"classes": {
        "corpus_columns": {"live_bytes": 9},
        "spmd_shard_sets": {"live_bytes": 8, "entries": 1, "by_device": {
            "0": 2, "1": 2, "2": 2, "3": 2, "4": 0}}}}}}
    assert bench_run.mesh_width(stats) == 4
    stats["telemetry"]["device_memory"]["classes"].pop("spmd_shard_sets")
    assert bench_run.mesh_width(stats) == 0
    root = str(tmp_path)
    copy_benchmark(root)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["chips"] = 4
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    proc = run_cell("--workload", bench["workloads"][0]["name"], "--seed",
                    "13", "--seconds", "2", "--trace", "0", "--dry-run",
                    cwd=root, env=WITH_REPO, chips=4)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "on a mesh of 0" in proc.stderr


# ------------------------------------------- shapes do not depend on seed

def traffic_of(files, cell: str, dry_run: bool) -> dict:
    t = files.traffic(files.workload(cell)["traffic"])
    return {**t, **t.get("dry_run", {})} if dry_run else t


def requests_classes(traffic: dict, queries: list) -> list:
    """The class composition of each request, in order."""
    batch = int(traffic.get("batch", 1))
    return [tuple(q.klass for q in queries[i:i + batch])
            for i in range(0, len(queries), batch)]


MSMARCO_CELLS = [w["name"] for w in FULL["workloads"]
                 if w["config"] == "msmarco-passage"]


@pytest.mark.parametrize("cell", MSMARCO_CELLS)
def test_full_size_classes_do_not_depend_on_the_seed(cell, files):
    """At the configuration's full size, by the block table alone: two
    seeds send the same multiset of query classes and every batch has
    the same composition in the same order; only the terms differ. The
    class of a query is what the executor compiles for: (distinct terms,
    pad_bucket(blocks of its terms, 8))."""
    config = files.config("msmarco-passage")
    builder = files.builder(config)
    table = builder.zipf_table(config, config["passages"])
    traffic = traffic_of(files, cell, dry_run=False)
    cycle = bench_run.class_cycle(traffic["classes"])
    batch = int(traffic.get("batch", 1))
    n = max(len(cycle), batch) * 6
    classes = [cycle[i % len(cycle)] for i in range(n)]
    drawn = {seed: builder.draw_queries(table, traffic["query"], classes,
                                        seed)
             for seed in (1, 3000000019)}
    a, b = drawn.values()
    assert collections.Counter(q.klass for q in a) \
        == collections.Counter(q.klass for q in b) \
        == collections.Counter((c["terms"], c["qb"]) for c in classes)
    comp_a, comp_b = requests_classes(traffic, a), requests_classes(traffic, b)
    assert comp_a == comp_b
    assert len(set(comp_a)) == 1 or batch == 1      # every batch alike
    assert [q.text for q in a] != [q.text for q in b]
    term_blocks = dict(zip(table["terms"], table["blocks"].tolist()))
    for q in a + b:
        terms = q.text.split()
        assert len(set(terms)) == len(terms) == q.klass[0]
        blocks = sum(term_blocks[t] for t in terms)
        assert builder.qb_bucket(blocks) == q.klass[1]
        assert q.work["lanes"] == blocks * 128
    for qs in (a, b):       # no whole query repeats
        assert len({frozenset(q.text.split()) for q in qs}) == len(qs)
    # which kernel the class runs, by the executor's own rule
    from opensearch_tpu.search.executor import (CANDIDATE_MAX_LANES,
                                                CANDIDATE_MAX_TERMS)
    for c in traffic["classes"]:
        assert c["terms"] <= CANDIDATE_MAX_TERMS
        dense = c["qb"] * 128 > CANDIDATE_MAX_LANES
        assert dense == (cell == "msmarco-natural-closed")


def test_term_df_is_fixed_by_rank_not_by_seed(files):
    config = files.config("msmarco-passage")
    builder = files.builder(config)
    small = builder.build(config, 1, True), builder.build(config, 2, True)
    assert small[0].table["df"].tolist() == small[1].table["df"].tolist()
    term = small[0].table["terms"][3]
    d0, d1 = small[0].postings(term)[0], small[1].postings(term)[0]
    assert len(d0) == len(d1) and d0.tolist() != d1.tolist()
    for docs in (d0, d1):       # sorted, distinct, inside the collection
        assert (docs[1:] > docs[:-1]).all()
        assert docs[0] >= 0 and docs[-1] < small[0].n
    full = builder.zipf_table(config, config["passages"])
    assert int(full["blocks"].sum()) <= 1 << 20     # nb_pad stays 2^20
    assert full["df"].max() > 5_000_000             # head terms
    assert full["df"].min() < 64                    # a few dozen postings
    assert int(full["rare"].sum()) >= 1000          # the rare band


@pytest.mark.parametrize("cell", CELLS)
def test_dry_run_classes_do_not_depend_on_the_seed(cell, files):
    traffic = traffic_of(files, cell, dry_run=True)
    config = files.config(files.workload(cell)["config"])
    builder = files.builder(config)
    cycle = bench_run.class_cycle(traffic["classes"])
    n = max(len(cycle), int(traffic.get("batch", 1))) * 3
    classes = [cycle[i % len(cycle)] for i in range(n)]
    comps = []
    for seed in (4, 2200000001):
        corpus = builder.build(config, seed, True)
        queries = corpus.draw(traffic.get("query", {}), classes, seed)
        comps.append(requests_classes(traffic, queries))
    assert comps[0] == comps[1]


def test_open_schedule_is_one_multiset_of_gaps_in_another_order():
    a = loadgen.fixed_gaps(500, 100.0, 99, seed=1)
    b = loadgen.fixed_gaps(500, 100.0, 99, seed=2)
    assert a != b and a[0] == b[0] == 0.0

    def gaps(offsets):
        return sorted(round(y - x, 12) for x, y in zip(offsets, offsets[1:]))
    # the last gap (to the window's end) is the one not among the diffs
    ga, gb = gaps(a + [5.0]), gaps(b + [5.0])
    assert ga == gb
    assert max(a) < 5.0 and max(b) < 5.0


def test_class_cycle_interleaves_evenly_and_is_fixed():
    classes = [{"id": "a", "per_cycle": 3}, {"id": "b", "per_cycle": 1},
               {"id": "c", "per_cycle": 2}]
    cycle = [c["id"] for c in bench_run.class_cycle(classes)]
    assert cycle == ["a", "c", "a", "b", "c", "a"]
    assert cycle == [c["id"] for c in bench_run.class_cycle(classes)]


# ------------------------------------------------ BENCHMARK.json's own rules

def chips_complaint(bench: dict):
    """The contract's rule for `chips`, or None: a cell takes 1 chip or
    4; of a benchmark's cells at most half, rounded down, may ask for
    4, and one always may."""
    chips = [w["chips"] for w in bench["workloads"]]
    if any(c not in (1, 4) for c in chips):
        return f"chips is 1 or 4, not {sorted(set(chips) - {1, 4})}"
    allowed = max(len(chips) // 2, 1)
    if chips.count(4) > allowed:
        return f"{chips.count(4)} of {len(chips)} cells ask for 4 chips; " \
               f"at most {allowed} may"
    return None


@pytest.mark.parametrize("chips,passes", [
    ([1], True), ([1, 4], True), ([4], True), ([1, 1, 1, 4, 4], True),
    ([1, 1, 4, 4, 4], False), ([1, 4, 4], False), ([4, 4], False),
    ([2], False), ([1, 2], False), ([1, 8], False), ([1, 0], False)],
    ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else None)
def test_a_cell_takes_one_chip_or_four_and_at_most_half_take_four(
        chips, passes):
    bench = {"workloads": [{"name": f"c{i}", "chips": c}
                           for i, c in enumerate(chips)]}
    assert (chips_complaint(bench) is None) is passes


@BOTH
def test_benchmark_json_has_exactly_the_contract_keys(bench):
    BENCH = bench
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert chips_complaint(BENCH) is None
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


@BOTH
def test_names_and_units_hold_only_allowed_characters(bench):
    BENCH = bench
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in BENCH[group]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for group in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in BENCH[group]}) == len(BENCH[group])
        for m in BENCH[group]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for e in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


@BOTH
def test_every_per_layer_metric_moves_a_metric_all_its_cells_report(bench):
    BENCH = bench
    CELLS = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS), (m, cell)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"].get("workloads") is None
    for cell in CELLS:
        assert len(names_of(cell, "end_to_end", BENCH)) >= 2
        assert len(names_of(cell, "per_layer", BENCH)) >= 1


@BOTH
def test_every_named_file_is_there_and_under_paths(bench):
    BENCH = bench
    bdir = os.path.join(REPO, "benchmark")
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmark/")
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["guarantees"] and cfg["source"]
        assert os.path.exists(os.path.join(
            bdir, "configs", cfg["builder"] + ".py"))
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(
            bdir, "traffic", w["traffic"] + ".json"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        spec = json.load(open(os.path.join(
            bdir, "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            bdir, "metrics", "readers", spec["reader"] + ".py"))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
