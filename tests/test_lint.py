"""Lint suite + host-sync sanitizer coverage (ISSUE 8).

Three layers:
  - per-checker self-tests on known-good / known-bad fixture snippets
    (each rule must FIRE on the bad shape and stay quiet on the good
    one — a checker that cannot fail is not a check);
  - "the repo is lint-clean": `run_all()` over the working tree returns
    zero violations, which is what makes the suite a tier-1 gate for
    every future PR (including the ROADMAP item-1/item-2 rewrites);
  - the runtime sanitizer: a deliberately-injected unattributed
    `jax.device_get` from a package frame raises UnattributedSyncError,
    while attributed regions and non-package callers pass.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from lint import gate_lint, retrace_lint, shared_state_lint, sync_lint  # noqa: E402
from lint.core import RULE_BITS, SourceFile, module_mutable_globals  # noqa: E402
from lint.runner import exit_code, run_all  # noqa: E402


def _source(tmp_path, text, rel="opensearch_tpu/_fixture.py"):
    p = tmp_path / "fixture.py"
    p.write_text(text)
    return SourceFile(str(p), rel)


def _retrace(sf):
    sf._lint_mutable_globals = module_mutable_globals(sf.tree)
    out, seen = [], set()
    for fn, jit_call, report in retrace_lint._jit_targets(sf):
        key = (id(fn), getattr(report, "lineno", 0))
        if key in seen:
            continue
        seen.add(key)
        out.extend(retrace_lint._check_target(sf, fn, jit_call, report))
    return out


# ------------------------------------------------------------------ sync-lint

BAD_SYNC = """\
import jax
import numpy as np

def collect(launched):
    fetched = jax.device_get(launched)          # line 5: no scope
    return np.asarray(fetched).tolist()         # line 6: two more
"""

GOOD_SYNC_SCOPED = """\
import jax
import numpy as np

def collect(launched, scope):
    fetched = jax.device_get(launched)
    _LEDGER.note_device_get(1.0, scope=scope)
    return np.asarray(fetched).tolist()
"""

GOOD_SYNC_ANNOTATED = """\
import numpy as np

def keys(bounds):
    table = np.asarray(bounds)  # sync-ok: host -- compile-time table
    return table.tolist()  # sync-ok: host
"""

MALFORMED_ANNOTATION = """\
import numpy as np

def keys(bounds):
    return np.asarray(bounds)  # sync-ok: NOT A CHANNEL!!
"""


def test_sync_lint_flags_unattributed_sites(tmp_path):
    vs = [v for v in sync_lint.check_file(_source(tmp_path, BAD_SYNC))
          if v.rule == "sync-lint"]
    assert len(vs) == 3
    assert {v.line for v in vs} == {5, 6}


def test_sync_lint_accepts_ledger_carrying_function(tmp_path):
    assert sync_lint.check_file(_source(tmp_path, GOOD_SYNC_SCOPED)) == []


def test_sync_lint_accepts_channel_annotation(tmp_path):
    assert sync_lint.check_file(
        _source(tmp_path, GOOD_SYNC_ANNOTATED)) == []


def test_sync_lint_rejects_malformed_channel(tmp_path):
    vs = sync_lint.check_file(_source(tmp_path, MALFORMED_ANNOTATION))
    assert len(vs) == 1 and "malformed" in vs[0].message


def test_sync_lint_nested_closure_inherits_attribution(tmp_path):
    src = (
        "import jax\n"
        "def outer(scope):\n"
        "    def _collect():\n"
        "        return jax.device_get([1])\n"
        "    return _collect()\n")
    assert sync_lint.check_file(_source(tmp_path, src)) == []


# collector-thread pattern (the overlapped wave pipeline): a LedgerScope
# handed across a queue/thread boundary still counts as attribution —
# the worker re-binds the request's scope before syncing

GOOD_SYNC_QUEUE_BINDING = """\
import jax

def collector_loop(q):
    while True:
        state, scope = q.get()      # scope crosses the thread boundary
        if state is None:
            return
        fetched = jax.device_get(state)
"""

GOOD_SYNC_WAVE_ATTR_BINDING = """\
import jax

def collect_wave(wave):
    scope = wave.scope              # re-bound from the wave record
    return jax.device_get(wave.pending)
"""

GOOD_SYNC_SCOPE_KWARG_FORWARD = """\
import jax

def collect_wave(wave, finish):
    finish(wave.state, scope=wave.scope)
    return jax.device_get(wave.pending)
"""

BAD_SYNC_QUEUE_NO_SCOPE = """\
import jax

def collector_loop(q):
    while True:
        state = q.get()             # nothing scope-shaped crosses
        if state is None:
            return
        fetched = jax.device_get(state)   # line 8: unattributed
"""


def test_sync_lint_accepts_queue_scope_binding(tmp_path):
    assert sync_lint.check_file(
        _source(tmp_path, GOOD_SYNC_QUEUE_BINDING)) == []


def test_sync_lint_accepts_wave_attr_scope_binding(tmp_path):
    assert sync_lint.check_file(
        _source(tmp_path, GOOD_SYNC_WAVE_ATTR_BINDING)) == []


def test_sync_lint_accepts_scope_kwarg_forwarding(tmp_path):
    assert sync_lint.check_file(
        _source(tmp_path, GOOD_SYNC_SCOPE_KWARG_FORWARD)) == []


def test_sync_lint_flags_collector_without_scope_handoff(tmp_path):
    vs = [v for v in sync_lint.check_file(
        _source(tmp_path, BAD_SYNC_QUEUE_NO_SCOPE))
        if v.rule == "sync-lint"]
    assert len(vs) == 1 and vs[0].line == 8


# -------------------------------------------------------------- except-breadth

def test_except_breadth_flags_blanket_handler(tmp_path):
    src = ("def f():\n"
           "    try:\n"
           "        return 1\n"
           "    except Exception:\n"
           "        return None\n")
    vs = sync_lint.check_file(_source(tmp_path, src))
    assert [v.rule for v in vs] == ["except-breadth"]


def test_except_breadth_accepts_annotation_reraise_and_typed(tmp_path):
    src = ("def f():\n"
           "    try:\n"
           "        return 1\n"
           "    except Exception:  # except-ok: isolation -- reason\n"
           "        return None\n"
           "def g():\n"
           "    try:\n"
           "        return 1\n"
           "    except Exception:\n"
           "        raise\n"
           "def h():\n"
           "    try:\n"
           "        return 1\n"
           "    except (ValueError, KeyError):\n"
           "        return None\n")
    assert sync_lint.check_file(_source(tmp_path, src)) == []


# --------------------------------------------------------------- retrace-lint

BAD_RETRACE = """\
import jax

STATE = [0]

def build(k):
    def run(seg, flat):
        if flat > 0:
            seg = seg + STATE[0]
        n = flat.nonzero()
        return seg + int(flat) + n
    return run

fn = jax.jit(build(3))
"""


def test_retrace_lint_flags_all_four_shapes(tmp_path):
    msgs = [v.message for v in _retrace(_source(tmp_path, BAD_RETRACE))]
    assert any("branches on tracer" in m for m in msgs)
    assert any("mutable module global [STATE]" in m for m in msgs)
    assert any(".nonzero()" in m for m in msgs)
    assert any("int() of tracer parameter" in m for m in msgs)


def test_retrace_lint_accepts_clean_closure_and_statics(tmp_path):
    src = (
        "import jax\n"
        "import functools\n"
        "CONST = (1, 2, 3)\n"
        "def build(plan, k):\n"
        "    table = [k, k + 1]\n"
        "    def run(seg, flat):\n"
        "        return seg * table[0] + flat + CONST[0]\n"
        "    return run\n"
        "fn = jax.jit(build(None, 4))\n"
        "@functools.partial(jax.jit, static_argnames=('mode',))\n"
        "def g(x, mode):\n"
        "    if mode == 'a':\n"      # static param: branch allowed
        "        return x\n"
        "    return -x\n")
    assert _retrace(_source(tmp_path, src)) == []


# ------------------------------------------------------------------ gate-lint

def test_gate_lint_repo_registry_is_clean():
    assert gate_lint.run(REPO) == []


def test_gate_lint_rejects_on_by_default_and_missing_guard(tmp_path):
    import ast
    bad = ("class Tracer:\n"
           "    def __init__(self):\n"
           "        self.enabled = True\n"
           "    def start_trace(self, name):\n"
           "        return object()\n")
    tree = ast.parse(bad)
    cls = tree.body[0]
    assert not gate_lint._init_defaults_false(cls, "enabled")
    assert not gate_lint._gate_ok(cls.body[1], "enabled")
    good = ("class Tracer:\n"
            "    def __init__(self):\n"
            "        self.enabled = False\n"
            "    def scope(self, trace=None):\n"
            "        if self.enabled:\n"
            "            return object()\n"
            "        return None\n")
    tree = ast.parse(good)
    cls = tree.body[0]
    assert gate_lint._init_defaults_false(cls, "enabled")
    assert gate_lint._gate_ok(cls.body[1], "enabled")


def test_gate_lint_flags_unguarded_fire_site(tmp_path):
    src = ("from opensearch_tpu.common import faults\n"
           "def hot():\n"
           "    faults.fire('query.dispatch')\n")
    sf = _source(tmp_path, src)
    # exercise the call-site walker directly on the fixture
    import ast
    hits = [n for n in ast.walk(sf.tree) if isinstance(n, ast.Call)]
    assert hits
    vs = []
    guarded_src = ("from opensearch_tpu.common import faults\n"
                   "def hot():\n"
                   "    if faults.ENABLED:\n"
                   "        faults.fire('query.dispatch')\n")
    for text, expect in ((src, 1), (guarded_src, 0)):
        sf = _source(tmp_path, text)
        found = 0
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and \
                    gate_lint.name_of(node.func) == "faults.fire":
                guarded = any(
                    isinstance(a, ast.If) and
                    gate_lint._mentions_flag(a.test, "ENABLED")
                    for a in sf.ancestors(node))
                if not guarded:
                    found += 1
        vs.append((expect, found))
    assert all(e == f for e, f in vs)


# ----------------------------------------------------------- shared-state-lint

BAD_SHARED = """\
COUNTS = [0]

def serve():
    COUNTS[0] += 1
    COUNTS.append(2)
"""

GOOD_SHARED = """\
import threading
_LOCK = threading.Lock()
CACHE = {}
BLESSED = [0]    # shared-state-ok: test-only counter

def serve():
    with _LOCK:
        CACHE["k"] = 1
    BLESSED[0] += 1
"""


def test_shared_state_lint_flags_unguarded_mutation(tmp_path):
    vs = shared_state_lint.check_file(_source(tmp_path, BAD_SHARED))
    assert len(vs) == 2
    assert all("COUNTS" in v.message for v in vs)


def test_shared_state_lint_accepts_lock_and_annotation(tmp_path):
    assert shared_state_lint.check_file(
        _source(tmp_path, GOOD_SHARED)) == []


# --------------------------------------------------------------- repo-is-clean

def test_repo_is_lint_clean():
    """The tier-1 gate: the working tree has zero violations, so every
    future PR runs the whole suite for free."""
    vs = run_all(REPO)
    assert vs == [], "\n".join(str(v) for v in vs)


def test_entry_scripts_never_select_a_platform():
    """The entry scripts run on the device jax gives them:
    no `jax_platforms` write, no FORCE_CPU switch. A run that finds no
    accelerator must fail, never quietly become a CPU number."""
    for name in ("__graft_entry__.py", "chip_smoke.py"):
        with open(os.path.join(REPO, name)) as f:
            src = f.read()
        for needle in ("jax_platforms", "FORCE_CPU"):
            assert needle not in src, f"{name} mentions {needle}"


def test_the_kernel_census_is_no_gated_subsystem():
    """The executable census is always on and its sampled timer is gone
    (PR 31): the registry holds no row for telemetry/kernels.py."""
    rows = gate_lint.GATED_SUBSYSTEMS
    assert len(rows) == 23
    assert not [r for r in rows if r[0].endswith("telemetry/kernels.py")]


def test_one_benchmark_is_named():
    """The benchmark is BENCHMARK.json with benchmark/run.py, and the
    README says so; no source names the pre-chip driver or its records
    (PR 31 deleted them)."""
    import re
    gone = re.compile("|".join((
        "bench" r"\.py", "BENCH" r"_[A-Z]*_r0", "SCALING" "_",
        "MULTICHIP" "_r0")))
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    assert "benchmark/run.py" in readme and "BENCHMARK.json" in readme
    hits = [f"README.md:{i}" for i, line in
            enumerate(readme.splitlines(), 1) if gone.search(line)]
    for top in ("opensearch_tpu", "tools", "tests", "benchmark"):
        for root, _dirs, files in os.walk(os.path.join(REPO, top)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                with open(path) as f:
                    hits += [f"{os.path.relpath(path, REPO)}:{i}"
                             for i, line in enumerate(f, 1)
                             if gone.search(line)]
    assert hits == []


def test_runner_cli_json_exit_zero():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--json", "--root", REPO],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["violations"] == []
    assert report["rule_bits"] == RULE_BITS


def test_exit_code_is_per_rule_bitmask():
    from lint.core import Violation
    vs = [Violation("sync-lint", "x.py", 1, "m"),
          Violation("shared-state-lint", "x.py", 2, "m")]
    assert exit_code(vs) == 9
    assert exit_code([]) == 0


# ------------------------------------------------------------------- sanitizer

def test_sanitizer_catches_unattributed_device_get():
    """Negative test: a deliberately-injected unattributed device_get
    from a package frame raises; the same call inside an attributed
    region — and from a non-package (test) frame — passes."""
    import jax
    import jax.numpy as jnp

    from opensearch_tpu.common.sanitize import (SANITIZER,
                                                UnattributedSyncError)
    from opensearch_tpu.telemetry import TELEMETRY

    assert SANITIZER.enabled and SANITIZER.installed, \
        "conftest must enable the sanitizer for the tier-1 run"
    x = jnp.ones((4,), dtype=jnp.float32)
    probe = compile("jax.device_get(x)", "<sanitizer-probe>", "eval")
    pkg_frame = {"__name__": "opensearch_tpu._sanitizer_probe",
                 "jax": jax, "x": x}
    before = SANITIZER.violations
    with pytest.raises(UnattributedSyncError):
        eval(probe, pkg_frame)
    assert SANITIZER.violations == before + 1
    # attributed region: same frame, no raise
    with TELEMETRY.ledger.attributed():
        assert list(eval(probe, pkg_frame)) == [1, 1, 1, 1]
    # non-package caller (this test frame): exempt
    assert list(jax.device_get(x)) == [1, 1, 1, 1]


def test_sanitizer_gate_discipline():
    """check() is a None-returning scope gate (gate-lint registered):
    disabled means None for any caller."""
    from opensearch_tpu.common.sanitize import SyncSanitizer
    s = SyncSanitizer()
    assert s.enabled is False and not s.installed
    assert s.check("opensearch_tpu.search.executor", "jax.device_get") \
        is None
    assert s.checked == 0


def test_sanitized_search_end_to_end():
    """A real search under the sanitizer: every sync on the path is
    attributed, so the query succeeds and the sanitizer records checks
    without violations."""
    from opensearch_tpu.common.sanitize import SANITIZER
    from opensearch_tpu.search.executor import SearchExecutor, ShardReader
    from opensearch_tpu.utils.demo import build_shards

    mapper, segments = build_shards(200, n_shards=1, vocab_size=50,
                                    avg_len=12, seed=7)
    ex = SearchExecutor(ShardReader(mapper, segments))
    before = SANITIZER.violations
    res = ex.search({"query": {"match_all": {}}, "size": 3})
    assert res["hits"]["hits"]
    assert SANITIZER.violations == before
