"""gate-lint: OFF-by-default subsystems must follow the None-returning
scope-gate pattern — the no-op discipline as a static check.

The contract (PR 4 tracer, PR 6 fault injector, PR 7 transfer ledger,
PR 8 sync sanitizer, PR 10 flight recorder): a subsystem that is OFF by
default costs the hot path ONE attribute load and a branch. Statically
that means:

1. the flag defaults to False — `self.enabled = False` in __init__ (or
   a module-level `ENABLED = False` for the faults-style module gate);
2. every registered gate method tests the flag and returns a constant
   no-op value (None / NOOP_SPAN / a plain return) on the disabled
   branch — callers guard with `if x is not None`, nothing allocates;
3. module-flag subsystems are guarded at the CALL SITE: every
   `faults.fire(...)` in the package must sit lexically under an `if`
   that tests `faults.ENABLED` (the disabled path must never enter the
   function at all).

The registry below is the list of gated subsystems; adding a subsystem
means adding a row, and the checker fails loudly if a registered
module/class/method disappears (a silently-unchecked gate is how the
discipline rots).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from .core import (SourceFile, Violation, load_files, name_of,
                   package_files)

RULE = "gate-lint"

# (file, class or None for module-level, flag name, gate methods)
GATED_SUBSYSTEMS = (
    ("opensearch_tpu/telemetry/tracer.py", "Tracer", "enabled",
     ("start_trace",)),
    ("opensearch_tpu/telemetry/ledger.py", "TransferLedger", "enabled",
     ("scope", "new_wave")),
    ("opensearch_tpu/common/faults.py", None, "ENABLED", ()),
    ("opensearch_tpu/common/sanitize.py", "SyncSanitizer", "enabled",
     ("check",)),
    ("opensearch_tpu/telemetry/lifecycle.py", "FlightRecorder", "enabled",
     ("timeline",)),
    # ISSUE 11 admission stages: every adaptive stage of the admission
    # pipeline (quota -> breaker -> deadline shed) is OFF by default —
    # the default node keeps the static permit gate exactly
    ("opensearch_tpu/common/admission.py", "TenantQuotas", "enabled",
     ("gate",)),
    ("opensearch_tpu/common/admission.py", "DeadlineShedder", "enabled",
     ("gate",)),
    ("opensearch_tpu/common/admission.py", "DeviceMemoryBreaker",
     "enabled", ("gate",)),
    # ISSUE 12 wave scheduler: the cross-request coalescing layer is
    # OFF by default — the default node executes every search inline,
    # exactly the pre-scheduler path
    ("opensearch_tpu/search/scheduler.py", "WaveScheduler", "enabled",
     ("gate",)),
    # ISSUE 13 write-path observability: the ingest lifecycle recorder
    # and the segment-churn ledger are OFF by default — the default
    # write path pays one attribute load + branch per op (timeline/
    # current) and per refresh (scope/current)
    ("opensearch_tpu/telemetry/lifecycle.py", "IngestRecorder",
     "enabled", ("timeline", "current")),
    ("opensearch_tpu/telemetry/ledger.py", "ChurnLedger", "enabled",
     ("scope", "current")),
    # ISSUE 14 sharded-serving observability: the per-device ledger
    # (per-chip transfer/phase attribution + straggler skew) and the
    # SPMD collective-phase timeline emitter are OFF by default — the
    # default SPMD query path pays one attribute load + branch per
    # query for each. (The scan counters are deliberately ALWAYS-ON —
    # the block-max trigger metric rides the inflight-wave-gauge
    # contract, not the per-request gate discipline.)
    ("opensearch_tpu/telemetry/ledger.py", "DeviceLedger", "enabled",
     ("scope",)),
    ("opensearch_tpu/telemetry/lifecycle.py", "SpmdTimeline", "enabled",
     ("gate",)),
    # ISSUE 15 query insights: the per-shape cost recorder is OFF by
    # default — the default query path pays one attribute load + branch
    # per sub-request — and the shape-aware deadline-shed pricing is a
    # SECOND gate on the shedder (its own flag on top of `enabled`):
    # the default shed stage never computes a shape key at admission
    ("opensearch_tpu/telemetry/insights.py", "QueryInsights", "enabled",
     ("gate",)),
    ("opensearch_tpu/common/admission.py", "DeadlineShedder",
     "shape_enabled", ("shape_gate",)),
    # ISSUE 16 ingest-concurrent serving: every fix is OFF by default —
    # the default node keeps the r01 write path exactly. Precompiler:
    # None-returning gate; memo carry / windowed merge: plain False
    # flags branched at their single call site (stats rebuild / merge
    # dispatch); delta publish: faults-style module flag branched in
    # publish_segment.
    ("opensearch_tpu/search/warmup.py", "Precompiler", "enabled",
     ("gate",)),
    # barrier mode is a SECOND gate on the precompiler (shape_enabled
    # idiom): stage-and-replay-before-publish only runs when both flags
    # are on — the default publish stays the direct atomic swap
    ("opensearch_tpu/search/warmup.py", "Precompiler", "barrier", ()),
    ("opensearch_tpu/search/executor.py", "ShardReader", "memo_carry",
     ()),
    ("opensearch_tpu/index/engine.py", "InternalEngine",
     "merge_windowed", ()),
    ("opensearch_tpu/ops/device_segment.py", None, "DELTA_PUBLISH", ()),
    # single-round-trip result page (ISSUE 17): OFF by default — the
    # legacy multi-channel collect is the pristine path
    ("opensearch_tpu/search/executor.py", None, "RESULT_PAGE", ()),
    # ISSUE 18 late-interaction rerank: the device-scoring arm of
    # rescore_maxsim is OFF by default — the pristine rerank path is
    # the host numpy mirror (same f32 math, no device dispatch)
    ("opensearch_tpu/searchpipeline/processors.py", None,
     "MAXSIM_DEVICE_RESCORE", ()),
    # ISSUE 20 block-max pruning: OFF by default — the pristine query
    # path compiles no tid/bscale inputs and masks nothing; the seal-
    # time bounds leaf is always present (upload cost, not query cost)
    # so flipping the gate never re-uploads segments
    ("opensearch_tpu/ops/bm25.py", None, "BLOCKMAX", ()),
)

# no-op constants a disabled gate may return
NOOP_NAMES = {"NOOP_SPAN", "None"}


def _find_class(tree: ast.Module, name: str) -> Optional[ast.ClassDef]:
    for stmt in tree.body:
        if isinstance(stmt, ast.ClassDef) and stmt.name == name:
            return stmt
    return None


def _method(cls: ast.ClassDef, name: str) -> Optional[ast.FunctionDef]:
    for stmt in cls.body:
        if isinstance(stmt, ast.FunctionDef) and stmt.name == name:
            return stmt
    return None


def _mentions_flag(node: ast.AST, flag: str) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == flag:
            return True
        if isinstance(n, ast.Name) and n.id == flag:
            return True
    return False


def _init_defaults_false(cls: ast.ClassDef, flag: str) -> bool:
    init = _method(cls, "__init__")
    if init is None:
        # class-level default (`enabled = False`) is acceptable
        for stmt in cls.body:
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name) and t.id == flag:
                        return isinstance(stmt.value, ast.Constant) and \
                            stmt.value.value is False
        return False
    for node in ast.walk(init):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Attribute) and t.attr == flag and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self":
                    return isinstance(node.value, ast.Constant) and \
                        node.value.value is False
    # fall back to class-level default
    for stmt in cls.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name) and t.id == flag:
                    return isinstance(stmt.value, ast.Constant) and \
                        stmt.value.value is False
    return False


def _module_flag_false(tree: ast.Module, flag: str) -> bool:
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name) and t.id == flag:
                    return isinstance(stmt.value, ast.Constant) and \
                        stmt.value.value is False
    return False


def _gate_ok(fn: ast.FunctionDef, flag: str) -> bool:
    """The method tests the flag AND has a no-op return (None constant,
    a NOOP_* name, or a bare `return`) reachable for the disabled case."""
    has_guard = any(isinstance(n, (ast.If, ast.IfExp)) and
                    _mentions_flag(n.test, flag)
                    for n in ast.walk(fn))
    if not has_guard:
        return False
    for n in ast.walk(fn):
        if isinstance(n, ast.Return):
            v = n.value
            if v is None:
                return True
            if isinstance(v, ast.Constant) and v.value is None:
                return True
            if isinstance(v, ast.Name) and (v.id in NOOP_NAMES or
                                            v.id.startswith("NOOP")):
                return True
    return False


def run(root: str) -> List[Violation]:
    out: List[Violation] = []
    by_rel = {}

    def _load(rel: str) -> Optional[SourceFile]:
        if rel not in by_rel:
            files = load_files(root, [rel])
            by_rel[rel] = files[0] if files else None
        return by_rel[rel]

    for rel, cls_name, flag, gates in GATED_SUBSYSTEMS:
        sf = _load(rel)
        if sf is None:
            out.append(Violation(RULE, rel, 1,
                                 "registered gated subsystem file is "
                                 "missing"))
            continue
        if cls_name is None:
            if not _module_flag_false(sf.tree, flag):
                out.append(Violation(
                    RULE, rel, 1,
                    f"module gate flag [{flag}] must default to a "
                    f"literal False"))
            continue
        cls = _find_class(sf.tree, cls_name)
        if cls is None:
            out.append(Violation(RULE, rel, 1,
                                 f"registered gated class [{cls_name}] "
                                 f"not found"))
            continue
        if not _init_defaults_false(cls, flag):
            out.append(Violation(
                RULE, rel, cls.lineno,
                f"{cls_name}.{flag} must be initialized to a literal "
                f"False (OFF by default)"))
        for gate in gates:
            m = _method(cls, gate)
            if m is None:
                out.append(Violation(
                    RULE, rel, cls.lineno,
                    f"registered gate method {cls_name}.{gate}() not "
                    f"found"))
                continue
            if not _gate_ok(m, flag):
                out.append(Violation(
                    RULE, rel, m.lineno,
                    f"{cls_name}.{gate}() must test [{flag}] and return "
                    f"a no-op constant (None / NOOP_*) on the disabled "
                    f"branch"))

    # call-site guard for the module-flag subsystem: faults.fire()
    for sf in load_files(root, package_files(root)):
        if sf.rel.endswith("common/faults.py"):
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = name_of(node.func)
            if callee not in ("faults.fire", "fire"):
                continue
            if callee == "fire" and "faults" not in sf.text:
                continue
            guarded = any(
                isinstance(anc, ast.If) and
                _mentions_flag(anc.test, "ENABLED")
                for anc in sf.ancestors(node))
            if not guarded:
                # early-return form: an enclosing function that bails
                # out first (`if not faults.ENABLED: return ...`) guards
                # every statement after it, nested closures included
                for fn in sf.enclosing_functions(node):
                    if isinstance(fn, ast.Lambda):
                        continue
                    for stmt in fn.body:
                        if getattr(stmt, "lineno", 1 << 30) >= node.lineno:
                            break
                        if isinstance(stmt, ast.If) and \
                                _mentions_flag(stmt.test, "ENABLED") and \
                                any(isinstance(s, ast.Return)
                                    for s in ast.walk(stmt)):
                            guarded = True
                            break
                    if guarded:
                        break
            if not guarded:
                out.append(Violation(
                    RULE, sf.rel, node.lineno,
                    "faults.fire() must sit under `if faults.ENABLED:` "
                    "— the disabled hot path must not enter the call"))
    return out
