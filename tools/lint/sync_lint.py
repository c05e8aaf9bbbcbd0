"""sync-lint: every host<->device sync site on the query path must be
lexically inside a LedgerScope-carrying function or carry an explicit
`# sync-ok: <channel>` annotation naming its ledger channel.

Unattributed syncs are exactly what re-opened the bytes_to_device=0 gap
PR 7 closed: a `jax.device_get` (or an implicit sync — device-array
`.tolist()`, `np.asarray` on a device value, `.block_until_ready()`)
that no LedgerScope sees is a transfer no profile's decomposition
can explain, and a wall the ROADMAP item-1 rewrite cannot budget.

A function is "LedgerScope-carrying" when it demonstrably participates
in ledger attribution:
  - it takes a `scope` / `ledger_scope` / `ledger` parameter, or
  - its body calls the TransferLedger API (`note_device_get`, or
    `record`/`scope`/`ambient`/`attributed`/`tagged`/`current`/
    `new_wave` on a ledger-named object), or references `LedgerScope`,
    or
  - it BINDS a scope-named local — `state, scope = queue.get()`,
    `scope = wave.scope`, `for _, scope in pending:` — or passes a
    `scope=`/`ledger_scope=` keyword onward. This is the collector-
    thread pattern (the overlapped wave pipeline): a scope handed
    across a queue/thread boundary still counts as attribution, since
    the worker re-binds the request's LedgerScope before syncing.
Nested closures inherit: a `_collect` defined inside an attributing
function is attributed (the scope is in lexical reach).

The same walker owns the exception-breadth rule (`except-breadth`):
a blanket `except Exception` / bare `except` on the query path must be
narrowed to typed errors (common/errors.py, the PR 6 retry allowlist)
or carry `# except-ok: <reason>`.
"""

from __future__ import annotations

import ast
from typing import List

from .core import (QUERY_PATH_FILES, SourceFile, Violation, func_params,
                   load_files, name_of)

SYNC_RULE = "sync-lint"
EXCEPT_RULE = "except-breadth"

# parameter names that mark a function as receiving request attribution
SCOPE_PARAMS = {"scope", "ledger_scope", "ledger", "led_scope"}
# attribute calls that mark a function as performing attribution, when
# made on a ledger-named receiver
LEDGER_METHODS = {"record", "scope", "ambient", "attributed", "tagged",
                  "current", "new_wave"}
LEDGER_RECEIVERS = {"ledger", "_ledger", "led"}

BROAD_EXC = {"Exception", "BaseException"}


def _ledger_receiver(node: ast.expr) -> bool:
    """True when the receiver expression names the ledger (`_LEDGER`,
    `ledger`, `TELEMETRY.ledger`, `_tel.ledger`, ...)."""
    name = name_of(node).lower()
    if not name:
        return False
    last = name.split(".")[-1]
    return last in LEDGER_RECEIVERS or "ledger" in last


def _binds_scope_name(node: ast.AST) -> bool:
    """True when an assignment/loop target binds a scope-named local —
    the queue/thread-boundary handoff of the collector pattern."""
    targets: List[ast.expr] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, ast.AnnAssign):
        targets = [node.target]
    elif isinstance(node, ast.NamedExpr):
        targets = [node.target]
    elif isinstance(node, ast.For):
        targets = [node.target]
    elif isinstance(node, ast.withitem) and node.optional_vars is not None:
        targets = [node.optional_vars]
    for t in targets:
        for leaf in ast.walk(t):
            if isinstance(leaf, ast.Name) and leaf.id in SCOPE_PARAMS:
                return True
    return False


def is_ledger_carrying(fn) -> bool:
    """Does this def/lambda carry a LedgerScope (see module docstring)?"""
    if not isinstance(fn, ast.Lambda):
        if any(p in SCOPE_PARAMS for p in func_params(fn)):
            return True
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id == "LedgerScope":
            return True
        if isinstance(node, ast.Attribute):
            if node.attr == "note_device_get":
                return True
            if node.attr in LEDGER_METHODS and _ledger_receiver(node.value):
                return True
        if _binds_scope_name(node):
            return True
        if isinstance(node, ast.Call):
            # forwarding a scope keyword marks participation the same
            # way receiving the parameter does
            if any(kw.arg in SCOPE_PARAMS for kw in node.keywords
                   if kw.arg is not None):
                return True
    return False


def _sync_kind(call: ast.Call) -> str:
    """'' when this call is not a sync site, else a label for the
    finding message."""
    fn = call.func
    if not isinstance(fn, ast.Attribute):
        return ""
    if fn.attr == "device_get" and isinstance(fn.value, ast.Name) \
            and fn.value.id == "jax":
        return "jax.device_get"
    if fn.attr == "block_until_ready":
        return ".block_until_ready()"
    if fn.attr == "tolist":
        return ".tolist()"
    if fn.attr == "asarray" and isinstance(fn.value, ast.Name) \
            and fn.value.id in ("np", "numpy", "_np"):
        return "np.asarray"
    return ""


def check_file(sf: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(sf.tree):
        # ---- sync sites -------------------------------------------------
        if isinstance(node, ast.Call):
            kind = _sync_kind(node)
            if kind:
                ann = sf.annotation_for(node, "sync-ok")
                if ann is not None:
                    if ann.channel is None:
                        out.append(Violation(
                            SYNC_RULE, sf.rel, node.lineno,
                            f"malformed sync-ok annotation "
                            f"[{ann.value!r}]: first token must be a "
                            f"ledger channel name"))
                    continue
                if any(is_ledger_carrying(f)
                       for f in sf.enclosing_functions(node)):
                    continue
                out.append(Violation(
                    SYNC_RULE, sf.rel, node.lineno,
                    f"{kind} outside any LedgerScope-carrying function; "
                    f"attribute it to the transfer ledger or annotate "
                    f"`# sync-ok: <channel>`"))
        # ---- exception breadth ------------------------------------------
        if isinstance(node, ast.ExceptHandler):
            broad = node.type is None or (
                isinstance(node.type, ast.Name)
                and node.type.id in BROAD_EXC) or (
                isinstance(node.type, ast.Tuple)
                and any(isinstance(e, ast.Name) and e.id in BROAD_EXC
                        for e in node.type.elts))
            if not broad:
                continue
            # a handler that only re-raises narrows nothing and hides
            # nothing — allowed without annotation
            if len(node.body) == 1 and isinstance(node.body[0], ast.Raise) \
                    and node.body[0].exc is None:
                continue
            if sf.annotation_for(node, "except-ok") is not None:
                continue
            label = "bare except" if node.type is None \
                else "except Exception"
            out.append(Violation(
                EXCEPT_RULE, sf.rel, node.lineno,
                f"{label} on the query path: narrow to typed errors "
                f"(common/errors.py / the retry allowlist) or annotate "
                f"`# except-ok: <reason>`"))
    return out


def run(root: str) -> List[Violation]:
    out: List[Violation] = []
    for sf in load_files(root, QUERY_PATH_FILES):
        out.extend(check_file(sf))
    return out
