"""Repo-specific static-analysis suite (ISSUE 8).

Four AST-based checkers enforce the invariants the ROADMAP item-1/item-2
rewrites (on-device top-k + overlapped transfers, async wave scheduler)
depend on — invariants that were previously enforced by convention
only:

- sync-lint          every host<->device sync site on the query path is
                     ledger-attributed or carries `# sync-ok: <channel>`
                     (+ the exception-breadth rule: no blanket
                     `except Exception` without `# except-ok: <reason>`)
- retrace-lint       jitted functions can't close over mutable module
                     globals, branch on tracer values, or call
                     shape-data-dependent ops
- gate-lint          OFF-by-default subsystems (tracer, fault injector,
                     transfer ledger, sync sanitizer) follow the
                     None-returning scope-gate pattern
- shared-state-lint  module-level mutable state mutated on the query
                     path must be lock-guarded, registry-owned, or
                     annotated `# shared-state-ok: <reason>`

Run via `python tools/lint.py` (or `python -m lint` with tools/ on the
path). The runtime counterpart is `opensearch_tpu/common/sanitize.py`.
"""

from .core import RULE_BITS, Violation, repo_root  # noqa: F401
from .runner import main, run_all  # noqa: F401
