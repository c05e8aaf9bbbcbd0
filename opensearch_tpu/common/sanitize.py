"""Test-time host-sync sanitizer: the runtime counterpart of sync-lint.

sync-lint (tools/lint/) proves LEXICALLY that every sync site on the
query path lives in LedgerScope-carrying code; this module proves it
DYNAMICALLY: when enabled, `jax.device_get` (and `jax.block_until_ready`
where present) is wrapped so that any call made from inside the
`opensearch_tpu` package while no ledger-attributed region is active on
the calling thread raises `UnattributedSyncError` instead of silently
moving bytes. "Attributed region" is the transfer ledger's thread-local
marker (`TransferLedger.attributed` / `ambient` / `tagged` — see
telemetry/ledger.py): exactly the regions whose transfers a profile's
decomposition can explain. Calls from tests, tools and bench probes are
exempt — the contract binds the serving code, not its harnesses.

Wired in one place: tests/conftest.py enables it for the whole tier-1
run, so ANY new unattributed sync on the query path fails the suite.

No-op discipline (gate-lint registered): the sanitizer is OFF by
default; while disabled nothing is wrapped at all — `jax.device_get` is
the pristine function and the query path pays literally zero. `check()`
is the None-returning scope gate the wrapper calls when installed.
"""

from __future__ import annotations

import sys
from typing import Optional


class UnattributedSyncError(AssertionError):
    """A host<->device sync executed on the query path outside any
    ledger-attributed region — the PR 7 bytes_to_device=0 gap, caught at
    the moment it happens instead of in a profile review."""


class SyncSanitizer:
    """Wraps jax's sync entry points with an attribution check."""

    def __init__(self):
        self.enabled = False
        self._originals: dict = {}
        self.checked = 0
        self.violations = 0

    # ------------------------------------------------------------ lifecycle

    @property
    def installed(self) -> bool:
        return bool(self._originals)

    def install(self) -> None:
        """Monkeypatch jax.device_get / jax.block_until_ready. Idempotent;
        separate from `enabled` so tests can install once per session and
        toggle cheaply."""
        import jax
        if self._originals:
            return
        for name in ("device_get", "block_until_ready"):
            orig = getattr(jax, name, None)
            if orig is None:
                continue
            self._originals[name] = orig
            setattr(jax, name, self._wrap(orig, f"jax.{name}"))

    def uninstall(self) -> None:
        import jax
        for name, orig in self._originals.items():
            # only restore what is still ours: a test that wrapped our
            # wrapper (test_transfer_ledger does) restores itself first
            current = getattr(jax, name, None)
            if getattr(current, "__sanitizer_original__", None) is orig:
                setattr(jax, name, orig)
        self._originals.clear()

    # ------------------------------------------------------------- checking

    def check(self, caller_module: str, label: str) -> Optional[str]:
        """The scope gate: None when the sync is allowed (sanitizer off,
        caller outside the package, or an attributed region is active),
        else a violation message."""
        if not self.enabled:
            return None
        if caller_module.split(".", 1)[0] != "opensearch_tpu":
            return None
        self.checked += 1
        from opensearch_tpu.telemetry import TELEMETRY
        if TELEMETRY.ledger.attribution_depth() > 0:
            return None
        self.violations += 1
        return (f"unattributed {label} from [{caller_module}]: sync "
                f"executed outside any ledger-attributed region "
                f"(LEDGER.attributed/ambient/tagged) — every query-path "
                f"transfer must be channel-attributed (PR 7 contract; "
                f"see tools/lint sync-lint)")

    def _wrap(self, orig, label: str):
        sanitizer = self

        def guarded(*args, **kwargs):
            if sanitizer.enabled:
                mod = sys._getframe(1).f_globals.get("__name__", "")
                msg = sanitizer.check(mod, label)
                if msg is not None:
                    raise UnattributedSyncError(msg)
            return orig(*args, **kwargs)

        guarded.__sanitizer_original__ = orig
        guarded.__name__ = getattr(orig, "__name__", label)
        guarded.__doc__ = getattr(orig, "__doc__", None)
        return guarded

    def stats(self) -> dict:
        return {"enabled": self.enabled, "installed": self.installed,
                "checked": self.checked, "violations": self.violations}


SANITIZER = SyncSanitizer()
