"""KAI002: host sync in the hot path.

``block_until_ready`` / ``device_get`` make the host wait for the device
(and ``device_get`` adds a transfer).  The device-guard is the ONE commit
point allowed to sync — it owns the watchdog deadline that makes a hung
sync recoverable (PR 1).  Anywhere else, a sync silently serializes the
pipelined cycle and bypasses the watchdog: a dead device hangs the
scheduler instead of tripping the breaker.

``print`` in hot-path modules (ops/, parallel/, framework/, actions/,
plugins/) is flagged too: printing a traced array forces the same sync,
and the repo's ScopedLogger is the sanctioned output path.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..astutil import dotted_name, in_path, iter_calls
from ..engine import Finding, ModuleContext, Rule

# The device-guard IS the commit point: its _sync() is where the
# watchdog-supervised materialization happens by design.
ALLOWLIST = ("utils/deviceguard.py",)

_SYNC_ATTRS = {"block_until_ready", "device_get"}
_PRINT_SCOPE = ("ops", "parallel", "framework", "actions", "plugins")


class HostSyncRule(Rule):
    id = "KAI002"
    name = "host-sync-in-hot-path"
    description = ("block_until_ready/device_get outside the device-guard "
                   "commit point; print in hot-path modules")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        allowed = any(ctx.path.endswith(a) for a in ALLOWLIST)
        hot = in_path(ctx.path, *_PRINT_SCOPE)
        for call in iter_calls(ctx.tree):
            name = dotted_name(call.func) or ""
            attr = call.func.attr if \
                isinstance(call.func, ast.Attribute) else name
            if not allowed and attr in _SYNC_ATTRS:
                yield self.finding(
                    ctx, call,
                    f"`{attr}` outside the device-guard commit point — "
                    f"route the dispatch through Session.dispatch_kernel "
                    f"so the watchdog supervises the sync")
            elif hot and name == "print":
                yield self.finding(
                    ctx, call,
                    "print() in a hot-path module — printing a traced "
                    "array forces a device sync; use the ScopedLogger")
