"""KAI004: unguarded device dispatch.

Every kernel invocation from host code must route through
``Session.dispatch_kernel`` — that is where the watchdog deadline,
bounded retry, circuit breaker, and CPU degradation live (PR 1).  A
direct call to a jitted kernel bypasses all of it: a hung device wedges
the scheduling cycle with no deadline and no breaker trip.

The kernel surface itself comes from the SHARED discovery module
``tools/kailint/jitsurface.py`` (the lockscope pattern): pass 1 scans
``ops/`` and ``parallel/`` modules for top-level functions that are
jit/pjit/Pallas-compiled OR (transitively) call a compiled sibling —
host-facing wrappers like ``allocate_grouped`` dispatch to the device
even though the ``@jit`` sits on an inner kernel.  kaijit (the
compilation-contract analyzer) consumes the same surface, so the two
tools cannot drift.  Pass 2 then flags any call to one of those names
from host layers, resolving ``from ..ops.x import k`` aliases and
``from ..ops import x as m; m.k(...)`` module aliases.  Calls inside a
``lambda`` are exempt — that is precisely the thunk handed to
``dispatch_kernel`` — and so are calls inside a named nested function
that is itself passed to a ``dispatch_kernel(...)`` call (the
multi-statement thunk idiom) or to ``Session._dispatch_and_fetch(...)``,
the pipelined form of the same guarded dispatch.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..astutil import dotted_name, in_path
from ..engine import Finding, ModuleContext, Rule
from ..jitsurface import (ModuleSurface, collect_module_surface,
                          kernel_aliases)


class UnguardedDispatchRule(Rule):
    id = "KAI004"
    name = "unguarded-dispatch"
    description = ("direct kernel call bypassing Session.dispatch_kernel "
                   "(no watchdog, no breaker, no CPU fallback)")

    def __init__(self):
        # module dotted name -> its discovered kernel surface
        self.surfaces: dict[str, ModuleSurface] = {}

    def applies_to(self, ctx: ModuleContext) -> bool:
        return True

    def collect(self, ctx: ModuleContext) -> None:
        surface = collect_module_surface(ctx.tree, ctx.lines,
                                         ctx.module_name, ctx.path)
        if surface is not None:
            self.surfaces[ctx.module_name] = surface

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        # ops/parallel modules compose kernels freely (they ARE the
        # device layer); the guard boundary is everything else.
        if in_path(ctx.path, "ops", "parallel") or \
                ctx.path.endswith("utils/deviceguard.py"):
            return
        direct, mod_alias = kernel_aliases(ctx.tree, ctx.module_name,
                                           self.surfaces)
        if not direct and not mod_alias:
            return
        thunks = self._dispatch_thunk_names(ctx.tree)
        yield from self._walk(ctx, ctx.tree, direct, mod_alias,
                              thunks, in_thunk=False)

    @staticmethod
    def _dispatch_thunk_names(tree: ast.AST) -> set[str]:
        """Names of functions passed (as a bare Name argument) to a
        ``dispatch_kernel(...)`` or ``_dispatch_and_fetch(...)`` call —
        named thunks are guarded."""
        out: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("dispatch_kernel",
                                       "_dispatch_and_fetch"):
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        out.add(arg.id)
        return out

    def _walk(self, ctx: ModuleContext, node: ast.AST, direct: dict,
              mod_alias: dict, thunks: set[str],
              in_thunk: bool) -> Iterator[Finding]:
        for child in ast.iter_child_nodes(node):
            child_in_thunk = in_thunk or isinstance(child, ast.Lambda) \
                or (isinstance(child, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
                    and child.name in thunks)
            if isinstance(child, ast.Call) and not child_in_thunk:
                name = dotted_name(child.func)
                flagged = None
                if name in direct:
                    flagged = direct[name][1]
                elif name and "." in name:
                    base, attr = name.split(".", 1)
                    mod = mod_alias.get(base)
                    if mod is not None and \
                            attr in self.surfaces[mod].kernels:
                        flagged = name
                if flagged:
                    yield self.finding(
                        ctx, child,
                        f"direct call to device kernel `{flagged}` — "
                        f"wrap it in a thunk and route through "
                        f"Session.dispatch_kernel")
            yield from self._walk(ctx, child, direct, mod_alias,
                                  thunks, child_in_thunk)
