#
# Device-timing scope rule (PR 17): the efficiency plane
# (ops_plane/efficiency.py, fed through the telemetry.py hooks) is the ONE
# owner of device-time attribution. Hand-rolled device timing anywhere else
# — a `jax.profiler.*` reference, or the classic
# `t0 = perf_counter(); ...; x.block_until_ready(); perf_counter() - t0`
# idiom — produces numbers the attribution ledger never sees, double-syncs
# boundaries the plane already times, and drifts from the execute/compile/
# host/idle split docs/observability.md documents.
#
# Two findings:
#   * any `jax.profiler.*` reference (trace, TraceAnnotation, start_trace,
#     ...) outside the exempt owners — the profiler surface is wrapped by
#     telemetry.span()/fit_scope and the SRML_PROFILE_DIR hook in core.py
#     (waived there: it IS the sanctioned whole-fit trace entry point);
#   * a `time.perf_counter` reference in a function whose IMMEDIATE body
#     also references `block_until_ready` — the sync-then-clock device-
#     timing idiom. Scoped to the immediate body (nested defs excluded) so
#     timing a closure that syncs internally does not double-report; the
#     PerfCounterRule still covers plain perf_counter use.
#
# Waiver: `# profiler-ok: <reason>`. Baseline: EMPTY — the tree is clean at
# introduction and stays clean.
#
from __future__ import annotations

import ast
from typing import List, Tuple

from ..engine import FileContext, RuleBase, dotted


class ProfilerScopeRule(RuleBase):
    id = "profiler-scope"
    waiver = "profiler"
    tree_scope = ("spark_rapids_ml_tpu",)
    exempt_files = frozenset({"telemetry.py", "efficiency.py"})  # the attribution owners
    description = (
        "hand-rolled device timing (jax.profiler.* or perf_counter around "
        "block_until_ready) outside the efficiency plane"
    )

    def check_module(self, tree: ast.Module, ctx: FileContext) -> None:
        # ONE finding per reference: `jax.profiler.trace` matches on the
        # outermost attribute only (its inner `jax.profiler` value node
        # would double-report — ast.walk is breadth-first, so the outer
        # node is seen first and its descendants are skipped)
        inner: set = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.Attribute, ast.Name)) and id(node) not in inner:
                d = dotted(node, ctx.imports)
                if d and (d == "jax.profiler" or d.startswith("jax.profiler.")):
                    for child in ast.walk(node):
                        if child is not node:
                            inner.add(id(child))
                    ctx.emit(
                        self,
                        node,
                        "direct jax.profiler use in the framework — device "
                        "timing goes through telemetry.device_wait()/"
                        "span() and the efficiency plane (or mark "
                        "`# profiler-ok: <reason>`)",
                    )
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_function(node, ctx)

    def _immediate_refs(
        self, fn: ast.AST
    ) -> List[Tuple[ast.AST, str]]:
        """(node, dotted-or-attr-name) pairs in `fn`'s immediate body —
        nested function/class bodies excluded, so a closure that syncs
        internally doesn't mark its enclosing function as device-timing."""
        out: List[Tuple[ast.AST, str]] = []
        stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue
            if isinstance(node, (ast.Attribute, ast.Name)):
                name = (
                    node.attr if isinstance(node, ast.Attribute) else node.id
                )
                out.append((node, name))
            stack.extend(ast.iter_child_nodes(node))
        return out

    def _check_function(
        self, fn: ast.AST, ctx: FileContext
    ) -> None:
        refs = self._immediate_refs(fn)
        if not any(name == "block_until_ready" for _, name in refs):
            return
        for node, _name in refs:
            if dotted(node, ctx.imports) in (
                "time.perf_counter",
                "time.perf_counter_ns",
            ):
                ctx.emit(
                    self,
                    node,
                    "perf_counter around block_until_ready — the sync-then-"
                    "clock device-timing idiom belongs to the efficiency "
                    "plane: use telemetry.device_wait(stage) (or mark "
                    "`# profiler-ok: <reason>`)",
                )
