#
# Hand-rolled binned-accumulation detector (the `raw-distance` taint pattern
# extended to histograms, seeded for ROADMAP item 4): the RF/tree family's
# `bin ids -> (node, feature, bin) accumulation` inner loop has ONE Pallas
# histogram core, ops/histogram.py (since PR 39: the accumulate over rows
# sorted by node as the kernel `srml_hist_accumulate_bf16`; the same
# consolidation ops/distance.py performed for the neighbor family), and this
# rule is the ratchet porting lands against — private copies of the loop are
# findings from day one, so a port can delete them without new ones growing
# back.
#
#   an accumulation sink — `segment_sum`, `scatter_add`, an
#   `.at[bins].add(...)` scatter, or a one-hot matmul (`one_hot(bins) @ x`,
#   `jnp.dot(one_hot(bins).T, x)`) — whose segment/index operand was built
#   from a LOCAL binning call (`jnp.digitize`, `jnp.searchsorted`,
#   `bucketize`) is a finding anywhere in the framework outside the
#   histogram core (ops/histogram.py).
#
# Taint is function-scoped and shallow exactly like raw-distance: names
# bound to binning-derived expressions are tainted, taint flows through
# arithmetic, subscripts, `astype`/`clip`/`reshape`/`ravel` and the
# shape-preserving combinators, and any other call launders — a bin tensor
# produced by one function and accumulated by another is the factored shape
# the core owns, not a hand-rolled loop. Genuinely different
# shapes waive with `# histogram-ok: <reason>`. The baseline lands EMPTY:
# today's tree bins (ops/trees.py `_bin_features`) and accumulates
# (`_grow_level`) in separate functions, which is exactly the boundary the
# rule preserves.
#
from __future__ import annotations

import ast
from typing import List, Optional, Set

from ..engine import FileContext, RuleBase, dotted

# taint sources: calls that turn values into BIN IDS
_BINNING_TAILS = {"digitize", "searchsorted", "bucketize"}
# function-call combinators taint flows through (positional args)
_PROPAGATING_TAILS = {
    "where", "maximum", "minimum", "concatenate", "pad", "clip",
    "broadcast_to", "one_hot",
}
# method calls whose RECEIVER carries the taint through
_METHOD_PROPAGATING = {"astype", "reshape", "ravel", "flatten", "clip"}
# accumulation sinks over a binned operand
_SEGMENT_TAILS = {"segment_sum"}
_SCATTER_TAILS = {"scatter_add", "scatter_add_p"}
_DOT_TAILS = {"dot", "matmul", "einsum", "tensordot"}

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


class HistogramLoopRule(RuleBase):
    id = "histogram-loop"
    waiver = "histogram"
    tree_scope = ("spark_rapids_ml_tpu",)
    exempt_files = frozenset({"histogram.py"})  # the core owns the loop
    description = (
        "hand-rolled binned accumulation (segment_sum/scatter/one-hot-matmul "
        "over locally-binned ids) outside the histogram core"
    )

    def check_module(self, tree: ast.Module, ctx: FileContext) -> None:
        self._scope(tree.body, set(), ctx)

    # ---------------------------------------------------------- traversal --

    def _scope(self, body: List[ast.stmt], inherited: Set[str], ctx: FileContext) -> None:
        tainted: Set[str] = set(inherited)
        for stmt in body:
            self._stmt(stmt, tainted, ctx)

    def _stmt(self, stmt: ast.stmt, tainted: Set[str], ctx: FileContext) -> None:
        if isinstance(stmt, _FUNC_NODES) or isinstance(stmt, ast.ClassDef):
            self._scope(stmt.body, tainted, ctx)
            return
        if isinstance(stmt, (ast.If, ast.For, ast.AsyncFor, ast.While,
                             ast.With, ast.AsyncWith, ast.Try)):
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    for node in ast.walk(child):
                        if isinstance(node, ast.Call):
                            self._check_call(node, tainted, ctx)
                elif isinstance(child, ast.withitem):
                    for node in ast.walk(child.context_expr):
                        if isinstance(node, ast.Call):
                            self._check_call(node, tainted, ctx)
            if isinstance(stmt, (ast.For, ast.AsyncFor)) and self._tainted(
                stmt.iter, tainted
            ):
                tainted.update(
                    n.id for n in ast.walk(stmt.target) if isinstance(n, ast.Name)
                )
            for field in ("body", "orelse", "finalbody"):
                for sub in getattr(stmt, field, []) or []:
                    self._stmt(sub, tainted, ctx)
            for handler in getattr(stmt, "handlers", []) or []:
                for sub in handler.body:
                    self._stmt(sub, tainted, ctx)
            return
        nested = [n for n in ast.walk(stmt) if isinstance(n, _FUNC_NODES)]
        skip: Set[int] = set()
        for fn in nested:
            for sub in ast.walk(fn):
                if sub is not fn:
                    skip.add(id(sub))
        for node in ast.walk(stmt):
            if id(node) in skip:
                continue
            if isinstance(node, ast.Call):
                self._check_call(node, tainted, ctx)
            elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
                self._check_matmul(node.left, node.right, node, tainted, ctx)
        for node in ast.walk(stmt):
            if id(node) in skip:
                continue
            targets: List[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets, value = [node.target], node.value
            elif isinstance(node, ast.NamedExpr):
                targets, value = [node.target], node.value
            if value is None:
                continue
            tnt = self._tainted(value, tainted)
            for t in targets:
                names = [n.id for n in ast.walk(t) if isinstance(n, ast.Name)]
                if tnt:
                    tainted.update(names)
                elif isinstance(node, ast.Assign) and isinstance(t, ast.Name):
                    tainted.discard(t.id)  # clean rebinding
        for fn in nested:
            self._scope(fn.body, tainted, ctx)

    # ------------------------------------------------------------- sinks ---

    def _check_call(self, node: ast.Call, tainted: Set[str], ctx: FileContext) -> None:
        name = dotted(node.func, ctx.imports)
        tail = name.split(".")[-1] if name else None
        if tail in _SEGMENT_TAILS and len(node.args) > 1:
            if self._tainted(node.args[1], tainted):
                self._emit(node, "segment_sum over locally-binned segment ids", ctx)
            return
        if tail in _SCATTER_TAILS and any(
            self._tainted(a, tainted) for a in node.args
        ):
            self._emit(node, "scatter-add over locally-binned indices", ctx)
            return
        if tail in _DOT_TAILS and name is not None:
            args = [
                a for a in node.args
                if not (isinstance(a, ast.Constant) and isinstance(a.value, str))
            ]
            self._check_matmul(
                args[0] if args else None,
                args[1] if len(args) > 1 else None, node, tainted, ctx,
            )
            return
        # `.at[bins].add(...)`: Call(add) over Subscript over `.at`
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "add"
            and isinstance(node.func.value, ast.Subscript)
            and isinstance(node.func.value.value, ast.Attribute)
            and node.func.value.value.attr == "at"
        ):
            if self._tainted(node.func.value.slice, tainted):
                self._emit(node, ".at[bins].add(...) over locally-binned indices", ctx)

    def _check_matmul(
        self, left: Optional[ast.expr], right: Optional[ast.expr],
        node: ast.AST, tainted: Set[str], ctx: FileContext,
    ) -> None:
        for side in (left, right):
            if side is not None and self._tainted(side, tainted):
                self._emit(node, "one-hot matmul over locally-binned ids", ctx)
                return

    def _emit(self, node: ast.AST, what: str, ctx: FileContext) -> None:
        ctx.emit(
            self,
            node,
            f"{what} — hand-rolled binned accumulation is the pattern the "
            "shared histogram core will own (ROADMAP item 4, the "
            "ops/distance.py consolidation shape); keep binning and "
            "accumulation behind the core boundary, or mark "
            "`# histogram-ok: <reason>`",
        )

    # --------------------------------------------------------------- taint --

    def _tainted(self, node: Optional[ast.expr], tainted: Set[str]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in tainted
        if isinstance(node, ast.BinOp):
            return self._tainted(node.left, tainted) or self._tainted(node.right, tainted)
        if isinstance(node, ast.UnaryOp):
            return self._tainted(node.operand, tainted)
        if isinstance(node, ast.Attribute):
            return self._tainted(node.value, tainted)  # `bins.T`, `oh.T`
        if isinstance(node, ast.Subscript):
            return self._tainted(node.value, tainted)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self._tainted(e, tainted) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self._tainted(node.body, tainted) or self._tainted(node.orelse, tainted)
        if isinstance(node, ast.Call):
            name = dotted(node.func, None)
            tail = name.split(".")[-1] if name else None
            if tail is None and isinstance(node.func, ast.Attribute):
                tail = node.func.attr
            if tail in _BINNING_TAILS:
                return True
            if tail in _PROPAGATING_TAILS or tail in _METHOD_PROPAGATING:
                if any(self._tainted(a, tainted) for a in node.args):
                    return True
                # method form: `bins.astype(i32)` carries the receiver's taint
                return isinstance(node.func, ast.Attribute) and self._tainted(
                    node.func.value, tainted
                )
            return False  # any other call launders (incl. the future core)
        return False
