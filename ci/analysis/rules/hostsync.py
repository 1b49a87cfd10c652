#
# Host-sync-in-hot-path detector: an implicit device->host fetch —
# `float()`/`int()`/`bool()` on a jax value, `.item()`, `np.asarray`,
# `jax.device_get` — inside a loop in the solver layer
# (spark_rapids_ml_tpu/ops/, checkpoint.py) blocks the Python host on the
# device EVERY iteration and drains the dispatch queue, which is why the
# framework's loops fetch at deliberate, annotated boundaries only
# (deferred convergence checks, checkpoint cadences, out-of-core per-chunk
# accumulation) and carry `# host-fetch-ok: <reason>` there.
#
# "jax value" is tracked per function with a flow-insensitive taint pass:
#   * sources — parameters annotated `jax.Array`, results of jax/jnp/lax
#     calls, results of module-local jit-wrapped functions, blocks yielded
#     by the streaming placement helper (`stream_place_blocks`), and any
#     call fed a tainted argument (a function of device values is assumed
#     to return device values);
#   * sinks that LAUNDER — a fetch call's result is a host value, so
#     `probs = np.asarray(min_d2) * sw` taints nothing downstream;
#   * never tainted — host-metadata reads (`.shape`, `.dtype`, `len()`).
#
from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set, Tuple

from ..engine import FileContext, RuleBase, dotted

_FETCH_BUILTINS = {"float", "int", "bool"}
_NP_FETCHES = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray"}
# jax calls that RETURN host values (so assignment from them does not taint)
_HOST_RETURNING = {
    "jax.device_get",
    "jax.process_index",
    "jax.process_count",
    "jax.devices",
    "jax.local_devices",
    "jax.device_count",
    "jax.local_device_count",
}
_METADATA_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "itemsize", "nbytes"}
# framework helpers known to yield/return device-resident values even though
# their dotted names are not jax-rooted (the framework-aware part)
_KNOWN_DEVICE_FUNCS = {"stream_place_blocks"}
_JIT_TAILS = {"jit", "pmap", "vmap"}


def _is_array_annotation(ann: Optional[ast.AST]) -> bool:
    # `jax.Array` (and friends spelled `...Array`) taint; `np.ndarray` is a
    # HOST array and must not
    return ann is not None and "Array" in ast.dump(ann)


def _jax_rooted(name: Optional[str]) -> bool:
    return name is not None and (
        name == "jax" or name.startswith(("jax.", "jnp.", "lax."))
    )


def _assign_targets(target: ast.AST) -> Iterable[str]:
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
        for el in target.elts:
            yield from _assign_targets(el)
    elif isinstance(target, ast.Starred):
        yield from _assign_targets(target.value)


def _iter_scope(body: Iterable[ast.AST]) -> Iterable[ast.AST]:
    """Walk statements without descending into nested function scopes."""
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


class HostSyncRule(RuleBase):
    id = "host-sync"
    waiver = "host-fetch"
    tree_scope = ("spark_rapids_ml_tpu",)
    description = "implicit device->host fetches inside solver-layer loops"
    hot_path_dirs: Tuple[str, ...] = ("spark_rapids_ml_tpu/ops/",)
    hot_path_files: Tuple[str, ...] = ("spark_rapids_ml_tpu/checkpoint.py",)

    def applies(self, ctx: FileContext) -> bool:
        if ctx.target not in self.tree_scope:
            return False
        return ctx.relpath in self.hot_path_files or any(
            ctx.relpath.startswith(d) for d in self.hot_path_dirs
        )

    def check_module(self, tree: ast.Module, ctx: FileContext) -> None:
        # module-local functions whose results live on device: jit-decorated
        # defs and `name = jax.jit(...)`-style wrappers anywhere in the file
        self._device_funcs: Set[str] = set(_KNOWN_DEVICE_FUNCS)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._is_jit_expr(dec, ctx):
                        self._device_funcs.add(node.name)
            elif isinstance(node, ast.Assign) and self._is_jit_expr(node.value, ctx):
                for t in node.targets:
                    self._device_funcs.update(_assign_targets(t))

        self._check_scope(tree.body, ctx, params=[])
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                params = list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
                self._check_scope(node.body, ctx, params=params)

    def _is_jit_expr(self, node: ast.AST, ctx: FileContext) -> bool:
        """`@jax.jit`, `@partial(jax.jit, ...)`, `jax.jit(f, ...)`."""
        name = dotted(node, ctx.imports)
        if _jax_rooted(name) and name.split(".")[-1] in _JIT_TAILS:
            return True
        if isinstance(node, ast.Call):
            fname = dotted(node.func, ctx.imports)
            if _jax_rooted(fname) and fname.split(".")[-1] in _JIT_TAILS:
                return True
            if fname is not None and fname.split(".")[-1] == "partial" and node.args:
                return self._is_jit_expr(node.args[0], ctx)
        return False

    def _check_scope(
        self, body: Iterable[ast.AST], ctx: FileContext, params: List[ast.arg]
    ) -> None:
        taints: Set[str] = {
            p.arg for p in params if _is_array_annotation(p.annotation)
        }
        assigns: List[Tuple[List[str], ast.AST]] = []
        for node in _iter_scope(body):
            if isinstance(node, ast.Assign):
                names: List[str] = []
                for t in node.targets:
                    names.extend(_assign_targets(t))
                assigns.append((names, node.value))
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                names = list(_assign_targets(node.target))
                if _is_array_annotation(node.annotation):
                    taints.update(names)
                assigns.append((names, node.value))
            elif isinstance(node, ast.AugAssign):
                assigns.append((list(_assign_targets(node.target)), node.value))
            elif isinstance(node, ast.NamedExpr):
                assigns.append((list(_assign_targets(node.target)), node.value))
            elif isinstance(node, ast.For):
                assigns.append((list(_assign_targets(node.target)), node.iter))

        for _ in range(12):  # fixpoint over the flow-insensitive assignment set
            grew = False
            for names, value in assigns:
                if names and self._expr_tainted(value, taints, ctx):
                    new = set(names) - taints
                    if new:
                        taints.update(new)
                        grew = True
            if not grew:
                break

        seen: Set[int] = set()  # a call inside nested loops is one finding
        for node in _iter_scope(body):
            if isinstance(node, (ast.For, ast.While)):
                self._check_loop(node, ctx, taints, seen)

    def _expr_tainted(self, expr: ast.AST, taints: Set[str], ctx: FileContext) -> bool:
        """Does this expression carry a device value?"""
        if isinstance(expr, ast.Name):
            return expr.id in taints
        if isinstance(expr, ast.Attribute):
            if expr.attr in _METADATA_ATTRS:
                return False  # host-metadata read, never blocks on the device
            return self._expr_tainted(expr.value, taints, ctx)
        if isinstance(expr, ast.Call):
            name = dotted(expr.func, ctx.imports)
            if name in _HOST_RETURNING:
                return False
            if self._fetch_kind(expr, taints, ctx, require_taint=False) is not None:
                return False  # a fetch's RESULT is a host value (taint laundered)
            if isinstance(expr.func, ast.Name) and expr.func.id == "len":
                return False
            if _jax_rooted(name):
                return True
            if name is not None and name.split(".")[-1] in self._device_funcs:
                return True
            # a call fed device values is assumed to return device values
            # (`centers, _, shift = step(centers, fast)`); method calls also
            # propagate their receiver's taint (`(x + d).astype(t)`)
            parts: List[ast.AST] = list(expr.args) + [k.value for k in expr.keywords]
            if isinstance(expr.func, ast.Attribute):
                parts.append(expr.func.value)
            return any(self._expr_tainted(p, taints, ctx) for p in parts)
        if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return False
        return any(
            self._expr_tainted(child, taints, ctx)
            for child in ast.iter_child_nodes(expr)
        )

    def _fetch_kind(
        self, node: ast.Call, taints: Set[str], ctx: FileContext, require_taint: bool = True
    ) -> Optional[str]:
        name = dotted(node.func, ctx.imports)
        if name == "jax.device_get":
            return "jax.device_get(...)"
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in _FETCH_BUILTINS
            and node.func.id not in ctx.imports
            and len(node.args) == 1
        ):
            if not require_taint or self._expr_tainted(node.args[0], taints, ctx):
                return f"{node.func.id}(...)"
            return None
        if name in _NP_FETCHES and node.args:
            if not require_taint or self._expr_tainted(node.args[0], taints, ctx):
                return f"{name}(...)"
            return None
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
        ):
            if not require_taint or self._expr_tainted(node.func.value, taints, ctx):
                return ".item()"
            return None
        return None

    def _check_loop(
        self, loop: ast.AST, ctx: FileContext, taints: Set[str], seen: Set[int]
    ) -> None:
        region: List[ast.AST] = list(loop.body) + list(getattr(loop, "orelse", []))
        if isinstance(loop, ast.While):
            region.append(loop.test)  # a while-test fetch syncs every iteration too
        for node in _iter_scope(region):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            kind = self._fetch_kind(node, taints, ctx)
            if kind is None:
                continue
            ctx.emit(
                self,
                node,
                f"implicit device->host fetch (`{kind}` on a jax value) "
                "inside a solver loop — each fetch synchronizes host and "
                "device; hoist it out of the loop, defer it (see the kmeans "
                "pipelined shift check), or mark the deliberate boundary "
                "`# host-fetch-ok: <reason>`",
            )
