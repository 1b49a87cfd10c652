#
# AST port of the regex-era perf_counter rule: stage timing inside the
# framework goes through telemetry spans (spark_rapids_ml_tpu/telemetry.py),
# not hand-rolled perf_counter deltas — ad-hoc timing is invisible to the
# registry/JSONL sinks and drifts from the span naming scheme. The AST form
# matches actual references to `time.perf_counter` (call or bare handle,
# through any import alias), so the string "perf_counter" in a comment or
# docstring no longer trips the gate.
#
from __future__ import annotations

import ast

from ..engine import FileContext, RuleBase, dotted


class PerfCounterRule(RuleBase):
    id = "bare-perf-counter"
    waiver = "telemetry"
    tree_scope = ("spark_rapids_ml_tpu",)
    # the clock owners: telemetry spans and the efficiency attribution plane
    exempt_files = frozenset({"telemetry.py", "efficiency.py"})
    description = "bare time.perf_counter timing outside telemetry.py"

    def check_module(self, tree: ast.Module, ctx: FileContext) -> None:
        for node in ast.walk(tree):
            if isinstance(node, (ast.Attribute, ast.Name)):
                # a bare HANDLE (`clock = time.perf_counter`) is as much a
                # bypass as a call, so references match, not just Calls; the
                # _ns variant kept regex-era coverage ("perf_counter" was a
                # substring match)
                if dotted(node, ctx.imports) in (
                    "time.perf_counter",
                    "time.perf_counter_ns",
                ):
                    ctx.emit(
                        self,
                        node,
                        "bare perf_counter timing in the framework — use "
                        "telemetry.span()/registry (or mark "
                        "`# telemetry-ok: <reason>`)",
                    )
