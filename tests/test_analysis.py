#
# Fixture corpus for the AST analysis gate (ci/analysis): per rule, at least
# one true-positive snippet and one false-positive guard — including the
# regex-era false-positive class, pinned as a regression: trigger text
# inside comments, docstrings, and string literals must NOT fire under the
# AST ports. Plus baseline ratchet behavior (new finding fails, baselined
# finding passes, fixed finding shrinks the baseline) and JSON verdict
# schema validation.
#
import json
import pathlib
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from ci.analysis import RegistrySources, analyze_source  # noqa: E402
from ci.analysis import baseline as baseline_mod  # noqa: E402
from ci.analysis.cli import main as cli_main  # noqa: E402
from ci.analysis.rules import (  # noqa: E402
    BlockingRule,
    ConfigKeyRule,
    ExporterScopeRule,
    HostSyncRule,
    HygieneRule,
    JsonlRule,
    MemStatsRule,
    MetricNameRule,
    PadRowsRule,
    PerfCounterRule,
    ProfilerScopeRule,
    RawDistanceRule,
    LedgerBypassRule,
    ServeDispatchRule,
    SleepRule,
    SpmdDivergenceRule,
    TracedImpurityRule,
    WallclockDeadlineRule,
)


def run(src, rule_factory, relpath="spark_rapids_ml_tpu/snippet.py", sources=None):
    return analyze_source(
        textwrap.dedent(src), relpath=relpath, rules=[rule_factory()], sources=sources
    )


def rule_ids(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------------
# legacy rule ports: true positives
# --------------------------------------------------------------------------


def test_perf_counter_true_positive():
    fs = run("import time\nt0 = time.perf_counter()\n", PerfCounterRule)
    assert rule_ids(fs) == ["bare-perf-counter"]
    assert fs[0].line == 2


def test_perf_counter_alias_still_caught():
    fs = run("from time import perf_counter as pc\nt = pc()\n", PerfCounterRule)
    assert rule_ids(fs) == ["bare-perf-counter"]


def test_profiler_scope_jax_profiler_true_positive():
    fs = run(
        """
        import jax
        def f(d):
            with jax.profiler.trace(d):
                pass
        """,
        ProfilerScopeRule,
    )
    assert rule_ids(fs) == ["profiler-scope"]


def test_profiler_scope_sync_then_clock_true_positive():
    fs = run(
        """
        import time
        def f(x):
            t0 = time.perf_counter()
            x.block_until_ready()
            return time.perf_counter() - t0
        """,
        ProfilerScopeRule,
    )
    assert rule_ids(fs) == ["profiler-scope"] * 2


def test_profiler_scope_waiver_and_exempt_files():
    src = """
    import jax
    def f(d):
        with jax.profiler.trace(d):  # profiler-ok: the sanctioned hook
            pass
    """
    assert run(src, ProfilerScopeRule) == []
    # the attribution owners are exempt wholesale
    bare = """
    import time
    def f(x):
        t0 = time.perf_counter()
        x.block_until_ready()
        return time.perf_counter() - t0
    """
    for owner in (
        "spark_rapids_ml_tpu/telemetry.py",
        "spark_rapids_ml_tpu/ops_plane/efficiency.py",
    ):
        assert run(bare, ProfilerScopeRule, relpath=owner) == []


def test_profiler_scope_false_positive_guards():
    # perf_counter WITHOUT a sync in the same immediate body: not this
    # rule's finding (PerfCounterRule owns plain perf_counter use)
    fs = run(
        "import time\ndef f():\n    return time.perf_counter()\n",
        ProfilerScopeRule,
    )
    assert fs == []
    # a sync inside a NESTED function doesn't mark the enclosing timer as
    # device-timing (a timer over a closure that syncs internally)
    fs = run(
        """
        import time
        def timer(run):
            def run_once():
                run().block_until_ready()
            t0 = time.perf_counter()
            run_once()
            return time.perf_counter() - t0
        """,
        ProfilerScopeRule,
    )
    assert fs == []
    # trigger text in comments/docstrings never fires the AST rule
    fs = run(
        '"""uses jax.profiler.trace and time.perf_counter"""\n'
        "# jax.profiler.start_trace idiom\n",
        ProfilerScopeRule,
    )
    assert fs == []


def test_blocking_while_true_and_bare_wait():
    fs = run(
        """
        def f(ev):
            while True:
                ev.wait()
        """,
        BlockingRule,
    )
    assert rule_ids(fs) == ["unbounded-blocking"] * 2


def test_blocking_bounded_wait_passes():
    fs = run("def f(ev):\n    ev.wait(5.0)\n    ev.wait(timeout=5.0)\n", BlockingRule)
    assert fs == []


def test_blocking_explicit_none_timeout_is_still_unbounded():
    fs = run("def f(ev):\n    ev.wait(None)\n    ev.wait(timeout=None)\n", BlockingRule)
    assert rule_ids(fs) == ["unbounded-blocking"] * 2


def test_jsonl_bypass_true_positive():
    fs = run(
        """
        import json
        def f(fh, rec):
            fh.write(json.dumps(rec) + "\\n")
        """,
        JsonlRule,
    )
    # ONE violation = ONE finding (the .write and the `+ "\n"` concat are
    # the same line; double-reporting would corrupt the baseline ratchet)
    assert rule_ids(fs) == ["jsonl-bypass"]


def test_jsonl_plain_dump_passes():
    fs = run(
        "import json\ndef f(fh, rec):\n    json.dump(rec, fh)\n    s = json.dumps(rec)\n",
        JsonlRule,
    )
    assert fs == []


def test_sleep_true_positive_including_alias():
    fs = run("import time as _t\n_t.sleep(2)\n", SleepRule)
    assert rule_ids(fs) == ["bare-sleep"]


def test_memstats_true_positive_and_owner_exempt():
    src = "def f(d):\n    return d.memory_stats()\n"
    assert rule_ids(run(src, MemStatsRule)) == ["direct-memstats"]
    assert run(src, MemStatsRule, relpath="spark_rapids_ml_tpu/memory.py") == []


def test_pad_rows_true_positive_and_bucket_passes():
    assert rule_ids(run("y = pad_rows(x, 8)\n", PadRowsRule)) == ["raw-pad-rows"]
    assert run("y = bucket_rows(x)\n", PadRowsRule) == []
    assert run("y = pad_rows(x, 8)\n", PadRowsRule, relpath="spark_rapids_ml_tpu/parallel/mesh.py") == []


# --------------------------------------------------------------------------
# raw-distance: hand-rolled x·cᵀ → argmin/top-k outside ops/distance.py
# --------------------------------------------------------------------------


def test_raw_distance_inline_matmul_argmin_fires():
    src = """
    import jax.numpy as jnp
    def assign(x, c):
        return jnp.argmin(jnp.sum(c * c, 1)[None, :] - 2.0 * x @ c.T, axis=1)
    """
    assert rule_ids(run(src, RawDistanceRule)) == ["raw-distance"]


def test_raw_distance_tainted_local_through_where_and_concat_fires():
    src = """
    import jax
    import jax.numpy as jnp
    def tile(q, items, valid, best):
        d2 = jnp.sum(items * items, 1)[None, :] - 2.0 * (q @ items.T)
        d2 = jnp.where(valid[None, :], d2, jnp.inf)
        cat = jnp.concatenate([best, d2], axis=1)
        return jax.lax.top_k(-cat, 4)
    """
    assert rule_ids(run(src, RawDistanceRule)) == ["raw-distance"]


def test_raw_distance_einsum_taint_and_method_argmin_fire():
    src = """
    import jax.numpy as jnp
    def f(q, bucket):
        d2 = -2.0 * jnp.einsum("bld,bd->bl", bucket, q)
        return d2.argmin(axis=1)
    """
    assert rule_ids(run(src, RawDistanceRule)) == ["raw-distance"]


def test_raw_distance_binding_inside_if_block_fires():
    # regression: a binding and its reduction inside ONE compound statement
    src = """
    import jax.numpy as jnp
    def f(x, c, small):
        if small:
            d2 = c_sq[None] - 2.0 * jnp.einsum("nd,kd->nk", x, c)
            return jnp.argmin(d2, axis=1)
        return None
    """
    assert rule_ids(run(src, RawDistanceRule)) == ["raw-distance"]


def test_raw_distance_core_call_results_are_clean():
    # the intended ported shape: distances from the shared core, reduction
    # on the call RESULT — calls launder taint
    src = """
    import jax
    import jax.numpy as jnp
    from .distance import pairwise_d2
    def f(q, items):
        d2 = pairwise_d2(q, items)
        return jax.lax.top_k(-d2, 4)
    """
    assert run(src, RawDistanceRule) == []


def test_raw_distance_non_matmul_reductions_pass():
    src = """
    import jax
    import jax.numpy as jnp
    def g(scores, probs, gumbel):
        a = jnp.argmin(scores, axis=1)            # no matmul anywhere
        keys = jnp.where(probs > 0, jnp.log(probs) + gumbel, -jnp.inf)
        _, idx = jax.lax.top_k(keys, 8)           # laundered through log()
        return a, idx
    """
    assert run(src, RawDistanceRule) == []


def test_raw_distance_exempt_in_core_and_waiver():
    src = """
    import jax.numpy as jnp
    def assign(x, c):
        return jnp.argmin(c_sq[None, :] - 2.0 * x @ c.T, axis=1)
    """
    assert run(src, RawDistanceRule, relpath="spark_rapids_ml_tpu/ops/distance.py") == []
    waived = """
    import jax.numpy as jnp
    def assign(x, c):
        return jnp.argmin(c_sq[None, :] - 2.0 * x @ c.T, axis=1)  # distance-ok: fixture rationale
    """
    assert run(waived, RawDistanceRule) == []
    bare = """
    import jax.numpy as jnp
    def assign(x, c):
        return jnp.argmin(c_sq[None, :] - 2.0 * x @ c.T, axis=1)  # distance-ok
    """
    assert rule_ids(run(bare, RawDistanceRule)) == ["raw-distance"]


def test_raw_distance_clean_rebinding_clears_taint():
    src = """
    import jax.numpy as jnp
    def f(x, c, scores):
        d2 = x @ c.T
        d2 = jnp.asarray(scores)   # rebinding from a laundering call cleans
        return jnp.argmin(d2, axis=1)
    """
    assert run(src, RawDistanceRule) == []


# --------------------------------------------------------------------------
# serve-dispatch: the serving plane's async contract (docs/serving.md)
# --------------------------------------------------------------------------

_SERVING_PATH = "spark_rapids_ml_tpu/serving/snippet.py"


def test_serve_dispatch_direct_jit_fires():
    src = """
    import jax
    def load(predict):
        return jax.jit(predict)
    """
    fs = run(src, ServeDispatchRule, relpath=_SERVING_PATH)
    assert rule_ids(fs) == ["serve-dispatch"]


def test_serve_dispatch_block_until_ready_both_forms_fire():
    src = """
    import jax
    def assemble(result):
        jax.block_until_ready(result)
        result.block_until_ready()
        return jax.device_get(result)
    """
    fs = run(src, ServeDispatchRule, relpath=_SERVING_PATH)
    assert rule_ids(fs) == ["serve-dispatch"] * 3


def test_serve_dispatch_waiver_and_import_alias():
    waived = """
    import jax
    def assemble(results):
        jax.block_until_ready(results)  # serve-ok: the one response-assembly sync point
        return results
    """
    assert run(waived, ServeDispatchRule, relpath=_SERVING_PATH) == []
    aliased = """
    from jax import jit as J
    def load(predict):
        return J(predict)
    """
    assert rule_ids(run(aliased, ServeDispatchRule, relpath=_SERVING_PATH)) == [
        "serve-dispatch"
    ]


def test_serve_dispatch_scoped_to_serving_only():
    # the same constructs are legal everywhere else in the framework (the
    # fit side jits freely) — and prose mentions never fire under AST rules
    src = """
    import jax
    def f(predict, result):
        g = jax.jit(predict)
        return g(result).block_until_ready()
    """
    assert run(src, ServeDispatchRule) == []  # default core-tree relpath
    prose = '''
    def doc():
        """Engines must not call jax.jit or block_until_ready directly."""
        s = "jax.jit(predict).block_until_ready()"
        return s
    '''
    assert run(prose, ServeDispatchRule, relpath=_SERVING_PATH) == []


def test_serve_dispatch_program_calls_pass():
    # the sanctioned surface: PredictProgram dispatch/fetch and plain numpy
    src = """
    import numpy as np
    def group(program, block):
        result, n = program.dispatch(block)
        return np.concatenate([program.fetch(result, n)])
    """
    assert run(src, ServeDispatchRule, relpath=_SERVING_PATH) == []


# --------------------------------------------------------------------------
# pinned regression: the regex-era false-positive class — trigger text in
# comments, docstrings, and string literals must not fire under AST ports
# --------------------------------------------------------------------------

_LEGACY_FP_SNIPPETS = [
    (PerfCounterRule, '# uses time.perf_counter() internally\ns = "time.perf_counter()"\n'),
    (
        BlockingRule,
        '''
        def f():
            """Spins in `while True` and calls `.wait()` — as PROSE."""
            msg = "while True: ev.wait()"
            return msg
        ''',
    ),
    (JsonlRule, 's = \'fh.write(json.dumps(rec) + "\\\\n")\'  # fh.write(json.dumps(rec))\n'),
    (SleepRule, '# time.sleep(5) would be wrong here\ndoc = "time.sleep(5)"\n'),
    (MemStatsRule, '"""Never call d.memory_stats() directly."""\ns = "d.memory_stats()"\n'),
    (PadRowsRule, '# pad_rows(x, 8) is forbidden\ns = "pad_rows(x, 8)"\n'),
    (
        RawDistanceRule,
        '"""Never write jnp.argmin(x @ c.T) by hand."""\ns = "jax.lax.top_k(-(x @ c.T), k)"\n',
    ),
]


@pytest.mark.parametrize(
    "rule_cls,src", _LEGACY_FP_SNIPPETS, ids=lambda p: getattr(p, "id", None) or "src"
)
def test_comment_and_string_mentions_do_not_fire(rule_cls, src):
    assert run(src, rule_cls) == []


def test_perf_counter_ns_kept_from_regex_era():
    fs = run("import time\nt0 = time.perf_counter_ns()\n", PerfCounterRule)
    assert rule_ids(fs) == ["bare-perf-counter"]


def test_waiver_inside_loop_body_does_not_waive_the_loop_finding():
    # a `.wait()` waiver deep in the body must not become an invisible
    # escape hatch for the enclosing while-True finding (header lines only)
    fs = run(
        """
        def f(ev):
            while True:
                ev.wait(5.0)
                ev.wait()  # blocking-ok: fixture reason for THIS call only
        """,
        BlockingRule,
    )
    assert rule_ids(fs) == ["unbounded-blocking"]
    assert fs[0].line == 3  # the while, not the waived call


def test_waiver_with_reason_suppresses_but_bare_waiver_does_not():
    waived = "import time\ntime.sleep(1)  # sleep-ok: fixture-bounded delay\n"
    assert run(waived, SleepRule) == []
    bare = "import time\ntime.sleep(1)  # sleep-ok\n"
    fs = analyze_source(bare, rules=[SleepRule(), HygieneRule()])
    assert sorted(rule_ids(fs)) == ["bare-sleep", "waiver-missing-reason"]


def test_hygiene_tabs_and_trailing_whitespace():
    fs = run("x =\t1\ny = 2  \n", HygieneRule)
    assert sorted(rule_ids(fs)) == ["tab", "trailing-whitespace"]


def test_waiver_mention_in_prose_is_not_a_waiver_attempt():
    fs = run("# the framework (`# hbm-ok` waiver) covers this\nx = 1\n", HygieneRule)
    assert fs == []


# --------------------------------------------------------------------------
# framework-aware detectors
# --------------------------------------------------------------------------


def test_spmd_divergence_rank_conditional():
    fs = run(
        """
        def f(ctx, rdv):
            if ctx.rank == 0:
                rdv.allgather("x")
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]
    assert "rank" in fs[0].message


def test_spmd_divergence_except_handler():
    fs = run(
        """
        def f(rdv, work):
            try:
                work()
            except Exception:
                rdv.barrier()
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]
    assert "except handler" in fs[0].message


def test_spmd_divergence_rank_guarded_early_exit():
    # the other spelling of the same hang: only rank 0 survives the guard,
    # so the straight-line collective below it is rank-dependent too
    fs = run(
        """
        def f(rank, rdv):
            if rank != 0:
                return
            rdv.barrier()
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]
    assert "early exit" in fs[0].message


def test_spmd_early_exit_is_block_local():
    # a rank-guarded `continue` diverges the rest of the LOOP BODY, not the
    # code after the loop
    fs = run(
        """
        def f(rank, rdv, items):
            for it in items:
                if rank != 0:
                    continue
                prep(it)
            rdv.barrier()
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_spmd_nested_loop_continue_is_not_an_early_exit():
    # the continue exits the INNER for-loop only; every rank reaches the
    # collective below the guard
    fs = run(
        """
        def f(rank, rdv, items):
            if rank == 0:
                for x in items:
                    if not x:
                        continue
                    handle(x)
            rdv.allgather("payload")
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_spmd_return_inside_nested_loop_is_an_early_exit():
    fs = run(
        """
        def f(rank, rdv, items):
            if rank != 0:
                for x in items:
                    return x
            rdv.allgather("payload")
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]


def test_spmd_symmetric_collective_in_both_arms_passes():
    # every rank enters the round — only the payload differs per arm
    fs = run(
        """
        def f(rank, ctx):
            if rank == 0:
                out = ctx.allgather(header)
            else:
                out = ctx.allgather("")
            return out
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_spmd_asymmetric_arms_still_flagged():
    fs = run(
        """
        def f(rank, ctx):
            if rank == 0:
                ctx.allgather(header)
                ctx.barrier()
            else:
                ctx.allgather("")
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"] * 3


def test_spmd_rank_dependent_payload_passes():
    fs = run(
        """
        def f(ctx, rdv):
            payload = "coord" if ctx.rank == 0 else ""
            rdv.allgather(payload)
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_spmd_submesh_scoped_full_mesh_collective_is_flagged():
    # PR 19: a full-clique control-plane round reachable only from sub-mesh
    # scoped code strands the ranks outside the carve — placement-induced
    # divergence, same hang as a rank conditional
    fs = run(
        """
        from spark_rapids_ml_tpu.parallel.mesh import chip_scope

        def f(devs, rdv):
            with chip_scope(devs):
                rdv.allgather("x")
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]
    assert "sub-mesh scope `chip_scope(...)`" in fs[0].message
    assert "# submesh-ok" in fs[0].message


def test_spmd_submesh_carve_with_as_binding_is_flagged():
    fs = run(
        """
        from spark_rapids_ml_tpu.parallel import submesh

        def f(mesh, ctx):
            with submesh(mesh, 4) as sub:
                ctx.barrier()
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]
    assert "submesh(...)" in fs[0].message


def test_spmd_submesh_waiver_suppresses_and_scope_exit_clears():
    # FP guards: a reasoned `# submesh-ok` waives the deliberate full-group
    # round, and collectives AFTER the carve (full mesh restored) are clean
    fs = run(
        """
        from spark_rapids_ml_tpu.parallel.mesh import chip_scope

        def f(devs, rdv):
            with chip_scope(devs):
                rdv.allgather("done")  # submesh-ok: whole clique joins the report round
            rdv.barrier()
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_spmd_submesh_waiver_is_tag_specific_and_needs_a_reason():
    # a `# spmd-ok` reason does NOT waive the sub-mesh finding (different
    # failure, different tag), and a bare `# submesh-ok` suppresses nothing
    wrong_tag = """
        from spark_rapids_ml_tpu.parallel.mesh import chip_scope

        def f(devs, rdv):
            with chip_scope(devs):
                rdv.allgather("x")  # spmd-ok: wrong tag for this finding
        """
    fs = run(wrong_tag, SpmdDivergenceRule)
    assert rule_ids(fs) == ["spmd-divergence"]
    bare = wrong_tag.replace(
        "# spmd-ok: wrong tag for this finding", "# submesh-ok"
    )
    fs = analyze_source(
        textwrap.dedent(bare),
        relpath="spark_rapids_ml_tpu/snippet.py",
        rules=[SpmdDivergenceRule(), HygieneRule()],
    )
    assert sorted(rule_ids(fs)) == ["spmd-divergence", "waiver-missing-reason"]


def test_spmd_non_carving_with_block_is_not_a_submesh_scope():
    # FP guard: ordinary context managers (locks, dataset scopes) around a
    # collective do not make it sub-mesh-scoped
    fs = run(
        """
        def f(lock, rdv):
            with lock:
                rdv.allgather("x")
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_spmd_rank_conditional_inside_submesh_scope_keeps_rank_message():
    # the innermost divergence frame wins: a rank conditional INSIDE the
    # carve is the rank-reachability bug, reported (and waived) as such
    fs = run(
        """
        from spark_rapids_ml_tpu.parallel.mesh import chip_scope

        def f(devs, rank, rdv):
            with chip_scope(devs):
                if rank == 0:
                    rdv.allgather("x")
        """,
        SpmdDivergenceRule,
    )
    assert rule_ids(fs) == ["spmd-divergence"]
    assert "rank-identity conditional" in fs[0].message


def test_spmd_nested_function_resets_conditional_context():
    fs = run(
        """
        def f(ctx):
            if ctx.rank == 0:
                def g(rdv):
                    rdv.allgather("")
                return g
        """,
        SpmdDivergenceRule,
    )
    assert fs == []


def test_host_sync_fetch_in_loop():
    fs = run(
        """
        import jax.numpy as jnp

        def solve(x0, n):
            x = jnp.asarray(x0)
            v = 0.0
            for _ in range(n):
                x = x * 2
                v = float(x.sum())
            return v
        """,
        HostSyncRule,
        relpath="spark_rapids_ml_tpu/ops/snippet.py",
    )
    assert rule_ids(fs) == ["host-sync"]


def test_host_sync_host_numpy_loop_passes():
    fs = run(
        """
        import numpy as np

        def host(n):
            a = np.zeros(n)
            s = 0.0
            for _ in range(n):
                s += float(np.dot(a, a))
            return s
        """,
        HostSyncRule,
        relpath="spark_rapids_ml_tpu/ops/snippet.py",
    )
    assert fs == []


def test_host_sync_metadata_and_final_fetch_pass():
    fs = run(
        """
        import numpy as np
        import jax.numpy as jnp

        def solve(x0, n):
            x = jnp.asarray(x0)
            for _ in range(n):
                k = int(x.shape[0])
                x = x * k
            return np.asarray(x)
        """,
        HostSyncRule,
        relpath="spark_rapids_ml_tpu/ops/snippet.py",
    )
    assert fs == []


def test_host_sync_only_in_hot_path_files():
    src = """
    import jax.numpy as jnp

    def solve(x0, n):
        x = jnp.asarray(x0)
        for _ in range(n):
            x = float(x) * x
        return x
    """
    assert run(src, HostSyncRule, relpath="spark_rapids_ml_tpu/tuning.py") == []


def test_traced_impurity_print_in_jitted():
    fs = run(
        """
        import jax

        @jax.jit
        def step(x):
            print("tracing", x)
            return x
        """,
        TracedImpurityRule,
    )
    assert rule_ids(fs) == ["traced-impurity"]


def test_traced_impurity_closure_append_in_loop_body():
    fs = run(
        """
        from jax import lax

        def solve(x):
            log = []
            def body(c):
                log.append(1)
                return c
            def cond(c):
                return c.sum() > 0
            return lax.while_loop(cond, body, x)
        """,
        TracedImpurityRule,
    )
    assert rule_ids(fs) == ["traced-impurity"]
    assert "log" in fs[0].message


def test_traced_impurity_debug_callback_is_sanctioned():
    fs = run(
        """
        import jax
        from functools import partial
        from spark_rapids_ml_tpu import telemetry

        @jax.jit
        def step(x):
            jax.debug.callback(partial(telemetry.record_convergence_point, "s"), x)
            return x
        """,
        TracedImpurityRule,
    )
    assert fs == []


def test_traced_impurity_untraced_function_passes():
    fs = run("def host():\n    print('fine on the host')\n", TracedImpurityRule)
    assert fs == []


def test_config_key_unknown_and_known():
    sources = RegistrySources(
        config_schema_keys={"alpha": 3},
        config_docs_text="| `alpha` | 1 | the knob |\n",
    )
    bad = run(
        "from spark_rapids_ml_tpu.core import config\nv = config['aplha']\n",
        ConfigKeyRule,
        sources=sources,
    )
    assert rule_ids(bad) == ["config-key"] and "aplha" in bad[0].message
    ok = run(
        "from spark_rapids_ml_tpu.core import config\nv = config['alpha']\nconfig.get('alpha', 1)\n",
        ConfigKeyRule,
        sources=sources,
    )
    assert ok == []


def test_config_key_ignores_other_config_objects():
    sources = RegistrySources(config_schema_keys={"alpha": 3})
    fs = run(
        "import jax\njax.config.update('jax_enable_x64', True)\nmycfg = {}\nmycfg['whatever'] = 1\n",
        ConfigKeyRule,
        sources=sources,
    )
    assert fs == []


def test_config_key_ignores_unrelated_locals_named_config():
    # a parameter/local named `config` outside core.py is NOT the schema dict
    sources = RegistrySources(config_schema_keys={"alpha": 3})
    fs = run(
        "def bench(config):\n    return config['batch_size']\n",
        ConfigKeyRule,
        relpath="benchmark/bench_x.py",
        sources=sources,
    )
    assert fs == []


def test_config_key_schema_docs_drift_both_directions():
    sources = RegistrySources(
        config_schema_keys={"alpha": 3, "beta": 4},
        config_docs_text="| `alpha` | 1 | doc |\n| `gamma` | 2 | ghost |\n",
    )
    fs = run("x = 1\n", ConfigKeyRule, sources=sources)
    msgs = " || ".join(f.message for f in fs)
    assert "`beta`" in msgs and "undocumented" in msgs
    assert "`gamma`" in msgs and "does not exist" in msgs


def test_metric_name_near_miss_and_documented():
    sources = RegistrySources(metric_docs_text="counters: `ingest.rows` and `fit.retries`.\n")
    bad = run(
        "from spark_rapids_ml_tpu import telemetry\ntelemetry.registry().inc('ingest.row')\n",
        MetricNameRule,
        sources=sources,
    )
    assert rule_ids(bad) == ["metric-name"]
    assert "near-miss" in bad[0].message and "ingest.rows" in bad[0].message
    ok = run(
        "from spark_rapids_ml_tpu import telemetry\ntelemetry.registry().inc('ingest.rows')\n",
        MetricNameRule,
        sources=sources,
    )
    assert ok == []


def test_metric_name_dynamic_names_are_skipped_not_flagged():
    sources = RegistrySources(metric_docs_text="`ingest.rows`\n")
    fs = run(
        "def f(reg, solver):\n    reg.inc(f'{solver}.fits')\n",
        MetricNameRule,
        sources=sources,
    )
    assert fs == []


def test_metric_name_convergence_partial_form_is_checked():
    sources = RegistrySources(metric_docs_text="`kmeans.shift`\n")
    fs = run(
        """
        from functools import partial
        from spark_rapids_ml_tpu import telemetry
        cb = partial(telemetry.record_convergence_point, "kmaens.shift")
        """,
        MetricNameRule,
        sources=sources,
    )
    assert rule_ids(fs) == ["metric-name"]


# --------------------------------------------------------------------------
# baseline ratchet + CLI verdict
# --------------------------------------------------------------------------


def _mini_repo(tmp_path, body):
    root = tmp_path / "repo"
    (root / "spark_rapids_ml_tpu").mkdir(parents=True)
    (root / "spark_rapids_ml_tpu" / "mod.py").write_text(body, encoding="utf-8")
    return root


def test_baseline_ratchet_new_fails_then_freezes_then_shrinks(tmp_path, capsys):
    root = _mini_repo(tmp_path, "import time\ntime.sleep(1)\n")
    bl = str(tmp_path / "baseline.json")
    args = ["spark_rapids_ml_tpu", "--root", str(root), "--baseline", bl, "--no-imports"]

    # 1. a new finding fails the gate
    assert cli_main(args) == 1
    # 2. plain --write-baseline refuses to GROW the ratchet...
    assert cli_main(args + ["--write-baseline"]) == 1
    assert baseline_mod.load(bl) == {}
    # ...freezing requires the explicit rule-landing flag
    assert cli_main(args + ["--write-baseline", "--allow-baseline-growth"]) == 0
    assert cli_main(args) == 0
    frozen = baseline_mod.load(bl)
    assert frozen == {"spark_rapids_ml_tpu/mod.py:bare-sleep": 1}
    # 3. a SECOND finding on top of the frozen one fails again
    (root / "spark_rapids_ml_tpu" / "mod.py").write_text(
        "import time\ntime.sleep(1)\ntime.sleep(2)\n", encoding="utf-8"
    )
    assert cli_main(args) == 1
    # 4. fixing everything passes, reports the stale entry, and
    #    --write-baseline shrinks the file to empty
    (root / "spark_rapids_ml_tpu" / "mod.py").write_text("x = 1\n", encoding="utf-8")
    assert cli_main(args) == 0
    assert "stale" in capsys.readouterr().out
    assert cli_main(args + ["--write-baseline"]) == 0
    assert baseline_mod.load(bl) == {}


def test_json_verdict_schema(tmp_path, capsys):
    root = _mini_repo(tmp_path, "import time\ntime.sleep(1)\n")
    bl = str(tmp_path / "baseline.json")
    rc = cli_main(
        ["spark_rapids_ml_tpu", "--root", str(root), "--baseline", bl,
         "--no-imports", "--json"]
    )
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["version"] == 1
    assert payload["verdict"] == "fail"
    assert payload["files_scanned"] == 1
    assert {r["id"] for r in payload["rules"]} >= {"bare-sleep", "spmd-divergence", "host-sync"}
    (finding,) = [f for f in payload["findings"] if f["rule"] == "bare-sleep"]
    assert set(finding) == {"path", "line", "col", "rule", "message", "status"}
    assert finding["status"] == "new" and finding["line"] == 2
    assert set(payload["baseline"]) == {"path", "stale", "counts"}
    assert payload["baseline"]["counts"] == {"spark_rapids_ml_tpu/mod.py:bare-sleep": 1}
    assert isinstance(payload["dynamic_metric_names"], list)


def test_subpath_target_still_applies_rules(tmp_path):
    # scanning a SUB-path must run the same rules as the full tree — never
    # a silently rule-less green pass
    root = tmp_path / "repo"
    (root / "spark_rapids_ml_tpu" / "sub").mkdir(parents=True)
    (root / "spark_rapids_ml_tpu" / "sub" / "mod.py").write_text(
        "import time\ntime.sleep(1)\n", encoding="utf-8"
    )
    rc = cli_main(
        ["spark_rapids_ml_tpu/sub", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports"]
    )
    assert rc == 1


def test_subset_write_baseline_preserves_unscanned_trees(tmp_path):
    # ratcheting one tree must not erase another tree's frozen entries
    root = tmp_path / "repo"
    for tree in ("spark_rapids_ml_tpu", "benchmark"):
        (root / tree).mkdir(parents=True)
        (root / tree / "mod.py").write_text("x =\t1\n", encoding="utf-8")
    bl = str(tmp_path / "baseline.json")
    base = ["--root", str(root), "--baseline", bl, "--no-imports"]
    assert cli_main(["spark_rapids_ml_tpu", "benchmark", *base,
                     "--write-baseline", "--allow-baseline-growth"]) == 0
    assert len(baseline_mod.load(bl)) == 2
    # fix only the framework tree, then ratchet ONLY that tree
    (root / "spark_rapids_ml_tpu" / "mod.py").write_text("x = 1\n", encoding="utf-8")
    # the './'-prefixed spelling must ratchet the same tree, not preserve it
    assert cli_main(["./spark_rapids_ml_tpu", *base, "--write-baseline"]) == 0
    assert baseline_mod.load(bl) == {"benchmark/mod.py:tab": 1}
    # and the full run still passes against the merged baseline
    assert cli_main(["spark_rapids_ml_tpu", "benchmark", *base]) == 0


def test_missing_registry_source_fails_instead_of_silently_disabling(tmp_path):
    # a repo whose docs/observability.md was moved must NOT get a green
    # metric-name pass with usages unchecked
    root = _mini_repo(
        tmp_path,
        "from spark_rapids_ml_tpu import telemetry\n"
        "telemetry.registry().inc('totally.bogus_metric')\n",
    )
    rc = cli_main(
        ["spark_rapids_ml_tpu", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports"]
    )
    assert rc == 1


def test_missing_target_fails_instead_of_green_zero_file_pass(tmp_path):
    root = _mini_repo(tmp_path, "x = 1\n")
    rc = cli_main(
        ["no_such_tree", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports"]
    )
    assert rc == 1


def test_utf8_bom_file_is_not_a_syntax_error(tmp_path):
    root = tmp_path / "repo"
    (root / "spark_rapids_ml_tpu").mkdir(parents=True)
    (root / "spark_rapids_ml_tpu" / "mod.py").write_bytes(b"\xef\xbb\xbfx = 1\n")
    rc = cli_main(
        ["spark_rapids_ml_tpu", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports"]
    )
    assert rc == 0


def test_verdict_catalog_covers_every_emitted_rule_id(tmp_path, capsys):
    root = _mini_repo(tmp_path, "import time\ntime.sleep(1)  # sleep-ok\nx =\t1  \n")
    cli_main(
        ["spark_rapids_ml_tpu", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports", "--json"]
    )
    payload = json.loads(capsys.readouterr().out)
    catalog_ids = {r["id"] for r in payload["rules"]}
    emitted_ids = {f["rule"] for f in payload["findings"]}
    assert emitted_ids  # tab, trailing-whitespace, waiver-missing-reason, bare-sleep
    assert emitted_ids <= catalog_ids
    assert {"syntax-error", "encoding"} <= catalog_ids


def test_syntax_error_is_a_structured_finding(tmp_path):
    root = _mini_repo(tmp_path, "def broken(:\n")
    rc = cli_main(
        ["spark_rapids_ml_tpu", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports"]
    )
    assert rc == 1


def test_nul_byte_is_a_structured_finding_not_a_crash(tmp_path):
    root = tmp_path / "repo"
    (root / "spark_rapids_ml_tpu").mkdir(parents=True)
    (root / "spark_rapids_ml_tpu" / "mod.py").write_bytes(b"x = 1\x00\n")
    rc = cli_main(
        ["spark_rapids_ml_tpu", "--root", str(root),
         "--baseline", str(tmp_path / "b.json"), "--no-imports"]
    )
    assert rc == 1


def test_write_baseline_ratchets_finalize_emitted_doc_paths(tmp_path):
    # a fixed docs-drift entry (emitted by the registry finalize pass at a
    # docs/ path outside the scanned code trees) must ratchet OUT, not be
    # preserved forever by the subset-protection
    root = _mini_repo(tmp_path, "x = 1\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(
        json.dumps({"version": 1, "counts": {"docs/observability.md:metric-name": 1}}),
        encoding="utf-8",
    )
    args = ["spark_rapids_ml_tpu", "--root", str(root),
            "--baseline", str(bl), "--no-imports"]
    assert cli_main(args + ["--write-baseline"]) == 0
    assert baseline_mod.load(str(bl)) == {}


def test_repo_gate_is_clean_with_empty_baseline():
    # the acceptance contract: the real tree passes with the checked-in
    # (empty) baseline — every finding is fixed or carries a reasoned waiver
    assert cli_main(["--no-imports"]) == 0
    assert baseline_mod.load(str(ROOT / "ci" / "analysis" / "baseline.json")) == {}


# --------------------------------------------------------------------------
# ledger-bypass: capacity math stays behind the shared HBM ledger
# (docs/scheduling.md "The shared ledger")
# --------------------------------------------------------------------------


def test_ledger_bypass_direct_admit_fit_fires():
    src = """
    from spark_rapids_ml_tpu import memory
    def place(est, ex, ctx):
        return memory.admit_fit(est, ex, ctx)
    """
    fs = run(src, LedgerBypassRule)
    assert rule_ids(fs) == ["ledger-bypass"]
    assert "admit_fit" in fs[0].message


def test_ledger_bypass_admit_model_load_and_memstats_fire():
    src = """
    def load(memory, model, dev):
        adm = memory.admit_model_load(model)
        cap = dev.memory_stats()
        return adm, cap
    """
    fs = run(src, LedgerBypassRule)
    assert rule_ids(fs) == ["ledger-bypass"] * 2


def test_ledger_bypass_from_import_alias_fires():
    src = """
    from ..memory import admit_fit as place
    def f(est, ex, ctx):
        return place(est, ex, ctx)
    """
    fs = run(src, LedgerBypassRule)
    assert rule_ids(fs) == ["ledger-bypass"]


def test_ledger_bypass_waiver_suppresses():
    src = """
    from spark_rapids_ml_tpu import memory
    def place(est, ex, ctx):
        return memory.admit_fit(est, ex, ctx)  # ledger-ok: the fit-entry admission — reserves through the shared ledger
    """
    assert run(src, LedgerBypassRule) == []


def test_ledger_bypass_exempt_in_owner_trees():
    src = """
    from spark_rapids_ml_tpu import memory
    def place(est, ex, ctx):
        return memory.admit_fit(est, ex, ctx)
    """
    # memory.py owns admission; scheduler/ owns the ledger; telemetry.py is
    # the sanctioned watermark sampler
    assert run(src, LedgerBypassRule, relpath="spark_rapids_ml_tpu/memory.py") == []
    assert (
        run(src, LedgerBypassRule, relpath="spark_rapids_ml_tpu/scheduler/queue.py")
        == []
    )
    assert run(src, LedgerBypassRule, relpath="spark_rapids_ml_tpu/telemetry.py") == []


def test_ledger_bypass_fp_guards():
    # prose/docstring mentions never fire under AST rules, and a LOCAL
    # function that shares the name is not the budgeter's admission
    prose = '''
    def doc():
        """Admissions go through memory.admit_fit and admit_model_load."""
        s = "memory.admit_fit(est, ex, ctx); d.memory_stats()"
        return s
    '''
    assert run(prose, LedgerBypassRule) == []
    local = """
    def admit_fit(a, b):
        return a + b
    def f():
        return admit_fit(1, 2)
    """
    assert run(local, LedgerBypassRule) == []


# --------------------------------------------------------------------------
# exporter-scope (the ops plane's export surface)
# --------------------------------------------------------------------------


def test_exporter_scope_http_server_import_fires():
    fs = run("import http.server\n", ExporterScopeRule)
    assert rule_ids(fs) == ["exporter-scope"]
    fs = run("from http.server import ThreadingHTTPServer\n", ExporterScopeRule)
    assert rule_ids(fs) == ["exporter-scope"]
    fs = run("import socketserver\n", ExporterScopeRule)
    assert rule_ids(fs) == ["exporter-scope"]


def test_exporter_scope_raw_socket_call_fires():
    src = """
    import socket
    def probe():
        with socket.socket() as s:
            s.bind(("", 0))
            return s.getsockname()[1]
    """
    fs = run(src, ExporterScopeRule)
    assert rule_ids(fs) == ["exporter-scope"]


def test_exporter_scope_prometheus_assembly_fires():
    src = """
    def render(counters):
        lines = []
        for name, v in counters.items():
            lines.append("# TYPE " + name + " counter")
        return lines
    """
    fs = run(src, ExporterScopeRule)
    assert rule_ids(fs) == ["exporter-scope"]


def test_exporter_scope_waiver_suppresses():
    src = """
    import socket
    def probe():
        with socket.socket() as s:  # exporter-ok: coordinator port probe, not a metrics endpoint
            return s.getsockname()[1]
    """
    assert run(src, ExporterScopeRule) == []


def test_exporter_scope_exempt_inside_ops_plane():
    src = """
    from http.server import ThreadingHTTPServer
    def render(counters):
        return ["# TYPE srml_x counter"]
    """
    assert (
        run(src, ExporterScopeRule, relpath="spark_rapids_ml_tpu/ops_plane/export.py")
        == []
    )


def test_exporter_scope_fp_guards():
    # non-server socket attribute use, urllib clients, and prose mentioning
    # the modules (no marker strings) must not fire
    clean = '''
    import socket
    import urllib.request
    def f():
        """Scrapes http.server-style endpoints via urllib, no server here."""
        host = socket.gethostname()
        return urllib.request.urlopen(f"http://{host}/metrics")
    '''
    assert run(clean, ExporterScopeRule) == []
    # "TYPE" without the exposition marker form is not Prometheus assembly
    assert run('KIND = "TYPE: counter"\n', ExporterScopeRule) == []


# --------------------------------------------------------------------------
# wallclock-deadline: time.time() feeding deadline/timeout arithmetic
# --------------------------------------------------------------------------


def test_wallclock_deadline_direct_compare_true_positive():
    fs = run(
        """
        import time
        def wait(deadline):
            if time.time() > deadline:
                raise TimeoutError
        """,
        WallclockDeadlineRule,
    )
    assert rule_ids(fs) == ["wallclock-deadline"]


def test_wallclock_deadline_tainted_name_compare_true_positive():
    # name assigned from time.time() carries the taint into the compare,
    # including through +/- arithmetic
    fs = run(
        """
        import time
        def wait(t0, timeout_s):
            now = time.time()
            while now - t0 < timeout_s:
                now = time.time()
        """,
        WallclockDeadlineRule,
    )
    assert rule_ids(fs) == ["wallclock-deadline"]


def test_wallclock_deadline_bound_assign_true_positive():
    fs = run(
        "import time\ndeadline = time.time() + 5.0\n",
        WallclockDeadlineRule,
    )
    assert rule_ids(fs) == ["wallclock-deadline"]
    assert fs[0].line == 2


def test_wallclock_deadline_keyword_true_positive():
    fs = run(
        """
        import time
        def f(fut):
            fut.result(timeout=time.time() + 1.0)
        """,
        WallclockDeadlineRule,
    )
    assert rule_ids(fs) == ["wallclock-deadline"]


def test_wallclock_deadline_alias_still_caught():
    fs = run(
        "from time import time as now\nexpires = now() + 3\n",
        WallclockDeadlineRule,
    )
    assert rule_ids(fs) == ["wallclock-deadline"]


def test_wallclock_deadline_fp_guards():
    # the timestamping idiom stays legal: record fields, bare stamps,
    # attribute stamps, and ALL monotonic-clock arithmetic
    clean = """
    import time
    class T:
        def stamp(self):
            self._w0 = time.time()
            return {"t": time.time(), "host": "x"}
    def wait(t0, timeout_s):
        while time.monotonic() - t0 < timeout_s:
            pass
    def unrelated():
        n = len("abc")
        return n > 2
    """
    assert run(clean, WallclockDeadlineRule) == []


def test_wallclock_deadline_taint_is_scope_local():
    # a tainted name in one function must not poison a same-named
    # monotonic reading in another
    clean = """
    import time
    def a():
        now = time.time()
        return {"t": now}
    def b(deadline):
        now = time.monotonic()
        return now > deadline
    """
    assert run(clean, WallclockDeadlineRule) == []


def test_wallclock_deadline_waiver():
    waived = (
        "import time\n"
        "now = time.time()\n"
        "if now - mtime > 60:  # wallclock-ok: compared against file mtimes\n"
        "    pass\n"
    )
    assert run(waived, WallclockDeadlineRule) == []
