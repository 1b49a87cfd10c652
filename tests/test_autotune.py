#
# Measured block autotuner (spark_rapids_ml_tpu/ops/autotune.py,
# docs/performance.md "Kernel autotuner") and the planner it overrides
# (distance.block_vmem_bytes / plan_blocks / _plan). The acceptance contract:
#
#   - the planner budgets what the kernels really hold in VMEM: blocks at
#     their STORED dtype, double-buffered, plus the fast path's bf16 copies
#     — so the fast plan is never larger than the f32 plan;
#   - a measured winner persists as JSON beside the compile cache and is
#     reused ACROSS PROCESSES (simulated here by dropping the in-memory
#     cache), hit/miss counters pinned;
#   - every table degradation path — disabled, off-TPU, malformed table,
#     stale version, bad entries, unset cache dir — falls back to the
#     heuristic without raising; a candidate the compiler refuses is
#     skipped, and only when EVERY candidate is refused does ensure raise.
#
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu import core as core_mod
from spark_rapids_ml_tpu import telemetry
from spark_rapids_ml_tpu.ops import autotune
from spark_rapids_ml_tpu.ops.distance import (
    _plan,
    block_vmem_bytes,
    plan_blocks,
    vmem_limit_bytes,
)

_KEYS = ("compilation_cache_dir", "autotune_enabled", "autotune_repeats")


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Isolated tuner: private table directory, clean in-memory cache and
    counters, config restored exactly (other files' fits must keep seeing
    the real settings)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)  # it would win
    saved = {k: core_mod.config[k] for k in _KEYS}
    core_mod.config["compilation_cache_dir"] = str(tmp_path)
    core_mod.config["autotune_enabled"] = True
    autotune.reset()
    telemetry.enable()
    telemetry.registry().reset()
    yield tmp_path
    core_mod.config.update(saved)
    autotune.reset()
    telemetry.disable()
    telemetry.registry().reset()


def _fake_timer(best=(256, 256)):
    """Deterministic stand-in for the on-device timer: the chosen winner
    times fastest, everything else slower by its distance from it."""
    calls = []

    def timer(br, bk):
        calls.append((br, bk))
        return 1.0 + abs(br - best[0]) + abs(bk - best[1])

    timer.calls = calls
    return timer


# ------------------------------------------------------ planner accounting --


def test_block_vmem_bytes_counts_stored_dtype_and_double_buffers():
    d = 3072  # lane-aligned, so the terms are exact
    full = block_vmem_bytes(512, 512, d, jnp.float32, fast=False)
    fast = block_vmem_bytes(512, 512, d, jnp.float32, fast=True)
    # the two [512, d] f32 blocks, each double-buffered, are the floor —
    # exactly the 24 MiB Mosaic reports for this shape on a v5e
    assert 2 * (512 + 512) * d * 4 == 24 << 20 < fast
    # both modes hold the SAME f32 blocks; the fast path adds a bf16 copy of
    # both, the fp32 contraction the row block's bf16 splits and residuals
    assert fast - (512 + 512) * d * 2 == full - 512 * d * 17
    # a non-128-multiple depth occupies whole lane tiles
    assert block_vmem_bytes(512, 512, 3000, jnp.float32, False) == full
    # f64 blocks are twice as wide
    assert block_vmem_bytes(512, 512, d, jnp.float64, False) > full


def test_full_precision_plan_never_outgrows_the_fast_plan(tuner):
    # a VMEM-tight depth: both modes must shrink, full precision at least
    # as much (it peels 17 bytes per row-block element, fast 2 per element)
    d = 7168
    full = plan_blocks(4096, 4096, d, jnp.float32, False)
    fast = plan_blocks(4096, 4096, d, jnp.float32, True)
    assert full is not None and fast is not None
    assert fast != (512, 512)
    assert full[0] * full[1] <= fast[0] * fast[1]
    # _plan threads the same accounting (no table entry here)
    assert _plan(4096, 4096, d, jnp.float32, False) == full
    assert _plan(4096, 4096, d, jnp.float32, True) == fast


def test_shape_class_buckets():
    # rows/k round UP to powers of two; depth exact; mode spelled out
    assert autotune.shape_class(1000, 5, 64, jnp.float32, True) == "r1024:k8:d64:float32:fast"
    assert autotune.shape_class(1024, 8, 64, jnp.float32, True) == "r1024:k8:d64:float32:fast"
    assert autotune.shape_class(1025, 9, 64, jnp.float64, False) == "r2048:k16:d64:float64:full"
    # same bucket => same key (one measurement covers the bucket)
    assert autotune.shape_class(513, 5, 32, jnp.float32, False) == autotune.shape_class(
        1024, 8, 32, jnp.float32, False
    )


# ------------------------------------------------- measure and persist ------


def test_ensure_measures_persists_and_reuses(tuner):
    timer = _fake_timer(best=(256, 256))
    won = autotune.ensure(4096, 512, 64, jnp.float32, True, timer=timer)
    assert won == (256, 256)
    assert len(timer.calls) >= 2  # a real grid was raced, not a single point
    # persisted beside the compile cache, schema-versioned
    path = os.path.join(str(tuner), "srml_autotune.json")
    with open(path) as f:
        raw = json.load(f)
    assert raw["version"] == 1
    key = autotune.shape_class(4096, 512, 64, jnp.float32, True)
    assert raw["entries"][key] == [256, 256]

    # "another process": drop the in-memory cache, the file alone must serve
    autotune.reset()
    assert autotune.lookup(4096, 512, 64, jnp.float32, True) == (256, 256)
    stats = autotune.stats()
    assert stats["hits"] == 1 and stats["misses"] == 0 and stats["entries"] == 1
    # the planner consumes the tuned winner over its heuristic
    assert _plan(4096, 512, 64, jnp.float32, True) == (256, 256)
    # second ensure is a pure table read — no re-measurement
    n_calls = len(timer.calls)
    assert autotune.ensure(4096, 512, 64, jnp.float32, True, timer=timer) == (256, 256)
    assert len(timer.calls) == n_calls
    assert autotune.stats()["measurements"] == 0  # this process never measured


def test_lookup_miss_counts_and_falls_back(tuner):
    assert autotune.lookup(4096, 512, 64, jnp.float32, False) is None
    stats = autotune.stats()
    assert stats["misses"] == 1 and stats["hits"] == 0
    assert telemetry.registry().snapshot()["counters"]["autotune.misses"] == 1
    # the planner still plans (heuristic)
    assert _plan(4096, 512, 64, jnp.float32, False) == plan_blocks(4096, 512, 64)


def test_candidates_respect_vmem_and_include_heuristic(tuner):
    cands = autotune._candidates(4096, 4096, 7168, jnp.float32, True)
    heuristic = plan_blocks(4096, 4096, 7168, jnp.float32, True)
    assert cands[0] == heuristic
    assert (512, 512) not in cands  # over the limit at this depth
    for br, bk in cands:
        assert block_vmem_bytes(br, bk, 7168, jnp.float32, True) <= vmem_limit_bytes()


# ------------------------------------------------------ degradation ---------


def test_malformed_table_degrades_to_heuristic(tuner):
    path = os.path.join(str(tuner), "srml_autotune.json")
    with open(path, "w") as f:
        f.write("{ not json")
    assert autotune.lookup(4096, 512, 64, jnp.float32, True) is None
    assert autotune.stats()["table_errors"] == 1
    assert _plan(4096, 512, 64, jnp.float32, True) is not None  # heuristic lives


def test_stale_version_discarded_wholesale(tuner):
    key = autotune.shape_class(4096, 512, 64, jnp.float32, True)
    path = os.path.join(str(tuner), "srml_autotune.json")
    with open(path, "w") as f:
        json.dump({"version": 0, "entries": {key: [256, 256]}}, f)
    assert autotune.lookup(4096, 512, 64, jnp.float32, True) is None
    assert autotune.stats()["table_errors"] == 1


def test_bad_entry_shapes_filtered(tuner):
    good = autotune.shape_class(4096, 512, 64, jnp.float32, True)
    path = os.path.join(str(tuner), "srml_autotune.json")
    with open(path, "w") as f:
        json.dump(
            {
                "version": 1,
                "entries": {
                    good: [256, 256],
                    "bad1": [256],          # wrong arity
                    "bad2": [0, 256],       # non-positive
                    "bad3": "256x256",      # wrong type
                },
            },
            f,
        )
    assert autotune.lookup(4096, 512, 64, jnp.float32, True) == (256, 256)
    stats = autotune.stats()
    assert stats["table_errors"] == 3 and stats["entries"] == 1


def test_refused_candidate_is_skipped_not_fatal(tuner):
    # the compiler refuses the heuristic's own pick (a Mosaic VMEM error):
    # the session goes on, a feasible candidate wins, nothing is counted as
    # a table error and the heuristic is NOT silently handed back
    heuristic = plan_blocks(4096, 512, 64, jnp.float32, True)
    inner = _fake_timer(best=(256, 256))

    def timer(br, bk):
        if (br, bk) == heuristic:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"
            )
        return inner(br, bk)

    won = autotune.ensure(4096, 512, 64, jnp.float32, True, timer=timer)
    assert won == (256, 256) and won != heuristic
    assert autotune.stats()["table_errors"] == 0
    assert autotune.stats()["measurements"] == 1
    assert _plan(4096, 512, 64, jnp.float32, True) == (256, 256)


def test_every_candidate_refused_raises_with_the_cause(tuner):
    def timer(br, bk):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    with pytest.raises(RuntimeError, match="no candidate tiling") as ei:
        autotune.ensure(4096, 512, 64, jnp.float32, True, timer=timer)
    assert "Mosaic failed to compile" in str(ei.value.__cause__)
    assert autotune.stats()["table_errors"] == 0
    assert not os.path.exists(os.path.join(str(tuner), "srml_autotune.json"))


def test_disabled_is_a_noop(tuner):
    core_mod.config["autotune_enabled"] = False
    assert autotune.lookup(4096, 512, 64, jnp.float32, True) is None
    assert autotune.ensure(
        4096, 512, 64, jnp.float32, True, timer=_fake_timer()
    ) is None
    stats = autotune.stats()
    assert stats == {"hits": 0, "misses": 0, "measurements": 0,
                     "table_errors": 0, "entries": 0}


def test_off_tpu_without_timer_measures_nothing(tuner):
    # CPU/CI contract: kernel_mode() != "pallas" here, so ensure() without
    # an injected timer must return None and write nothing
    assert autotune.ensure(4096, 512, 64, jnp.float32, True) is None
    assert not os.path.exists(os.path.join(str(tuner), "srml_autotune.json"))
    assert autotune.stats()["measurements"] == 0


def test_table_lives_beside_the_compile_cache(tuner, monkeypatch, tmp_path):
    from spark_rapids_ml_tpu.parallel.mesh import compilation_cache_dir

    # config-resolved directory (JAX_COMPILATION_CACHE_DIR unset by the fixture)
    assert autotune.table_path() == os.path.join(str(tuner), "srml_autotune.json")
    # where JAX_COMPILATION_CACHE_DIR is set it wins, and the table follows
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    assert compilation_cache_dir() == str(env_dir)
    autotune.ensure(4096, 512, 64, jnp.float32, True, timer=_fake_timer())
    assert os.path.exists(env_dir / "srml_autotune.json")
    assert not os.path.exists(os.path.join(str(tuner), "srml_autotune.json"))
    # a None config is never "no cache": it resolves to the fixed default
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    core_mod.config["compilation_cache_dir"] = None
    assert autotune.table_path() == os.path.join(
        core_mod._DEFAULT_COMPILE_CACHE_DIR, "srml_autotune.json"
    )


def test_env_seed_of_autotune_enabled(monkeypatch):
    # SRML_AUTOTUNE=0 seeds config["autotune_enabled"] False at load; the
    # seeding helper is pinned directly (config itself loaded long ago)
    import subprocess
    import sys

    code = (
        "from spark_rapids_ml_tpu.core import config; "
        "print(config['autotune_enabled'])"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "SRML_AUTOTUNE": "0", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.stdout.strip() == "False", out.stderr
