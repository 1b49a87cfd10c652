#
# Fault-tolerant control-plane tests: the fault-injection suite that PROVES
# docs/robustness.md. A rank that dies mid-fit must become a prompt, TYPED,
# correctly-attributed error on every survivor — never a hang, never a raw
# threading.BrokenBarrierError — and a transient fault must retry to a
# bit-identical model.
#
import json
import os
import signal
import subprocess
import sys
import threading
import time
import uuid

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import core as core_mod
from spark_rapids_ml_tpu.errors import (
    RankFailedError,
    RendezvousTimeoutError,
    SolverDivergedError,
    SrmlError,
)
from spark_rapids_ml_tpu.parallel import (
    ChaosRendezvous,
    FileRendezvous,
    LocalRendezvous,
    Rendezvous,
    TpuContext,
)
from spark_rapids_ml_tpu.parallel import chaos

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _clean_chaos_plan():
    chaos.clear_fault_plan()
    yield
    chaos.clear_fault_plan()


@pytest.fixture
def fast_backoff():
    saved = core_mod.config["fit_retry_backoff_s"]
    core_mod.config["fit_retry_backoff_s"] = 0.01
    yield
    core_mod.config["fit_retry_backoff_s"] = saved


# ---------------------------------------------------------------- plan spec --


def test_fault_plan_parsing():
    plan = chaos.parse_fault_plan(
        "kill:rank=1:round=3; delay:rank=0:round=2:seconds=0.5;"
        "abort:rank=2:round=1:reason=boom; drop:rank=1:round=4:times=2;"
        "fail:stage=fit:times=1"
    )
    kinds = [f.kind for f in plan]
    assert kinds == ["kill", "delay", "abort", "drop", "fail"]
    assert plan[0].rank == 1 and plan[0].round == 3 and plan[0].times == 1
    assert plan[1].seconds == 0.5
    assert plan[2].reason == "boom"
    assert plan[3].times == 2
    assert plan[4].stage == "fit"


@pytest.mark.parametrize(
    "bad",
    [
        "explode:rank=1:round=0",  # unknown kind
        "kill:rank=1",  # missing round
        "fail:times=1",  # missing stage
        "kill:rank1:round=0",  # malformed field
        "kill:rank=1:round=0:color=red",  # unknown field
    ],
)
def test_fault_plan_rejects_malformed(bad):
    with pytest.raises(ValueError):
        chaos.parse_fault_plan(bad)


def test_fault_plan_parses_burst():
    plan = chaos.parse_fault_plan("burst:stage=serve:rows=4096:seconds=2")
    assert [f.kind for f in plan] == ["burst"]
    assert plan[0].stage == "serve"
    assert plan[0].rows == 4096
    assert plan[0].seconds == 2.0
    assert plan[0].times == 1


@pytest.mark.parametrize(
    "bad",
    [
        "burst:rows=4096:seconds=2",  # missing stage
        "burst:stage=serve:seconds=2",  # missing rows
        "burst:stage=serve:rows=4096",  # missing seconds
        "burst:stage=serve:rows=0:seconds=2",  # zero load is a typo
        "burst:stage=serve:rows=4096:seconds=0",  # zero duration is a typo
    ],
)
def test_fault_plan_rejects_malformed_burst(bad):
    with pytest.raises(ValueError):
        chaos.parse_fault_plan(bad)


def test_maybe_burst_stage_consumes_one_firing():
    chaos.set_fault_plan("burst:stage=serve:rows=128:seconds=1")
    try:
        # wrong stage leaves the entry un-spent
        assert chaos.maybe_burst_stage("fit") is None
        fault = chaos.maybe_burst_stage("serve")
        assert fault is not None
        assert fault.rows == 128 and fault.seconds == 1.0
        # the firing was consumed: the same entry never fires twice
        assert chaos.maybe_burst_stage("serve") is None
    finally:
        chaos.clear_fault_plan()


# ------------------------------------------------------- LocalRendezvous ----


def test_local_rendezvous_round_deadline_is_typed():
    # a peer that never arrives must surface as RendezvousTimeoutError (a
    # TimeoutError subclass), not threading.BrokenBarrierError
    rdv = LocalRendezvous.create(2, timeout_s=0.25)[0]
    t0 = time.monotonic()
    with pytest.raises(RendezvousTimeoutError) as ei:
        rdv.allgather("hello")
    assert time.monotonic() - t0 < 5.0
    assert isinstance(ei.value, TimeoutError) and isinstance(ei.value, SrmlError)
    assert ei.value.round_index == 0


def test_local_rendezvous_abort_wakes_peers_promptly():
    # rank 1 publishes ABORT while rank 0 is blocked in a round with a LONG
    # deadline: rank 0 must raise RankFailedError naming rank 1 well before
    # the deadline (no test relies on the round timeout elapsing)
    rvs = LocalRendezvous.create(2, timeout_s=60.0)
    err: list = [None]
    started = threading.Event()

    def work():
        started.set()
        try:
            rvs[0].allgather("payload")
        except Exception as e:  # noqa: BLE001 - capturing for assertion
            err[0] = e

    t = threading.Thread(target=work)
    t.start()
    started.wait()
    time.sleep(0.05)  # let rank 0 reach the barrier
    t0 = time.monotonic()
    rvs[1].abort("injected failure")
    t.join(timeout=10)
    assert not t.is_alive()
    assert time.monotonic() - t0 < 2.0
    assert isinstance(err[0], RankFailedError)
    assert err[0].failed_rank == 1
    assert "injected failure" in err[0].reason
    # the sentinel rode the extra slot write
    assert rvs[1]._shared.slots[1].startswith("ABORT:1:")
    # later rounds fail FAST (no waiting at all) while the abort stands
    t0 = time.monotonic()
    with pytest.raises(RankFailedError):
        rvs[0].allgather("again")
    assert time.monotonic() - t0 < 0.5


def test_local_rendezvous_begin_epoch_clears_abort():
    rvs = LocalRendezvous.create(2, timeout_s=5.0)
    rvs[1].abort("transient blip")
    with pytest.raises(RankFailedError):
        rvs[0].allgather("x")
    for r in rvs:
        r.begin_epoch(1)
    results = [None, None]

    def work(r):
        results[r] = rvs[r].allgather(f"rank{r}")

    threads = [threading.Thread(target=work, args=(r,)) for r in range(2)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert results[0] == results[1] == ["rank0", "rank1"]


# -------------------------------------------------------- FileRendezvous ----


def test_file_rendezvous_round_deadline_is_typed(tmp_path):
    rdv = FileRendezvous(
        0, 2, str(tmp_path), timeout_s=0.3, run_id="t", heartbeat_interval_s=60.0
    )
    try:
        with pytest.raises(RendezvousTimeoutError) as ei:
            rdv.allgather("x")
    finally:
        rdv.close()
    assert isinstance(ei.value, TimeoutError)  # back-compat with the old raise
    assert ei.value.missing_ranks == [1]
    assert ei.value.round_index == 0


def test_file_rendezvous_abort_file_detection(tmp_path):
    # rank 0 blocks in a round with a long deadline; rank 1 publishes its
    # abort file — rank 0 must raise RankFailedError within a poll tick
    r0 = FileRendezvous(
        0, 2, str(tmp_path), timeout_s=60.0, run_id="t", heartbeat_interval_s=60.0
    )
    r1 = FileRendezvous(
        1, 2, str(tmp_path), timeout_s=60.0, run_id="t", heartbeat_interval_s=60.0
    )
    err: list = [None]

    def work():
        try:
            r0.allgather("payload")
        except Exception as e:  # noqa: BLE001
            err[0] = e

    t = threading.Thread(target=work)
    t.start()
    time.sleep(0.1)
    t0 = time.monotonic()
    r1.abort("worker exception")
    t.join(timeout=10)
    r0.close()
    r1.close()
    assert not t.is_alive()
    assert time.monotonic() - t0 < 2.0
    assert isinstance(err[0], RankFailedError)
    assert err[0].failed_rank == 1 and "worker exception" in err[0].reason


def test_file_rendezvous_rejoin_marker_outranks_heartbeat(tmp_path):
    # a respawned incarnation of a dead rank resumes touching the SAME
    # heartbeat file from construction — so the corpse looks alive to a
    # survivor blocked in a round. The rejoin_wait marker (written at
    # rejoin() entry) is positive death evidence and must fire within a
    # failure-scan tick even while the heartbeat keeps progressing.
    r0 = FileRendezvous(
        0, 2, str(tmp_path), timeout_s=60.0, run_id="t", heartbeat_interval_s=0.1
    )
    # the respawn: same rank/root, heartbeating from construction (this is
    # exactly what masks the death), but stuck ahead of its reform vote
    r1_respawn = FileRendezvous(
        1, 2, str(tmp_path), timeout_s=60.0, run_id="t", heartbeat_interval_s=0.1
    )
    err: list = [None]

    def work():
        try:
            r0.allgather("payload")
        except Exception as e:  # noqa: BLE001
            err[0] = e

    t = threading.Thread(target=work)
    t.start()
    time.sleep(0.3)  # several heartbeat touches land: rank 1 "looks alive"
    t0 = time.monotonic()
    # what rejoin() publishes first
    with open(r1_respawn._rejoin_wait_path(1), "w") as f:
        f.write("{}")
    t.join(timeout=10)
    r0.close()
    r1_respawn.close()
    assert not t.is_alive()
    assert time.monotonic() - t0 < 2.0
    assert isinstance(err[0], RankFailedError)
    assert err[0].failed_rank == 1 and "rejoin" in err[0].reason


def test_file_rendezvous_stale_heartbeat_detection(tmp_path):
    # a rank that HEARTBEAT then died silently (no abort file) must be
    # declared failed once its heartbeat goes stale — well before the round
    # deadline
    interval = 0.2
    r0 = FileRendezvous(
        0, 2, str(tmp_path), timeout_s=60.0, heartbeat_interval_s=interval
    )
    # simulate rank 1: one heartbeat touch, then death (no round payload ever)
    hb1 = r0._heartbeat_path(1)
    with open(hb1, "w"):
        pass
    t0 = time.monotonic()
    try:
        with pytest.raises(RankFailedError) as ei:
            r0.allgather("x")
    finally:
        r0.close()
    elapsed = time.monotonic() - t0
    assert ei.value.failed_rank == 1
    assert "heartbeat" in ei.value.reason
    assert elapsed < 2 * interval + 1.0  # stale threshold 1.5x + poll slack


def test_file_rendezvous_epoch_namespacing(tmp_path):
    # an abort published in epoch 0 must NOT poison a retry in epoch 1
    r0 = FileRendezvous(
        0, 1, str(tmp_path), timeout_s=5.0, run_id="t", heartbeat_interval_s=60.0
    )
    r0.abort("attempt 0 failure")
    r0.begin_epoch(1)
    try:
        assert r0.allgather("fresh") == ["fresh"]
        assert r0._round == 1
    finally:
        r0.close()
    # the epoch-0 abort file exists with the documented name, untouched
    assert os.path.exists(os.path.join(r0.root, "abort_rank_0"))


# -------------------------------------------------------- ChaosRendezvous ---


def _run_ranks(rvs, rounds=3):
    """Drive all ranks through `rounds` allgathers; returns per-rank outcome
    (the exception instance or the last gather)."""
    out = [None] * len(rvs)

    def work(r):
        try:
            for i in range(rounds):
                out[r] = rvs[r].allgather(f"{r}:{i}")
        except Exception as e:  # noqa: BLE001
            out[r] = e

    threads = [threading.Thread(target=work, args=(r,)) for r in range(len(rvs))]
    [t.start() for t in threads]
    [t.join(timeout=30) for t in threads]
    assert not any(t.is_alive() for t in threads)
    return out


def test_chaos_delay_is_benign():
    inner = LocalRendezvous.create(2, timeout_s=30.0)
    plan = chaos.parse_fault_plan("delay:rank=0:round=1:seconds=0.05")
    rvs = [ChaosRendezvous(inner[0], plan), ChaosRendezvous(inner[1], [])]
    out = _run_ranks(rvs, rounds=3)
    assert out[0] == out[1] == ["0:2", "1:2"]
    assert plan[0].spent()


def test_chaos_abort_fault_blames_the_injected_rank():
    inner = LocalRendezvous.create(2, timeout_s=30.0)
    plan = chaos.parse_fault_plan("abort:rank=1:round=1:reason=injected")
    rvs = [ChaosRendezvous(inner[0], []), ChaosRendezvous(inner[1], plan)]
    out = _run_ranks(rvs, rounds=3)
    # the survivor gets the typed, attributed error
    assert isinstance(out[0], RankFailedError) and out[0].failed_rank == 1
    # the injected rank raised its own (chaos) error after publishing
    assert isinstance(out[1], RuntimeError) and "chaos" in str(out[1])


# ---------------------------------------------- subprocess kill-at-round ----


def _launch_chaos_workers(nranks, tmp_path, plan, *, rounds, heartbeat_s, timeout_s):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SRML_FAULT_PLAN"] = plan
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    rdv_dir = str(tmp_path / "rdv")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir, exist_ok=True)
    run_id = uuid.uuid4().hex
    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.join(HERE, "chaos_worker.py"),
                str(r), str(nranks), rdv_dir, out_dir, run_id,
                str(rounds), str(heartbeat_s), str(timeout_s),
            ],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for r in range(nranks)
    ]
    outputs = [p.communicate(timeout=180)[0].decode() for p in procs]
    return out_dir, procs, outputs


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def test_killed_rank_detected_within_heartbeat_budget(tmp_path):
    # THE acceptance scenario: SIGKILL a rank entering an arbitrary round
    # (no abort file, no atexit — heartbeats are the only evidence) and
    # require every survivor to raise RankFailedError blaming that rank
    # within 2x the heartbeat interval — NOT after the 60s round deadline.
    heartbeat_s = 0.75
    kill_round = 3
    out_dir, procs, outputs = _launch_chaos_workers(
        3, tmp_path, f"kill:rank=2:round={kill_round}",
        rounds=6, heartbeat_s=heartbeat_s, timeout_s=60.0,
    )
    assert procs[2].returncode == -signal.SIGKILL
    marks = _read_json(os.path.join(out_dir, "marks_rank2.json"))
    assert marks[-1]["round"] == kill_round  # died entering the planned round
    kill_t = marks[-1]["t"]
    for r in (0, 1):
        assert procs[r].returncode == 0, f"rank {r}:\n{outputs[r]}"
        res = _read_json(os.path.join(out_dir, f"result_rank{r}.json"))
        assert res["error"] == "RankFailedError", res
        assert res["failed_rank"] == 2
        assert res["rounds_done"] == kill_round
        detect_lag = res["detected_at"] - kill_t
        assert detect_lag < 2 * heartbeat_s, (
            f"rank {r} took {detect_lag:.2f}s to detect the kill "
            f"(budget {2 * heartbeat_s}s)"
        )


def test_aborting_rank_detected_within_poll_tick(tmp_path):
    # graceful failure: the failing rank PUBLISHES, so survivors don't even
    # need a heartbeat miss — detection is one poll tick
    out_dir, procs, outputs = _launch_chaos_workers(
        3, tmp_path, "abort:rank=1:round=2:reason=synthetic",
        rounds=5, heartbeat_s=5.0, timeout_s=60.0,
    )
    aborter = _read_json(os.path.join(out_dir, "result_rank1.json"))
    assert aborter["error"] == "RuntimeError"  # its own chaos raise
    for r in (0, 2):
        assert procs[r].returncode == 0, f"rank {r}:\n{outputs[r]}"
        res = _read_json(os.path.join(out_dir, f"result_rank{r}.json"))
        assert res["error"] == "RankFailedError", res
        assert res["failed_rank"] == 1
        assert "synthetic" in str(res)
        assert res["detected_at"] - aborter["detected_at"] < 2.0


# ---------------------------------------------------------- TpuContext ------


class _SpyRendezvous(Rendezvous):
    def __init__(self, nranks=2):
        self.rank = 0
        self.nranks = nranks
        self.aborted = []
        self.gathers = []

    def _allgather_impl(self, payload):
        self.gathers.append(payload)
        return [payload] * self.nranks

    def abort(self, reason):
        self.aborted.append(reason)


def test_tpu_context_exit_propagates_abort():
    spy = _SpyRendezvous()
    ctx = TpuContext(0, 2, spy)
    ctx.__exit__(RuntimeError, RuntimeError("solver blew up"), None)
    assert spy.aborted == ["RuntimeError: solver blew up"]
    assert spy.gathers == []  # no success barrier on the failure path


def test_tpu_context_exit_does_not_cascade_rank_failures():
    # relaying a PEER's failure must not publish a fresh abort: a cascade of
    # abort files would let later scanners blame a healthy survivor
    spy = _SpyRendezvous(nranks=3)
    ctx = TpuContext(0, 3, spy)
    err = RankFailedError(2, "root cause")
    ctx.__exit__(RankFailedError, err, None)
    assert spy.aborted == []


def test_tpu_context_teardown_swallows_peer_failure():
    # a peer that died AFTER our work completed surfaces at the teardown
    # barrier; our results are whole, so this is a warning, not a raise
    class _PeerDiedAtTeardown(_SpyRendezvous):
        def _allgather_impl(self, payload):
            raise RankFailedError(1, "died between solve and teardown")

    ctx = TpuContext(0, 2, _PeerDiedAtTeardown())
    ctx.__exit__(None, None, None)  # must not raise


def test_local_rendezvous_round_desync_is_typed_not_silent():
    # a straggler exchanging a DIFFERENT round's payload on the same barrier
    # must surface as the transient desync error on both sides — never as a
    # silent mixed-round gather
    rvs = LocalRendezvous.create(2, timeout_s=10.0)
    rvs[1]._round = 5  # straggler believes it is 5 rounds ahead
    out = _run_ranks(rvs, rounds=1)
    assert isinstance(out[0], RendezvousTimeoutError) and "desync" in str(out[0])
    assert isinstance(out[1], RendezvousTimeoutError) and "desync" in str(out[1])


def test_tpu_context_exit_success_barrier_runs():
    spy = _SpyRendezvous()
    ctx = TpuContext(0, 2, spy)
    ctx.__exit__(None, None, None)
    assert spy.gathers == [""]


def test_tpu_context_teardown_barrier_is_bounded():
    # peer already exited: the success-path barrier must time out after
    # config["teardown_timeout_s"] with a warning, NOT hang for the full
    # rendezvous deadline (satellite: bounded teardown)
    rdv = LocalRendezvous.create(2)[0]  # rank 1 will never arrive
    ctx = TpuContext(0, 2, rdv)
    saved = core_mod.config["teardown_timeout_s"]
    core_mod.config["teardown_timeout_s"] = 0.3
    t0 = time.monotonic()
    try:
        ctx.__exit__(None, None, None)  # must swallow the timeout
    finally:
        core_mod.config["teardown_timeout_s"] = saved
    assert time.monotonic() - t0 < 5.0


# ------------------------------------------------------- retryable_stage ----


def test_retryable_stage_retries_transient_and_resyncs_epochs(fast_backoff):
    calls, epochs = [], []

    class _R:
        def begin_epoch(self, e):
            epochs.append(e)

    def fn(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise RendezvousTimeoutError("flaky round")
        return "ok"

    assert core_mod.retryable_stage(fn, stage="t", rendezvous=_R(), max_retries=3) == "ok"
    assert calls == [0, 1, 2]
    assert epochs == [1, 2]


def test_retryable_stage_permanent_errors_propagate_immediately(fast_backoff):
    calls = []

    def fn(attempt):
        calls.append(attempt)
        raise RankFailedError(1, "dead peer")

    with pytest.raises(RankFailedError):
        core_mod.retryable_stage(fn, stage="t", max_retries=3)
    assert calls == [0]  # permanent: no second attempt


def test_retryable_stage_bounded_exhaustion(fast_backoff):
    calls = []

    def fn(attempt):
        calls.append(attempt)
        raise RendezvousTimeoutError("always down")

    with pytest.raises(RendezvousTimeoutError):
        core_mod.retryable_stage(fn, stage="t", max_retries=2)
    assert calls == [0, 1, 2]  # initial try + 2 retries, then gives up


def test_retryable_stage_chaos_injection(fast_backoff):
    chaos.set_fault_plan("fail:stage=probe:times=1")
    calls = []
    result = core_mod.retryable_stage(
        lambda attempt: calls.append(attempt) or attempt, stage="probe", max_retries=2
    )
    assert result == 1 and calls == [1]  # attempt 0 was injected away


def test_fit_retry_is_bit_identical_and_counted(rng, fast_backoff):
    # acceptance: a fit interrupted by an injected transient rendezvous fault
    # retries and produces a BIT-IDENTICAL model; the retry counter reaches
    # model._fit_metrics and the telemetry snapshot (the bench JSON source)
    from spark_rapids_ml_tpu import telemetry
    from spark_rapids_ml_tpu.models.classification import LogisticRegression

    n, d = 400, 4
    x = rng.normal(size=(n, d))
    y = (x[:, 0] + 0.2 * rng.normal(size=n) > 0).astype(np.float64)
    df = pd.DataFrame({"features": list(x), "label": y})

    def make():
        return LogisticRegression(maxIter=25, float32_inputs=False).setFeaturesCol(
            "features"
        )

    clean = make().fit(df)
    chaos.set_fault_plan("fail:stage=fit:times=1")
    telemetry.enable()
    try:
        retried = make().fit(df)
    finally:
        telemetry.disable()
    np.testing.assert_array_equal(np.asarray(retried.coef_), np.asarray(clean.coef_))
    np.testing.assert_array_equal(
        np.asarray(retried.intercept_), np.asarray(clean.intercept_)
    )
    assert retried.n_iter_ == clean.n_iter_
    assert retried._fit_metrics["counters"]["fit.retries"] == 1
    assert telemetry.snapshot()["counters"]["fit.retries"] >= 1


# ------------------------------------------------------ solver divergence ---


def test_kmeans_divergence_guard_carries_last_good(mesh8, rng):
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit
    from spark_rapids_ml_tpu.parallel import make_global_rows

    x = rng.normal(size=(64, 3)).astype(np.float64)
    x[5] = np.inf  # poisons sums -> centers -> the fetched shift scalar
    X, w, _ = make_global_rows(mesh8, x)
    centers0 = jnp.asarray(rng.normal(size=(4, 3)).astype(np.float64))
    with pytest.raises(SolverDivergedError) as ei:
        kmeans_fit(X, w, centers0, mesh=mesh8, max_iter=5, tol=0.0)
    e = ei.value
    assert e.solver == "kmeans"
    assert e.iteration >= 1
    assert np.isfinite(e.last_good["cluster_centers_"]).all()
    assert e.last_good["cluster_centers_"].shape == (4, 3)


def test_check_glm_result_guard():
    from spark_rapids_ml_tpu.ops.logistic import check_glm_result

    ok = {
        "coef_": np.ones((1, 2)), "intercept_": np.zeros(1),
        "objective_": 0.5, "n_iter_": 3,
    }
    assert check_glm_result(ok) is ok
    bad = {
        "coef_": np.array([[1.0, np.nan]]), "intercept_": np.zeros(1),
        "objective_": np.array(np.inf), "n_iter_": np.array(7),
    }
    with pytest.raises(SolverDivergedError) as ei:
        check_glm_result(bad)
    assert ei.value.solver == "logistic"
    assert ei.value.iteration == 7
    assert "intercept_" in ei.value.last_good  # the finite remainder survives
    assert "coef_" not in ei.value.last_good


def test_check_pca_state_guard():
    from spark_rapids_ml_tpu.ops.pca import check_pca_state

    ok = {
        "components_": np.eye(2), "explained_variance_": np.ones(2),
        "mean_": np.zeros(2), "explained_variance_ratio_": np.ones(2),
        "singular_values_": np.ones(2),
    }
    assert check_pca_state(ok, k=2) is ok
    bad = dict(ok, components_=np.full((2, 2), np.nan))
    with pytest.raises(SolverDivergedError) as ei:
        check_pca_state(bad, k=2)
    assert ei.value.solver == "pca" and ei.value.iteration == 0
    assert "mean_" in ei.value.last_good


# ------------------------------------- elastic recovery (subprocess) --------
# The chaos_worker `recover` mode: a small distributed Lloyd fit (numpy +
# rendezvous collectives — the control-plane shape of a real SPMD fit) under
# `core.recoverable_stage` with solver checkpoints on. SIGKILLs here are real
# process deaths on a real FileRendezvous plane.


def _lloyd_reference(iters):
    """Single-process reference of the harness fit: same dataset, same math,
    one shard. The distributed result re-associates the per-shard float64
    sums, so agreement is to reduction-order tolerance, not bitwise — the
    documented degraded-mesh contract (docs/robustness.md)."""
    from tests.chaos_worker import _lloyd_local_sums, _recover_dataset

    X, centers = _recover_dataset()
    for _ in range(iters):
        sums, counts = _lloyd_local_sums(X, centers)
        centers = np.where(
            counts[:, None] > 0,
            sums / np.maximum(counts[:, None], 1.0),
            centers,
        )
    return centers


def _launch_recover_workers(
    nranks, tmp_path, plan, *, iters, heartbeat_s, timeout_s,
    rejoin_grace_s=0.0, trace_id=None,
):
    """Launch `recover`-mode workers; returns (procs, spawn, out_dir,
    flightrec_dir). `spawn(rank, mode)` launches one more worker in the same
    run (the kill+rejoin harness respawns the victim with mode='rejoin')."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SRML_FAULT_PLAN"] = plan
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SRML_TEST_REJOIN_GRACE"] = str(rejoin_grace_s)
    flightrec = str(tmp_path / "flightrec")
    env["SRML_FLIGHTREC_DIR"] = flightrec
    if trace_id:
        env["SRML_TRACE_ID"] = trace_id
    rdv_dir = str(tmp_path / "rdv")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir, exist_ok=True)
    run_id = uuid.uuid4().hex

    def spawn(rank, mode, **env_overrides):
        # a RESPAWNED victim must not inherit the plan that killed it: the
        # Fault `times` ledger is per-process, so the fresh incarnation would
        # re-fire the same kill at the same round and SIGKILL itself again —
        # exhausting the recovery budget (found the hard way)
        child_env = dict(env, **env_overrides)
        return subprocess.Popen(
            [
                sys.executable, os.path.join(HERE, "chaos_worker.py"),
                str(rank), str(nranks), rdv_dir, out_dir, run_id,
                str(iters), str(heartbeat_s), str(timeout_s), mode,
            ],
            env=child_env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )

    procs = [spawn(r, "recover") for r in range(nranks)]
    return procs, spawn, out_dir, flightrec


def test_sigkill_mid_solve_recovers_on_survivor_mesh(tmp_path):
    # THE elastic-recovery acceptance scenario: a 3-process FileRendezvous
    # fit, one rank SIGKILLed mid-solve. Survivors must reform to a 2-rank
    # group, RESUME from the collective-consistent checkpoint, and complete —
    # centers within the documented tolerance of the uninterrupted fit,
    # fit.recoveries == 1, and the post-mortem timeline naming the epoch.
    from spark_rapids_ml_tpu import diagnostics

    # Round arithmetic: allgather_ndarray is TWO control-plane rounds per
    # call (chunk-count agreement + data), so with the resume-consensus
    # gather first, iteration k occupies rounds (2k+2, 2k+3). Round 8 is
    # iteration 3 — AFTER the iteration-2 checkpoint landed, so survivors
    # must RESUME (restores >= 1), not restart. Heartbeat 2.0s: the 1.5x
    # staleness threshold must comfortably exceed scheduler pauses with
    # several worker processes sharing few cores (a 2-core CI box starved a
    # live rank's heartbeat thread past a 1.5s threshold — falsely killing
    # it mid-recovery), at the cost of slower detection (unasserted here).
    iters = 6
    trace_id = f"recover-{uuid.uuid4().hex[:8]}"
    procs, _, out_dir, flightrec = _launch_recover_workers(
        3, tmp_path, "kill:rank=2:round=8", iters=iters,
        heartbeat_s=2.0, timeout_s=45.0, trace_id=trace_id,
    )
    outputs = [p.communicate(timeout=180)[0].decode() for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    ref = _lloyd_reference(iters)
    for r in (0, 1):
        assert procs[r].returncode == 0, f"rank {r}:\n{outputs[r]}"
        res = _read_json(os.path.join(out_dir, f"result_rank{r}.json"))
        assert res["error"] is None, res
        assert res["live_final"] == [0, 1]
        assert res["generation"] == 1
        assert res["orig_rank"] == r
        np.testing.assert_allclose(res["centers"], ref, rtol=1e-9)
        c = res["counters"]
        assert c["fit.recoveries"] == 1
        assert c["recovery.epochs"] == 1
        assert c["recovery.rank_losses"] == 1
        assert c["rendezvous.reforms"] == 1
        # resumed from the checkpoint, not from scratch
        assert c["checkpoint.saves"] >= 1
        assert c["checkpoint.restores"] >= 1
    # survivors dumped their rings after the reform; the assembled
    # post-mortem names the failure AND the recovery epoch
    pm = diagnostics.assemble_postmortem(flightrec, nranks=3, trace_id=trace_id)
    assert pm["failed_rank"] == 2
    assert pm["recovery_epochs"] == [
        {"generation": 1, "survivors": [0, 1], "dead": [2]}
    ]
    text = diagnostics.render_postmortem(pm)
    assert "recovery epoch g1" in text and "survivors [0, 1]" in text


@pytest.mark.slow
def test_sigkill_then_rejoin_restores_full_strength(tmp_path):
    # kill+rejoin recovery injection: the victim is respawned after death and
    # rejoins at the epoch boundary — the reform window stays open
    # `recovery_rejoin_grace_s` — so the fit completes at FULL strength, the
    # fresh rank catching up from the resume-consensus round (it has no local
    # checkpoint; it adopts the most advanced member's).
    #
    # Slow lane: 4 python processes (one respawned mid-run) on a small CI box
    # stretch heartbeat/vote timing far past the nominal path — the fast lane
    # keeps the single-kill recovery acceptance test; heartbeat 3.0s buys the
    # respawn import + vote extra starvation headroom at the cost of slower
    # detection (unasserted here).
    iters = 6
    procs, spawn, out_dir, _ = _launch_recover_workers(
        3, tmp_path, "kill:rank=2:round=8:respawn=1", iters=iters,
        heartbeat_s=3.0, timeout_s=90.0, rejoin_grace_s=60.0,
    )
    assert procs[2].wait(timeout=120) == -signal.SIGKILL
    respawned = spawn(2, "rejoin", SRML_FAULT_PLAN="")
    outputs = [p.communicate(timeout=180)[0].decode() for p in procs[:2]]
    out2 = respawned.communicate(timeout=180)[0].decode()
    ref = _lloyd_reference(iters)
    for r, (rc, out) in enumerate(
        [(procs[0].returncode, outputs[0]), (procs[1].returncode, outputs[1]),
         (respawned.returncode, out2)]
    ):
        assert rc == 0, f"rank {r}:\n{out}"
        res = _read_json(os.path.join(out_dir, f"result_rank{r}.json"))
        assert res["error"] is None, res
        assert res["live_final"] == [0, 1, 2], res
        assert res["orig_rank"] == r
        np.testing.assert_allclose(res["centers"], ref, rtol=1e-9)


@pytest.mark.parametrize(
    "kill_round",
    [
        # kill-at-every-round sweep: wherever the SIGKILL lands — the resume-
        # consensus agreement round (0), its data round (1), the first solve
        # round (2), a post-checkpoint solve round (7), or the very last
        # round (11) — every kill point must end in CLEAN RECOVERY (here:
        # recovery budget 1 covers the single loss) or a typed error, within
        # the deadline budget. Never a hang: the communicate() timeout is the
        # hang detector. The fast lane keeps the two qualitatively distinct
        # extremes (death before first contact: no heartbeat file ever, only
        # the timeout path can surface it; and a post-checkpoint solve round:
        # the resume-not-restart proof lives in the acceptance test above,
        # which kills at a post-checkpoint solve round and asserts
        # checkpoint.restores) — the other points ride the nightly --runslow
        # lane, each test being 3 subprocesses (~9 s nominal, several× under
        # CI load).
        0,
        pytest.param(1, marks=pytest.mark.slow),
        pytest.param(2, marks=pytest.mark.slow),
        pytest.param(7, marks=pytest.mark.slow),
        pytest.param(11, marks=pytest.mark.slow),
    ],
)
def test_kill_at_every_round_recovers_or_types(tmp_path, kill_round):
    iters = 5  # rounds per attempt: 2 consensus + 2 per Lloyd iteration
    procs, _, out_dir, _ = _launch_recover_workers(
        3, tmp_path, f"kill:rank=1:round={kill_round}", iters=iters,
        heartbeat_s=2.0, timeout_s=45.0,
    )
    outputs = [p.communicate(timeout=120)[0].decode() for p in procs]
    assert procs[1].returncode == -signal.SIGKILL
    ref = _lloyd_reference(iters)
    for r in (0, 2):
        assert procs[r].returncode == 0, f"rank {r}:\n{outputs[r]}"
        res = _read_json(os.path.join(out_dir, f"result_rank{r}.json"))
        assert res["error"] is None, res
        assert res["live_final"] == [0, 2]
        assert res["counters"]["fit.recoveries"] == 1
        np.testing.assert_allclose(res["centers"], ref, rtol=1e-9)
