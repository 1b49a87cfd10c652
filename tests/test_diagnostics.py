#
# Distributed-diagnostics tests: trace correlation (per-rank JSONL -> Chrome
# trace-event JSON, clock-skew aligned), the always-on flight recorder
# (ring bounds, SrmlError tails, dumps), cross-rank post-mortem assembly
# (incl. the 3-rank SIGKILL acceptance harness), and the perf-regression
# gate over the BENCH trajectory.
#
import json
import os
import signal
import subprocess
import sys
import threading
import uuid

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import diagnostics, telemetry
from spark_rapids_ml_tpu.errors import RankFailedError, RendezvousTimeoutError

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)


@pytest.fixture
def fresh_recorder():
    """Reset the process flight recorder around the test (it is always-on
    and global, so other suites leave events in it)."""
    rec = diagnostics.flight_recorder()
    rec.reset()
    yield rec
    rec.reset()


@pytest.fixture
def tele(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    telemetry.registry().reset()
    telemetry.enable(path)
    yield path
    telemetry.disable()
    telemetry._STATE.sink_path = None
    telemetry.registry().reset()


def _binary_df(rng, n=150, d=4):
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    return pd.DataFrame({"features": list(x), "label": y})


# ------------------------------------------------------------ flight recorder


def test_flight_recorder_ring_bound_and_drop_counter(tele):
    rec = diagnostics.FlightRecorder(capacity=4, enabled=True)
    for i in range(10):
        rec.record("tick", i=i)
    evs = rec.events()
    assert len(evs) == 4
    assert [e["i"] for e in evs] == [6, 7, 8, 9]  # oldest overwritten first
    stats = rec.stats()
    assert stats["recorded"] == 10 and stats["dropped"] == 6
    # truncation is NEVER silent: the registry counter mirrors the drops
    assert telemetry.snapshot()["counters"]["flightrec.events_dropped"] == 6
    assert rec.tail(2) == evs[-2:]


def test_flight_recorder_dump_roundtrip(tmp_path, fresh_recorder):
    fresh_recorder.record("alpha", x=1)
    fresh_recorder.record("beta", x=2)
    path = str(tmp_path / "flightrec_rank_0.jsonl")
    assert fresh_recorder.dump(path, reason="unit test") == path
    lines = [json.loads(l) for l in open(path)]
    assert [l["kind"] for l in lines] == ["alpha", "beta", "flightrec_dump"]
    footer = lines[-1]
    assert footer["reason"] == "unit test" and footer["recorded"] == 2


def test_flight_recorder_disabled_records_nothing(monkeypatch):
    rec = diagnostics.FlightRecorder(capacity=8, enabled=False)
    rec.record("tick")
    assert rec.events() == []
    assert rec.dump("/nonexistent/should/not/matter") is None


def test_srml_error_attaches_tail_and_dumps(tmp_path, monkeypatch, fresh_recorder):
    monkeypatch.setenv("SRML_FLIGHTREC_DIR", str(tmp_path))
    diagnostics.record_event("marker", round=41)
    try:
        raise RankFailedError(2, "peer died", round_index=7)
    except RankFailedError as e:
        tail = e.flightrec_tail
    assert tail, "SrmlError must carry the flight-recorder tail"
    assert tail[-1]["kind"] == "error"
    assert tail[-1]["failed_rank"] == 2 and tail[-1]["round_index"] == 7
    assert any(ev["kind"] == "marker" for ev in tail)
    dump = tmp_path / "flightrec_rank_0.jsonl"
    assert dump.exists(), "SrmlError with a dump dir configured must dump the ring"
    kinds = [json.loads(l)["kind"] for l in open(dump)]
    assert "marker" in kinds and "error" in kinds
    # SRML_FLIGHTREC_TAIL=0 means NO tail, not the whole ring (evs[-0:] trap)
    monkeypatch.setenv("SRML_FLIGHTREC_TAIL", "0")
    try:
        raise RankFailedError(2, "no-tail case")
    except RankFailedError as e2:
        assert e2.flightrec_tail == []


def test_config_flightrec_dir_without_env(tmp_path, monkeypatch, fresh_recorder):
    # config["flightrec_dir"] works when core is loaded (the in-process
    # path); resolution must NOT import core itself — inside SrmlError
    # construction that import chain (~1s) would ride every survivor's
    # failure-detection latency in control-plane-only processes (pinned by
    # test_chaos.py::test_killed_rank_detected_within_heartbeat_budget)
    from spark_rapids_ml_tpu import core as core_mod

    monkeypatch.delenv("SRML_FLIGHTREC_DIR", raising=False)
    monkeypatch.setitem(core_mod.config, "flightrec_dir", str(tmp_path))
    try:
        raise RankFailedError(1, "via config dir")
    except RankFailedError:
        pass
    assert (tmp_path / "flightrec_rank_0.jsonl").exists()


def test_timeout_error_also_carries_round(fresh_recorder):
    # attributes are set BEFORE super().__init__ so the hook records them
    try:
        raise RendezvousTimeoutError("round 3 timed out", round_index=3, timeout_s=1.0)
    except RendezvousTimeoutError as e:
        assert e.flightrec_tail[-1]["round_index"] == 3


def test_summary_and_snapshot_expose_flightrec_health(tele, fresh_recorder):
    diagnostics.record_event("tick")
    s = telemetry.summary()
    assert "flightrec rank0:" in s and "recorded" in s and "dropped" in s
    snap = telemetry.snapshot()
    assert snap["flightrec"]["recorded"] >= 1
    assert snap["flightrec"]["enabled"] is True


# --------------------------------------------------------- trace correlation


def test_trace_scope_tags_span_and_fit_records(tele, fresh_recorder):
    with diagnostics.trace_scope("UnitTest"):
        tags = diagnostics.trace_tags()
        assert tags["trace_id"] and tags["fit_id"].startswith("fit-")
        with telemetry.span("stage_a"):
            pass
    assert diagnostics.trace_tags() == {}  # scope exited cleanly
    recs = [json.loads(l) for l in open(tele)]
    spans = [r for r in recs if r["kind"] == "span"]
    assert spans and all(r["trace_id"] == tags["trace_id"] for r in spans)
    assert all("t0" in r for r in spans)
    # the flight recorder saw the scope too, with the same identity
    kinds = {e["kind"] for e in diagnostics.flight_recorder().events()}
    assert {"trace_begin", "span_begin", "span_end", "trace_end"} <= kinds


def test_trace_scope_spmd_propagates_rank0_id():
    # rank 0 mints, every rank adopts — one extra allgather round, lockstep
    from spark_rapids_ml_tpu.parallel import LocalRendezvous

    class _Ctx:
        is_spmd = True

        def __init__(self, rank, rdv):
            self.rank = rank
            self.rendezvous = rdv

    rvs = LocalRendezvous.create(2, timeout_s=10.0)
    seen = [None, None]

    def run(r):
        with diagnostics.trace_scope("spmd", _Ctx(r, rvs[r])) as tags:
            seen[r] = tags["trace_id"]

    threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen[0] is not None and seen[0] == seen[1]


def test_trace_exchange_failure_is_nonfatal(fresh_recorder):
    # the trace-id round runs BEFORE the fit body enters retryable_stage:
    # a control-plane failure there must degrade correlation (local id),
    # never kill the fit — the next real round fails WITH retry protection
    class _Ctx:
        is_spmd = True
        rank = 1

        class rendezvous:  # noqa: N801 - stub namespace
            @staticmethod
            def allgather(payload):
                raise RendezvousTimeoutError("peer slow entering fit", round_index=0)

    with diagnostics.trace_scope("degraded", _Ctx()) as tags:
        assert tags["trace_id"]  # locally-minted fallback
    kinds = [e["kind"] for e in diagnostics.flight_recorder().events()]
    assert "trace_exchange_failed" in kinds


def test_malformed_flightrec_capacity_env_does_not_crash(monkeypatch):
    monkeypatch.setenv("SRML_FLIGHTREC_EVENTS", "2k")  # operator typo
    rec = diagnostics.FlightRecorder()
    assert rec.capacity == 2048  # default, not a ValueError at import


def test_fits_get_distinct_trace_ids_and_sequenced_fit_ids(tele, rng):
    from spark_rapids_ml_tpu.models.classification import LogisticRegression

    df = _binary_df(rng)
    LogisticRegression(maxIter=5).setFeaturesCol("features").fit(df)
    LogisticRegression(maxIter=5).setFeaturesCol("features").fit(df)
    fit_recs = [json.loads(l) for l in open(tele)]
    fit_recs = [r for r in fit_recs if r["kind"] == "fit"]
    assert len(fit_recs) == 2
    assert fit_recs[0]["trace_id"] != fit_recs[1]["trace_id"]
    n0 = int(fit_recs[0]["fit_id"].split("-")[1])
    n1 = int(fit_recs[1]["fit_id"].split("-")[1])
    assert n1 == n0 + 1


def test_env_trace_id_tags_records_without_a_scope(monkeypatch, fresh_recorder):
    monkeypatch.setenv("SRML_TRACE_ID", "launcher-minted")
    diagnostics.record_event("tick")
    assert diagnostics.flight_recorder().events()[-1]["trace_id"] == "launcher-minted"


# ---------------------------------------------------------------- trace merge


def _mk_span(rank, name, path, t0, wall, trace_id="t1", **extra):
    return {"kind": "span", "name": name, "path": path, "wall_s": wall,
            "rank": rank, "trace_id": trace_id, "fit_id": "fit-1", "t0": t0,
            **extra}


def _synthetic_rank_records(skew_rank1=5.0):
    """Three lockstep rendezvous rounds on 2 ranks + per-rank work spans.
    rank 1's clock runs `skew_rank1` seconds FAST (its recorded t0s are
    shifted); rank 1 is also RAGGED (missing the last work span)."""
    base = 1000.0
    r0, r1 = [], []
    for rnd in range(3):
        t = base + rnd * 2.0
        r0.append(_mk_span(0, "rendezvous.allgather", "rendezvous.allgather",
                           t, 0.5, round=rnd, nranks=2))
        # rank1 entered a touch later but (physically) exited in lockstep;
        # its CLOCK shifts every timestamp by skew_rank1
        r1.append(_mk_span(1, "rendezvous.allgather", "rendezvous.allgather",
                           t + 0.2 + skew_rank1, 0.3, round=rnd, nranks=2))
    r0.append(_mk_span(0, "solve", "fit/solve", base + 6.5, 1.0))
    r1_work_missing = True  # ragged: rank 1 never recorded its solve span
    assert r1_work_missing
    return {0: r0, 1: r1}


def _validate_chrome_trace(trace):
    """Chrome trace-event JSON-object-format schema invariants (what
    Perfetto/chrome://tracing require to load the file)."""
    assert isinstance(trace, dict)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert isinstance(ev.get("ph"), str) and ev["ph"]
        assert isinstance(ev.get("name"), str)
        assert isinstance(ev.get("pid"), int)
        assert isinstance(ev.get("tid"), int)
        if ev["ph"] in ("X", "s", "f"):
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "M":
            assert "args" in ev
    json.dumps(trace)  # round-trippable


def test_merge_chrome_trace_schema_tracks_and_flows():
    trace = diagnostics.merge_chrome_trace(_synthetic_rank_records())
    _validate_chrome_trace(trace)
    events = trace["traceEvents"]
    thread_names = {e["tid"]: e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert thread_names == {0: "rank 0", 1: "rank 1"}  # one track per rank
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["tid"] for e in xs} == {0, 1}
    # rendezvous rounds render as flow arrows (one start + one finish each)
    starts = [e for e in events if e["ph"] == "s"]
    finishes = [e for e in events if e["ph"] == "f"]
    assert len(starts) == 3 and len(finishes) == 3
    assert {e["id"] for e in starts} == {e["id"] for e in finishes}


def test_merge_aligns_clock_skew_on_barrier_rounds():
    trace = diagnostics.merge_chrome_trace(_synthetic_rank_records(skew_rank1=5.0))
    # the recovered offset is the barrier-exit delta: ~-5s for the rank whose
    # clock runs 5s fast (median over rounds; exact here — constant skew)
    off = trace["otherData"]["clock_offsets_s"]
    assert abs(off["1"] + 5.0) < 0.11 and off["0"] == 0.0
    # after alignment the two ranks' round-0 allgather exits coincide
    xs = [e for e in trace["traceEvents"]
          if e["ph"] == "X" and e["name"] == "rendezvous.allgather"]
    ends = {(e["tid"], e["args"]["round"]): e["ts"] + e["dur"] for e in xs}
    assert abs(ends[(0, 0)] - ends[(1, 0)]) < 0.11 * 1e6
    # unaligned, they are ~5s apart
    raw = diagnostics.merge_chrome_trace(
        _synthetic_rank_records(skew_rank1=5.0), align_clocks=False
    )
    raw_ends = {(e["tid"], e["args"]["round"]): e["ts"] + e["dur"]
                for e in raw["traceEvents"]
                if e["ph"] == "X" and e["name"] == "rendezvous.allgather"}
    assert abs(raw_ends[(0, 0)] - raw_ends[(1, 0)]) > 4.0 * 1e6


def test_load_telemetry_jsonl_tolerates_missing_and_garbage(tmp_path):
    base = str(tmp_path / "m.jsonl")
    with open(base, "w") as f:
        for rec in _synthetic_rank_records()[0]:
            f.write(json.dumps(rec) + "\n")
        f.write("NOT JSON\n")  # torn line — skipped, not fatal
    with open(base + ".rank1", "w") as f:
        for rec in _synthetic_rank_records()[1]:
            f.write(json.dumps(rec) + "\n")
    # rank 2's file simply does not exist (killed before its first flush)
    per_rank = diagnostics.load_telemetry_jsonl(base)
    assert sorted(per_rank) == [0, 1]
    trace = diagnostics.merge_chrome_trace(per_rank)
    _validate_chrome_trace(trace)
    assert trace["otherData"]["ranks"] == [0, 1]


def test_trace_merge_filters_by_trace_id():
    per_rank = {0: [_mk_span(0, "solve", "fit/solve", 1.0, 0.5, trace_id="a"),
                    _mk_span(0, "solve", "fit/solve", 2.0, 0.5, trace_id="b")]}
    trace = diagnostics.merge_chrome_trace(per_rank, trace_id="a")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["args"]["trace_id"] == "a"


def test_cv_fit_jsonl_merges_to_valid_chrome_trace(tele, rng):
    # THE acceptance path: a CrossValidator fit's telemetry JSONL -> valid
    # Chrome trace-event JSON, via the same entry point the CLI uses
    from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
    from spark_rapids_ml_tpu.models.classification import LogisticRegression
    from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

    lr = LogisticRegression(maxIter=5, float32_inputs=False).setFeaturesCol("features")
    grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 0.1]).build()
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy"),
        numFolds=2, seed=3,
    )
    cv.fit(_binary_df(rng, n=120))
    trace = diagnostics.chrome_trace_from_files(tele)
    _validate_chrome_trace(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) >= 3
    assert any(e["name"].endswith("solve") for e in xs)
    # every span slice carries its trace identity in args, and the WHOLE
    # cross-validation (fold fits, held-out scoring, refit) is ONE trace
    assert all("trace_id" in e["args"] for e in xs)
    assert len({e["args"]["trace_id"] for e in xs}) == 1
    # ...while the fold/refit fits keep their own fit_ids under it
    fit_ids = {e["args"].get("fit_id") for e in xs if e["name"] == "fit"}
    assert len(fit_ids) >= 2


def test_trace_merge_cli(tmp_path):
    base = str(tmp_path / "m.jsonl")
    with open(base, "w") as f:
        for rec in _synthetic_rank_records()[0]:
            f.write(json.dumps(rec) + "\n")
    out = str(tmp_path / "trace.json")
    from benchmark.trace_merge import main

    assert main([base, "-o", out]) == 0
    with open(out) as f:
        _validate_chrome_trace(json.load(f))


# ---------------------------------------------------------------- post-mortem


def _write_dump(tmp_path, rank, events):
    with open(tmp_path / f"flightrec_rank_{rank}.jsonl", "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def _ev(rank, kind, t, **fields):
    return {"t": t, "kind": kind, "rank": rank, "trace_id": "tr1", **fields}


def test_postmortem_names_failed_rank_round_and_blockage(tmp_path):
    # ranks 0/1 survived long enough to dump; rank 2 was hard-killed (no
    # file). Both survivors recorded RankFailedError(2) at round 3 and were
    # still INSIDE round 3 when they noticed.
    for r in (0, 1):
        evs = []
        for rnd in range(3):
            evs.append(_ev(r, "rdv_enter", 10.0 + rnd, round=rnd, nranks=3))
            evs.append(_ev(r, "rdv_exit", 10.4 + rnd, round=rnd))
        evs.append(_ev(r, "rdv_enter", 13.0 + 0.01 * r, round=3, nranks=3))
        evs.append(_ev(r, "error", 14.0 + 0.01 * r, error="RankFailedError",
                       failed_rank=2, round_index=3, reason="heartbeat stale"))
        _write_dump(tmp_path, r, evs)
    pm = diagnostics.assemble_postmortem(str(tmp_path), nranks=3)
    assert pm["failed_rank"] == 2
    assert pm["failed_round"] == 3
    assert pm["missing_ranks"] == [2]
    assert pm["trace_id"] == "tr1"
    for r in (0, 1):
        assert pm["ranks"][r]["blocked_on"] == "rendezvous round 3"
        assert pm["ranks"][r]["error"] == "RankFailedError"
    # timeline is merged + time-sorted across ranks
    ts = [e["t"] for e in pm["timeline"]]
    assert ts == sorted(ts)
    text = diagnostics.render_postmortem(pm)
    assert "rank 2 failed at round 3" in text
    assert "heartbeat stale" in text
    assert "missing dumps" in text


def test_postmortem_ragged_and_empty(tmp_path):
    # one rank dumped, the rest never started: still assembles, blames the
    # missing rank only via absence (no error events to vote with)
    _write_dump(tmp_path, 0, [_ev(0, "rdv_enter", 1.0, round=0, nranks=2)])
    pm = diagnostics.assemble_postmortem(str(tmp_path), nranks=2)
    assert pm["failed_rank"] == 1  # absence as evidence
    assert pm["ranks"][0]["blocked_on"] == "rendezvous round 0"
    empty = diagnostics.assemble_postmortem(str(tmp_path / "nothing_here"), nranks=2)
    assert empty["failed_rank"] is None and empty["missing_ranks"] == [0, 1]


def test_postmortem_timeout_failure_names_missing_rank_and_round(tmp_path):
    # timeout-shaped failure: nobody published an abort (the hung rank is
    # alive but wedged), so survivors raise RendezvousTimeoutError carrying
    # round_index + missing_ranks — the post-mortem must still name both
    for r in (0, 1):
        evs = [_ev(r, "rdv_enter", 10.0, round=5, nranks=3),
               _ev(r, "error", 70.0, error="RendezvousTimeoutError",
                   round_index=5, missing_ranks=[2],
                   message="rendezvous round 5: ranks [2] missing after 60s")]
        _write_dump(tmp_path, r, evs)
    _write_dump(tmp_path, 2, [_ev(2, "rdv_enter", 9.0, round=4, nranks=3)])  # wedged
    pm = diagnostics.assemble_postmortem(str(tmp_path), nranks=3)
    assert pm["failed_rank"] == 2
    assert pm["failed_round"] == 5
    assert "missing after 60s" in pm["failure_reason"]


def test_postmortem_selects_latest_trace(tmp_path):
    old = [_ev(0, "error", 5.0, error="RankFailedError", failed_rank=1,
               round_index=0) | {"trace_id": "old"}]
    new = [_ev(0, "error", 50.0, error="RankFailedError", failed_rank=2,
               round_index=4) | {"trace_id": "new"}]
    _write_dump(tmp_path, 0, old + new)
    pm = diagnostics.assemble_postmortem(str(tmp_path))
    assert pm["trace_id"] == "new" and pm["failed_rank"] == 2


# -------------------------------------------- 3-rank SIGKILL e2e acceptance --


def _launch_diag_chaos_workers(nranks, tmp_path, plan, *, rounds, heartbeat_s,
                               timeout_s, trace_id):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SRML_FAULT_PLAN"] = plan
    env["SRML_FLIGHTREC_DIR"] = str(tmp_path / "flightrec")
    env["SRML_TRACE_ID"] = trace_id
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    rdv_dir = str(tmp_path / "rdv")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir, exist_ok=True)
    os.makedirs(env["SRML_FLIGHTREC_DIR"], exist_ok=True)
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
    return env["SRML_FLIGHTREC_DIR"], procs, outputs


def test_sigkilled_rank_yields_postmortem_naming_rank_and_round(tmp_path):
    # THE acceptance scenario: a 3-rank FileRendezvous run, rank 2 SIGKILLed
    # entering round 3 (no abort file, no atexit, no dump — hard death).
    # Survivors' SrmlErrors dump their flight-recorder rings; the assembled
    # post-mortem must name the dead rank AND the round, and show what each
    # survivor was blocked on, all correlated by the launcher's trace id.
    kill_round = 3
    trace_id = f"chaos-{uuid.uuid4().hex[:8]}"
    dump_dir, procs, outputs = _launch_diag_chaos_workers(
        3, tmp_path, f"kill:rank=2:round={kill_round}",
        rounds=6, heartbeat_s=0.75, timeout_s=60.0, trace_id=trace_id,
    )
    assert procs[2].returncode == -signal.SIGKILL
    dumps = sorted(os.listdir(dump_dir))
    assert dumps == ["flightrec_rank_0.jsonl", "flightrec_rank_1.jsonl"], (
        f"survivors must dump, the SIGKILLed rank must not: {dumps}\n"
        f"{outputs[0]}\n{outputs[1]}"
    )
    pm = diagnostics.assemble_postmortem(dump_dir, nranks=3, trace_id=trace_id)
    assert pm["failed_rank"] == 2
    assert pm["failed_round"] == kill_round
    assert pm["missing_ranks"] == [2]
    for r in (0, 1):
        info = pm["ranks"][r]
        assert info["blocked_on"] == f"rendezvous round {kill_round}"
        assert info["error"] == "RankFailedError"
        assert info["last_events"], "last-K events from every survivor"
        assert all(
            ev.get("trace_id") == trace_id for ev in info["last_events"]
        ), "all dump events correlated by the launcher trace id"
    text = diagnostics.render_postmortem(pm)
    assert f"rank 2 failed at round {kill_round}" in text
    # the CLI agrees (exit 0 = verdict reached)
    from benchmark.postmortem import main

    assert main([dump_dir, "--nranks", "3", "--trace-id", trace_id]) == 0


# ------------------------------------------------------------ regression gate


def _bench_record(value, counters=None, incomplete=False):
    unit = "rows/sec/chip (geomean of ..." + ("; INCOMPLETE, missing pca)" if incomplete else ")")
    rec = {"metric": "classical_ml_fit_throughput_geomean", "value": value,
           "unit": unit, "vs_baseline": 1.0}
    if counters is not None:
        rec["telemetry"] = {"counters": counters}
    return rec


HIST = [
    _bench_record(100_000.0, {"ingest.rows": 1e6, "ingest.datasets": 2,
                              "placement.device_put_calls": 10}),
    _bench_record(110_000.0, {"ingest.rows": 1e6, "ingest.datasets": 2,
                              "placement.device_put_calls": 10}),
    _bench_record(105_000.0),
]


def test_regression_gate_passes_on_steady_trajectory():
    from benchmark.regression import run_gate

    verdict = run_gate(_bench_record(102_000.0, {"ingest.rows": 1e6,
                                                 "ingest.datasets": 2}), HIST)
    assert verdict["verdict"] == "pass", verdict
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["throughput_geomean"]["status"] == "pass"
    assert lanes["ingest.rows"]["status"] == "pass"
    assert lanes["placement.device_put_calls"]["status"] == "skipped"  # absent current-side


def test_regression_gate_fails_on_2x_slowdown():
    from benchmark.regression import run_gate

    verdict = run_gate(_bench_record(52_500.0), HIST)  # half the median
    assert verdict["verdict"] == "fail"
    assert "throughput_geomean" in verdict["failed_lanes"]


def test_regression_gate_fails_on_counter_blowup_despite_wall_time():
    # the cache-regression class: wall time fine, ingest work DOUBLED
    from benchmark.regression import run_gate

    verdict = run_gate(
        _bench_record(106_000.0, {"ingest.rows": 2e6, "ingest.datasets": 4}), HIST
    )
    assert verdict["verdict"] == "fail"
    assert set(verdict["failed_lanes"]) == {"ingest.rows", "ingest.datasets"}
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["throughput_geomean"]["status"] == "pass"


def test_regression_counter_reference_is_one_coherent_snapshot():
    # a counter that stopped being emitted rounds ago must NOT gate the
    # current run against that stale reference: the reference set is the
    # newest counter-bearing complete run, taken whole
    from benchmark.regression import run_gate

    hist = [
        _bench_record(100_000.0, {"ingest.rows": 1e6, "sparse.csr_to_ell_calls": 1}),
        _bench_record(101_000.0, {"ingest.rows": 1e6}),  # newest counter-bearing
    ]
    verdict = run_gate(
        _bench_record(100_500.0, {"ingest.rows": 1e6, "sparse.csr_to_ell_calls": 5}),
        hist,
    )
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["sparse.csr_to_ell_calls"]["status"] == "skipped"
    assert verdict["verdict"] == "pass"


def test_regression_latency_lanes_gate_lower_better():
    # serving p50/p99 gate as LOWER-is-better lanes (the counter machinery,
    # generalized): within tolerance passes, a p99 blowup fails even though
    # every throughput lane is fine
    from benchmark.regression import run_gate

    def lat_rec(value, p50, p99):
        rec = _bench_record(value)
        rec["latency_lanes"] = {"serving_p50_ms": p50, "serving_p99_ms": p99}
        return rec

    hist = [lat_rec(100_000.0, 1.0, 5.0), lat_rec(102_000.0, 1.2, 5.5)]
    ok = run_gate(lat_rec(101_000.0, 1.1, 6.0), hist)
    lanes = {ln["lane"]: ln for ln in ok["lanes"]}
    assert lanes["latency:serving_p99_ms"]["status"] == "pass"
    assert lanes["latency:serving_p99_ms"]["direction"] == "lower-better"
    assert ok["verdict"] == "pass"

    bad = run_gate(lat_rec(103_000.0, 1.1, 12.0), hist)  # p99 blowup only
    assert bad["verdict"] == "fail"
    assert bad["failed_lanes"] == ["latency:serving_p99_ms"]
    lanes = {ln["lane"]: ln for ln in bad["lanes"]}
    assert lanes["throughput_geomean"]["status"] == "pass"
    assert lanes["latency:serving_p50_ms"]["status"] == "pass"


def test_regression_latency_lane_trajectory_start_is_skipped():
    # the first artifact carrying latency_lanes must not false-fail against
    # history that predates the serving lane
    from benchmark.regression import run_gate

    cur = _bench_record(101_000.0)
    cur["latency_lanes"] = {"serving_p99_ms": 4.0}
    verdict = run_gate(cur, HIST)
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["latency:serving_p99_ms"]["status"] == "skipped"
    assert "trajectory start" in lanes["latency:serving_p99_ms"]["note"]
    assert verdict["verdict"] == "pass"


def test_regression_latency_ratio_is_configurable():
    from benchmark.regression import run_gate

    def lat_rec(value, p99):
        rec = _bench_record(value)
        rec["latency_lanes"] = {"serving_p99_ms": p99}
        return rec

    hist = [lat_rec(100_000.0, 5.0)]
    strict = run_gate(lat_rec(100_000.0, 6.0), hist, max_latency_ratio=1.1)
    assert strict["verdict"] == "fail"
    loose = run_gate(lat_rec(100_000.0, 6.0), hist, max_latency_ratio=2.0)
    assert loose["verdict"] == "pass"


def test_regression_new_lanes_start_their_own_trajectory():
    # the first artifact carrying per-lane values (kmeans_scale/knn joining
    # the geomean) must NOT false-fail against history that lacks them: the
    # geomean lane is skipped (different composition), the per-lane gates
    # are skipped (trajectory start), and the counter lanes still run
    from benchmark.regression import run_gate

    cur = _bench_record(80_000.0, {"ingest.rows": 1e6, "ingest.datasets": 2})
    cur["lanes"] = {"pca": 1e6, "kmeans": 1e5, "kmeans_scale": 3e6, "knn": 5e4}
    verdict = run_gate(cur, HIST)
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["throughput_geomean"]["status"] == "skipped"
    assert "new" in lanes["throughput_geomean"]["note"]
    for name in ("pca", "kmeans", "kmeans_scale", "knn"):
        assert lanes[f"lane:{name}"]["status"] == "skipped"
        assert "trajectory start" in lanes[f"lane:{name}"]["note"]
    assert lanes["ingest.rows"]["status"] == "pass"
    assert verdict["verdict"] == "pass"


def test_regression_per_lane_gate_catches_single_lane_slowdown():
    # once two runs share the lane composition: a 2x slowdown in ONE lane
    # fails its per-lane gate even when the other lanes lift the geomean
    from benchmark.regression import run_gate

    def lane_rec(value, lanes):
        rec = _bench_record(value)
        rec["lanes"] = dict(lanes)
        return rec

    hist = [
        lane_rec(100_000.0, {"kmeans_scale": 3e6, "knn": 5e4}),
        lane_rec(101_000.0, {"kmeans_scale": 3e6, "knn": 5e4}),
    ]
    cur = lane_rec(102_000.0, {"kmeans_scale": 6e6, "knn": 2e4})  # knn halved
    verdict = run_gate(cur, hist)
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["throughput_geomean"]["status"] == "pass"  # same composition
    assert lanes["lane:kmeans_scale"]["status"] == "pass"
    assert lanes["lane:knn"]["status"] == "fail"
    assert verdict["verdict"] == "fail"
    assert "lane:knn" in verdict["failed_lanes"]


def test_regression_optional_extra_lane_does_not_skip_geomean_gate():
    # BENCH_OOCORE toggled on for one round adds an EXTRA embedded lane but
    # the geomean composition (geomean_lanes) is unchanged — the headline
    # gate must still run (and fail here: 2x slowdown), while the extra
    # lane just starts its own trajectory
    from benchmark.regression import run_gate

    def rec(value, extras=None):
        r = _bench_record(value)
        r["lanes"] = {"pca": 1e6, "kmeans": 1e5}
        r["lanes"].update(extras or {})
        r["geomean_lanes"] = ["kmeans", "pca"]
        return r

    hist = [rec(100_000.0), rec(101_000.0)]
    verdict = run_gate(rec(50_000.0, extras={"oocore_stream": 7e4}), hist)
    lanes = {ln["lane"]: ln for ln in verdict["lanes"]}
    assert lanes["throughput_geomean"]["status"] == "fail"
    assert lanes["lane:oocore_stream"]["status"] == "skipped"
    assert "trajectory start" in lanes["lane:oocore_stream"]["note"]
    assert verdict["verdict"] == "fail"


def test_regression_gate_incomplete_run_is_no_data_not_failure():
    from benchmark.regression import run_gate

    verdict = run_gate(_bench_record(0.0, incomplete=True), HIST)
    assert verdict["verdict"] == "no-data"
    # and incomplete runs never poison the reference either
    verdict2 = run_gate(
        _bench_record(102_000.0), HIST + [_bench_record(0.0, incomplete=True)]
    )
    assert verdict2["verdict"] == "pass"
    assert verdict2["reference_runs"] == 3


def test_regression_gate_cli_and_exit_codes(tmp_path):
    from benchmark.regression import main

    # wrap like the round driver does ({"parsed": <record>}) + one bare file
    for i, rec in enumerate(HIST, start=1):
        with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as f:
            json.dump({"n": i, "rc": 0, "parsed": rec}, f)
    with open(tmp_path / "BENCH_r04.json", "w") as f:
        json.dump(_bench_record(50_000.0), f)  # bare record, 2x slowdown
    assert main(["--root", str(tmp_path), "--report-only"]) == 0  # reports, never gates
    assert main(["--root", str(tmp_path)]) == 1  # strict mode fails
    out = tmp_path / "verdict.json"
    assert main(["--root", str(tmp_path), "--report-only", "--out", str(out)]) == 0
    verdict = json.loads(out.read_text())
    assert verdict["verdict"] == "fail" and verdict["current_artifact"] == "BENCH_r04.json"
    # numeric round ordering: r10 sorts after r04, not between r01/r02
    with open(tmp_path / "BENCH_r10.json", "w") as f:
        json.dump(_bench_record(104_000.0), f)
    assert main(["--root", str(tmp_path)]) == 0


def test_regression_gate_no_artifacts_is_no_data(tmp_path):
    from benchmark.regression import main

    assert main(["--root", str(tmp_path)]) == 0


def test_checked_in_trajectory_is_no_data(tmp_path):
    # the ci/test.sh lane must hold on the real repo: no BENCH_r*.json is
    # checked in (the pre-ledger records were retired), so the verdict is
    # no-data, exit 0, and the --out artifact is still written
    from benchmark.regression import main

    out = tmp_path / "verdict.json"
    assert main(["--root", REPO, "--report-only", "--out", str(out)]) == 0
    assert json.loads(out.read_text())["verdict"] == "no-data"


# ------------------------------------------------------------ bench satellite


def test_bench_record_names_device_and_failed_lanes(capsys):
    # one process, no retry history: the record explains ITSELF by naming
    # the device it ran on and the lanes that raised
    import bench

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    bench.emit({"pca": 1e5}, device=device, failed=["kmeans"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["device"] == device
    assert rec["failed"] == ["kmeans"]
    assert "INCOMPLETE" in rec["unit"]  # regression.is_complete drops it


def test_bench_refuses_a_non_tpu_platform(capsys):
    # the suite runs on CPU: bench.py must name what it found and return
    # non-zero without running (or emitting) anything
    import bench

    assert bench.main() != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "refusing" in captured.err and "cpu" in captured.err


def test_protocol_runner_refuses_a_non_tpu_platform():
    from benchmark.benchmark_runner import main

    with pytest.raises(SystemExit, match="refusing.*cpu"):
        main(["protocol", "--num_rows", "100", "--num_cols", "8"])
