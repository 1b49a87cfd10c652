#
# Chaos smoke lane (ci/test.sh): two tiny end-to-end fault scenarios.
#
# (1) kill+recover: a 3-process FileRendezvous `recover`-mode fit
# (tests/chaos_worker.py — a distributed Lloyd loop under
# core.recoverable_stage with solver checkpoints on), SIGKILLs rank 2
# mid-solve via SRML_FAULT_PLAN, and asserts the elastic-recovery contract
# held: survivors reform to a 2-rank group, resume from the checkpoint,
# finish clean, and the assembled post-mortem NAMES the killed rank and the
# recovery epoch.
#
# (2) oom-demotion: a single-process fit under an `oom:budget=` chaos plan
# (tests/oom_worker.py) must complete via the RESIDENT -> STREAM demotion
# ladder — fit.demotions == 1, overlap measured, model matching the clean
# resident fit the same process runs once the plan is spent (docs/
# robustness.md "Memory safety").
#
# The full parametrized sweeps live in tests/test_chaos.py +
# tests/test_oocore.py; this is the pre-merge canary.
#
import json
import os
import signal
import subprocess
import sys
import tempfile
import uuid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "chaos_worker.py")
OOM_WORKER = os.path.join(REPO, "tests", "oom_worker.py")

NRANKS = 3
ITERS = 6
# round 8 = iteration 3 of the worker's 2-rounds-per-iteration traffic —
# after the iteration-2 checkpoint landed, so survivors must RESUME
PLAN = "kill:rank=2:round=8"


def fail(msg: str) -> None:
    print(f"chaos smoke: FAIL — {msg}")
    sys.exit(1)


def oom_demotion_case(tmp: str) -> None:
    """An injected-budget OOM at fit entry completes the fit via demotion."""
    out = os.path.join(tmp, "oom_demote.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SRML_FAULT_PLAN"] = "oom:budget=16000"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the 16000-byte budget is calibrated per device over the same 8-device
    # CPU mesh the pytest harness forces (tests/conftest.py): demote the
    # resident placement, admit the streaming working set
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, OOM_WORKER, "demote", out],
        env=env, capture_output=True, timeout=240,
    )
    if proc.returncode != 0:
        fail(
            "oom worker exited "
            f"{proc.returncode}:\n{proc.stdout.decode()}{proc.stderr.decode()}"
        )
    with open(out) as f:
        res = json.load(f)
    if res["error"] is not None:
        fail(f"oom worker raised {res['error']}: {res.get('detail')}")
    if res["admission_faulted"].get("verdict") != "stream":
        fail(f"faulted fit was not demoted: {res['admission_faulted']}")
    if res["admission_clean"].get("verdict") != "resident":
        fail(f"clean fit did not run resident: {res['admission_clean']}")
    if res["counters"].get("fit.demotions") != 1:
        fail(f"fit.demotions == {res['counters'].get('fit.demotions')}, expected 1")
    if not res["gauges"].get("ingest.overlap_fraction", 0) > 0:
        fail("no double-buffer overlap measured on the demoted fit")
    if not res["max_rel_center_diff"] < 1e-9:
        fail(f"streamed centers diverged: {res['max_rel_center_diff']}")
    print(
        "chaos smoke: OK — injected-budget OOM demoted to streaming "
        f"(overlap {res['gauges']['ingest.overlap_fraction']:.2f}), "
        "model matches resident"
    )


def main() -> None:
    # this parent never initialises a JAX backend (diagnostics is host-side
    # file assembly), and every child is pinned to CPU: no process here
    # needs, or could be starved of, a chip
    sys.path.insert(0, REPO)
    from spark_rapids_ml_tpu import diagnostics

    tmp = tempfile.mkdtemp(prefix="srml_chaos_smoke_")
    flightrec = os.path.join(tmp, "flightrec")
    out_dir = os.path.join(tmp, "out")
    os.makedirs(out_dir, exist_ok=True)
    run_id = uuid.uuid4().hex
    trace_id = f"smoke-{run_id[:8]}"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SRML_FAULT_PLAN"] = PLAN
    env["SRML_FLIGHTREC_DIR"] = flightrec
    env["SRML_TRACE_ID"] = trace_id
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [
                sys.executable, WORKER, str(r), str(NRANKS),
                os.path.join(tmp, "rdv"), out_dir, run_id,
                str(ITERS), "2.0", "45.0", "recover",
            ],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for r in range(NRANKS)
    ]
    outputs = [p.communicate(timeout=180)[0].decode() for p in procs]

    if procs[2].returncode != -signal.SIGKILL:
        fail(f"victim rank 2 exited {procs[2].returncode}, expected SIGKILL")
    for r in (0, 1):
        if procs[r].returncode != 0:
            fail(f"survivor rank {r} exited {procs[r].returncode}:\n{outputs[r]}")
        with open(os.path.join(out_dir, f"result_rank{r}.json")) as f:
            res = json.load(f)
        if res["error"] is not None:
            fail(f"survivor rank {r} raised {res['error']}: {res.get('detail')}")
        if res["live_final"] != [0, 1]:
            fail(f"survivor rank {r} finished on {res['live_final']}, expected [0, 1]")
        c = res["counters"]
        if c.get("fit.recoveries") != 1:
            fail(f"rank {r} fit.recoveries == {c.get('fit.recoveries')}, expected 1")
        if not c.get("checkpoint.restores"):
            fail(f"rank {r} resumed from scratch (no checkpoint.restores)")

    pm = diagnostics.assemble_postmortem(flightrec, nranks=NRANKS, trace_id=trace_id)
    if pm.get("failed_rank") != 2:
        fail(f"post-mortem blamed rank {pm.get('failed_rank')}, expected 2")
    epochs = pm.get("recovery_epochs") or []
    if not any(e.get("survivors") == [0, 1] for e in epochs):
        fail(f"post-mortem shows no [0, 1]-survivor recovery epoch: {epochs}")
    print(
        "chaos smoke: OK — rank 2 SIGKILLed, survivors resumed from "
        f"checkpoint, post-mortem names rank 2 and epoch g{epochs[0]['generation']}"
    )
    oom_demotion_case(tmp)


if __name__ == "__main__":
    main()
