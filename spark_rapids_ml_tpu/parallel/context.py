#
# Distributed process-group context — the TPU-native replacement for the
# reference's `CumlContext` (reference common/cuml_context.py:36-167), which
# builds a NCCL clique (rank0 mints a uid, BarrierTaskContext.allGather
# broadcasts it, each rank nccl.init) plus an optional UCX endpoint mesh.
#
# On TPU there is no uid/endpoint plumbing: each worker process calls
# `jax.distributed.initialize(coordinator, num_processes, process_id)` and XLA
# compiles collectives onto ICI/DCN. What remains of the reference design is the
# *rendezvous pattern*: rank0 picks the coordinator endpoint and an
# allgather-of-strings control plane distributes it — exactly where the
# reference broadcasts the NCCL uid. Teardown mirrors destroy-on-success /
# abort-on-exception (cuml_context.py:150-167).
#
from __future__ import annotations

import contextlib
import json
import os
import re
import socket
import sys
import threading
import time
from typing import List, Optional, Tuple

from ..errors import RankFailedError, RendezvousTimeoutError
from ..utils import lockcheck

__all__ = [
    "Rendezvous",
    "LocalRendezvous",
    "FileRendezvous",
    "TpuContext",
    "allgather_ndarray",
    "ABORT_PREFIX",
]

# --------------------------------------------------------------------------
# Abort channel: a failing rank PUBLISHES its failure so survivors raise a
# typed RankFailedError within ~one heartbeat interval instead of blocking
# until (or past) the round deadline. The sentinel is a plain string so it
# travels over whatever substrate the rendezvous uses (slot write in
# LocalRendezvous, `abort_rank_<r>` file in FileRendezvous).
# --------------------------------------------------------------------------

ABORT_PREFIX = "ABORT:"

# A dead rank is declared failed when its heartbeat file is staler than
# MISS_FACTOR x heartbeat_interval_s: 1.5 gives half an interval of scheduler
# slack against false positives while keeping worst-case detection at
# 1.5 x interval after the last touch — inside the 2 x interval budget the
# fault-injection suite asserts.
_HEARTBEAT_MISS_FACTOR = 1.5

# FileRendezvous polls its round files every 5ms, but the failure scan (abort
# files + heartbeat mtimes — O(nranks) stat calls against a possibly-shared
# filesystem) runs at this coarser cadence: detection budgets are "promptly,
# well before the deadline", which ~50ms meets without a stat storm.
_FAILURE_SCAN_INTERVAL_S = 0.05


def format_abort(rank: int, reason: str) -> str:
    """``ABORT:<rank>:<reason>`` sentinel (reason newline-flattened)."""
    return f"{ABORT_PREFIX}{int(rank)}:{' '.join(str(reason).split())}"


def parse_abort(payload: str) -> Optional[Tuple[int, str]]:
    """(rank, reason) when `payload` is an abort sentinel, else None."""
    if not payload.startswith(ABORT_PREFIX):
        return None
    body = payload[len(ABORT_PREFIX):]
    rank_s, _, reason = body.partition(":")
    try:
        return int(rank_s), reason
    except ValueError:  # malformed — treat as unknown-rank abort
        return -1, body


def allgather_ndarray(rendezvous: "Rendezvous", arr, chunk_bytes: Optional[int] = None) -> List:
    """Allgather a host numpy array through the string control plane (base64 of
    the .npy encoding); returns the per-rank arrays in rank order. The analog of
    the reference's base64-over-BarrierTaskContext.allGather payloads
    (reference tree.py:343, knn.py:689-700).

    Large arrays are split into row chunks of at most `chunk_bytes` (default:
    the framework's ``config["broadcast_chunk_bytes"]`` — the reference's 8 GB
    broadcast-chunking knob, clustering.py:1013-1091) so no single control-plane
    round carries an unbounded payload."""
    import base64
    import io

    import numpy as np

    if chunk_bytes is None:
        from ..core import config

        chunk_bytes = int(config.get("broadcast_chunk_bytes", 8 << 30))

    arr = np.ascontiguousarray(arr)
    if arr.ndim == 0:  # scalars can't be row-chunked; one round carries them
        buf = io.BytesIO()
        np.save(buf, arr, allow_pickle=False)
        payloads = rendezvous.allgather(base64.b64encode(buf.getvalue()).decode("ascii"))
        return [
            np.load(io.BytesIO(base64.b64decode(p)), allow_pickle=False)
            for p in payloads
        ]
    row_bytes = max(1, arr[:1].nbytes)
    rows_per_chunk = max(1, chunk_bytes // row_bytes)
    n = arr.shape[0]
    n_chunks = max(1, -(-n // rows_per_chunk))
    # every rank must agree on the ROUND COUNT, not just its own chunking
    n_chunks = max(
        int(p) for p in rendezvous.allgather(str(n_chunks))
    )
    rows_per_chunk = max(1, -(-n // n_chunks))

    def ser(a):
        buf = io.BytesIO()
        np.save(buf, a, allow_pickle=False)
        return base64.b64encode(buf.getvalue()).decode("ascii")

    def de(p):
        return np.load(io.BytesIO(base64.b64decode(p)), allow_pickle=False)

    gathered_chunks: List[List] = []
    for c in range(n_chunks):
        part = arr[c * rows_per_chunk : (c + 1) * rows_per_chunk]
        gathered_chunks.append([de(p) for p in rendezvous.allgather(ser(part))])
    out = []
    for r in range(rendezvous.nranks):
        parts = [gathered_chunks[c][r] for c in range(n_chunks)]
        out.append(np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0])
    return out


def allgather_concat(rendezvous: "Rendezvous", arr):
    """Gather every rank's row block and concatenate in rank order; returns
    ``(global_array, this_rank_row_offset)`` — the shared idiom behind the
    replicated-data strategies (DBSCAN full-set gather, ANN/kNN query
    replication, UMAP fit-sample union)."""
    import numpy as np

    blocks = allgather_ndarray(rendezvous, arr)
    offset = sum(len(b) for b in blocks[: rendezvous.rank])
    return np.concatenate(blocks, axis=0), offset


class Rendezvous:
    """Control-plane interface: allgather small strings + barrier.

    Implementations: `LocalRendezvous` (in-process threads, for tests and
    single-controller mode), and — when running under Spark barrier stages — a
    thin wrapper over `BarrierTaskContext` (see spark/integration module) whose
    `allGather` this API is shaped after.

    In-tree implementations provide `_allgather_impl`; the base `allgather`
    wraps it with telemetry (round-trip counter, payload bytes, latency
    histogram — rank-tagged, no collectives of its own). Out-of-tree
    subclasses overriding `allgather` directly keep working, minus telemetry.

    Failure contract (docs/robustness.md): every round is bounded by a
    deadline (``config["rendezvous_timeout_s"]`` unless the instance sets its
    own) and raises `RendezvousTimeoutError` when it elapses; a failing rank
    calls `abort(reason)` so survivors raise `RankFailedError` promptly
    instead of waiting the deadline out. `begin_epoch(n)` re-namespaces the
    round state so the fit driver's retries never read a failed attempt's
    stale rounds.
    """

    rank: int
    nranks: int

    # --- elastic membership (docs/robustness.md "Elastic recovery") -------
    # Whether this substrate can agree on a reduced live-rank set after a
    # peer dies (`reform`). Substrates with their own supervisor (Spark
    # barrier stages) leave this False: the stage fails and Spark relaunches.
    can_reform: bool = False
    # Original rank ids of the current membership, in current-rank order
    # (identity for a never-reformed group). `reform` results carry the
    # surviving subset so failures and post-mortems keep naming ORIGINAL
    # ranks across recovery epochs.
    _live_ranks: Optional[List[int]] = None
    reform_generation: int = 0

    @property
    def live_ranks(self) -> List[int]:
        return list(self._live_ranks) if self._live_ranks is not None else list(range(self.nranks))

    @property
    def orig_rank(self) -> int:
        return self.live_ranks[self.rank]

    def reform(self, dead_ranks=(), generation: int = 1) -> "Rendezvous":
        """Membership reform round: agree with the other live ranks on the
        surviving rank set (admitting any respawned rank that votes within
        the window) and return a NEW rendezvous over it — fresh namespace,
        ranks renumbered 0..len(live)-1, `live_ranks` mapping back to the
        original ids. `dead_ranks` (ORIGINAL ids) seeds the known-dead set;
        the protocol converges on votes + liveness beyond the hint."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support membership reform; "
            "rank failures stay terminal on this substrate"
        )

    def allgather(self, payload: str) -> List[str]:
        from .. import diagnostics, telemetry

        # round index + epoch are best-effort (in-tree impls track `_round`/
        # `_epoch`; a custom subclass without them still records, just
        # untagged) — they are what the flight recorder / trace merge
        # correlate lockstep rounds by. Epoch matters: `begin_epoch` resets
        # the round counter, so (epoch, round) is unique where round alone
        # collides across retry attempts.
        round_index = getattr(self, "_round", None)
        epoch = getattr(self, "_epoch", None)
        diagnostics.record_event(
            "rdv_enter", round=round_index, epoch=epoch, nranks=self.nranks
        )
        try:
            if not telemetry.enabled():
                out = self._allgather_impl(payload)
            else:
                t_enter = time.time()
                with telemetry.span(
                    "rendezvous.allgather",
                    nranks=self.nranks, round=round_index, epoch=epoch,
                ):
                    out = self._allgather_impl(payload)
                reg = telemetry.registry()
                reg.inc("rendezvous.rounds")
                reg.inc("rendezvous.payload_bytes", len(payload))
                # fleet-plane straggler stamps (sys.modules probe — the
                # control plane never pays the ops_plane import chain; a
                # process without the fleet plane records nothing). Entry +
                # exit wall-clock per (epoch, round) ride the next ops-round
                # payload so the merger can attribute cross-rank skew.
                fleet = sys.modules.get(
                    (__package__ or "spark_rapids_ml_tpu.parallel").rsplit(".", 1)[0]
                    + ".ops_plane.fleet"
                )
                if fleet is not None:
                    try:
                        fleet.note_round_exit(
                            self.rank, round_index, epoch, t_enter, time.time()
                        )
                    except Exception:  # pragma: no cover - stamps are best-effort
                        pass
        except BaseException as e:
            diagnostics.record_event(
                "rdv_fail", round=round_index, error=type(e).__name__
            )
            raise
        diagnostics.record_event("rdv_exit", round=round_index)
        return out

    def _allgather_impl(self, payload: str) -> List[str]:
        raise NotImplementedError

    def barrier(self, timeout_s: Optional[float] = None) -> None:
        """Barrier = empty-payload allgather. `timeout_s` overrides this one
        round's deadline (bounded teardown — TpuContext.__exit__)."""
        if timeout_s is None:
            self.allgather("")
            return
        prev = self._get_timeout_override()
        self._set_timeout_override(timeout_s)
        try:
            self.allgather("")
        finally:
            self._set_timeout_override(prev)

    # the override lives behind a hook pair so WRAPPERS (ChaosRendezvous, any
    # future decorator) can forward it to the inner instance whose
    # _allgather_impl actually reads it
    def _get_timeout_override(self) -> Optional[float]:
        return getattr(self, "_timeout_override", None)

    def _set_timeout_override(self, value: Optional[float]) -> None:
        self._timeout_override = value

    def abort(self, reason: str) -> None:
        """Publish this rank's failure so peers stop waiting. Default no-op:
        substrates with their own supervisor (Spark barrier stages fail the
        whole stage when a task dies) need no in-band abort channel."""

    def begin_epoch(self, epoch: int) -> None:
        """Re-namespace round state for retry attempt `epoch` (fit driver
        resync): implementations reset round counters and clear the previous
        epoch's abort markers so a coordinated retry starts clean."""

    def close(self) -> None:
        """Release background resources (heartbeat threads, file handles)."""

    def _round_timeout_s(self) -> float:
        """Effective per-round deadline: a one-round override (bounded
        teardown) > the instance's own timeout > the framework config knob."""
        override = getattr(self, "_timeout_override", None)
        if override is not None:
            return float(override)
        own = getattr(self, "timeout_s", None)
        if own is not None:
            return float(own)
        from ..core import config

        return float(config.get("rendezvous_timeout_s", 300.0))

    def _raise_rank_failed(self, rank: int, reason: str, round_index: Optional[int]) -> None:
        from .. import telemetry

        telemetry.registry().inc("rendezvous.rank_failures")
        raise RankFailedError(rank, reason, round_index=round_index)

    def _raise_timeout(
        self, round_index: int, missing: Optional[List[int]], timeout_s: float
    ) -> None:
        from .. import telemetry

        telemetry.registry().inc("rendezvous.timeouts")
        who = f"ranks {missing} " if missing else ""
        raise RendezvousTimeoutError(
            f"rendezvous round {round_index}: {who}missing after {timeout_s}s",
            round_index=round_index,
            missing_ranks=missing,
            timeout_s=timeout_s,
        )


class LocalRendezvous(Rendezvous):
    """Thread-barrier rendezvous for N ranks inside one process (test harness).

    The analog of running the reference's barrier stage in Spark local mode
    (tests/conftest.py:44-70 there): real collective code paths, one machine.
    """

    can_reform = True

    class _Shared:
        def __init__(self, nranks: int):
            self.barrier = threading.Barrier(nranks)
            self.slots: List[Optional[str]] = [None] * nranks
            self.lock = lockcheck.make_lock("parallel.context.LocalRendezvous._Shared.lock")
            self.abort_info: Optional[Tuple[int, str]] = None
            self.epoch = 0
            # generation -> (live original-rank list, the survivors' _Shared):
            # the FIRST reformer builds the entry; peers adopt it, so every
            # survivor agrees on one membership + one fresh barrier
            self.reforms: dict = {}

    def __init__(self, rank: int, shared: "_Shared", timeout_s: Optional[float] = None):
        self.rank = rank
        self.nranks = shared.barrier.parties
        self.timeout_s = timeout_s  # None -> config["rendezvous_timeout_s"]
        self._shared = shared
        self._round = 0
        self._epoch = 0

    @classmethod
    def create(cls, nranks: int, timeout_s: Optional[float] = None) -> List["LocalRendezvous"]:
        shared = cls._Shared(nranks)
        return [cls(r, shared, timeout_s) for r in range(nranks)]

    def reform(self, dead_ranks=(), generation: int = 1) -> "LocalRendezvous":
        """Thread-substrate membership reform: the first surviving rank to
        arrive computes the live set (current membership minus `dead_ranks`)
        and builds the survivors' fresh shared barrier; later arrivals adopt
        that entry, so all survivors agree by construction."""
        from .. import diagnostics, telemetry

        shared = self._shared
        generation = int(generation)
        with shared.lock:
            entry = shared.reforms.get(generation)
            if entry is None:
                dead = {int(r) for r in dead_ranks}
                live = [r for r in self.live_ranks if r not in dead]
                if not live:
                    raise RankFailedError(-1, "reform left no live ranks", round_index=None)
                entry = (live, LocalRendezvous._Shared(len(live)))
                shared.reforms[generation] = entry
        live, new_shared = entry
        if self.orig_rank not in live:
            raise RankFailedError(
                self.orig_rank, "this rank was declared dead by the reform round"
            )
        new = LocalRendezvous(live.index(self.orig_rank), new_shared, self.timeout_s)
        new._live_ranks = list(live)
        new.reform_generation = generation
        telemetry.registry().inc("rendezvous.reforms")
        diagnostics.record_event(
            "recovery_reform", generation=generation, survivors=list(live)
        )
        return new

    def abort(self, reason: str) -> None:
        """Publish ``ABORT:<rank>:<reason>`` (extra slot write) and break the
        barrier so every peer blocked in `barrier.wait` wakes immediately
        with a typed RankFailedError instead of its raw BrokenBarrierError."""
        from .. import diagnostics, telemetry

        shared = self._shared
        with shared.lock:
            if shared.abort_info is None:
                shared.abort_info = (self.rank, str(reason))
                cur = shared.slots[self.rank]
                if not (isinstance(cur, tuple) and cur[0] == self._epoch):
                    # leave a current-epoch payload in place: peers that
                    # completed the round's data barrier but have not yet
                    # copied the slots must still receive the full round (a
                    # rank dying BETWEEN rounds must not retroactively tear
                    # the round it finished); they learn of the abort from
                    # `abort_info` via the broken release fence instead
                    shared.slots[self.rank] = format_abort(self.rank, reason)
        telemetry.registry().inc("rendezvous.aborts_published")
        diagnostics.record_event("abort_published", reason=str(reason)[:200])
        diagnostics.flight_recorder().dump(reason="abort published")
        shared.barrier.abort()

    def begin_epoch(self, epoch: int) -> None:
        # idempotent per epoch: only the FIRST rank to request it performs the
        # barrier reset + state clear. A later rank repeating the reset would
        # break peers that already re-entered the new epoch's round 0 wait —
        # spuriously burning their bounded retry budget.
        shared = self._shared
        with shared.lock:
            if shared.epoch >= epoch > 0:
                # another rank already reset for this epoch — adopt it (the
                # slot tags compare against the INSTANCE epoch, so it must
                # advance on the idempotent path too)
                self._round = 0
                self._epoch = int(epoch)
                return
            shared.epoch = epoch
            shared.abort_info = None
            for i in range(self.nranks):
                shared.slots[i] = None
            # reset INSIDE the lock: no peer can observe the new epoch (and
            # re-enter round 0's wait) until the lock is released, so the
            # reset can never break a waiter of the epoch it is creating;
            # reset() does not block when nobody waits
            shared.barrier.reset()
        self._round = 0
        self._epoch = int(epoch)
        from .. import diagnostics

        diagnostics.record_event("epoch_begin", epoch=int(epoch))

    def _wait(self, round_index: int, timeout_s: float) -> None:
        """`barrier.wait` bounded by the round deadline; BrokenBarrierError
        (a peer aborted, a peer timed out, or WE timed out — `wait(timeout)`
        breaks the barrier for everyone) never leaks to callers: it converts
        to RankFailedError when an abort was published, else the symmetric
        RendezvousTimeoutError."""
        try:
            self._shared.barrier.wait(timeout=timeout_s)  # blocking-ok: deadline-bounded
        except threading.BrokenBarrierError:
            info = self._shared.abort_info
            if info is not None:
                self._raise_rank_failed(info[0], info[1], round_index)
            self._raise_timeout(round_index, None, timeout_s)

    def _allgather_impl(self, payload: str) -> List[str]:
        shared = self._shared
        round_index = self._round
        self._round += 1
        info = shared.abort_info
        if info is not None:  # a peer failed in an earlier round — fail fast
            self._raise_rank_failed(info[0], info[1], round_index)
        timeout_s = self._round_timeout_s()
        # slots carry an (epoch, round, payload) tag: a straggler still in a
        # FAILED epoch that only now reaches its old round must not silently
        # exchange payloads with a retried epoch's round on the same barrier —
        # the tag mismatch surfaces as the transient desync error below (the
        # file substrate gets the same protection from e<N>_round_<i> naming)
        shared.slots[self.rank] = (self._epoch, round_index, payload)  # type: ignore[assignment]
        self._wait(round_index, timeout_s)
        out_tagged = list(shared.slots)
        try:
            self._wait(round_index, timeout_s)  # don't let a fast rank overwrite slots early
        except (RankFailedError, RendezvousTimeoutError):
            # The first wait tripped, so every rank published this round and
            # our copy above is the complete exchange; only the RELEASE FENCE
            # broke — a peer died between completing this round and entering
            # the next. If the copy is consistent for (epoch, round), the
            # round happened: return it so survivors keep the progress (and
            # the checkpoint) it carries. The failure still surfaces at the
            # next round's entry fail-fast. A torn copy re-raises. Late
            # copiers are safe because after an abort no rank writes slots
            # again (entry fail-fast precedes the slot write) and `abort`
            # never clobbers a current-epoch payload.
            if not all(
                isinstance(item, tuple)
                and item[0] == self._epoch
                and item[1] == round_index
                for item in out_tagged
            ):
                raise
        out: List[str] = []
        for r, item in enumerate(out_tagged):
            aborted = parse_abort(item) if isinstance(item, str) else None
            if aborted is not None:
                self._raise_rank_failed(aborted[0], aborted[1], round_index)
            if (
                not isinstance(item, tuple)
                or item[0] != self._epoch
                or item[1] != round_index
            ):
                from .. import telemetry

                telemetry.registry().inc("rendezvous.timeouts")
                raise RendezvousTimeoutError(
                    f"rendezvous round {round_index}: rank {r} delivered a "
                    "payload from a different epoch/round (desync after a "
                    "failed attempt)",
                    round_index=round_index,
                    missing_ranks=[r],
                    timeout_s=timeout_s,
                )
            out.append(item[2])
        return out


class BarrierRendezvous(Rendezvous):
    """Adapter over a Spark `BarrierTaskContext`-shaped object — anything with
    ``allGather(str) -> list[str]`` plus a task-info surface. This is the
    control plane the reference uses directly (cuml_context.py:80-103,
    utils.py:205-207): running the framework inside a Spark barrier stage means
    constructing ``TpuContext(rank, nranks, BarrierRendezvous(ctx))`` in the
    task body, exactly where the reference builds its CumlContext."""

    def __init__(self, barrier_ctx, rank: Optional[int] = None, nranks: Optional[int] = None):
        self._ctx = barrier_ctx
        if rank is None:
            rank = int(barrier_ctx.partitionId())
        if nranks is None:
            infos = barrier_ctx.getTaskInfos()
            nranks = len(infos)
        self.rank = rank
        self.nranks = nranks

    def _allgather_impl(self, payload: str) -> List[str]:
        return list(self._ctx.allGather(payload))


class FileRendezvous(Rendezvous):
    """Cross-PROCESS rendezvous over a shared directory.

    The control plane for multi-process SPMD launches outside Spark (and for
    the subprocess test harness): each rank writes its payload to
    ``<dir>/round_<i>/rank_<r>`` and polls until all N files exist — the same
    allgather-of-strings contract the reference gets from
    `BarrierTaskContext.allGather` (reference cuml_context.py:80-103). Works on
    any shared filesystem; write-then-rename makes each file's appearance
    atomic.
    """

    can_reform = True

    def __init__(
        self,
        rank: int,
        nranks: int,
        root: str,
        timeout_s: Optional[float] = None,
        run_id: Optional[str] = None,
        heartbeat_interval_s: Optional[float] = None,
        live_ranks: Optional[List[int]] = None,
        anchor_root: Optional[str] = None,
    ):
        """`run_id` should be a fresh nonce minted by the LAUNCHER and passed to
        every rank — it namespaces this run's rounds so stale files from a
        previous run in the same root can never be read as current. Without it,
        the caller must guarantee `root` is a fresh directory per run.

        `anchor_root` (set by `reform`, never by launchers) pins the reform /
        rejoin coordination directory to the ORIGINAL run root across
        generations: reformed planes nest under ``<anchor>/reform_g<N>/plane``,
        so a respawned rank constructing over the original root and a
        twice-reformed survivor still agree on where membership windows open
        and where rejoin markers appear.

        `timeout_s` is the per-round deadline (None -> the framework's
        ``config["rendezvous_timeout_s"]``). `heartbeat_interval_s` (None ->
        ``config["heartbeat_interval_s"]``) paces the liveness file each rank
        touches from a daemon thread; survivors declare a pending rank dead —
        and raise RankFailedError — when its heartbeat is staler than
        1.5x the interval, so a SIGKILLed peer surfaces within 2x the
        interval instead of after the full round deadline. All ranks must be
        configured with the SAME interval."""
        self.rank = rank
        self.nranks = nranks
        self.root = os.path.join(root, run_id) if run_id else root
        self._anchor = anchor_root if anchor_root else self.root
        self.timeout_s = timeout_s
        self._round = 0
        self._epoch = 0
        if heartbeat_interval_s is None:
            from ..core import config

            heartbeat_interval_s = float(config.get("heartbeat_interval_s", 5.0))
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        # per-peer (last observed mtime, local monotonic when first observed):
        # staleness is measured as LACK OF MTIME PROGRESS on our own monotonic
        # clock, never writer-clock vs reader-clock — cross-host skew on a
        # shared FS must not kill healthy ranks
        self._hb_seen: dict = {}
        self._live_ranks = list(live_ranks) if live_ranks is not None else None
        os.makedirs(self.root, exist_ok=True)
        # stale-state hygiene: when the caller reuses a root WITHOUT a fresh
        # run_id, a previous crashed run's `abort_rank_<r>` file for OUR rank
        # would poison this run's peers into declaring us instantly dead —
        # each rank removes its own stale abort markers (every epoch prefix)
        # before any peer can scan them. run_id-namespaced roots never
        # collide, so this is a no-op there.
        if run_id is None:
            pat = re.compile(
                rf"^((e\d+_)?abort|rejoin_wait)_rank_{self.rank}$"
            )
            try:
                for name in os.listdir(self.root):
                    if pat.match(name):
                        with contextlib.suppress(OSError):
                            os.unlink(os.path.join(self.root, name))
            except OSError:  # pragma: no cover - racing cleanup is best-effort
                pass
            if anchor_root is None:
                self._clean_stale_reform_dirs()
        # heartbeat from CONSTRUCTION, not first allgather: a rank that dies
        # between the two leaves a STALE file (detectable within the
        # staleness window) instead of NO file (indistinguishable from a
        # peer still importing, so survivors would wait out the full round
        # deadline — found by the kill-at-round-0 chaos sweep)
        self._ensure_heartbeat()

    def _clean_stale_reform_dirs(self) -> None:
        """Root-reuse hygiene (no run_id, original-root construction only): a
        previous crashed run's ``reform_g*`` trees would poison this run's
        first recovery epoch — stale member votes close the window instantly
        with the wrong live set, and the stale plane's round files corrupt
        the confirmation allgather. Only trees with NO recent file activity
        are removed: a LIVE window (a peer already reforming, or survivors
        still heartbeating on a reformed plane while we respawn) keeps fresh
        vote/heartbeat mtimes and is left alone."""
        import shutil

        bound = max(
            60.0,
            2.0 * self._round_timeout_s(),
            4.0 * max(0.0, self.heartbeat_interval_s),
        )
        now = time.time()
        try:
            names = [
                n for n in os.listdir(self.root) if re.match(r"^reform_g\d+$", n)
            ]
        except OSError:  # pragma: no cover - root vanished
            return
        for name in names:
            tree = os.path.join(self.root, name)
            newest = 0.0
            for dirpath, _dirnames, filenames in os.walk(tree):
                for entry in [dirpath] + [os.path.join(dirpath, f) for f in filenames]:
                    with contextlib.suppress(OSError):
                        newest = max(newest, os.path.getmtime(entry))
            if now - newest > bound:  # wallclock-ok: compared against file mtimes, which are wall-clock — monotonic would be the wrong clock here
                shutil.rmtree(tree, ignore_errors=True)

    # -- file layout -------------------------------------------------------
    def _eprefix(self) -> str:
        """Epoch namespace for round/abort files ('' for the first attempt —
        the historical layout — so single-attempt runs keep their file names)."""
        return "" if self._epoch == 0 else f"e{self._epoch}_"

    def _abort_path(self, rank: int) -> str:
        return os.path.join(self.root, f"{self._eprefix()}abort_rank_{rank}")

    def _heartbeat_path(self, rank: int) -> str:
        return os.path.join(self.root, f"heartbeat_rank_{rank}")

    def _rejoin_wait_path(self, orig_rank: int) -> str:
        # keyed by ORIGINAL rank id (stable across reforms), epoch-less (the
        # marker describes an incarnation, not a round), and ANCHORED at the
        # original run root — a respawn writing over the original root and a
        # reformed survivor scanning from its g<N> plane must agree on it
        return os.path.join(self._anchor, f"rejoin_wait_rank_{orig_rank}")

    # -- heartbeat ---------------------------------------------------------
    def _touch_heartbeat(self) -> None:
        path = self._heartbeat_path(self.rank)
        try:
            with open(path, "a"):
                pass
            os.utime(path, None)
        except OSError:  # pragma: no cover - transient FS hiccup; next beat retries
            pass

    def _ensure_heartbeat(self) -> None:
        if self.heartbeat_interval_s <= 0:  # escape hatch: liveness via deadline only
            return
        if self._hb_thread is not None and self._hb_thread.is_alive():
            return
        self._touch_heartbeat()

        def beat() -> None:
            # Event.wait(interval) is the pacing AND the stop signal; a
            # SIGKILL stops the touches instantly — which is the point.
            while not self._hb_stop.wait(self.heartbeat_interval_s):
                self._touch_heartbeat()

        self._hb_stop.clear()
        self._hb_thread = threading.Thread(
            target=beat, name=f"srml-heartbeat-rank{self.rank}", daemon=True
        )
        self._hb_thread.start()

    def close(self) -> None:
        """Stop the heartbeat thread (daemonized, so leaking one is harmless —
        but long-lived launchers creating many rendezvous should close)."""
        self._hb_stop.set()

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self._hb_stop.set()
        except Exception:
            pass

    # -- abort channel -----------------------------------------------------
    def abort(self, reason: str) -> None:
        """Publish ``abort_rank_<rank>`` (write-then-rename, atomic appearance)
        carrying the ABORT sentinel; survivors' poll loops see it within one
        poll tick and raise RankFailedError."""
        from .. import diagnostics, telemetry

        tmp = os.path.join(self.root, f".abort_rank_{self.rank}.tmp")
        try:
            with open(tmp, "w") as f:
                f.write(format_abort(self.rank, reason))
            os.replace(tmp, self._abort_path(self.rank))
        except OSError:  # pragma: no cover - abort is best-effort by design
            return
        telemetry.registry().inc("rendezvous.aborts_published")
        diagnostics.record_event("abort_published", reason=str(reason)[:200])
        diagnostics.flight_recorder().dump(reason="abort published")

    def begin_epoch(self, epoch: int) -> None:
        self._epoch = int(epoch)
        self._round = 0
        from .. import diagnostics

        diagnostics.record_event("epoch_begin", epoch=int(epoch))

    # -- membership reform (elastic recovery) -----------------------------
    def _reform_dir(self, generation: int) -> str:
        # anchored: generation N+1's window must be discoverable both by
        # survivors rooted at the g<N> plane and by a respawn constructing
        # over the ORIGINAL root
        return os.path.join(self._anchor, f"reform_g{int(generation)}")

    def latest_generation(self) -> Optional[int]:
        """Highest reform generation already opened under the anchor root
        (how a respawned rank discovers which epoch boundary to rejoin at)."""
        best = None
        try:
            for name in os.listdir(self._anchor):
                m = re.match(r"^reform_g(\d+)$", name)
                if m:
                    g = int(m.group(1))
                    best = g if best is None else max(best, g)
        except OSError:  # pragma: no cover - root vanished
            return None
        return best

    def rejoin(self, generation: Optional[int] = None) -> "FileRendezvous":
        """Respawned-rank entry point: vote in the open reform round (found
        via `latest_generation` when not given) and join the reformed group
        at the epoch boundary. With no generation given, POLLS for a reform
        window to open (deadline-bounded) — a respawned process typically
        launches while survivors are still detecting the death, before any
        window exists. The survivors' window must still be open when the vote
        lands (``config["recovery_rejoin_grace_s"]`` keeps it open for
        prompt respawns).

        Entry publishes a ``rejoin_wait_rank_<orig>`` marker FIRST: this
        incarnation's heartbeat resumes touching the dead rank's liveness
        file from construction, which would otherwise make the corpse look
        alive to survivors blocked in a round — they'd wait out the full
        round deadline instead of detecting the death within the heartbeat
        budget (and this rejoiner's window poll can expire before any reform
        opens). The marker is positive evidence the ORIGINAL incarnation
        died, so survivors raise RankFailedError within one failure-scan
        tick and the reform window opens while we are still polling for it.
        The marker is removed on admission."""
        me = self.orig_rank
        tmp = os.path.join(self.root, f".rejoin_wait_rank_{me}.tmp")
        try:
            with open(tmp, "w") as f:
                f.write(json.dumps({"rank": me, "t": time.time()}))  # sink-ok: control-plane marker payload, not a telemetry record
            os.replace(tmp, self._rejoin_wait_path(me))
        except OSError:  # pragma: no cover - best-effort; survivors fall back to the round deadline
            pass
        if generation is None:
            deadline = time.monotonic() + self._round_timeout_s()
            while True:  # blocking-ok: deadline-bounded window poll
                generation = self.latest_generation()
                if generation is not None:
                    break
                if time.monotonic() > deadline:
                    raise RendezvousTimeoutError(
                        "rejoin: no reform round opened under this root "
                        "within the deadline",
                        timeout_s=self._round_timeout_s(),
                    )
                time.sleep(0.02)  # sleep-ok: poll tick inside the deadline-bounded rejoin wait
        reformed = self.reform(dead_ranks=(), generation=generation)
        with contextlib.suppress(OSError):
            os.unlink(self._rejoin_wait_path(me))
        return reformed

    def reform(self, dead_ranks=(), generation: int = 1) -> "FileRendezvous":
        """File-substrate membership reform.

        Each participant votes by writing ``member_rank_<orig>`` under
        ``reform_g<generation>`` (write-then-rename), then waits until every
        currently-expected rank has either voted or is evidently dead (its
        abort file exists, or its heartbeat/vote never materializes within
        the staleness window). Votes from OUTSIDE the expected set — a
        respawned rank rejoining — are admitted. The window stays open at
        least ``config["recovery_rejoin_grace_s"]`` so a prompt respawn is
        admitted deterministically. The agreed live set is then CONFIRMED
        with one allgather round on the reformed plane: any membership
        mismatch (a straggler vote landing after one side closed) surfaces
        as the transient `RendezvousTimeoutError`, never a silently split
        group."""
        from .. import diagnostics, telemetry
        from ..core import config

        generation = int(generation)
        member_dir = self._reform_dir(generation)
        os.makedirs(member_dir, exist_ok=True)
        me = self.orig_rank
        tmp = os.path.join(member_dir, f".member_rank_{me}.tmp")
        with open(tmp, "w") as f:
            f.write(json.dumps({"rank": me, "t": time.time()}))  # sink-ok: control-plane vote payload, not a telemetry record
        os.replace(tmp, os.path.join(member_dir, f"member_rank_{me}"))

        dead = {int(r) for r in dead_ranks}
        expected = set(self.live_ranks)
        live_map = self.live_ranks  # current index <- position of orig id
        stale_after = (
            _HEARTBEAT_MISS_FACTOR * self.heartbeat_interval_s
            if self.heartbeat_interval_s > 0
            else 2.0
        )
        grace = float(config.get("recovery_rejoin_grace_s", 0.0))
        timeout_s = self._round_timeout_s()
        start = time.monotonic()
        deadline = start + timeout_s
        member_pat = re.compile(r"^member_rank_(\d+)$")
        while True:  # blocking-ok: deadline- and staleness-bounded vote scan
            filed = set()
            for name in os.listdir(member_dir):
                m = member_pat.match(name)
                if m:
                    filed.add(int(m.group(1)))
            now_m = time.monotonic()
            pending = expected - filed - dead
            for r in list(pending):
                cur = live_map.index(r)
                if os.path.exists(self._abort_path(cur)):
                    dead.add(r)
                    pending.discard(r)
                    continue
                # no vote yet: alive only if its heartbeat keeps progressing
                try:
                    mtime = os.path.getmtime(self._heartbeat_path(cur))
                except OSError:
                    mtime = None
                seen = self._hb_seen.get(("reform", r))
                if mtime is not None and (seen is None or mtime != seen[0]):
                    self._hb_seen[("reform", r)] = (mtime, now_m)
                    continue
                base_t = seen[1] if seen is not None else start
                if now_m - base_t > stale_after:
                    dead.add(r)
                    pending.discard(r)
            if not pending and (
                now_m - start >= grace
                # every ORIGINALLY-expected member (incl. a respawned
                # incarnation of a dead rank) has voted: no further vote can
                # arrive, so the grace window may close early — a prompt
                # rejoin doesn't cost survivors the full grace wait
                or filed >= expected
            ):
                break
            if now_m > deadline:
                telemetry.registry().inc("rendezvous.timeouts")
                raise RendezvousTimeoutError(
                    f"reform generation {generation}: ranks {sorted(pending)} "
                    f"neither voted nor died within {timeout_s}s",
                    missing_ranks=sorted(pending),
                    timeout_s=timeout_s,
                )
            time.sleep(0.01)  # sleep-ok: poll tick inside the deadline-bounded reform scan
        # a VOTE proves a live process — the dead set only governs who the
        # window stops waiting for. A respawned incarnation of a killed rank
        # that votes inside the window is admitted even though its original
        # id was seeded dead (that is the whole rejoin path).
        live = sorted(filed)
        if me not in live or not live:
            raise RankFailedError(
                me, "this rank was excluded by the reform round", round_index=None
            )
        new = FileRendezvous(
            live.index(me),
            len(live),
            os.path.join(member_dir, "plane"),
            timeout_s=self.timeout_s,
            heartbeat_interval_s=self.heartbeat_interval_s,
            live_ranks=live,
            anchor_root=self._anchor,
        )
        new.reform_generation = generation
        # confirmation round: every member states the set it computed; a
        # mismatch means a vote landed after somebody closed the window
        confirmed = new.allgather("REFORM:" + json.dumps(live))
        if any(p != confirmed[0] for p in confirmed):
            telemetry.registry().inc("rendezvous.timeouts")
            raise RendezvousTimeoutError(
                f"reform generation {generation}: members disagree on the "
                "live set (vote landed after the window closed)",
                timeout_s=timeout_s,
            )
        telemetry.registry().inc("rendezvous.reforms")
        diagnostics.record_event(
            "recovery_reform", generation=generation, survivors=live,
            dead=sorted(dead),
        )
        return new

    def _check_failures(self, pending, round_index: int) -> None:
        """Raise RankFailedError when any rank published an abort for this
        epoch, a PENDING peer's respawned incarnation announced it is
        waiting to rejoin (the original is dead even though the respawn's
        heartbeat keeps the liveness file fresh), or a PENDING peer's
        heartbeat went stale (killed process — it cannot publish
        anything)."""
        for r in range(self.nranks):
            if r == self.rank:
                continue
            path = self._abort_path(r)
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        parsed = parse_abort(f.read())
                except OSError:
                    parsed = None
                rank, reason = parsed if parsed is not None else (r, "abort file unreadable")
                self._raise_rank_failed(rank, reason, round_index)
        live = self.live_ranks
        for r in pending:
            if r == self.rank:
                continue
            # a rejoin marker is POSITIVE death evidence for the original
            # incarnation — and it must outrank heartbeat progress, because
            # the respawn resumes touching the same liveness file from
            # construction (a corpse that looks alive would otherwise pin
            # survivors in this round until the full deadline)
            if os.path.exists(self._rejoin_wait_path(live[r])):
                # raise the CURRENT index (like the abort/heartbeat paths —
                # recoverable_stage maps failed_rank through live_ranks once;
                # raising the original id here would double-map it after a
                # prior reform and blame an innocent survivor)
                self._raise_rank_failed(
                    r,
                    f"process died (original rank {live[r]}); a respawned "
                    "incarnation is waiting to rejoin at the next reform round",
                    round_index,
                )
        if self.heartbeat_interval_s <= 0:
            return
        stale_after = _HEARTBEAT_MISS_FACTOR * self.heartbeat_interval_s
        now_m = time.monotonic()
        for r in pending:
            if r == self.rank:
                continue
            try:
                mtime = os.path.getmtime(self._heartbeat_path(r))
            except OSError:
                continue  # not started yet — only the round deadline applies
            seen = self._hb_seen.get(r)
            if seen is None or mtime != seen[0]:
                self._hb_seen[r] = (mtime, now_m)  # progress observed — alive
                continue
            stale_for = now_m - seen[1]
            if stale_for > stale_after:
                self._raise_rank_failed(
                    r,
                    f"heartbeat stale for {stale_for:.2f}s "
                    f"(interval {self.heartbeat_interval_s}s) — process presumed dead",
                    round_index,
                )

    def _allgather_impl(self, payload: str) -> List[str]:
        self._ensure_heartbeat()
        round_index = self._round
        round_dir = os.path.join(self.root, f"{self._eprefix()}round_{round_index}")
        self._round += 1
        os.makedirs(round_dir, exist_ok=True)
        tmp = os.path.join(round_dir, f".rank_{self.rank}.tmp")
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, os.path.join(round_dir, f"rank_{self.rank}"))
        timeout_s = self._round_timeout_s()
        deadline = time.monotonic() + timeout_s
        out: List[Optional[str]] = [None] * self.nranks
        pending = set(range(self.nranks))
        next_failure_scan = 0.0  # first iteration scans immediately
        while pending:  # blocking-ok: deadline- and heartbeat-bounded poll
            for r in list(pending):
                path = os.path.join(round_dir, f"rank_{r}")
                if os.path.exists(path):
                    with open(path) as f:
                        out[r] = f.read()
                    pending.discard(r)
            if pending:
                now_m = time.monotonic()
                # round files poll at 5ms, but the failure scan (abort files +
                # heartbeat mtimes: O(nranks) stats against a possibly-shared
                # FS) is throttled — ~50ms detection granularity meets every
                # promised budget without a stat storm
                if now_m >= next_failure_scan:
                    self._check_failures(pending, round_index)
                    next_failure_scan = now_m + _FAILURE_SCAN_INTERVAL_S
                if now_m > deadline:
                    self._raise_timeout(round_index, sorted(pending), timeout_s)
                time.sleep(0.005)  # sleep-ok: poll tick inside the deadline-bounded round wait
        return out  # type: ignore[return-value]


def _free_port() -> int:
    with socket.socket() as s:  # exporter-ok: jax.distributed coordinator port probe, not a metrics endpoint
        s.bind(("", 0))
        return s.getsockname()[1]


# The context active for the current fit call, set by TpuContext.__enter__.
# Estimators pick this up so `with TpuContext(...): est.fit(local_df)` routes
# the fit through the caller's process group — the analog of the reference's
# train-UDF body running inside its CumlContext (reference core.py:768-781).
_ACTIVE_CONTEXT: Optional["TpuContext"] = None


class TpuContext:
    """Context manager that stands up the per-job process group and mesh.

    Modes:
      * ``nranks == 1`` or single-controller (one process drives all local
        devices): no distributed init; mesh spans local devices.
      * SPMD multi-process: rank0 advertises ``host:port`` through the
        rendezvous, every rank calls ``jax.distributed.initialize``; the mesh
        then spans the global device list. ICI carries collectives within a pod
        slice, DCN across slices — no in-tree data plane is needed (the UCX
        layer of the reference has no TPU analog).
    """

    def __init__(
        self,
        rank: int,
        nranks: int,
        rendezvous: Optional[Rendezvous] = None,
        *,
        require_distributed: bool = False,
        num_devices: Optional[int] = None,
    ):
        self.rank = rank
        self.nranks = nranks
        self.rendezvous = rendezvous
        self.require_distributed = require_distributed
        self.num_devices = num_devices
        self.mesh = None
        self._initialized_distributed = False
        self._prev_active: Optional["TpuContext"] = None

    @classmethod
    def current(cls) -> Optional["TpuContext"]:
        """The context entered by the caller, if any (estimators consult this)."""
        return _ACTIVE_CONTEXT

    def adopt_reform(self, new_rendezvous: "Rendezvous") -> None:
        """Adopt a reformed (survivor) rendezvous: renumbered rank/nranks,
        and the mesh rebuilt over the survivors' devices (the dead rank's
        chips leave the mesh; its row shards are re-placed from
        host-retained ingest chunks when the fit re-enters). Called by
        `core.recoverable_stage` at each recovery epoch."""
        old_live = set(self.live_ranks_hint())
        self.rendezvous = new_rendezvous
        self.rank = new_rendezvous.rank
        self.nranks = new_rendezvous.nranks
        self.recovery_generation = int(getattr(new_rendezvous, "reform_generation", 0))
        dead_procs = old_live - set(
            getattr(new_rendezvous, "live_ranks", range(new_rendezvous.nranks))
        )
        if self.mesh is not None and dead_procs:
            import jax

            from .mesh import survivor_mesh

            if jax.process_count() > 1:
                try:
                    self.mesh = survivor_mesh(self.mesh, dead_procs)
                except Exception as e:  # pragma: no cover - backend-specific
                    from ..utils import get_logger

                    get_logger("TpuContext").warning(
                        "could not rebuild the mesh over survivors (%s: %s); "
                        "keeping the previous mesh", type(e).__name__, e,
                    )

    def live_ranks_hint(self) -> List[int]:
        """Original rank ids of the current membership (identity when the
        rendezvous tracks none)."""
        if self.rendezvous is not None:
            return list(getattr(self.rendezvous, "live_ranks", range(self.nranks)))
        return list(range(self.nranks))

    @property
    def is_spmd(self) -> bool:
        """True when each rank holds only its LOCAL row block (multi-process
        SPMD), so estimators must rendezvous for global layout/host stats."""
        return self.nranks > 1

    def __enter__(self) -> "TpuContext":
        global _ACTIVE_CONTEXT
        import jax

        if self.nranks > 1:
            # nranks > 1 always means multi-process SPMD: the process group
            # must be live and a control-plane rendezvous present, or ranks
            # would silently fit their local block as if it were global
            if self.rendezvous is None:
                raise RuntimeError(
                    "TpuContext with nranks > 1 needs a rendezvous (control-plane "
                    "allgather for partition layout and host-side statistics)"
                )
            # probe distributed state WITHOUT jax.process_count(): that call
            # initializes the XLA backend, after which distributed init is
            # rejected
            if not jax.distributed.is_initialized():
                if self.rank == 0:
                    coordinator = json.dumps({"addr": f"{socket.gethostname()}:{_free_port()}"})
                else:
                    coordinator = json.dumps({})
                gathered = self.rendezvous.allgather(coordinator)
                addr = json.loads(gathered[0])["addr"]
                jax.distributed.initialize(
                    coordinator_address=addr, num_processes=self.nranks, process_id=self.rank
                )
                self._initialized_distributed = True
            if jax.process_count() != self.nranks:
                raise RuntimeError(
                    f"jax.distributed is initialized with {jax.process_count()} "
                    f"processes but TpuContext was built for nranks={self.nranks}"
                )

        from .mesh import get_mesh

        self.mesh = get_mesh(self.num_devices)
        self._prev_active = _ACTIVE_CONTEXT
        _ACTIVE_CONTEXT = self
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> bool:
        global _ACTIVE_CONTEXT
        import jax

        _ACTIVE_CONTEXT = self._prev_active
        if (
            self.rendezvous is not None
            and exc_type is not None
            and not (isinstance(exc_val, RankFailedError) or issubclass(exc_type, RankFailedError))
        ):
            # propagate the failure FIRST (before any local teardown) so peers
            # blocked in a rendezvous round unwind within one failure scan —
            # the abort-on-exception side of the reference's destroy-on-
            # success / abort-on-exception teardown (cuml_context.py:150-167).
            # A RankFailedError is NOT re-published: we are relaying a peer's
            # failure, and a cascade of abort files would let later scanners
            # blame a healthy survivor instead of the root-cause rank. Abort
            # is best-effort and must never mask the original exception.
            try:
                self.rendezvous.abort(f"{exc_type.__name__}: {exc_val}")
            except Exception:
                pass
        if self._initialized_distributed:
            # destroy on success, abort-equivalent on exception
            # (reference cuml_context.py:150-167)
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
        if self.rendezvous is not None and exc_type is None:
            # success-path sync is BOUNDED: a peer that already exited (or
            # died without publishing) must not hang our teardown forever. A
            # timeout here is a warning, not an error — our own work
            # succeeded; a published peer failure still propagates.
            from ..core import config
            from ..utils import get_logger

            teardown_s = min(
                float(config.get("teardown_timeout_s", 15.0)),
                self.rendezvous._round_timeout_s(),
            )
            try:
                self.rendezvous.barrier(timeout_s=teardown_s)
            except RendezvousTimeoutError:
                get_logger("TpuContext").warning(
                    "teardown barrier timed out after %.1fs (a peer already "
                    "exited?); continuing — local results are complete",
                    teardown_s,
                )
            except RankFailedError as e:
                # a peer died between finishing its work and the teardown
                # sync: OUR fit succeeded, so this is a warning, not an error
                # — failing here would discard completed local results
                get_logger("TpuContext").warning(
                    "peer failure during teardown barrier (%s); continuing — "
                    "local results are complete", e,
                )
        return False
