#
# Distributed diagnostics: cross-rank trace correlation, an always-on flight
# recorder, and the post-mortem / trace-merge assemblers built on both.
#
# The barrier-mode design (PAPER.md) makes every fit a lockstep dance across
# ranks, but per-rank telemetry files observe each rank in isolation. This
# module is the correlation layer on top of the telemetry registry (PR 2) and
# the fault-tolerant control plane (PR 3):
#
#   * TRACE CORRELATION — every fit runs inside `trace_scope()`: rank 0 mints
#     a `trace_id`, propagates it through one rendezvous round at trace begin
#     (the Dapper pattern: the id rides the control plane the fit already
#     trusts), and every span / fit / flight-recorder record emitted during
#     the scope carries `trace_id` + `fit_id` + rank. `merge_chrome_trace`
#     turns the per-rank telemetry JSONL files into one Chrome trace-event
#     JSON (one track per rank, rendezvous rounds as flow arrows, clock skew
#     aligned on barrier rounds) loadable in Perfetto / chrome://tracing.
#   * FLIGHT RECORDER — a bounded, always-on, lock-cheap per-rank ring of
#     structured events (span begin/end, rendezvous round enter/exit, solver
#     ticks, chaos injections, retry attempts; control-plane events record
#     unconditionally, span/solver events only while telemetry is enabled —
#     disabled spans are a no-op object with nothing to record, the PR-2
#     zero-cost contract). On any `SrmlError` the ring
#     is dumped to `flightrec_rank_<r>.jsonl` (when a dump dir is configured)
#     and the last-K events are attached to the exception as
#     ``exc.flightrec_tail`` — "the failure already happened; what was
#     everyone doing?" answered without re-running.
#   * POST-MORTEM — `assemble_postmortem` correlates all ranks' dumps by
#     trace id into one timeline naming the failed rank, the round it died
#     in, and what every survivor was blocked on when it noticed.
#
# Contracts:
#   * ALWAYS ON, NEAR-FREE: recording an event is one time.time() + one dict
#     + one lock'd ring write; no I/O until a dump is requested. Disable
#     entirely with SRML_FLIGHTREC=0.
#   * NO SILENT CAPS (PR-2 convention): ring overwrites are counted — the
#     recorder's `stats()["dropped"]`, the `flightrec.events_dropped`
#     registry counter, and a `telemetry.summary()` health line all surface
#     truncation.
#   * NO COLLECTIVES OF ITS OWN except the single trace-id round inside
#     `trace_scope` under SPMD — which runs in lockstep on every rank, at a
#     point where the control plane is already live.
#
from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import re
import sys
import threading
import time
import uuid
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .utils import lockcheck

__all__ = [
    "trace_scope",
    "current_trace",
    "trace_tags",
    "set_process_rank",
    "FlightRecorder",
    "flight_recorder",
    "record_event",
    "on_srml_error",
    "flightrec_dir",
    "flightrec_dump_path",
    "load_flightrec_dumps",
    "assemble_postmortem",
    "render_postmortem",
    "load_telemetry_jsonl",
    "merge_chrome_trace",
    "chrome_trace_from_files",
]

FLIGHTREC_FILE_PREFIX = "flightrec_rank_"

# Default ring capacity / exception-tail length. Both env-overridable; the
# capacity bound is what keeps "always-on" honest on a long-lived process.
_DEFAULT_CAPACITY = 2048
_DEFAULT_TAIL = 25


# Process-rank override for launchers that run no TpuContext (the subprocess
# chaos harness, bare-rendezvous drivers): without it every worker would tag
# events rank 0 and clobber one shared flightrec_rank_0.jsonl dump.
_PROCESS_RANK: Optional[int] = None


def set_process_rank(rank: int) -> None:
    """Pin this process's rank for record tagging + dump naming when no
    `TpuContext` is entered (an active context always wins). The `SRML_RANK`
    env var is the no-code-change equivalent for subprocess launchers."""
    global _PROCESS_RANK
    _PROCESS_RANK = int(rank)


def _rank() -> int:
    """This rank, for event tagging: active TpuContext > `set_process_rank`
    > `SRML_RANK` env > 0. Control-plane only (never initializes an XLA
    backend). telemetry._rank delegates here, so the JSONL sink's per-rank
    file naming follows the same resolution."""
    try:
        from .parallel.context import TpuContext

        ctx = TpuContext.current()
        if ctx is not None:
            return ctx.rank
    except Exception:  # pragma: no cover - import cycles during teardown
        pass
    if _PROCESS_RANK is not None:
        return _PROCESS_RANK
    env = os.environ.get("SRML_RANK")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    return 0


# ------------------------------------------------------- trace correlation --

# The active trace, context-local so concurrent fits on different threads
# carry their own ids (same isolation argument as core's DeviceDataset scope).
_TRACE: "contextvars.ContextVar[Optional[Dict[str, Any]]]" = contextvars.ContextVar(
    "srml_trace", default=None
)
_FIT_SEQ = itertools.count(1)

# Payload prefix for the trace-id rendezvous round — versioned so a future
# format change is detectable instead of silently misparsed.
_TRACE_ROUND_PREFIX = "TRACE1:"


def current_trace() -> Optional[Dict[str, Any]]:
    """The active trace dict ``{"trace_id", "fit_id"}``, or None."""
    return _TRACE.get()


def trace_tags() -> Dict[str, Any]:
    """Tags every span/metric/flight-recorder record should carry. Inside a
    `trace_scope` these are the scope's ids; outside one, a launcher-minted
    ``SRML_TRACE_ID`` (the subprocess-harness path: one env id correlates all
    ranks of a run without any in-band exchange) still tags records."""
    t = _TRACE.get()
    if t is not None:
        return t
    env_id = os.environ.get("SRML_TRACE_ID")
    if env_id:
        return {"trace_id": env_id}
    return {}


@contextlib.contextmanager
def trace_scope(label: str, ctx: Any = None):
    """Mint + propagate the per-fit trace identity for the dynamic extent.

    ``fit_id`` is a process-local sequence number ("fit-<n>"); under lockstep
    barrier execution every rank's counter advances identically, so it agrees
    across ranks without communication. ``trace_id`` must be GLOBALLY unique
    and identical on all ranks: single-controller mints locally (or adopts a
    launcher's ``SRML_TRACE_ID``); SPMD mints on rank 0 and propagates the id
    through one rendezvous round at trace begin — every rank enters the round
    in lockstep, so this adds exactly one control-plane round per fit.

    NESTED scopes ADOPT the enclosing trace_id (Dapper semantics: a
    CrossValidator fit is ONE trace; each fold/refit inside it gets its own
    fit_id under that trace) and skip the rendezvous exchange — the outer
    scope already coordinated the id."""
    fit_id = f"fit-{next(_FIT_SEQ)}"
    outer = _TRACE.get()
    if outer is not None:
        trace_id = outer["trace_id"]
    else:
        trace_id = os.environ.get("SRML_TRACE_ID") or uuid.uuid4().hex[:16]
        rendezvous = getattr(ctx, "rendezvous", None)
        if ctx is not None and getattr(ctx, "is_spmd", False) and rendezvous is not None:
            # the exchange is NON-FATAL: this round runs before the fit body
            # enters core.retryable_stage, so an error here would bypass the
            # retry machinery — and diagnostics must never turn a working
            # fit into a failed one. On failure, fall back to the local id
            # (degraded correlation, fit proceeds); a genuinely broken
            # control plane surfaces at the fit's own next round, WITH retry
            # protection, and the typed desync guards cover any round-count
            # divergence a one-sided timeout could leave behind.
            try:
                # the fleet plane piggybacks its ops-round scheduling on this
                # round (docs/observability.md "Fleet plane"): rank 0 ALONE
                # evaluates the time throttle and broadcasts the decision as
                # a `|ops` suffix — a per-rank local throttle would desync
                # the lockstep round counters. sys.modules probe: trace
                # exchange must not pay the ops_plane import chain, and a
                # process that never imported the fleet plane runs zero ops
                # rounds. Trace ids are hex, so "|" cannot collide.
                fleet = sys.modules.get(__package__ + ".ops_plane.fleet")
                flag = (
                    "|" + fleet.OPS_ROUND_FLAG
                    if fleet is not None and ctx.rank == 0 and fleet.ops_due()
                    else ""
                )
                payload = _TRACE_ROUND_PREFIX + (trace_id if ctx.rank == 0 else "") + flag
                gathered = rendezvous.allgather(payload)
                root = gathered[0]
                ops_follows = False
                if root.startswith(_TRACE_ROUND_PREFIX):
                    rest = root[len(_TRACE_ROUND_PREFIX):]
                    rid, sep, tail = rest.partition("|")
                    if rid:
                        trace_id = rid
                    if sep and "ops" in tail.split("|"):
                        if fleet is None:
                            # rank 0 runs the fleet plane but this process
                            # never imported it — import now rather than
                            # desync the lockstep round rank 0 is entering
                            from .ops_plane import fleet  # noqa: PLC0415
                        ops_follows = True
                if ops_follows:
                    # every rank saw the same root payload, so every rank
                    # enters the ops round in lockstep — including ranks
                    # whose local telemetry is off (they send the bare
                    # marker). ops_round never raises (non-fatal contract).
                    fleet.ops_round(rendezvous)
            except Exception as e:
                record_event("trace_exchange_failed", label=label,
                             error=type(e).__name__)
    tags = {"trace_id": trace_id, "fit_id": fit_id}
    token = _TRACE.set(tags)
    record_event("trace_begin", label=label)
    try:
        yield dict(tags)
    finally:
        record_event("trace_end", label=label)
        _TRACE.reset(token)


# --------------------------------------------------------- flight recorder --


class FlightRecorder:
    """Bounded always-on ring buffer of structured diagnostic events.

    `record` is the hot call: one wall-clock read, one small dict, one lock'd
    slot write. The ring OVERWRITES oldest-first at capacity; overwrites are
    counted (never silent — `stats()`, the `flightrec.events_dropped` registry
    counter, and the `telemetry.summary()` health line all expose them)."""

    # ring overwrites of every recorder of this process: what the registry's
    # `flightrec.events_dropped` follows (`MetricsRegistry._sync_flightrec`)
    overwritten = 0

    def __init__(self, capacity: Optional[int] = None, enabled: Optional[bool] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("SRML_FLIGHTREC_EVENTS", _DEFAULT_CAPACITY))
            except ValueError:  # a typo'd knob must not crash module import
                capacity = _DEFAULT_CAPACITY
        if enabled is None:
            enabled = os.environ.get("SRML_FLIGHTREC", "1") not in ("0", "false", "off")
        self.capacity = max(1, int(capacity))
        self.enabled = bool(enabled)
        self._lock = lockcheck.make_lock("diagnostics.FlightRecorder._lock")
        self._buf: List[Optional[Dict[str, Any]]] = [None] * self.capacity  # guarded-by: _lock
        self._next = 0  # next slot to write  # guarded-by: _lock
        self._total = 0  # events ever recorded  # guarded-by: _lock
        self._dropped = 0  # events overwritten (total - retained)  # guarded-by: _lock

    # -- record (the hot path) ---------------------------------------------
    def record(self, kind: str, **fields: Any) -> None:
        if self.enabled:
            self.record_as(_rank(), trace_tags(), kind, fields)

    def record_as(self, rank: int, tags: Dict[str, Any], kind: str, fields: Dict[str, Any]) -> None:
        """`record` for a caller that has resolved this process's rank and
        the trace tags already (a telemetry span does, once for its two
        events and its record)."""
        if not self.enabled:
            return
        ev = {"t": time.time(), "kind": kind, "rank": rank, **tags, **fields}
        with self._lock:
            if self._buf[self._next] is not None:
                # counted here alone: the registry's `flightrec.events_dropped`
                # is brought up to `overwritten` where a snapshot is taken
                self._dropped += 1
                FlightRecorder.overwritten += 1
            self._buf[self._next] = ev
            self._next = (self._next + 1) % self.capacity
            self._total += 1

    # -- read --------------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        """All retained events, oldest first."""
        with self._lock:
            ordered = self._buf[self._next:] + self._buf[: self._next]
        return [dict(e) for e in ordered if e is not None]

    def tail(self, k: int = _DEFAULT_TAIL) -> List[Dict[str, Any]]:
        """The newest `k` retained events, oldest first. ``k <= 0`` means no
        tail (NOT the whole ring — evs[-0:] would be everything)."""
        if k <= 0:
            return []
        evs = self.events()
        return evs[-k:] if k < len(evs) else evs

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "capacity": self.capacity,
                "recorded": self._total,
                "retained": min(self._total, self.capacity) if self.enabled else 0,
                "dropped": self._dropped,
            }

    def reset(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._next = 0
            self._total = 0
            self._dropped = 0

    # -- dump --------------------------------------------------------------
    def dump(self, path: Optional[str] = None, reason: str = "") -> Optional[str]:
        """Write the whole retained ring as JSONL (one event per line, plus a
        trailing ``{"kind": "flightrec_dump"}`` footer carrying stats + the
        dump reason). `path` defaults to ``flightrec_rank_<r>.jsonl`` under
        the configured dump dir; no dir configured -> no file, returns None.
        Write-then-rename so a concurrently-assembling post-mortem never reads
        a torn file. Each dump is a full snapshot (later dumps supersede)."""
        if not self.enabled:
            return None
        if path is None:
            path = flightrec_dump_path()
            if path is None:
                return None
        footer = {"kind": "flightrec_dump", "t": time.time(), "rank": _rank(),
                  "reason": reason, **trace_tags(), **self.stats()}
        tmp = f"{path}.tmp{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(tmp, "w") as f:
                for ev in self.events():
                    f.write(json.dumps(ev, default=str) + "\n")  # sink-ok: flight-recorder dump owner
                f.write(json.dumps(footer, default=str) + "\n")  # sink-ok: flight-recorder dump owner
            os.replace(tmp, path)
        except OSError:  # pragma: no cover - dump is best-effort by design
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            return None
        return path


_RECORDER = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _RECORDER


def record_event(kind: str, **fields: Any) -> None:
    """Module-level convenience over the process recorder (the call sites in
    telemetry/context/chaos/core use this)."""
    _RECORDER.record(kind, **fields)


def flightrec_dir() -> Optional[str]:
    """Dump directory: ``SRML_FLIGHTREC_DIR`` env, else
    ``config["flightrec_dir"]``. None -> exception tails still attach, but no
    dump files are written.

    The config fallback consults `sys.modules` instead of importing: this
    runs inside SrmlError construction, and control-plane-only processes
    (the rendezvous harness) may never have loaded `core` — paying its full
    import chain (numpy/pandas) HERE would add ~1s to every survivor's
    failure-detection latency, measured blowing the 2x-heartbeat budget. If
    `core` was never imported, its config cannot have been customized."""
    d = os.environ.get("SRML_FLIGHTREC_DIR")
    if d:
        return d
    core = sys.modules.get(__package__ + ".core")
    if core is not None:
        try:
            return core.config.get("flightrec_dir") or None
        except Exception:  # pragma: no cover - partially-initialized module
            return None
    return None


def flightrec_dump_path(rank: Optional[int] = None) -> Optional[str]:
    d = flightrec_dir()
    if not d:
        return None
    r = _rank() if rank is None else rank
    return os.path.join(d, f"{FLIGHTREC_FILE_PREFIX}{r}.jsonl")


def on_srml_error(exc: BaseException) -> None:
    """Called from ``SrmlError.__init__``: record the error as a ring event,
    attach the last-K events to the exception (``exc.flightrec_tail``), and
    dump the ring to the per-rank file. Must never raise — a diagnostics
    failure must not mask the error being constructed."""
    if not _RECORDER.enabled:
        return
    fields: Dict[str, Any] = {"error": type(exc).__name__, "message": str(exc)[:500]}
    for attr in ("failed_rank", "round_index", "missing_ranks", "reason",
                 "solver", "iteration", "column"):
        v = getattr(exc, attr, None)
        if v is not None:
            fields[attr] = v
    _RECORDER.record("error", **fields)
    try:
        k = int(os.environ.get("SRML_FLIGHTREC_TAIL", _DEFAULT_TAIL))
    except ValueError:
        k = _DEFAULT_TAIL
    exc.flightrec_tail = _RECORDER.tail(k)
    dumped = _RECORDER.dump(reason=f"{type(exc).__name__}: {str(exc)[:200]}")
    if dumped is not None:
        # ride an ops-plane snapshot (SLO verdicts, decision log, tenant
        # accounting) next to the flight-recorder dump, so a post-mortem
        # carries the VERDICT context too. sys.modules probe, same argument
        # as flightrec_dir: error construction must never pay an import
        # chain, and a process that never loaded the ops plane has no ops
        # state to snapshot.
        ops = sys.modules.get(__package__ + ".ops_plane")
        if ops is not None:
            try:
                ops.export.write_snapshot(
                    os.path.join(os.path.dirname(dumped),
                                 f"ops_snapshot_rank_{_rank()}.json")
                )
            except Exception:  # pragma: no cover - snapshot is best-effort
                pass


# ------------------------------------------------------------- post-mortem --


def load_flightrec_dumps(
    dump_dir: str, nranks: Optional[int] = None
) -> Tuple[Dict[int, List[Dict[str, Any]]], List[int]]:
    """Read every ``flightrec_rank_<r>.jsonl`` under `dump_dir`. Returns
    (events per rank, missing ranks). A rank is MISSING when `nranks` says it
    should exist but no dump is present — a SIGKILLed process writes nothing,
    so absence is itself evidence."""
    per_rank: Dict[int, List[Dict[str, Any]]] = {}
    pat = re.compile(re.escape(FLIGHTREC_FILE_PREFIX) + r"(\d+)\.jsonl$")
    if os.path.isdir(dump_dir):
        for name in sorted(os.listdir(dump_dir)):
            m = pat.match(name)
            if not m:
                continue
            events: List[Dict[str, Any]] = []
            with open(os.path.join(dump_dir, name)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        events.append(json.loads(line))
                    except ValueError:
                        continue  # torn/garbage line — keep what parses
            per_rank[int(m.group(1))] = events
    expected = range(nranks) if nranks else []
    missing = [r for r in expected if r not in per_rank]
    return per_rank, missing


def _latest_trace_id(per_rank: Dict[int, List[Dict[str, Any]]]) -> Optional[str]:
    """The most recently seen trace id across all dumps (dumps may hold
    events from several fits; post-mortems care about the one that died)."""
    best_t, best_id = float("-inf"), None
    for events in per_rank.values():
        for ev in events:
            tid = ev.get("trace_id")
            if tid and ev.get("t", 0) > best_t:
                best_t, best_id = ev["t"], tid
    return best_id


def assemble_postmortem(
    dump_dir: str,
    nranks: Optional[int] = None,
    trace_id: Optional[str] = None,
    last_k: int = _DEFAULT_TAIL,
) -> Dict[str, Any]:
    """Correlate all ranks' flight-recorder dumps into one failure timeline.

    Returns a machine-readable dict:
      * ``failed_rank`` / ``failed_round`` / ``failure_reason`` — majority
        verdict of the survivors' recorded errors (``RankFailedError`` events
        name the rank they blame), with a rank whose dump is MISSING promoted
        to prime suspect (hard-killed processes write nothing);
      * ``ranks`` — per rank: last-K events, the last rendezvous round it
        entered, and what it was blocked on when the failure surfaced;
      * ``timeline`` — every rank's events merged and time-sorted.
    """
    per_rank, missing = load_flightrec_dumps(dump_dir, nranks)
    if trace_id is None:
        trace_id = _latest_trace_id(per_rank)
    if trace_id is not None:
        per_rank = {
            r: [e for e in evs if e.get("trace_id") in (trace_id, None)]
            for r, evs in per_rank.items()
        }

    blame: Dict[int, int] = {}
    missing_votes: Dict[int, int] = {}  # RendezvousTimeoutError.missing_ranks
    blame_round: Dict[int, int] = {}
    reasons: List[str] = []
    ranks: Dict[int, Dict[str, Any]] = {}
    timeline: List[Dict[str, Any]] = []
    # recovery epochs (elastic recovery): every reform a rank recorded,
    # deduped by generation — the post-mortem NAMES each epoch, its survivor
    # set, and the dead ranks it excluded
    recovery_epochs: Dict[int, Dict[str, Any]] = {}
    for r, events in sorted(per_rank.items()):
        timeline.extend(events)
        last_enter: Optional[Dict[str, Any]] = None
        blocked_on: Optional[str] = None
        open_round: Optional[Dict[str, Any]] = None
        for ev in events:
            k = ev.get("kind")
            if k == "rdv_enter":
                open_round = ev
                last_enter = ev
            elif k in ("rdv_exit", "rdv_fail"):
                open_round = None
            elif k in ("recovery_reform", "recovery_epoch_begin", "chaos_reform"):
                gen = ev.get("generation")
                if gen is not None:
                    entry = recovery_epochs.setdefault(
                        int(gen), {"generation": int(gen)}
                    )
                    if ev.get("survivors") is not None:
                        entry["survivors"] = list(ev["survivors"])
                    if ev.get("dead") is not None:
                        entry["dead"] = sorted(ev["dead"])
                    elif ev.get("dead_ranks") is not None:
                        entry.setdefault("dead", sorted(ev["dead_ranks"]))
            elif k == "error":
                fr = ev.get("failed_rank")
                if fr is not None:
                    blame[int(fr)] = blame.get(int(fr), 0) + 1
                for m in ev.get("missing_ranks") or []:
                    # timeout-shaped failure: nobody published, but the
                    # survivor recorded WHO it was still waiting on
                    missing_votes[int(m)] = missing_votes.get(int(m), 0) + 1
                rnd = ev.get("round_index")
                if rnd is not None:
                    blame_round[int(rnd)] = blame_round.get(int(rnd), 0) + 1
                if ev.get("reason"):
                    reasons.append(str(ev["reason"]))
                elif ev.get("message"):
                    reasons.append(str(ev["message"]))
        if open_round is not None:
            blocked_on = f"rendezvous round {open_round.get('round')}"
        errs = [e for e in events if e.get("kind") == "error"]
        ranks[r] = {
            "events": len(events),
            "last_events": events[-last_k:],
            "last_round_entered": last_enter.get("round") if last_enter else None,
            "blocked_on": blocked_on,
            "error": errs[-1].get("error") if errs else None,
        }
    timeline.sort(key=lambda e: e.get("t", 0.0))

    failed_rank: Optional[int] = None
    failed_round: Optional[int] = None
    if blame:
        # strongest evidence: survivors' errors NAMED the rank (abort
        # sentinel or heartbeat staleness)
        failed_rank = max(blame, key=lambda r: blame[r])
    elif missing_votes:
        # timeout-shaped: nobody published, but survivors recorded who they
        # were still waiting on when the deadline fired
        failed_rank = max(missing_votes, key=lambda r: missing_votes[r])
    elif missing and per_rank:
        # absence as evidence — but only when at least one rank DID report;
        # an empty dump dir is "no evidence", not "rank 0 failed"
        failed_rank = missing[0]
    if blame_round:
        failed_round = max(blame_round, key=lambda k: blame_round[k])
    if failed_round is None and failed_rank is not None and failed_rank in ranks:
        failed_round = ranks[failed_rank].get("last_round_entered")

    return {
        "trace_id": trace_id,
        "nranks": nranks if nranks is not None else len(per_rank),
        "ranks_reporting": sorted(per_rank),
        "missing_ranks": missing,
        "failed_rank": failed_rank,
        "failed_round": failed_round,
        "failure_reason": reasons[0] if reasons else None,
        "recovery_epochs": [
            recovery_epochs[g] for g in sorted(recovery_epochs)
        ],
        "ranks": ranks,
        "timeline": timeline,
    }


def render_postmortem(pm: Dict[str, Any]) -> str:
    """Human-readable rendering of an `assemble_postmortem` result."""
    lines = [
        f"POST-MORTEM trace={pm.get('trace_id') or '?'} "
        f"({len(pm.get('ranks_reporting', []))}/{pm.get('nranks', '?')} ranks reporting)"
    ]
    fr, rd = pm.get("failed_rank"), pm.get("failed_round")
    if fr is not None:
        where = f" at round {rd}" if rd is not None else ""
        lines.append(f"verdict: rank {fr} failed{where}")
        if pm.get("failure_reason"):
            lines.append(f"reason: {pm['failure_reason']}")
    else:
        lines.append("verdict: no failure evidence found")
    if pm.get("missing_ranks"):
        lines.append(
            f"missing dumps (hard-killed? never started?): ranks {pm['missing_ranks']}"
        )
    for ep in pm.get("recovery_epochs") or []:
        dead = f", excluded {ep['dead']}" if ep.get("dead") else ""
        lines.append(
            f"recovery epoch g{ep.get('generation')}: survivors "
            f"{ep.get('survivors')}{dead} — the fit CONTINUED on the "
            "reformed group"
        )
    for r, info in sorted(pm.get("ranks", {}).items()):
        status = info.get("error") or (
            f"blocked on {info['blocked_on']}" if info.get("blocked_on") else "ran to dump"
        )
        lines.append(
            f"  rank {r}: {info['events']} events, "
            f"last round entered {info.get('last_round_entered')}, {status}"
        )
        for ev in info.get("last_events", [])[-5:]:
            detail = {
                k: v for k, v in ev.items()
                if k not in ("t", "kind", "rank", "trace_id", "fit_id")
            }
            lines.append(f"    {ev.get('t', 0):.3f} {ev.get('kind')} {detail or ''}")
    return "\n".join(lines)


# -------------------------------------------------------------- trace merge --


def load_telemetry_jsonl(base_path: str) -> Dict[int, List[Dict[str, Any]]]:
    """Discover + read the per-rank telemetry JSONL family: rank 0 owns
    `base_path`, rank r writes ``<base_path>.rank<r>`` (telemetry sink
    contract). Missing / empty / ragged files are fine — you merge what you
    have."""
    per_rank: Dict[int, List[Dict[str, Any]]] = {}
    candidates: List[Tuple[int, str]] = []
    if os.path.exists(base_path):
        candidates.append((0, base_path))
    d = os.path.dirname(os.path.abspath(base_path)) or "."
    base_name = os.path.basename(base_path)
    if os.path.isdir(d):
        pat = re.compile(re.escape(base_name) + r"\.rank(\d+)$")
        for name in os.listdir(d):
            m = pat.match(name)
            if m:
                candidates.append((int(m.group(1)), os.path.join(d, name)))
    for rank, path in sorted(candidates):
        records: List[Dict[str, Any]] = []
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict):
                        records.append(rec)
        except OSError:
            continue
        per_rank[rank] = records
    return per_rank


def _span_end(rec: Dict[str, Any]) -> Optional[float]:
    t0, wall = rec.get("t0"), rec.get("wall_s")
    if t0 is None or wall is None:
        return None
    return float(t0) + float(wall)


def _round_key(rec: Dict[str, Any]) -> Tuple:
    """Identity of one lockstep rendezvous round, unique across retries and
    across the fits sharing a trace: round counters reset on `begin_epoch`
    (retry attempts) and fits interleave under one CV trace, so the bare
    round index collides — (trace, fit, epoch, round) cannot. Every field
    agrees across ranks: fit_id advances in lockstep, epoch/round come from
    the rendezvous the ranks synchronized through."""
    return (rec.get("trace_id"), rec.get("fit_id"), rec.get("epoch"), rec["round"])


def _barrier_offsets(per_rank: Dict[int, List[Dict[str, Any]]]) -> Dict[int, float]:
    """Clock-skew offsets per rank, anchored on rank 0 (or the lowest rank
    present). Barrier rounds are the sync points: all ranks LEAVE a
    rendezvous round at (physically) the same instant, so for every round
    both sides recorded, ``anchor_end - rank_end`` samples that rank's clock
    offset; the median over rounds rejects outliers (a slow record on one
    side). Ranks sharing no rounds with the anchor get offset 0."""
    ends: Dict[int, Dict[Any, float]] = {}
    for r, recs in per_rank.items():
        by_round: Dict[Any, float] = {}
        for rec in recs:
            if rec.get("kind") != "span" or rec.get("name") != "rendezvous.allgather":
                continue
            end = _span_end(rec)
            if rec.get("round") is None or end is None:
                continue
            by_round[_round_key(rec)] = end
        if by_round:
            ends[r] = by_round
    offsets: Dict[int, float] = {r: 0.0 for r in per_rank}
    if not ends:
        return offsets
    anchor = min(ends)
    for r, by_round in ends.items():
        if r == anchor:
            continue
        deltas = sorted(
            ends[anchor][k] - v for k, v in by_round.items() if k in ends[anchor]
        )
        if deltas:
            offsets[r] = deltas[len(deltas) // 2]
    return offsets


def merge_chrome_trace(
    per_rank: Dict[int, List[Dict[str, Any]]],
    *,
    trace_id: Optional[str] = None,
    align_clocks: bool = True,
) -> Dict[str, Any]:
    """Merge per-rank telemetry JSONL records into Chrome trace-event JSON
    (the Perfetto / chrome://tracing "JSON Array Format" with metadata):

      * one track (``tid``) per rank under one process (``pid`` 0), named via
        ``thread_name`` metadata events;
      * every span record becomes a complete ("X") event at its recorded
        wall-clock start, duration ``wall_s`` — microsecond units, rebased to
        the earliest aligned timestamp;
      * rendezvous rounds become flow arrows (``s``/``f`` events bound by
        round id) from the anchor rank's round exit to every other rank's —
        the lockstep structure made visible;
      * clock skew is corrected per rank using barrier rounds as sync points
        (`align_clocks`; see `_barrier_offsets`).
    """
    if trace_id is not None:
        per_rank = {
            r: [rec for rec in recs if rec.get("trace_id") == trace_id]
            for r, recs in per_rank.items()
        }
    offsets = _barrier_offsets(per_rank) if align_clocks else {r: 0.0 for r in per_rank}

    starts = [
        rec["t0"] + offsets.get(r, 0.0)
        for r, recs in per_rank.items()
        for rec in recs
        if rec.get("kind") == "span" and rec.get("t0") is not None
    ]
    base = min(starts) if starts else 0.0

    events: List[Dict[str, Any]] = [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": f"srml trace {trace_id or 'all'}"}},
    ]
    flow_ends: Dict[Any, Dict[int, float]] = {}
    for r in sorted(per_rank):
        events.append(
            {"ph": "M", "name": "thread_name", "pid": 0, "tid": r,
             "args": {"name": f"rank {r}"}}
        )
        events.append(
            {"ph": "M", "name": "thread_sort_index", "pid": 0, "tid": r,
             "args": {"sort_index": r}}
        )
        for rec in per_rank[r]:
            if rec.get("kind") != "span" or rec.get("t0") is None:
                continue
            ts_us = (rec["t0"] + offsets.get(r, 0.0) - base) * 1e6
            dur_us = max(0.0, float(rec.get("wall_s", 0.0))) * 1e6
            args = {
                k: v for k, v in rec.items()
                if k not in ("kind", "name", "path", "t0", "wall_s", "rank")
            }
            events.append(
                {"ph": "X", "cat": "span", "name": rec.get("path") or rec.get("name", "?"),
                 "pid": 0, "tid": r, "ts": ts_us, "dur": dur_us, "args": args}
            )
            if rec.get("name") == "rendezvous.allgather" and rec.get("round") is not None:
                flow_ends.setdefault(_round_key(rec), {})[r] = ts_us + dur_us

    # flow arrows: anchor rank's round exit -> every other participant's exit
    flow_id = 0
    for key in sorted(flow_ends, key=lambda k: min(flow_ends[k].values())):
        by_rank = flow_ends[key]
        if len(by_rank) < 2:
            continue
        anchor = min(by_rank)
        flow_id += 1
        name = f"rendezvous round {key[-1]}"
        events.append(
            {"ph": "s", "cat": "rendezvous", "name": name, "id": flow_id,
             "pid": 0, "tid": anchor, "ts": by_rank[anchor]}
        )
        for r, ts in sorted(by_rank.items()):
            if r == anchor:
                continue
            events.append(
                {"ph": "f", "bp": "e", "cat": "rendezvous", "name": name,
                 "id": flow_id, "pid": 0, "tid": r, "ts": ts}
            )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "generator": "spark_rapids_ml_tpu.diagnostics.merge_chrome_trace",
            "trace_id": trace_id,
            "ranks": sorted(per_rank),
            "clock_offsets_s": {str(r): o for r, o in offsets.items()},
        },
    }


def chrome_trace_from_files(
    base_path: str, *, trace_id: Optional[str] = None, align_clocks: bool = True
) -> Dict[str, Any]:
    """`load_telemetry_jsonl` + `merge_chrome_trace` in one call (what the
    `benchmark/trace_merge.py` CLI wraps)."""
    return merge_chrome_trace(
        load_telemetry_jsonl(base_path), trace_id=trace_id, align_clocks=align_clocks
    )
