"""Job-lifecycle event journal + recovery-latency attribution.

The chaos harness (faults.py), the liveness detector and the elastic
driver can *survive* failures; until now nothing could *account* for
them. PR 1's metrics and PR 5's flight recorder are per-process and
die with the process (SURVEY §7 hard-part 3: surviving membership
churn), so a chaos soak proved recovery only by "the test passed" —
no durable record of when the heartbeat expired, how long
rendezvous/respawn/restore took, or which host caused it. This module
is the recovery observability layer that survives SIGKILL:

* **Crash-safe event journal** — every process in the job (the
  elastic driver AND every worker) appends typed JSONL records to
  ``$HOROVOD_JOURNAL_DIR/journal-<role>.jsonl``, fsync'd per record
  (batched via ``HOROVOD_JOURNAL_FSYNC``; lifecycle-critical events
  always flush). Records carry ``time.monotonic_ns()`` anchored at
  journal construction exactly like PR 5's per-rank timelines — the
  wall-clock field is *derived* from the monotonic clock via the
  anchor, so an NTP step mid-run cannot tear a process's timeline —
  plus the per-rank CLOCK_SYNC offsets from tracing.py's calibrator
  when one is live, which is what lets the offline merge align
  journals recorded on N different clocks.

* **Typed lifecycle events** — membership epochs and rank
  assignments, heartbeat verdicts and hung-worker kills, blacklist
  escalations, every phase of a gang restart (detect → teardown →
  rendezvous → respawn → restore/sync → first post-recovery commit),
  elastic commit/restore/sync, numerics escalations, fault-injection
  firings, and postmortem references (tracing.py's dumps become
  first-class events the analyzer can link).

* **Runtime SLO instrumentation** — ``hvd_recovery_seconds{phase}``
  histograms, ``hvd_recoveries_total{cause}``, and
  ``hvd_committed_step_loss_total``: the committed-step watermark is
  carried across restarts *via the journal* (a respawned worker reads
  the highest step any incarnation ever committed and compares it to
  the step it actually resumed at), so step loss is measured, not
  assumed.

* **Offline analyzer** — ``python -m horovod_tpu.runner.doctor
  incident <dir>`` (also ``hvdrun --incident-report``) merges the
  driver + worker journals into a byte-deterministic
  ``incident_report.json``: one entry per recovery with the full MTTR
  decomposition, cause attribution (host, rank, injection seam, exit
  code or heartbeat age), step-loss accounting, linked postmortems,
  and a human-readable timeline. This is the proof surface the
  ROADMAP's preemption-storm and elastic-serving items are accepted
  against: "zero committed-step loss" becomes a number in a committed
  artifact (benchmarks/INCIDENT_chaos_r11.json), not a test name.

Fast path: with HOROVOD_JOURNAL_DIR unset the module journal is None
and record() is one attribute load + compare — the same disarmed-seam
contract as faults.fire and tracing.record, guarded by the same style
of overhead test (tests/test_journal.py).
"""

from __future__ import annotations

import dataclasses
import glob as _glob
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .common import config as _config
from .common import logging as hlog
from .metrics import RECOVERY_BUCKETS, REGISTRY as _METRICS

SCHEMA = "hvd-journal-v1"
REPORT_SCHEMA = "hvd-incident-report-v1"

_m_recovery = _METRICS.histogram(
    "hvd_recovery_seconds",
    "Wall time of one recovery phase (detect / teardown / rendezvous "
    "/ respawn / restore / first_commit) — the runtime face of the "
    "offline incident report's MTTR decomposition.",
    ("phase",), buckets=RECOVERY_BUCKETS)
_m_recoveries = _METRICS.counter(
    "hvd_recoveries_total",
    "Recoveries the elastic driver ran, by detected cause "
    "(crash / hung / preempt / internal_error).", ("cause",))
_m_step_loss = _METRICS.counter(
    "hvd_committed_step_loss_total",
    "Committed steps a recovery failed to resume at (journal "
    "watermark minus the step actually restored) — nonzero means the "
    "zero-committed-step-loss recovery contract was violated.")
_m_events = _METRICS.counter(
    "hvd_journal_events_total",
    "Lifecycle events appended to this process's journal.")

# Envelope fields Journal.event() stamps on EVERY record; writers
# never pass them and schemas never declare them.
BASE_FIELDS = frozenset({"type", "role", "rank", "pid", "mono_ns",
                         "t", "n"})


@dataclasses.dataclass(frozen=True)
class EventSchema:
    """One declared journal event type: the typed vocabulary contract
    between every writer (`journal.record("<name>", field=...)`) and
    every offline consumer (`doctor incident` / `doctor serve` /
    serving_trace.py). hvdlint rule HVD008 checks both sides of the
    contract against this registry — the journal-event analog of
    config.py's Knob registry."""

    name: str
    writer: str                       # driver | worker | serving | any
    doc: str
    required: Tuple[str, ...] = ()
    optional: Tuple[str, ...] = ()
    # True: fsync'd unconditionally (the last thing a dying process
    # says, or a phase edge the MTTR decomposition is built from);
    # False: batched under HOROVOD_JOURNAL_FSYNC (hot-path volume).
    critical: bool = False


# The declared journal-event vocabulary. One EventSchema per type,
# with its required/optional field sets and fsync criticality. This
# list is the single source of truth three ways:
#   * runtime: CRITICAL_EVENTS and the HOROVOD_JOURNAL_STRICT
#     validation derive from it;
#   * static analysis: hvdlint HVD008 AST-extracts it (never imports
#     this module) and checks every record site and consumer key
#     repo-wide against it;
#   * docs: the user_guide event-schema table is generated from it
#     (event_schema_table_md), so a new event that skips the registry
#     fails lint instead of silently missing the docs.
# Keep entries statically declarative — literal names and literal
# field tuples — or the AST extraction (and therefore HVD008's
# whole-repo check) cannot see them.
EVENT_SCHEMAS: List[EventSchema] = [
    # -- journal plumbing (every process) -----------------------------
    EventSchema(
        "journal_meta", "any",
        "Segment header: schema id, monotonic/wall anchors, host, "
        "elastic epoch, the armed fault spec + seed. Critical via "
        "the write-site flag (first line of every segment).",
        required=("schema", "anchor_mono_ns", "anchor_unix", "host",
                  "epoch", "faults", "faults_seed"),
        optional=("slice",)),
    EventSchema(
        "init_done", "worker",
        "Worker joined a world: elastic epoch, world size, local "
        "rank.",
        required=("epoch", "world_size", "local_rank")),
    EventSchema(
        "clock_sync", "worker",
        "PR-5 calibrated offset to rank 0 persisted for the offline "
        "merge's cross-host clock alignment.",
        required=("offset_ns", "rtt_ns")),
    # -- elastic worker lifecycle -------------------------------------
    EventSchema(
        "assignment", "worker",
        "Elastic rank reassignment accepted by a live worker.",
        required=("new_rank", "size", "epoch")),
    EventSchema(
        "reinit_begin", "worker",
        "Worker entering re-initialization for a new epoch.",
        required=("epoch",)),
    EventSchema(
        "restore", "worker",
        "In-process state restore ran (HorovodInternalError path).",
        required=("step",), critical=True),
    EventSchema(
        "sync_done", "worker",
        "state.sync() finished: the restore phase edge, with the "
        "step resumed at.",
        required=("step", "epoch"), critical=True),
    EventSchema(
        "snapshot_loaded", "worker",
        "Durable snapshot loaded on (re)start, with its step.",
        required=("step",), critical=True),
    EventSchema(
        "commit", "worker",
        "One elastic commit; `durable` marks commits that issued the "
        "snapshot write — the watermark a restarted gang is held to.",
        required=("epoch",), optional=("durable", "step"),
        critical=True),
    EventSchema(
        "first_commit", "worker",
        "First post-recovery commit — closes the MTTR decomposition.",
        required=("seconds", "epoch"), optional=("durable", "step"),
        critical=True),
    EventSchema(
        "watermark", "worker",
        "Measured loss check: journal watermark vs resumed step "
        "(feeds hvd_committed_step_loss_total).",
        required=("watermark", "resumed", "loss"), critical=True),
    EventSchema(
        "hosts_updated", "worker",
        "Membership-change notification observed at a commit "
        "boundary.",
        required=("epoch", "step")),
    EventSchema(
        "internal_error", "worker",
        "HorovodInternalError at the elastic boundary.",
        required=("error", "step"), critical=True),
    EventSchema(
        "numerics_escalation", "worker",
        "Skip-step escalation: consecutive non-finite steps hit the "
        "configured limit.",
        required=("skips", "limit"), critical=True),
    EventSchema(
        "replica_divergence", "worker",
        "SDC sentinel verdict: parameter digests diverged across "
        "replicas.",
        required=("divergent_ranks",), optional=("non_restorable",),
        critical=True),
    # -- chaos / flight recorder (any role) ---------------------------
    EventSchema(
        "fault_fired", "any",
        "A chaos-seam firing (point, action, hit count) — fsync'd "
        "BEFORE the action applies, so even a `crash` names its own "
        "cause.",
        required=("point", "action", "hit"), optional=("tag",),
        critical=True),
    EventSchema(
        "postmortem_written", "any",
        "This process dumped its own flight recorder (SIGUSR2, "
        "internal error, or teardown).",
        required=("file", "reason", "trigger", "step"),
        critical=True),
    # -- elastic driver -----------------------------------------------
    EventSchema(
        "driver_start", "driver",
        "Driver booted: command line and the elastic np window.",
        required=("command", "min_np", "max_np")),
    EventSchema(
        "spawn", "driver",
        "One worker slot (re)spawned: rank, host, child pid.",
        required=("exit_rank", "host", "child_pid")),
    EventSchema(
        "epoch_published", "driver",
        "Membership epoch published: size and rank→host assignments "
        "(and slice map on multi-slice pods).",
        required=("epoch", "size", "hosts"), optional=("slices",),
        critical=True),
    EventSchema(
        "respawn_done", "driver",
        "Every slot of the new epoch spawned.",
        required=("epoch", "ranks"), critical=True),
    EventSchema(
        "worker_exit", "driver",
        "A worker process exited, with its code.",
        required=("exit_rank", "host", "code"), critical=True),
    EventSchema(
        "hung_worker", "driver",
        "Stale-heartbeat verdict: the liveness detector shot a "
        "worker (age vs timeout).",
        required=("exit_rank", "host", "age_s", "timeout_s"),
        critical=True),
    EventSchema(
        "detect", "driver",
        "Failure classification (crash / hung / preempt) that opens "
        "a recovery — one per bad rank.",
        required=("cause", "exit_rank", "host", "code", "age_s",
                  "reset"),
        optional=("slice",), critical=True),
    EventSchema(
        "gang_restart_begin", "driver",
        "Teardown phase opened for a gang restart.",
        required=("reset", "epoch"), critical=True),
    EventSchema(
        "teardown_done", "driver",
        "Gang dead: the teardown phase edge.",
        required=("reset",), critical=True),
    EventSchema(
        "blacklist", "driver",
        "Host blacklisted, with the escalated window and failure "
        "count (and its slice, when it has one).",
        required=("host", "window_s", "failures"),
        optional=("slice",), critical=True),
    EventSchema(
        "slice_lost", "driver",
        "Whole-slice eviction: member hosts, cause, window, failure "
        "count — the slice-atomicity ledger.",
        required=("slice", "hosts", "cause", "window_s", "failures"),
        critical=True),
    EventSchema(
        "slice_admitted", "driver",
        "Whole-slice (re-)admission with member hosts and slots.",
        required=("slice", "hosts", "slots"), critical=True),
    EventSchema(
        "host_preempt", "driver",
        "The host.preempt seam's SIGTERM storm against one host "
        "(ranks hit, grace); anchors the following detect's t_fail.",
        required=("host", "ranks", "grace_s"), optional=("slice",),
        critical=True),
    EventSchema(
        "postmortem", "driver",
        "A dead worker's flight-recorder dump linked as a "
        "first-class event (rank, file, reason, step).",
        required=("exit_rank", "code", "file", "reason", "step",
                  "trigger", "in_flight"),
        critical=True),
    EventSchema(
        "task_exit", "driver",
        "Per-host task service observed a local worker exit.",
        required=("exit_rank", "code", "host")),
    EventSchema(
        "job_done", "driver",
        "Job finished with this exit code.",
        required=("code",), critical=True),
    EventSchema(
        "wire_reject", "any",
        "Control-plane service rejected an unauthenticated or "
        "malformed peer frame.",
        required=("service", "peer", "error")),
    # -- serving batch plane (rounds 15-16) ---------------------------
    EventSchema(
        "serving_meta", "serving",
        "Serving frontend's one-shot config record: ladder digest, "
        "batch/budget/SLO knobs, trace tag, weights dir — what "
        "`doctor serve` keys a leg's identity on.",
        required=("ladder", "max_batch", "budget_ms", "trace",
                  "default_slo_ms", "tag"),
        # optional, not required: r16 artifacts predate the live
        # weight pipeline and must keep validating unchanged.
        optional=("weights",), critical=True),
    EventSchema(
        "batch_admitted", "serving",
        "One batch cut from the admission queue (hot-path volume; "
        "batched fsync).",
        required=("batch", "size", "bucket", "bucket_len",
                  "queue_depth", "wait_ms")),
    EventSchema(
        "batch_trace", "serving",
        "Per-batch phase stamps + per-request submit/done arrays — "
        "the raw material of `doctor serve`'s phase decomposition "
        "(hot-path volume; batched fsync).",
        required=("batch", "worker", "attempt", "bucket", "size",
                  "requests", "slo", "deadline_hit", "submit_ns",
                  "done_ns", "admit_ns", "claim_ns", "exec0_ns",
                  "exec1_ns", "unpad_ns", "hops"),
        # optional, not required: r16 artifacts predate the live
        # weight pipeline and must keep validating unchanged.
        optional=("weights",)),
    EventSchema(
        "batch_retried", "serving",
        "A batch re-dispatched after a worker death, with the hop's "
        "cause and attempt.",
        required=("batch", "attempt", "cause", "worker", "pending"),
        critical=True),
    EventSchema(
        "batch_failed", "serving",
        "Retry budget exhausted: the batch failed visibly, with its "
        "lost requests and hop history.",
        required=("batch", "attempts", "cause", "worker", "lost",
                  "slo", "hops"),
        critical=True),
    EventSchema(
        "scale_event", "serving",
        "Worker pool resize (autoscale or worker death), with queue "
        "depth and reason.",
        required=("direction", "workers_from", "workers_to",
                  "queue_depth", "reason"),
        optional=("worker", "epoch"), critical=True),
    # -- live weight pipeline (round 17) ------------------------------
    EventSchema(
        "weights_published", "any",
        "A weight version published to the pull plane (kind: "
        "publish / rollback / repair).",
        required=("digest", "seq", "step", "kind", "ms"),
        critical=True),
    EventSchema(
        "weights_adopted", "serving",
        "A serving worker hot-swapped to a published version, with "
        "swap latency and staleness.",
        required=("worker", "digest", "seq", "step", "ms",
                  "staleness_steps"),
        critical=True),
    EventSchema(
        "weights_rejected", "serving",
        "A serving worker refused a version (digest mismatch, torn "
        "snapshot, rollback fence), naming what it kept serving.",
        required=("worker", "digest", "seq", "reason", "detail",
                  "serving"),
        critical=True),
    # -- continuous-batching decode plane (round 18) ------------------
    EventSchema(
        "decode_meta", "serving",
        "Decode frontend's one-shot config record: slot count, "
        "watermark stride, SLO/lane/retry knobs, KV ladder digest.",
        required=("slots", "watermark_stride", "interactive_slo_ms",
                  "lane_budget", "retry_limit", "kv_ladder",
                  "workers"),
        critical=True),
    EventSchema(
        "seq_admitted", "serving",
        "One sequence admitted to a decode slot (token-path volume; "
        "batched fsync).",
        required=("sid", "worker", "lane", "slo", "prompt_len",
                  "max_new", "queue_wait_ms")),
    EventSchema(
        "seq_watermark", "serving",
        "Durable KV watermark advanced for one sequence (per-stride "
        "volume; batched fsync — recovery value is bounded by the "
        "stride).",
        required=("sid", "worker", "token", "lane")),
    EventSchema(
        "seq_resumed", "serving",
        "A sequence re-admitted after a worker death, resuming from "
        "the journaled KV watermark — the exactly-once edge MTTR "
        "attribution keys on.",
        required=("sid", "worker", "lane", "from_token", "watermark",
                  "cause", "attempt"),
        critical=True),
    EventSchema(
        "seq_shed", "serving",
        "A batch-lane sequence shed under pool shrinkage, at its "
        "token frontier.",
        required=("sid", "worker", "lane", "at_token", "sheds"),
        critical=True),
    EventSchema(
        "seq_done", "serving",
        "Sequence lifecycle terminal with outcome, token counts and "
        "the submit/admit/first/done stamps `doctor serve`'s decode "
        "lanes decompose (token-path volume; batched fsync).",
        required=("sid", "outcome", "lane", "slo", "tokens",
                  "prompt_len", "worker", "resumes", "sheds",
                  "deadline_hit", "submit_ns", "admit_ns", "first_ns",
                  "done_ns")),
    EventSchema(
        "seq_failed", "serving",
        "Retry budget exhausted for one sequence: failed visibly at "
        "its token frontier.",
        required=("sid", "worker", "cause", "resumes", "at_token"),
        critical=True),
    EventSchema(
        "telemetry_meta", "telemetry",
        "First record of a telemetry time-series shard "
        "(telemetry-*.jsonl, telemetry.py): schema version, the "
        "monotonic/wall anchor pair the offline merge aligns on, and "
        "the sampling config in force.",
        required=("schema", "anchor_mono_ns", "anchor_unix", "host",
                  "interval_s", "ring"),
        critical=True),
    EventSchema(
        "telemetry_sample", "telemetry",
        "One time-series sample: counter deltas folded into rates "
        "over dt_s, raw gauges, per-histogram count/mean deltas, and "
        "the per-source beat counts since the previous sample "
        "(volume; batched fsync).",
        required=("beat", "seq", "dt_s", "beats", "rates", "gauges",
                  "hist"),
        optional=("recovering",)),
    EventSchema(
        "health_alert", "telemetry",
        "An online health detector fired: observed value vs the "
        "rolling baseline and threshold that tripped it. "
        "`attributed` marks alerts raised while a recovery signal "
        "was moving — expected fallout, not an anomaly.",
        required=("detector", "beat", "signal", "value", "baseline",
                  "threshold", "window"),
        optional=("attributed",),
        critical=True),
]

SCHEMA_BY_NAME: Dict[str, EventSchema] = {
    s.name: s for s in EVENT_SCHEMAS}
EVENT_NAMES = frozenset(SCHEMA_BY_NAME)

# Events that must hit the disk even when HOROVOD_JOURNAL_FSYNC
# batches — derived from the registry's criticality bit (the
# historical literal set is pinned by tests/test_journal.py).
CRITICAL_EVENTS = frozenset(
    s.name for s in EVENT_SCHEMAS if s.critical)


def schema_problems(type_: str,
                    fields: Dict[str, Any]) -> List[str]:
    """Deviations of one (type, fields) write from the declared
    registry; empty when conformant. Never raises — this backs the
    HOROVOD_JOURNAL_STRICT warning path and the artifact-validation
    tests, not a hard gate."""
    schema = SCHEMA_BY_NAME.get(type_)
    if schema is None:
        return [f"undeclared event type '{type_}' (add an "
                f"EventSchema to journal.EVENT_SCHEMAS)"]
    out = []
    names = set(fields)
    missing = sorted(set(schema.required) - names)
    if missing:
        out.append(f"event '{type_}' missing required field(s) "
                   f"{missing}")
    unknown = sorted(names - set(schema.required)
                     - set(schema.optional) - BASE_FIELDS)
    if unknown:
        out.append(f"event '{type_}' carries undeclared field(s) "
                   f"{unknown}")
    return out


def validate_event(rec: Dict[str, Any]) -> List[str]:
    """schema_problems for a PARSED journal record: the envelope
    fields Journal.event stamped (and the loader's `_src`) are
    stripped before checking."""
    type_ = str(rec.get("type", ""))
    fields = {k: v for k, v in rec.items()
              if k not in BASE_FIELDS and k != "_src"}
    return schema_problems(type_, fields)


def event_schema_table_md() -> str:
    """The user_guide's event-schema table, generated from
    EVENT_SCHEMAS so docs cannot drift from the registry (hvdlint
    HVD008 checks the committed table against this rendering)."""
    lines = [
        "| Event | Writer | Fields (`*` = optional) | Meaning |",
        "|---|---|---|---|",
    ]
    for s in EVENT_SCHEMAS:
        flds = ", ".join(
            [f"`{f}`" for f in s.required]
            + [f"`{f}`*" for f in s.optional]) or "—"
        name = f"`{s.name}`" + (" †" if s.critical else "")
        lines.append(f"| {name} | {s.writer} | {flds} | {s.doc} |")
    lines.append("")
    lines.append("† fsync'd unconditionally (CRITICAL_EVENTS); "
                 "unmarked events batch under "
                 "`HOROVOD_JOURNAL_FSYNC`.")
    return "\n".join(lines)


class Journal:
    """Append-only JSONL journal for one process.

    One record per line, written under a lock with O_APPEND semantics
    (concurrent incarnations of a respawned slot interleave whole
    lines, never tear them), fsync'd per ``fsync_every`` records and
    unconditionally for CRITICAL_EVENTS. Rotation: past
    ``rotate_bytes`` the live file is renamed to ``<path>.1``
    (replacing any previous rotation) and a fresh segment starts with
    its own journal_meta, so an unattended soak is bounded at two
    segments per process. Never raises into the caller — a full disk
    degrades observability, not training."""

    def __init__(self, path: str, role: str, rank: int = -1,
                 fsync_every: int = 1, rotate_bytes: int = 0,
                 strict: bool = False):
        self.path = path
        self.role = role
        self.rank = int(rank)
        self._fsync_every = max(1, int(fsync_every))
        self._rotate_bytes = int(rotate_bytes)
        self._strict = bool(strict)
        self._schema_warned: set = set()
        self._lock = threading.Lock()
        self._n = 0
        self._since_sync = 0
        self._anchor_mono = time.monotonic_ns()
        self._anchor_unix = time.time()
        self._f = open(path, "a", encoding="utf-8")
        self._write_meta()

    # -- record plumbing ----------------------------------------------

    def _now(self) -> Tuple[int, float]:
        mono = time.monotonic_ns()
        # Wall clock DERIVED from the monotonic anchor: an NTP step
        # mid-run cannot reorder this process's own records.
        unix = self._anchor_unix + (mono - self._anchor_mono) / 1e9
        return mono, unix

    def _write_meta(self) -> None:
        # The slice field appears only for workers launched with a
        # slice id (multi-slice pods) — single-slice journals keep
        # their historical meta shape.
        extra: Dict[str, Any] = {}
        slice_id = _config.env_value("HOROVOD_ELASTIC_SLICE_ID")
        if slice_id:
            extra["slice"] = slice_id
        self.event("journal_meta", _critical=True,
                   schema=SCHEMA,
                   anchor_mono_ns=self._anchor_mono,
                   anchor_unix=round(self._anchor_unix, 6),
                   host=_config.env_value("HOROVOD_HOSTNAME") or "",
                   epoch=_config.env_value("HOROVOD_ELASTIC_EPOCH"),
                   faults=_config.env_value("HOROVOD_FAULTS"),
                   faults_seed=_config.env_value("HOROVOD_FAULTS_SEED"),
                   **extra)

    def event(self, type_: str, _critical: bool = False,
              **fields: Any) -> None:
        if self._strict and type_ not in self._schema_warned:
            # Warn-once per event type, never raise: schema drift
            # degrades observability, it must not kill training.
            problems = schema_problems(type_, fields)
            if problems:
                self._schema_warned.add(type_)
                hlog.warning("journal: HOROVOD_JOURNAL_STRICT: %s",
                             "; ".join(problems))
        mono, unix = self._now()
        rec: Dict[str, Any] = dict(fields)
        rec.update({
            "type": type_, "role": self.role, "rank": self.rank,
            "pid": os.getpid(), "mono_ns": mono,
            "t": round(unix, 6),
        })
        try:
            line = json.dumps(rec, sort_keys=True,
                              separators=(",", ":"), default=str)
        except (TypeError, ValueError) as e:
            hlog.debug("journal: unserializable %s event: %s",
                       type_, e)
            return
        rotated = False
        with self._lock:
            # per-segment sequence: the merge's stable tiebreak
            line = line[:-1] + f',"n":{self._n}}}'
            self._n += 1
            self._since_sync += 1
            try:
                self._f.write(line + "\n")
                if (_critical or type_ in CRITICAL_EVENTS
                        or self._since_sync >= self._fsync_every):
                    self._f.flush()
                    os.fsync(self._f.fileno())
                    self._since_sync = 0
                if self._rotate_bytes > 0:
                    rotated = self._maybe_rotate()
            except (OSError, ValueError) as e:
                hlog.debug("journal: write failed: %s", e)
        if rotated:
            # New segment gets its own meta so the merge can map its
            # monotonic records without the rotated sibling.
            self._write_meta()
        _m_events.inc()

    def _maybe_rotate(self) -> bool:
        """Called under the lock after a write; True when a fresh
        segment was started (meta re-emission is the caller's job,
        outside the lock)."""
        try:
            if self._f.tell() < self._rotate_bytes:
                return False
            self._f.flush()
            os.fsync(self._f.fileno())
            self._f.close()
            os.replace(self.path, self.path + ".1")
            self._f = open(self.path, "a", encoding="utf-8")
            self._n = 0
            return True
        except OSError as e:  # pragma: no cover - disk-state dependent
            hlog.debug("journal: rotation failed: %s", e)
            return False

    def close(self) -> None:
        with self._lock:
            try:
                self._f.flush()
                os.fsync(self._f.fileno())
                self._f.close()
            except (OSError, ValueError):
                pass


# ---------------------------------------------------------------------------
# module journal (one per process; same disarmed-fast-path contract as
# faults.fire / tracing.record)
# ---------------------------------------------------------------------------

_journal: Optional[Journal] = None
# Set once a recovery is in flight on this worker (watermark found, or
# an in-process restore ran): the next State.commit closes the MTTR's
# first_commit phase.
_first_commit_pending: Optional[float] = None


def enabled() -> bool:
    return _journal is not None


def get() -> Optional[Journal]:
    return _journal


def journal_dir(env: Optional[Dict[str, str]] = None) -> str:
    return _config.env_value("HOROVOD_JOURNAL_DIR", env=env)


def configure(role: str, rank: int = -1,
              env: Optional[Dict[str, str]] = None
              ) -> Optional[Journal]:
    """(Re)arm the module journal for this process; no-op (and
    disarm-preserving) when HOROVOD_JOURNAL_DIR is unset. A rank
    change (elastic reassignment) re-points at the new rank's file."""
    global _journal
    d = journal_dir(env)
    if not d:
        return None
    safe_role = "".join(c if (c.isalnum() or c in "._-") else "_"
                        for c in role)
    name = (f"journal-{safe_role}.jsonl" if rank < 0
            else f"journal-rank{rank}.jsonl")
    path = os.path.join(d, name)
    if _journal is not None:
        if _journal.path == path:
            return _journal
        _journal.close()
        _journal = None
    try:
        os.makedirs(d, exist_ok=True)
        _journal = Journal(
            path, role, rank,
            fsync_every=_config.env_value("HOROVOD_JOURNAL_FSYNC",
                                          env=env),
            rotate_bytes=_config.env_value("HOROVOD_JOURNAL_ROTATE_MB",
                                           env=env) * (1 << 20),
            strict=_config.env_value("HOROVOD_JOURNAL_STRICT",
                                     env=env))
    except OSError as e:
        hlog.warning("journal: cannot open %s (%s); lifecycle "
                     "journal disabled for this process", path, e)
        _journal = None
    return _journal


def disarm() -> None:
    """Close and detach this process's journal (bench legs that
    journal into per-leg directories, test hygiene). Safe when
    already disarmed."""
    global _journal
    if _journal is not None:
        _journal.close()
        _journal = None


def record(type_: str, **fields: Any) -> None:
    """The instrumentation seam: one load + compare when disarmed."""
    j = _journal
    if j is None:
        return
    j.event(type_, **fields)


def on_init(cfg, state) -> None:
    """Worker wiring from common/basics.init: (re)bind the journal to
    this rank's file and record the world this process just joined.
    Best effort — observability never fails init."""
    try:
        j = configure("worker", state.topology.rank)
        if j is None:
            return
        j.event("init_done",
                epoch=_config.env_value("HOROVOD_ELASTIC_EPOCH"),
                world_size=state.topology.size,
                local_rank=state.topology.local_rank)
        # PR 5's clock calibration, shared: when the tracing layer
        # estimated this rank's offset to rank 0, persist it so the
        # offline merge can align worker journals recorded on
        # different hosts' clocks.
        from . import tracing as _tracing
        cal = _tracing.current_calibration()
        if cal is not None:
            j.event("clock_sync", offset_ns=cal[0], rtt_ns=cal[1])
    except Exception as e:  # noqa: BLE001 — observability only
        hlog.warning("journal: init wiring failed (%s); continuing", e)


# ---------------------------------------------------------------------------
# committed-step watermark (carried across restarts via the journal)
# ---------------------------------------------------------------------------

def watermark(dir_: Optional[str] = None) -> int:
    """Highest step any incarnation in `dir_` ever committed — read
    from the worker journals, so a respawned gang can MEASURE what it
    lost instead of assuming the snapshot was current. Commits that
    issued a durable snapshot write (rank 0 of a JaxState with
    snapshot_path) take precedence: a non-writing rank running a step
    ahead of the snapshot owner has not advanced what a restarted
    gang can restore. Falls back to the plain max when no commit was
    ever flagged durable (in-memory-only states). -1 when no commit
    was ever journaled (fresh job, or journaling disabled)."""
    d = dir_ if dir_ is not None else journal_dir()
    if not d:
        return -1
    best = -1
    best_durable = -1
    for path in _glob.glob(os.path.join(d, "journal-rank*.jsonl*")):
        try:
            events, _ = read_journal(path)
        except OSError:
            continue
        for e in events:
            if e.get("type") == "commit":
                try:
                    step = int(e.get("step", -1))
                except (TypeError, ValueError):
                    continue
                best = max(best, step)
                if e.get("durable"):
                    best_durable = max(best_durable, step)
    return best_durable if best_durable >= 0 else best


def note_sync(resumed_step: Optional[int]) -> None:
    """Called by elastic run() after state.sync(): compare the step
    this attempt resumed at against the journal watermark. A positive
    difference is committed-step LOSS (the contract violation the
    metric exists to catch); any prior watermark at all means this is
    a post-failure attempt, so the next commit closes the recovery's
    first_commit phase."""
    global _first_commit_pending
    j = _journal
    if j is None or resumed_step is None:
        return
    try:
        resumed_step = int(resumed_step)
    except (TypeError, ValueError):
        return
    w = watermark()
    if w < 0:
        return  # fresh job: nothing was ever committed
    loss = max(0, w - int(resumed_step))
    if loss:
        _m_step_loss.inc(loss)
    j.event("watermark", watermark=w, resumed=int(resumed_step),
            loss=loss)
    _first_commit_pending = time.monotonic()


def note_commit(step: Optional[int],
                durable: bool = False) -> None:
    """Called by State.commit AFTER the snapshot saved: the committed
    watermark advances (durably — commit is a CRITICAL_EVENT), and a
    pending recovery closes its first_commit phase. `durable` marks
    commits that issued a persistent snapshot write — the ones a
    restarted gang can actually restore to."""
    global _first_commit_pending
    j = _journal
    if j is None:
        return
    fields: Dict[str, Any] = {
        "epoch": _config.env_value("HOROVOD_ELASTIC_EPOCH")}
    if durable:
        fields["durable"] = True
    try:
        if step is not None:
            fields["step"] = int(step)
    except (TypeError, ValueError):
        pass  # non-integer user step attr: commit still journals
    pend = _first_commit_pending
    if pend is not None:
        _first_commit_pending = None
        dt = time.monotonic() - pend
        _m_recovery.labels(phase="first_commit").observe(dt)
        j.event("first_commit", seconds=round(dt, 6), **fields)
    j.event("commit", **fields)


def observe_phase(phase: str, seconds: float) -> None:
    """Runtime SLO seam for driver/worker recovery phases."""
    _m_recovery.labels(phase=phase).observe(max(0.0, seconds))


def count_recovery(cause: str) -> None:
    _m_recoveries.labels(cause=cause).inc()


# ---------------------------------------------------------------------------
# offline: parse / merge / MTTR decomposition
# ---------------------------------------------------------------------------

def read_journal(path: str) -> Tuple[List[dict], int]:
    """Parse one JSONL journal, tolerating the torn tail a SIGKILL
    mid-write leaves behind. Returns (events, dropped_line_count);
    only undecodable lines are dropped (the fsync discipline means
    damage is bounded to the final unflushed write)."""
    events: List[dict] = []
    dropped = 0
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                dropped += 1
                continue
            if isinstance(rec, dict) and "type" in rec:
                events.append(rec)
            else:
                dropped += 1
    return events, dropped


def find_journal_files(dir_: str) -> List[str]:
    """Journal segments under `dir_`, rotated siblings first so each
    file's events stay in write order after the stable sort."""
    paths = sorted(_glob.glob(os.path.join(dir_, "journal-*.jsonl")))
    rotated = sorted(_glob.glob(os.path.join(dir_,
                                             "journal-*.jsonl.1")))
    return rotated + paths


def load_journals(dir_: str) -> Tuple[List[dict], List[dict]]:
    """All events under `dir_`, globally time-ordered, plus per-file
    source descriptors for the report's provenance block."""
    events: List[dict] = []
    sources: List[dict] = []
    for path in find_journal_files(dir_):
        base = os.path.basename(path)
        try:
            evs, dropped = read_journal(path)
        except OSError as e:
            hlog.warning("journal: skipping unreadable %s (%s)",
                         path, e)
            continue
        for e in evs:
            e["_src"] = base
        events.extend(evs)
        sources.append({
            "file": base,
            "events": len(evs),
            "repaired_tail_lines": dropped,
            "roles": sorted({str(e.get("role", "?")) for e in evs}),
            "ranks": sorted({int(e.get("rank", -1)) for e in evs}),
        })
    if not events:
        raise ValueError(
            f"no journal files under {dir_!r} (produced by runs with "
            "HOROVOD_JOURNAL_DIR set)")
    # Clock alignment: every record's `t` is derived from its own
    # process's monotonic anchor (wall clock at journal open). Worker
    # clock_sync records (PR 5's calibrated offsets to rank 0) refine
    # cross-host alignment when present; same-host journals are
    # already coherent to anchor-read granularity.
    offs: Dict[str, float] = {}
    rank0_off: Optional[float] = None
    for e in events:
        if e.get("type") == "clock_sync":
            off = float(e.get("offset_ns", 0)) / 1e9
            offs[e["_src"]] = off
            if int(e.get("rank", -1)) == 0:
                rank0_off = off
    if offs and rank0_off is not None:
        for e in events:
            off = offs.get(e["_src"])
            if off is not None:
                e["t"] = round(float(e["t"]) + (off - rank0_off), 6)
    events.sort(key=lambda e: (float(e.get("t", 0.0)),
                               str(e.get("_src", "")),
                               int(e.get("n", 0))))
    return events, sources


def _rel(t: float, t0: float) -> float:
    return round(float(t) - t0, 6)


def _phase(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if a is None or b is None:
        return None
    return round(max(0.0, b - a), 6)


def _cause_of(rec: dict, worker_events: List[dict]) -> dict:
    """Attribute one recovery: triggering rank/host/code from the
    driver's detect event, the injection seam (or numerics
    escalation) from the failed rank's last journaled breaths."""
    cause = {
        "kind": rec["cause_kind"],
        "rank": rec.get("cause_rank"),
        "host": rec.get("cause_host"),
    }
    if rec.get("cause_slice") is not None:
        cause["slice"] = rec["cause_slice"]
    if rec.get("exit_code") is not None:
        cause["exit_code"] = rec["exit_code"]
    if rec.get("stale_age_s") is not None:
        cause["heartbeat_stale_age_s"] = rec["stale_age_s"]
    t_detect = rec["t_detect"]
    seam = None
    t_seam = None
    t_fail = None
    for e in worker_events:
        if float(e["t"]) >= t_detect:
            break
        if int(e.get("rank", -2)) != cause.get("rank"):
            continue
        t_fail = float(e["t"])
        if e["type"] == "fault_fired":
            seam = f'{e.get("point")}:{e.get("action")}'
            t_seam = t_fail
        elif e["type"] in ("numerics_escalation",
                           "replica_divergence", "internal_error"):
            seam = e["type"]
            t_seam = t_fail
    # A preemption is driver-originated: the host_preempt event (the
    # SIGTERM storm) is the seam and the failure instant — the dying
    # workers' own last journal lines are ordinary commits.
    if rec.get("t_preempt") is not None:
        seam = "host.preempt:preempt"
        t_seam = float(rec["t_preempt"])
        t_fail = t_seam
    # A seam only explains the failure if it was (nearly) the rank's
    # last act — a fault fired minutes before a natural death is
    # coincidence, not cause.
    if seam is not None and t_fail is not None and \
            t_fail - t_seam > 2.0:
        seam = None
    cause["seam"] = seam
    rec["t_fail"] = t_fail if t_fail is not None else t_detect
    return cause


def build_incidents(events: List[dict]) -> Tuple[List[dict],
                                                 List[dict]]:
    """The MTTR state machine over the merged stream. Returns
    (recoveries, epochs): one recovery per detect→first-commit arc,
    one epoch entry per membership publication (kind start / resize /
    recovery)."""
    t0 = float(events[0]["t"]) if events else 0.0
    driver = [e for e in events if e.get("role") == "driver"]
    workers = [e for e in events if e.get("role") == "worker"]
    recoveries: List[dict] = []
    epochs: List[dict] = []
    cur: Optional[dict] = None
    # host -> time of the driver's last SIGTERM storm against it (the
    # host.preempt seam); a following preempt-caused detect of that
    # host anchors its failure instant here.
    last_preempt: Dict[str, float] = {}
    for e in driver:
        t = float(e["t"])
        ty = e["type"]
        if ty == "host_preempt":
            if e.get("host") is not None:
                last_preempt[str(e["host"])] = t
        elif ty == "detect":
            if cur is None or cur.get("t_respawn") is not None:
                cur = {"t_detect": t,
                       "cause_kind": str(e.get("cause", "crash")),
                       "cause_rank": e.get("exit_rank"),
                       "cause_host": e.get("host"),
                       "cause_slice": e.get("slice"),
                       "exit_code": e.get("code"),
                       "stale_age_s": e.get("age_s"),
                       "reset": e.get("reset"),
                       "triggers": []}
                if (cur["cause_kind"] == "preempt"
                        and e.get("host") in last_preempt):
                    cur["t_preempt"] = last_preempt[e["host"]]
                recoveries.append(cur)
            trig = {"t": _rel(t, t0), "rank": e.get("exit_rank"),
                    "host": e.get("host"), "cause": e.get("cause"),
                    "code": e.get("code")}
            if e.get("slice") is not None:
                trig["slice"] = e["slice"]
            cur["triggers"].append(trig)
        elif ty == "slice_lost" and cur is not None:
            cur.setdefault("slices_lost", []).append(
                {"slice": e.get("slice"),
                 "hosts": e.get("hosts"),
                 "cause": e.get("cause"),
                 "window_s": e.get("window_s"),
                 "failures": e.get("failures")})
        elif ty == "gang_restart_begin" and cur is not None:
            cur.setdefault("t_restart", t)
        elif ty == "teardown_done" and cur is not None:
            cur.setdefault("t_teardown", t)
        elif ty == "epoch_published":
            epoch = int(e.get("epoch", -1))
            in_recovery = (cur is not None
                           and cur.get("t_epoch") is None
                           and cur.get("t_teardown") is not None)
            entry = {
                "epoch": epoch,
                "t": _rel(t, t0),
                "size": e.get("size"),
                "hosts": e.get("hosts"),
                "kind": ("recovery" if in_recovery
                         else ("start" if not epochs else "resize")),
            }
            if e.get("slices") is not None:
                entry["slices"] = e["slices"]
            epochs.append(entry)
            if in_recovery:
                cur["t_epoch"] = t
                cur["epoch"] = epoch
        elif ty == "respawn_done" and cur is not None:
            cur.setdefault("t_respawn", t)
        elif ty == "blacklist" and cur is not None:
            entry = {"host": e.get("host"),
                     "window_s": e.get("window_s"),
                     "failures": e.get("failures")}
            if e.get("slice") is not None:
                entry["slice"] = e["slice"]
            cur.setdefault("blacklisted", []).append(entry)
        elif ty == "postmortem" and cur is not None:
            cur.setdefault("postmortems", []).append(
                {"rank": e.get("exit_rank", e.get("rank")),
                 "file": e.get("file"), "reason": e.get("reason"),
                 "step": e.get("step")})
    out: List[dict] = []
    for i, rec in enumerate(recoveries):
        epoch = rec.get("epoch")
        t_restore_end = None
        t_first_commit = None
        first_commit_step = None
        restored_step = None
        wm_event = None
        for e in workers:
            t = float(e["t"])
            if t < rec["t_detect"]:
                continue
            ty = e["type"]
            if epoch is not None and int(e.get("epoch", -1)) == epoch:
                if ty == "sync_done":
                    t_restore_end = (t if t_restore_end is None
                                     else max(t_restore_end, t))
                elif ty == "commit" and t_first_commit is None:
                    t_first_commit = t
                    try:
                        first_commit_step = int(e.get("step"))
                    except (TypeError, ValueError):
                        pass
            if ty == "snapshot_loaded" and restored_step is None:
                try:
                    restored_step = int(e.get("step"))
                except (TypeError, ValueError):
                    pass
            if ty == "watermark" and wm_event is None:
                wm_event = e
        cause = _cause_of(rec, workers)
        # Committed watermark at failure time: the highest step any
        # rank journaled a commit for before detection — durable
        # (snapshot-issuing) commits take precedence, same rule as
        # the runtime watermark() check.
        wm = -1
        wm_durable = -1
        for e in workers:
            if (e["type"] == "commit"
                    and float(e["t"]) < rec["t_detect"]):
                try:
                    step = int(e.get("step", -1))
                except (TypeError, ValueError):
                    continue
                wm = max(wm, step)
                if e.get("durable"):
                    wm_durable = max(wm_durable, step)
        if wm_durable >= 0:
            wm = wm_durable
        if restored_step is None and wm_event is not None:
            restored_step = int(wm_event.get("resumed", -1))
        if restored_step is None and first_commit_step is not None:
            restored_step = first_commit_step - 1
        loss = (max(0, wm - restored_step)
                if (wm >= 0 and restored_step is not None) else None)
        phases = {
            "detect": _phase(rec["t_fail"], rec["t_detect"]),
            "teardown": _phase(rec["t_detect"],
                               rec.get("t_teardown")),
            "rendezvous": _phase(rec.get("t_teardown"),
                                 rec.get("t_epoch")),
            "respawn": _phase(rec.get("t_epoch"),
                              rec.get("t_respawn")),
            "restore": _phase(rec.get("t_respawn"), t_restore_end),
            "first_commit": _phase(t_restore_end, t_first_commit),
        }
        entry = {
            "index": i,
            "cause": cause,
            "reset": rec.get("reset"),
            "epoch": epoch,
            "t_fail": _rel(rec["t_fail"], t0),
            "t_recovered": (_rel(t_first_commit, t0)
                            if t_first_commit is not None else None),
            "mttr_s": _phase(rec["t_fail"], t_first_commit),
            "complete": all(v is not None for v in phases.values()),
            "phases": phases,
            "steps": {
                "watermark": wm if wm >= 0 else None,
                "resumed": restored_step,
                "committed_step_loss": loss,
            },
            "blacklisted": rec.get("blacklisted", []),
            "postmortems": rec.get("postmortems", []),
            "triggers": rec["triggers"],
        }
        # Multi-slice attribution rides along only when the driver
        # journaled it (single-slice reports keep their r11 shape).
        if rec.get("slices_lost"):
            entry["slices_lost"] = rec["slices_lost"]
        out.append(entry)
    return out, epochs


def _timeline_entries(events: List[dict], t0: float) -> List[list]:
    """Compact human-scannable event log for the report (lifecycle
    events only — commits are summarized, not itemized)."""
    keep = {
        "detect", "worker_exit", "hung_worker", "gang_restart_begin",
        "teardown_done", "epoch_published", "spawn", "respawn_done",
        "blacklist", "postmortem", "fault_fired", "internal_error",
        "restore", "snapshot_loaded", "sync_done", "watermark",
        "first_commit", "numerics_escalation", "replica_divergence",
        "init_done", "job_done", "hosts_updated", "assignment",
        "postmortem_written", "task_exit",
        "slice_lost", "slice_admitted", "host_preempt",
        "weights_published", "weights_adopted", "weights_rejected",
    }
    out = []
    for e in events:
        if e["type"] not in keep:
            continue
        who = ("driver" if e.get("role") == "driver"
               else f'rank {e.get("rank", "?")}')
        detail = {k: v for k, v in sorted(e.items())
                  if k not in ("t", "mono_ns", "n", "type", "role",
                               "rank", "pid", "_src")}
        out.append([_rel(float(e["t"]), t0), who, e["type"], detail])
    return out


# Functions whose OUTPUT BYTES are pinned by committed artifacts:
# identical journal bytes must always produce identical report bytes.
# hvdlint HVD009 seeds its call-graph reachability from these names
# and flags any nondeterminism source (wall clock, unseeded random,
# set-order iteration, unsorted directory walks, json without
# sort_keys) on a reachable path.
DETERMINISTIC_ENTRYPOINTS = (
    "incident_report",
    "write_incident_report",
    "render_incident_report",
    "journal_digest",
)


def incident_report(dir_: str) -> Dict[str, Any]:
    """The byte-deterministic analyzer result: identical journal
    bytes always produce identical report bytes (sorted keys, fixed
    rounding, times relative to the first journaled event, no
    absolute paths, no generation timestamps)."""
    events, sources = load_journals(dir_)
    t0 = float(events[0]["t"])
    recoveries, epochs = build_incidents(events)
    commits = [e for e in events if e["type"] == "commit"]
    faults_specs = sorted({
        (str(e.get("faults", "")), int(e.get("faults_seed", 0)))
        for e in events if e["type"] == "journal_meta"
        and e.get("faults")})
    losses = [r["steps"]["committed_step_loss"] for r in recoveries
              if r["steps"]["committed_step_loss"] is not None]
    mttrs = [r["mttr_s"] for r in recoveries
             if r["mttr_s"] is not None]
    by_cause: Dict[str, int] = {}
    for r in recoveries:
        k = r["cause"]["kind"]
        by_cause[k] = by_cause.get(k, 0) + 1
    # Slice attribution appears only when some recovery carries it —
    # a single-slice job's report keeps its historical key set.
    by_slice: Dict[str, int] = {}
    for r in recoveries:
        for sl in r.get("slices_lost", []):
            sid = str(sl.get("slice"))
            by_slice[sid] = by_slice.get(sid, 0) + 1
    summary_extra = ({"by_slice": by_slice} if by_slice else {})
    return {
        "schema": REPORT_SCHEMA,
        "source": {
            "files": sources,
            "faults": [{"spec": s, "seed": seed}
                       for s, seed in faults_specs],
        },
        "epochs": epochs,
        "recoveries": recoveries,
        "commits": {
            "total": len(commits),
            "max_step": max(
                (int(e.get("step", -1)) for e in commits),
                default=-1),
        },
        "summary": {
            "recoveries": len(recoveries),
            "complete_decompositions": sum(
                1 for r in recoveries if r["complete"]),
            "by_cause": by_cause,
            "committed_step_loss_total": (sum(losses) if losses
                                          else None),
            "total_downtime_s": (round(sum(mttrs), 6) if mttrs
                                 else None),
            "max_mttr_s": (max(mttrs) if mttrs else None),
            **summary_extra,
        },
        "timeline": _timeline_entries(events, t0),
    }


def write_incident_report(dir_: str,
                          out: Optional[str] = None
                          ) -> Tuple[str, Dict[str, Any]]:
    report = incident_report(dir_)
    path = out or os.path.join(dir_, "incident_report.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    return path, report


def render_incident_report(report: Dict[str, Any]) -> str:
    """Human-readable incident summary for the doctor CLI."""
    s = report["summary"]
    lines = [
        f"recoveries: {s['recoveries']} "
        f"(complete decompositions: {s['complete_decompositions']}) "
        f"by cause: {s['by_cause']}",
        f"committed-step loss: {s['committed_step_loss_total']}   "
        f"total downtime: {s['total_downtime_s']} s   "
        f"worst MTTR: {s['max_mttr_s']} s",
    ]
    for r in report["recoveries"]:
        c = r["cause"]
        head = (f"\n#{r['index']} {c['kind']} on {c['host']} "
                f"(rank {c['rank']}"
                + (f", slice {c['slice']}" if c.get("slice") else "")
                + (f", exit {c['exit_code']}"
                   if c.get("exit_code") is not None else "")
                + (f", seam {c['seam']}" if c.get("seam") else "")
                + f") -> epoch {r['epoch']}  "
                  f"MTTR {r['mttr_s']} s")
        lines.append(head)
        for sl in r.get("slices_lost", []):
            lines.append(
                f"    slice lost: {sl['slice']} "
                f"({','.join(sl.get('hosts') or [])}) "
                f"cause {sl['cause']} -> blacklisted "
                f"{sl['window_s']} s (failure {sl['failures']})")
        for ph in ("detect", "teardown", "rendezvous", "respawn",
                   "restore", "first_commit"):
            v = r["phases"][ph]
            bar = ("" if v is None else
                   "#" * min(60, max(1, int(v * 20))))
            lines.append(f"    {ph:<12} "
                         f"{'?' if v is None else f'{v:8.3f}'} s  "
                         f"{bar}")
        st = r["steps"]
        lines.append(f"    steps: watermark {st['watermark']} -> "
                     f"resumed {st['resumed']} "
                     f"(committed loss {st['committed_step_loss']})")
        for pm in r["postmortems"]:
            lines.append(f"    postmortem: rank {pm['rank']} "
                         f"{pm['file']} ({pm['reason']})")
    return "\n".join(lines)


def journal_digest() -> Dict[str, Any]:
    """Compact digest for bench.py's JSON artifact: event counts by
    type from this process's own journal file (empty when the journal
    is disarmed — the common bench case)."""
    j = _journal
    if j is None:
        return {"enabled": False}
    counts: Dict[str, int] = {}
    try:
        events, dropped = read_journal(j.path)
    except OSError:
        return {"enabled": True, "error": "unreadable"}
    for e in events:
        counts[e["type"]] = counts.get(e["type"], 0) + 1
    return {"enabled": True, "path": os.path.basename(j.path),
            "events": len(events), "repaired_tail_lines": dropped,
            "by_type": {k: counts[k] for k in sorted(counts)}}
