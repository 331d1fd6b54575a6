"""Chrome-trace timeline of eager collective lifecycle phases.

TPU-native analog of the reference timeline
(reference: horovod/common/timeline.cc — Timeline::NegotiateStart /
ActivityStart / WriteEvent, TimelineWriter background thread). EVERY
rank writes a Chrome-trace JSON (chrome://tracing / Perfetto-loadable)
with one lane per tensor name and phases ENQUEUE → NEGOTIATE → QUEUE →
FUSE → DISPATCH → DONE; rank 0 keeps the configured path, other ranks
write `<path>.rankN.json` siblings, and `hvdrun --timeline-merge`
fuses them on calibrated clocks (tracing.py). Device-side detail comes
from jax.profiler (XPlane) instead — this file covers the host-side
engine semantics the XLA trace cannot see.

Timestamps are `time.monotonic_ns()` anchored once at construction —
NEVER the wall clock, which steps under NTP and would fold spans over
each other mid-run. The anchor (both monotonic and wall-clock epoch)
rides the file's `hvd_trace_meta` record, which is what the merge
step consumes to place N ranks' monotonic clocks on one axis.

Events are queued to a dedicated writer thread so the hot path never
blocks on file IO, matching the reference's TimelineWriter design.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time


class Timeline:
    def __init__(self, path: str, mark_cycles: bool = False,
                 rank: int = 0):
        self.path = path
        self.mark_cycles = mark_cycles
        self.rank = rank
        self._q: "queue.Queue" = queue.Queue()
        # One-time clock anchor: spans are monotonic-since-anchor (in
        # us, the Chrome-trace unit); the wall-clock epoch is recorded
        # ONCE here for humans — it is never used for span math.
        self._anchor_mono_ns = time.monotonic_ns()
        self._tids: dict = {}
        self._next_tid = 1
        self._lock = threading.Lock()
        self._file = open(path, "w")
        self._file.write("[\n")
        self._first = True
        self._closed = False
        self._q.put({"name": "hvd_trace_meta", "ph": "M", "pid": 0,
                     "tid": 0, "args": {
                         "rank": rank,
                         "anchor_mono_ns": self._anchor_mono_ns,
                         "anchor_unix_ns": time.time_ns(),
                         "version": 1}})
        self._writer = threading.Thread(target=self._write_loop,
                                        name="hvd-timeline", daemon=True)
        self._writer.start()

    @staticmethod
    def rank_path(path: str, rank: int) -> str:
        """Per-rank trace file for a configured HOROVOD_TIMELINE path:
        rank 0 keeps the path verbatim (reference compatibility);
        rank N writes a `.rankN` sibling the merge step discovers."""
        if rank <= 0:
            return path
        root, ext = os.path.splitext(path)
        return f"{root}.rank{rank}{ext or '.json'}"

    # -- event API (called from the engine hot path) -------------------------
    def _ts_us(self) -> float:
        return (time.monotonic_ns() - self._anchor_mono_ns) / 1e3

    def to_trace_us(self, mono_ns: int) -> float:
        """Map a raw time.monotonic_ns() reading onto this trace's
        axis (used to attach submit-arrival times captured before the
        event is emitted)."""
        return (mono_ns - self._anchor_mono_ns) / 1e3

    def clock_sync(self, offset_ns: int, rtt_ns: int) -> None:
        """Record a calibration estimate mapping THIS rank's
        monotonic clock onto rank 0's (tracing.ClockCalibrator). The
        merge picks the min-RTT record per file."""
        if self._closed:
            return
        self._q.put({"name": "CLOCK_SYNC", "ph": "M", "pid": 0,
                     "tid": 0, "args": {"offset_ns": int(offset_ns),
                                        "rtt_ns": int(rtt_ns),
                                        "at_us": self._ts_us()}})

    def _tid(self, name: str) -> int:
        with self._lock:
            if name not in self._tids:
                self._tids[name] = self._next_tid
                self._q.put({"name": "thread_name", "ph": "M", "pid": 0,
                             "tid": self._next_tid,
                             "args": {"name": name}})
                self._next_tid += 1
            return self._tids[name]

    def _emit(self, name: str, phase: str, ph: str) -> None:
        if self._closed:
            return
        self._q.put({"name": phase, "ph": ph, "pid": 0,
                     "tid": self._tid(name), "ts": self._ts_us()})

    def enqueue(self, name: str) -> None:
        self._emit(name, "QUEUE", "B")

    def negotiate_start(self, name: str) -> None:
        self._emit(name, "NEGOTIATE", "B")

    def negotiate_end(self, name: str, negotiate_us: int = 0,
                      seq: int = -1, step: int = -1,
                      arrival_us: float = None,
                      tier: int = -1) -> None:
        """Closes the NEGOTIATE span. negotiate_us (if provided) is
        the coordinator-measured submit->agreed duration carried on
        the batch entry wire format — the lane itself uses this
        rank's local clock, so the arg is attached for diagnosis.

        seq/step are the trace context (the agreed collective
        sequence id — identical on every rank by construction — and
        the training step); arrival_us is this rank's local submit
        time on the trace axis. Together they are what the merge step
        keys its cross-rank arrival-delta attribution on.

        tier >= 0 records this rank's control-tree tier
        (HOROVOD_CONTROL_TREE_ARITY; 0 = root) on the span, so a
        merged trace shows which aggregation hop a rank's
        negotiation rode through."""
        if self._closed:
            return
        ev = {"name": "NEGOTIATE", "ph": "E", "pid": 0,
              "tid": self._tid(name), "ts": self._ts_us()}
        args = {}
        if negotiate_us:
            args["coordinator_negotiate_us"] = negotiate_us
        if tier >= 0:
            args["tier"] = tier
        if seq >= 0:
            args.update(seq=seq, step=step, tensor=name)
            if arrival_us is not None:
                args["arrival_us"] = round(arrival_us, 3)
        if args:
            ev["args"] = args
        self._q.put(ev)

    def span(self, name: str, phase: str, begin_mono_ns: int,
             end_mono_ns: int, args: dict = None) -> None:
        """One closed B/E span on `name`'s lane from raw
        time.monotonic_ns() readings captured elsewhere (the serving
        frontend hands over a request's phases afterwards), landing
        them on the same merged-trace axis as the engine's eager
        lanes."""
        if self._closed:
            return
        tid = self._tid(name)
        begin = {"name": phase, "ph": "B", "pid": 0, "tid": tid,
                 "ts": self.to_trace_us(begin_mono_ns)}
        if args:
            begin["args"] = dict(args)
        self._q.put(begin)
        self._q.put({"name": phase, "ph": "E", "pid": 0, "tid": tid,
                     "ts": self.to_trace_us(end_mono_ns)})

    def fuse(self, name: str, bucket: int) -> None:
        if self._closed:
            return
        self._q.put({"name": f"FUSE(bucket={bucket})", "ph": "i", "pid": 0,
                     "tid": self._tid(name), "ts": self._ts_us(), "s": "t"})

    def dispatched(self, name: str) -> None:
        self._emit(name, "QUEUE", "E")
        self._emit(name, "DISPATCH", "B")

    def done(self, name: str, error: bool = False) -> None:
        if error:
            # ERROR instant rides inside the DISPATCH span so the lane
            # shows WHERE the failure landed, then the span closes —
            # keeping the trace well-formed (the error-path analog of
            # error(), which covers pre-dispatch failures).
            self.error_marker(name)
        self._emit(name, "DISPATCH", "E")

    def error(self, name: str) -> None:
        """Close the QUEUE span for an op that failed before dispatch,
        keeping the trace well-formed."""
        self._emit(name, "QUEUE", "E")
        self.error_marker(name)

    def error_marker(self, name: str) -> None:
        """Instant ERROR marker without closing any span (used for
        negotiation-time errors, where no QUEUE span is open)."""
        if self._closed:
            return
        self._q.put({"name": "ERROR", "ph": "i", "pid": 0,
                     "tid": self._tid(name), "ts": self._ts_us(), "s": "t"})

    def cycle(self, index: int) -> None:
        if not self.mark_cycles or self._closed:
            return
        self._q.put({"name": f"CYCLE {index}", "ph": "i", "pid": 0,
                     "tid": 0, "ts": self._ts_us(), "s": "g"})

    # -- writer thread -------------------------------------------------------
    def _write_loop(self) -> None:
        # Durability: flush once per DRAIN of the queue, not per event
        # — a SIGKILLed rank keeps everything written up to its last
        # quiet moment, while a busy hot path amortizes the flush over
        # the whole backlog.
        while True:
            ev = self._q.get()
            if ev is None:
                self._file.flush()
                return
            batch = [ev]
            closing = False
            while True:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    closing = True
                    break
                batch.append(nxt)
            for e in batch:
                line = json.dumps(e)
                if not self._first:
                    line = ",\n" + line
                self._first = False
                self._file.write(line)
            self._file.flush()
            if closing:
                return

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._writer.join(timeout=5)
        self._file.write("\n]\n")
        self._file.close()
