"""Structured tracing for the serving engines: host spans on the
profiler's clock, and a fixed-capacity ring buffer of them, exportable as
Chrome trace-event JSON.

One mechanism, ``span(tracer, name, **args)``:

  * It always enters ``jax.profiler.TraceAnnotation("repro." + name)``.
    Under a profiler (``jax.profiler.trace``) the span lands in the same
    ``.xplane.pb`` as the device ops, on the same clock, so every idle gap
    of the device can be put down to what the host was doing in it.  With
    the profiler off the annotation is one small object and records nothing.
  * When a ``TraceRecorder`` is attached and enabled, the span also appends
    the same interval to the ring.

Design constraints of the ring (these ARE the feature):

  * O(1) append into a preallocated ring — recording a span is a few tuple
    stores, no allocation growth, no locks (the serve loops are
    single-threaded per engine; the metrics HTTP thread only READS exported
    snapshots).
  * Zero device-side cost: spans are host timestamps around work the engine
    does anyway.  Tracing never adds a ``block_until_ready`` or a transfer.
  * Off by default: engines take ``tracer=None``; a disabled or absent ring
    costs no clock read and no ring slot.
  * Overflow drops the OLDEST spans (ring semantics) and counts them in
    ``dropped`` — a long serve with a small buffer keeps the most recent
    window instead of dying or silently truncating the tail.

The clock: the profiler stamps host events with ``CLOCK_REALTIME``
(``time.time_ns``) and writes them relative to the session's
``profile_start_time`` (a stat of the trace's ``Task Environment`` plane).
The ring records on that clock too, so a ring export and a profiler trace
of the same run line up: a ring time ``t`` sits at
``t * 1e9 - profile_start_time`` ns in the trace.  Stamps a caller took on
``time.perf_counter`` (the scheduler's submit/admit times) are moved onto it
with ``TraceRecorder.from_perf``.

Lane conventions (how the engines use pid/tid):

  * request-lifecycle spans: ``pid`` = shard id, ``tid`` = slot index —
    one Perfetto row per slot, "queued" (submit -> admit) and "request"
    (admit -> retire) spans with rid/rounds/accepts/theta_live attributes.
  * boundary spans: ``pid`` = shard id, ``tid`` = num_slots + lane —
    dispatch (or compile) / device_wait / harvest / admit / step rows
    underneath the slots.
  * fused front-end spans: ``pid`` = num_shards (one past the shard ids),
    named "frontend" — the single fused dispatch/device-wait lanes.

Export is the Chrome trace-event JSON array format ("X" complete events
with ts/dur in microseconds plus "M" metadata name events), which
https://ui.perfetto.dev loads directly.
"""

from __future__ import annotations

import json
import time
from typing import Optional

from jax.profiler import TraceAnnotation

# every program span's name in a profiler trace starts with this
SPAN_PREFIX = "repro."


def clock() -> float:
    """Seconds on the clock the profiler stamps host events with
    (``CLOCK_REALTIME``)."""
    return time.time_ns() * 1e-9


class span:
    """One host interval of the serving program: a profiler annotation
    named ``repro.<name>`` with ``args`` as its metadata, and, when
    ``tracer`` is an enabled ``TraceRecorder``, the same interval in its
    ring on lane (``pid``, ``tid``).

    ``set(**args)`` adds arguments known only at the span's end.  A
    profiler reader should cut a raw event name at ``#``: the annotation
    folds its arguments into the name as ``name#k=v#``.
    """

    __slots__ = ("_ann", "_ring", "_t0", "name", "args", "pid", "tid",
                 "pname", "tname")

    def __init__(self, tracer, name: str, *, pid: int = 0, tid: int = 0,
                 pname: Optional[str] = None, tname: Optional[str] = None,
                 **args):
        self._ann = TraceAnnotation(SPAN_PREFIX + name, **args)
        self._ring = tracer if tracer is not None and tracer.enabled else None
        if self._ring is not None:
            self.name, self.args = name, args
            self.pid, self.tid, self.pname, self.tname = pid, tid, pname, tname

    @property
    def on(self) -> bool:
        """Whether anything records this span (the ring or a profiler)."""
        return self._ring is not None or TraceAnnotation.is_enabled()

    def set(self, **args) -> None:
        self._ann.set_metadata(**args)
        if self._ring is not None:
            self.args.update(args)

    def __enter__(self) -> "span":
        if self._ring is not None:
            self._t0 = clock()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        if self._ring is not None:
            self._ring.add_span(self.name, self._t0, clock(), pid=self.pid,
                                tid=self.tid, pname=self.pname,
                                tname=self.tname, args=self.args)


class TraceRecorder:
    """Fixed-capacity ring buffer of trace spans.

    Args:
      capacity: maximum retained events; older events are dropped (and
        counted) once exceeded.
      enabled: record-site gate; a disabled recorder ignores appends so a
        CLI can build one unconditionally and flip it on for a window.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self.dropped = 0
        # epoch: export timestamps are relative to recorder construction so
        # traces from one run are comparable and deterministic in layout;
        # the export carries it, to line the ring up with a profiler trace
        self.epoch_ns = time.time_ns()
        self.epoch = self.epoch_ns * 1e-9
        # perf_counter -> profiler clock, for stamps callers already took
        self._perf_offset = clock() - time.perf_counter()
        self._buf: list = [None] * self.capacity
        self._start = 0  # ring read position
        self._n = 0      # live events
        self._seq = 0    # insertion counter (stable export order)
        # lane names, registered once per (pid)/(pid, tid): exported as
        # Chrome "M" metadata events so Perfetto labels the rows
        self._pnames: dict = {}
        self._tnames: dict = {}

    def __len__(self) -> int:
        return self._n

    @staticmethod
    def now() -> float:
        """The clock spans are recorded against: the profiler's."""
        return clock()

    def from_perf(self, t: float) -> float:
        """A ``time.perf_counter`` stamp on the ring's clock."""
        return t + self._perf_offset

    def _append(self, event: tuple) -> None:
        if self._n == self.capacity:  # drop-oldest ring overflow
            self._buf[self._start] = event
            self._start = (self._start + 1) % self.capacity
            self.dropped += 1
        else:
            self._buf[(self._start + self._n) % self.capacity] = event
            self._n += 1
        self._seq += 1

    def _register(self, pid: int, tid: Optional[int],
                  pname: Optional[str], tname: Optional[str]) -> None:
        if pname is not None and pid not in self._pnames:
            self._pnames[pid] = pname
        if tname is not None and tid is not None and (
                (pid, tid) not in self._tnames):
            self._tnames[(pid, tid)] = tname

    def add_span(self, name: str, t0: float, t1: float, *,
                 pid: int = 0, tid: int = 0,
                 pname: Optional[str] = None, tname: Optional[str] = None,
                 args: Optional[dict] = None) -> None:
        """Record one complete span [t0, t1] (``now()`` seconds)."""
        if not self.enabled:
            return
        self._register(pid, tid, pname, tname)
        self._append(("X", name, t0, max(t1 - t0, 0.0), pid, tid,
                      args, self._seq))

    def add_instant(self, name: str, t: float, *,
                    pid: int = 0, tid: int = 0,
                    pname: Optional[str] = None, tname: Optional[str] = None,
                    args: Optional[dict] = None) -> None:
        """Record one instant event at ``t`` (``now()`` seconds)."""
        if not self.enabled:
            return
        self._register(pid, tid, pname, tname)
        self._append(("i", name, t, 0.0, pid, tid, args, self._seq))

    def clear(self) -> None:
        """Empty the ring (names and the epoch are kept)."""
        self._buf = [None] * self.capacity
        self._start = 0
        self._n = 0
        self.dropped = 0

    # -- export --------------------------------------------------------------

    def _events(self) -> list:
        return [self._buf[(self._start + i) % self.capacity]
                for i in range(self._n)]

    def spans(self) -> list:
        """Snapshot of the retained events as dicts, insertion-ordered."""
        out = []
        for ph, name, t0, dur, pid, tid, args, _ in self._events():
            d = {"ph": ph, "name": name, "t0": t0, "dur": dur,
                 "pid": pid, "tid": tid}
            if args:
                d["args"] = dict(args)
            out.append(d)
        return out

    def to_chrome(self) -> dict:
        """The Chrome trace-event object: "M" metadata name events first,
        then the retained spans sorted by (ts, insertion order) — a stable
        layout, so the export is deterministic for a given recording."""
        events = []
        for pid in sorted(self._pnames):
            events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": self._pnames[pid]}})
        for pid, tid in sorted(self._tnames):
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": self._tnames[(pid, tid)]}})
        recs = sorted(self._events(), key=lambda e: (e[2], e[7]))
        for ph, name, t0, dur, pid, tid, args, _ in recs:
            ev = {
                "ph": ph, "name": name,
                "ts": round((t0 - self.epoch) * 1e6, 3),
                "pid": pid, "tid": tid,
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            else:
                ev["s"] = "t"  # instant scope: thread
            if args:
                ev["args"] = {k: v for k, v in args.items() if v is not None}
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "droppedEvents": self.dropped,
                "otherData": {"clock": "CLOCK_REALTIME",
                              "epoch_ns": self.epoch_ns}}

    def export_chrome_trace(self, path: str) -> dict:
        """Write the Chrome trace JSON to ``path`` (open in Perfetto:
        https://ui.perfetto.dev -> Open trace file).  Returns the object."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f, sort_keys=True, separators=(",", ":"))
            f.write("\n")
        return doc
